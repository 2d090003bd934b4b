"""Elastic-fleet chaos worker (ISSUE 11).

Companion script of ``tests/test_elastic.py``'s
``test_a_killed_rank_is_resharded_around_and_rejoins``, run by
``distributed.launch.start_procs`` under the PADDLE_* env contract.
One script, five phases — the CHAOS run exercises the recovery path,
the CLEAN run produces the uninterrupted reference with the SAME
topology schedule (the only definition under which bitwise equality is
meaningful: dp math is shard-count-dependent, so the reference changes
world size at the same boundaries, just without the kill):

- ``chaos_a`` (2 procs, elastic): train from step 0; rank 1 is killed
  by ``faultinject.crash_point("elastic.step_boundary")`` at boundary
  ``kill_at`` — after completing step kill_at-1, before any heartbeat
  for kill_at, modeling a SIGKILL between steps.  Rank 0's bounded
  boundary sync times out, declares the rank dead, force-saves, and
  SHRINKS IN PROCESS: ``restore_resharded`` onto its local 1-device
  mesh + ``retarget_dp``, then continues with the full global batch.
  While the transition is in flight it scrapes its own /healthz
  (expects 503 reason=elastic_transition; 200 after commit).  At
  boundary ``grow_at`` the pre-posted join intent for rank 1 surfaces:
  GROW force-saves the rendezvous checkpoint, commits world 2, and
  exits with action "relaunch".
- ``chaos_b`` (2 procs, elastic): the relaunched fleet — both ranks
  ``resume()`` the committed topology, ``restore_resharded`` onto the
  fresh 2-process mesh, and train to the end.  This IS the re-admit:
  the fresh rank joins through the checkpoint rendezvous.
- ``clean_a``/``clean_b``/``clean_c``: the same three topology legs
  (2 procs to kill_at, 1 proc to grow_at, 2 procs to the end) as
  scheduled, uninterrupted runs with no elastic machinery — restore
  between legs goes through the same ``restore_resharded``.

Rank 0 of every phase writes ``<report>.r0`` with losses, counters,
healthz probes, and (final phases) the trained parameters; telemetry
JSONL streams land rank-tagged in ``<out_dir>/telemetry`` so the
parent can merge the topology history with telemetry_report --fleet.

argv: config.json path (see that test's ``run_phase``).
"""

import json
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

import jax  # noqa: E402
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from paddle_tpu.distributed.env import (  # noqa: E402
    get_rank,
    get_world_size,
    init_parallel_env,
)


def build_model(fluid):
    with fluid.unique_name.guard():
        main_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup):
            x = fluid.data("x", [None, 8])
            y = fluid.data("y", [None, 1])
            h = fluid.layers.fc(x, 8, act="relu")
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main_p, startup, loss


def make_batches(total_steps, batch):
    rng = np.random.default_rng(7)
    return [(rng.standard_normal((batch, 8)).astype(np.float32),
             rng.standard_normal((batch, 1)).astype(np.float32))
            for _ in range(total_steps)]


def host_state(scope, names):
    """Single-writer host snapshot: replicated arrays are identical on
    every shard, so .addressable_data(0) is the full value and the
    save needs no cross-process coordination (a dead peer can never
    hang it)."""
    out = {}
    for n in names:
        v = scope.find_var(n)
        if v is None:
            continue
        if hasattr(v, "addressable_data"):
            v = v.addressable_data(0)
        out[n] = np.asarray(v)
    return out


def scrape_health(port):
    import urllib.request

    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            return {"status": r.status,
                    **json.loads(r.read().decode())}
    except urllib.error.HTTPError as e:  # 503 raises in urllib
        return {"status": e.code, **json.loads(e.read().decode())}


def main():
    with open(sys.argv[1]) as f:
        cfg = json.load(f)
    phase = cfg["phase"]
    ckdir = cfg["ckpt_dir"]
    total = int(cfg["total_steps"])
    kill_at = int(cfg["kill_at"])
    grow_at = int(cfg["grow_at"])
    batch = int(cfg["batch"])
    start = int(cfg["start_step"])
    end = int(cfg["end_step"])
    elastic_on = bool(cfg["elastic"])
    report_path = cfg["report"]

    init_parallel_env()
    rank, world = get_rank(), get_world_size()

    import paddle_tpu as fluid
    from paddle_tpu import monitor, resilience
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.monitor import exporter
    from paddle_tpu.resilience import TopologyChanged, elastic

    tdir = os.path.join(cfg["out_dir"], "telemetry")
    os.makedirs(tdir, exist_ok=True)
    monitor.reset()
    monitor.enable(jsonl_path=os.path.join(
        tdir, f"telemetry_{phase}_r{rank}.jsonl"))

    main_p, startup, loss = build_model(fluid)
    prog = fluid.CompiledProgram(main_p).with_data_parallel(
        loss_name=loss.name,
        places=(jax.local_devices() if world == 1 else None)
    ).with_telemetry(f"elastic_{phase}")
    mesh = prog._dp_mesh()
    exe = fluid.Executor()
    sc = fluid.Scope()
    persist = sorted(v.name for v in main_p.list_vars() if v.persistable)
    # npz writer: collective-free saves, so rank 0 can write alone
    # while peers train — and still write after peers DIE
    mgr = CheckpointManager(ckdir, max_to_keep=4, writer="npz")

    report = {"rank": rank, "world": world, "phase": phase,
              "losses": [], "events": [], "health": {}}

    # -- state: fresh startup at step 0, resharded restore otherwise --
    exe.run(startup, scope=sc)
    if start > 0:
        template = {n: sc.find_var(n) for n in persist
                    if sc.find_var(n) is not None}
        state, ck = mgr.restore_resharded(template, mesh=mesh)
        assert ck == start, (ck, start)
        for n, v in state.items():
            sc.set_var(n, v)
        report["restored_step"] = ck
        report["restored_topology"] = mgr.load_topology(ck)
    elif world > 1:
        # identical per-process init (same seed): contribute full
        # copies as global replicated arrays
        rep = NamedSharding(mesh, P())
        for n in persist:
            v = sc.find_var(n)
            if v is not None:
                sc.set_var(n, jax.make_array_from_process_local_data(
                    rep, np.asarray(v)))

    coord = None
    srv = None
    if elastic_on:
        srv = exporter.start(0, host="127.0.0.1")

        def on_transition(payload):
            # the in-flight window: /healthz must answer 503 with
            # reason=elastic_transition until commit
            report["health"]["during"] = scrape_health(srv.port)

        coord = elastic.ElasticCoordinator(
            mgr, peer_timeout_s=float(cfg.get("peer_timeout_s", 10.0)),
            install_signals=False, on_transition=on_transition)
        coord.install()
        if start > 0:
            coord.resume(step=start)
        if phase == "chaos_a" and rank == cfg.get("kill_rank", 1):
            resilience.faultinject.arm(
                crash_points={"elastic.step_boundary": kill_at})

    batches = make_batches(total, batch)
    dp_shard = NamedSharding(mesh, P("dp"))

    def feed_for(i, cur_world, cur_mesh, cur_rank):
        xb, yb = batches[i]
        if cur_world == 1:
            return {"x": xb, "y": yb}
        half = batch // cur_world
        shard = NamedSharding(cur_mesh, P("dp"))
        return {n: jax.make_array_from_process_local_data(
            shard, a[cur_rank * half:(cur_rank + 1) * half])
            for n, a in (("x", xb), ("y", yb))}

    cur_world, cur_mesh, cur_rank = world, mesh, rank
    exit_action = "done"
    i = start
    try:
        while i < end:
            if coord is not None:
                ev = coord.step_boundary(i)
                if ev is not None:
                    report["events"].append(ev)
                    if ev["kind"] in ("rank_death", "rank_leave"):
                        template = {n: sc.find_var(n) for n in persist
                                    if sc.find_var(n) is not None}
                        state, ck, new_mesh = coord.shrink(
                            template, i, dead=ev["ranks"],
                            save_state=host_state(sc, persist))
                        for n, v in state.items():
                            sc.set_var(n, v)
                        exe._check_state_placement = True
                        prog.retarget_dp(list(jax.local_devices()))
                        cur_mesh = prog._dp_mesh()
                        cur_world, cur_rank = 1, 0
                        report["health"]["after"] = scrape_health(
                            srv.port)
                        report["shrunk_at"] = i
                        continue      # re-run THIS boundary shrunken
                    if ev["kind"] == "rank_join":
                        coord.grow(i, ev["ranks"],
                                   save_state=host_state(sc, persist))
            try:
                out = exe.run(prog, feed=feed_for(i, cur_world, cur_mesh,
                                                  cur_rank),
                              fetch_list=[loss], scope=sc)
            except Exception as e:
                # a peer died MID-step: the gloo collective surfaces a
                # preemption-shaped failure and this step's state is
                # suspect — shrink from the newest complete checkpoint
                # and rewind the data cursor to it
                ev = (coord.on_dispatch_error(e, step=i)
                      if coord is not None else None)
                if ev is None:
                    raise
                report["events"].append(ev)
                template = {n: sc.find_var(n) for n in persist
                            if sc.find_var(n) is not None}
                state, ck, new_mesh = coord.shrink(
                    template, i, dead=ev["ranks"])
                for n, v in state.items():
                    sc.set_var(n, v)
                exe._check_state_placement = True
                prog.retarget_dp(list(jax.local_devices()))
                cur_mesh = prog._dp_mesh()
                cur_world, cur_rank = 1, 0
                report["shrunk_at"] = i
                report["rewound_to"] = ck
                report["losses"] = report["losses"][:ck - start]
                i = ck
                continue
            report["losses"].append(float(np.asarray(out[0])))
            i += 1
            if cur_rank == 0:
                # single-writer host-side checkpoint at every boundary,
                # stamped with the coordinator's committed topology
                mgr.save(host_state(sc, persist), i, force=True,
                         topology=(coord.topology()
                                   if coord is not None else None))
    except TopologyChanged as tc:
        exit_action = tc.action
        report["topology_changed"] = {"step": tc.step,
                                      "event": tc.event,
                                      "action": tc.action}

    report["exit_action"] = exit_action
    report["steps_done"] = i
    report["ckpt_latest"] = mgr.latest_step()
    if cur_rank == 0 and i >= end:
        report["final_params"] = {
            n: np.asarray(host_state(sc, [n]).get(n)).tolist()
            for n in persist}
    snap = monitor.snapshot()
    report["counters"] = {k: v for k, v in
                          snap.get("counters", {}).items()
                          if k.startswith("resilience.")}
    report["gauges"] = {k: v for k, v in snap.get("gauges", {}).items()
                        if k.startswith("fleet.")}
    report["elastic_records"] = [
        {k: r.get(k) for k in ("event", "transition", "from_world",
                               "to_world", "world", "gen", "step")}
        for r in monitor.elastic_records()]
    monitor.disable()
    if coord is not None:
        coord.uninstall()
    with open(f"{report_path}.{phase}.r{rank}", "w") as f:
        json.dump(report, f)
        f.flush()
        os.fsync(f.fileno())
    if phase.startswith("chaos"):
        # a dead peer can wedge jax.distributed's atexit teardown; the
        # report is durable, so skip straight past it — modeling the
        # orchestrator reaping the container
        os._exit(0)


if __name__ == "__main__":
    main()
