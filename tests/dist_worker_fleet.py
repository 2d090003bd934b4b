"""2-process fleet-observability smoke worker (ISSUE 10).

Companion script of ``tests/test_fleet.py``'s
``test_two_processes_name_their_straggler``,
run by distributed.launch.start_procs under the PADDLE_* env contract.
Each rank drives the PUBLIC Executor dp path over a REAL 2-process CPU
mesh; rank 1 is slowed by ``faultinject.stall_point("executor.step")``
with a repeating ("every", seconds) spec — the stall lands BEFORE the
skew probe's host timestamp is taken, so the injected straggler looks
exactly like a genuinely slow host to the barrier-wait attribution.

What each rank writes to ``<out_path>.r<rank>``:

- ``table`` — ``monitor.fleet_skew()`` over the post-warmup window
  (who is the straggler, per-rank wait/behind stats, wait fraction).
- ``rows`` — the raw per-step wait vectors (``fleet.skew_rows``) the
  parent recomputes the table from EXACTLY (no trust in the rolling
  aggregation).
- rank 0 additionally scrapes its own live ``/metrics`` exporter
  (ephemeral port) and reports the parsed scrape next to
  ``monitor.snapshot()`` so the parent can assert the two views agree.

Telemetry JSONL streams land in ``<out_dir>/telemetry/`` rank-tagged,
so the parent can also run the fleet merge over them.

argv: out_path [stall_s] [steps]
"""

import json
import os
import sys

# exactly one CPU device per process so the 2-process world is 2 devices
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

WARMUP = 3          # compile + clock-settle steps excluded from the table


def main():
    out_path = sys.argv[1]
    stall_s = float(sys.argv[2]) if len(sys.argv) > 2 else 0.08
    steps = int(sys.argv[3]) if len(sys.argv) > 3 else 12

    init_parallel_env()
    rank, world = get_rank(), get_world_size()
    assert world == 2, world

    import paddle_tpu as fluid
    from paddle_tpu import monitor, resilience
    from paddle_tpu.monitor import exporter, fleet

    tag = monitor.rank_tag()
    assert tag["process_index"] == rank, (tag, rank)

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

    # all (GLOBAL) devices on the dp axis — the real multi-host shape
    prog = fluid.CompiledProgram(main_p).with_data_parallel(
        loss_name=loss.name).with_telemetry("fleet_smoke")
    mesh = prog._dp_mesh()
    assert mesh.devices.size == world

    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    # startup ran per-process from the same FLAGS_global_seed, so the
    # values are identical; re-place them as GLOBAL replicated arrays
    # (each process contributes its full copy) so shard_map sees state
    # covering the whole mesh
    rep = NamedSharding(mesh, P())
    for v in main_p.list_vars():
        if not v.persistable:
            continue
        val = sc.find_var(v.name)
        if val is None:
            continue
        sc.set_var(v.name, jax.make_array_from_process_local_data(
            rep, np.asarray(val)))

    out_dir = os.path.dirname(os.path.abspath(out_path))
    tdir = os.path.join(out_dir, "telemetry")
    os.makedirs(tdir, exist_ok=True)
    monitor.reset()
    monitor.enable(jsonl_path=os.path.join(tdir,
                                           f"telemetry_r{rank}.jsonl"))

    if rank == 1 and stall_s > 0:
        # the injected straggler: EVERY dispatch on this rank sleeps
        # stall_s before its pre-sync timestamp is taken
        resilience.faultinject.arm(
            stall_points={"executor.step": ("every", stall_s)})

    # global dp feeds: each rank contributes its half of the batch
    # (both ranks draw the same batches — same seed — so the halves
    # are consistent shards of one global batch)
    dp_shard = NamedSharding(mesh, P("dp"))
    batch = 8
    half = batch // world
    rng = np.random.default_rng(0)

    def gfeed(a):
        return jax.make_array_from_process_local_data(
            dp_shard, a[rank * half:(rank + 1) * half])

    losses = []
    for _ in range(steps):
        xb = rng.standard_normal((batch, 8)).astype(np.float32)
        yb = rng.standard_normal((batch, 1)).astype(np.float32)
        out = exe.run(prog, feed={"x": gfeed(xb), "y": gfeed(yb)},
                      fetch_list=[loss], scope=sc)
        losses.append(float(np.asarray(out[0])))
    resilience.faultinject.disarm()

    window = steps - WARMUP
    rows = fleet.skew_rows()
    table = fleet.fleet_skew(window=window)
    monitor.record_fleet_skew(table)
    snap = monitor.snapshot()

    result = {
        "rank": rank,
        "world": world,
        "stall_s": stall_s,
        "steps": steps,
        "window": window,
        "losses": losses,
        "rank_tag": tag,
        "table": table,
        "rows": [{"step": r.get("step"),
                  "step_time_s": r.get("step_time_s"),
                  "waits_us": r["waits_us"]} for r in rows],
    }

    if rank == 0:
        # live scrape: ephemeral port, localhost, parsed back with the
        # same helper the tests use — recorded NEXT TO snapshot() so
        # the parent proves the two views agree without a live process
        import urllib.request

        srv = exporter.start(0, host="127.0.0.1")
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        parsed = exporter.parse_prometheus(text)
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            health = json.loads(r.read().decode())
            health["status"] = r.status
        exporter.stop()
        result["metrics"] = {
            "parsed": {exporter.metric_key(name, labels): v
                       for (name, labels), v in parsed.items()},
            "health": health,
        }
        result["snapshot_counters"] = snap.get("counters", {})
        result["snapshot_gauges"] = {
            k: v for k, v in snap.get("gauges", {}).items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}
        result["snapshot_fleet"] = snap.get("fleet")

    monitor.disable()
    with open(f"{out_path}.r{rank}", "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main()
