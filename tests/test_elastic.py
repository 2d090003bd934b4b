"""Elastic fleet runtime tests (ISSUE 11).

Covers the tentpole and satellites in-process on the 8-device virtual
CPU mesh: topology-change resharding (shrink 4→2/4→1, grow 2→4,
bitwise params + data cursor + corrupted-newest fallback), the
ElasticCoordinator control plane (heartbeat liveness vs progress,
bounded-timeout death detection, leave/join intents, drain signal,
transition window -> /healthz + /metrics), the skew policy ladder
(warn → rebalance → evict with hysteresis and share quantization), the
taxonomy/retry agreement on "a rank died", and the executor's
elastic= hook.  The REAL multi-process kill/reshard/rejoin arc
(tests/dist_worker_elastic.py) runs at the end of this file.
"""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import monitor, resilience
from paddle_tpu import checkpoint as ck
from paddle_tpu.checkpoint import CheckpointManager
from paddle_tpu.monitor import exporter
from paddle_tpu.resilience import elastic, taxonomy
from paddle_tpu.resilience.elastic import (ElasticCoordinator,
                                           ElasticPolicy,
                                           TopologyChanged)


@pytest.fixture(autouse=True)
def _clean():
    """No test may leak coordinators/faults/flags into the next."""
    yield
    c = elastic.active_coordinator()
    if c is not None:
        c.uninstall()
    elastic._transition = None
    resilience.faultinject.disarm()
    resilience.clear_preemption()
    resilience.clear_drain()
    monitor.disable()
    monitor.reset()


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def _state_on(mesh, seed=0):
    rng = np.random.default_rng(seed)
    rep = NamedSharding(mesh, P())
    return {
        "w": jax.device_put(
            rng.standard_normal((4, 3)).astype(np.float32), rep),
        "m": jax.device_put(
            rng.standard_normal((3,)).astype(np.float32), rep),
    }


def _host(state):
    return {n: np.asarray(v.addressable_data(0)
                          if hasattr(v, "addressable_data") else v)
            for n, v in state.items()}


# ---------------------------------------------------------------------
# restore_resharded: shrink / grow / cursor / fallback
# ---------------------------------------------------------------------

@pytest.mark.parametrize("to_n", [2, 1])
def test_restore_resharded_shrink_bitwise(tmp_path, to_n):
    """Acceptance: save on a 4-shard mesh, restore onto 2 and 1 shards
    — bitwise-identical params, replicated on the TARGET mesh, cursor
    at the saved step."""
    m4 = _mesh(4)
    state = _state_on(m4)
    mgr = CheckpointManager(tmp_path)
    mgr.save(state, 7, force=True)
    target = _mesh(to_n)
    restored, step = mgr.restore_resharded(state, mesh=target)
    assert step == 7
    for n in state:
        assert np.array_equal(_host({n: restored[n]})[n],
                              _host({n: state[n]})[n])
        assert (set(restored[n].sharding.device_set)
                == set(target.devices.flat))


def test_restore_resharded_grow_bitwise(tmp_path):
    m2 = _mesh(2)
    state = _state_on(m2, seed=3)
    CheckpointManager(tmp_path).save(state, 5, force=True)
    m4 = _mesh(4)
    restored, step = ck.restore_resharded(str(tmp_path), state, mesh=m4)
    assert step == 5
    for n in state:
        assert np.array_equal(_host({n: restored[n]})[n],
                              _host({n: state[n]})[n])
        assert (set(restored[n].sharding.device_set)
                == set(m4.devices.flat))


def test_restore_resharded_host_arrays_when_no_mesh(tmp_path):
    """mesh=None returns host arrays (callers doing their own
    placement — the relaunch path before the new mesh exists)."""
    state = _state_on(_mesh(4), seed=1)
    CheckpointManager(tmp_path).save(state, 2, force=True)
    restored, step = ck.restore_resharded(str(tmp_path), state)
    assert step == 2
    for n in state:
        got = np.asarray(restored[n])
        assert np.array_equal(got, _host({n: state[n]})[n])


def test_resharded_cursor_math():
    """Global batch preserved: one step is one global batch whatever
    the world — cursor unchanged.  Per-rank batch preserved: the
    global batch scales with the world, so the cursor rescales (floor:
    re-consume a partial batch, never skip data)."""
    assert ck.resharded_cursor(12) == 12
    assert ck.resharded_cursor(12, old_world=4, new_world=2,
                               preserve_global_batch=False) == 24
    assert ck.resharded_cursor(12, old_world=2, new_world=4,
                               preserve_global_batch=False) == 6
    assert ck.resharded_cursor(13, old_world=2, new_world=4,
                               preserve_global_batch=False) == 6  # floor
    with pytest.raises(ValueError):
        ck.resharded_cursor(5, preserve_global_batch=False)


def test_topology_sidecar_roundtrip(tmp_path):
    """Every checkpoint records what fleet shape wrote it; an explicit
    topology= merges over the auto-captured process/device counts."""
    state = _state_on(_mesh(2))
    mgr = CheckpointManager(tmp_path)
    mgr.save(_host(state), 4, force=True,
             topology={"world": 2, "gen": 3, "members": [0, 1]})
    topo = mgr.load_topology()
    assert topo["world"] == 2 and topo["gen"] == 3
    assert topo["members"] == [0, 1]
    assert topo["step"] == 4
    assert "process_count" in topo           # auto-captured base


def test_corrupted_newest_checkpoint_falls_back(tmp_path):
    """Acceptance: the newest checkpoint is truncated AFTER its marker
    was written — restore_resharded must detect it (checksum manifest)
    and fall back to the previous complete step."""
    mgr = CheckpointManager(tmp_path, writer="npz")
    s1 = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    s2 = {"w": np.arange(6, 12, dtype=np.float32).reshape(2, 3)}
    mgr.save(s1, 1, force=True)
    mgr.save(s2, 2, force=True)
    payload = os.path.join(str(tmp_path), "step_2", "state",
                           "arrays.npz")
    with open(payload, "r+b") as f:       # torn copy: half the bytes
        f.truncate(os.path.getsize(payload) // 2)
    restored, step = mgr.restore_resharded(s1, mesh=_mesh(1))
    assert step == 1
    assert np.array_equal(np.asarray(restored["w"]), s1["w"])


def test_npz_writer_roundtrip_and_autodetect(tmp_path):
    """The collective-free npz writer (what elastic stores use — orbax
    saves run a cross-process barrier) round-trips through BOTH
    loaders, which auto-detect the format per checkpoint."""
    mgr = CheckpointManager(tmp_path, writer="npz")
    state = _state_on(_mesh(2), seed=9)
    mgr.save(state, 3, force=True)
    got, step = mgr.restore_latest(_host(state))
    assert step == 3
    assert np.array_equal(np.asarray(got["w"]), _host(state)["w"])
    got2, _ = mgr.restore_resharded(state, mesh=_mesh(4))
    assert np.array_equal(_host({"w": got2["w"]})["w"],
                          _host(state)["w"])
    with pytest.raises(ValueError):
        ck.save_checkpoint(str(tmp_path), state, 4, writer="bogus")


@pytest.mark.parametrize("writer", ["orbax", "npz"])
def test_restore_resharded_across_mesh_shapes(tmp_path, writer):
    """ISSUE 16 satellite: the reshard arc beyond pure-dp — {dp=2} →
    {dp=1,mp=2} → {dp=2,mp=2} → {dp=2}, bitwise at every hop with BOTH
    writers, `_TOPOLOGY.json` carrying the writing mesh's axes."""
    from paddle_tpu.distributed.mesh import build_rule_mesh

    shapes = [{"dp": 2}, {"dp": 1, "mp": 2}, {"dp": 2, "mp": 2},
              {"dp": 2}]
    rng = np.random.default_rng(7)
    host = {"w": rng.standard_normal((4, 4)).astype(np.float32),
            "m": rng.standard_normal((4,)).astype(np.float32)}
    mesh = build_rule_mesh(shapes[0])
    state = {n: jax.device_put(v, NamedSharding(mesh, P()))
             for n, v in host.items()}
    for step, axes in enumerate(shapes[1:], start=1):
        d = str(tmp_path / f"hop{step}")
        ck.save_checkpoint(d, state, step, writer=writer)
        topo = ck.load_topology(d)
        assert topo["mesh_axes"] == {k: int(v) for k, v in
                                     mesh.shape.items()}
        mesh = build_rule_mesh(axes)
        state, got_step = ck.restore_resharded(d, state, mesh=mesh)
        assert got_step == step
        for n, want in host.items():
            assert np.array_equal(np.asarray(state[n]), want)
            assert (set(state[n].sharding.device_set)
                    == set(mesh.devices.flat))


def test_restore_resharded_state_specs_places_sharded(tmp_path):
    """state_specs= lowers a TP plan's layout at restore: named leaves
    land SHARDED on the target mesh (per-shard bytes below full),
    unnamed leaves replicate as before — values bitwise either way."""
    from paddle_tpu.analysis.sharding import ShardSpec
    from paddle_tpu.distributed.mesh import build_rule_mesh

    state = {"w": np.arange(16, dtype=np.float32).reshape(4, 4),
             "m": np.arange(4, dtype=np.float32)}
    ck.save_checkpoint(str(tmp_path), state, 1, writer="npz")
    mesh = build_rule_mesh({"dp": 2, "mp": 2})
    restored, _ = ck.restore_resharded(
        str(tmp_path), state, mesh=mesh,
        state_specs={"w": ShardSpec((None, "mp"))})
    w = restored["w"]
    assert tuple(w.sharding.spec) == (None, "mp")
    assert w.addressable_shards[0].data.nbytes * 2 == w.nbytes
    assert np.array_equal(np.asarray(w), state["w"])
    assert restored["m"].sharding.spec == P()
    assert np.array_equal(np.asarray(restored["m"]), state["m"])


# ---------------------------------------------------------------------
# coordinator control plane
# ---------------------------------------------------------------------

def _coord(tmp_path, rank, world, **kw):
    kw.setdefault("peer_timeout_s", 0.4)
    kw.setdefault("poll_interval_s", 0.01)
    kw.setdefault("heartbeat_interval_s", 0.05)
    # the boundary sync is a BARRIER: in these single-threaded tests a
    # live-but-never-arriving peer must degrade to death quickly, not
    # after the production 600s wedge backstop
    kw.setdefault("progress_timeout_s", 3.0)
    kw.setdefault("install_signals", False)
    return ElasticCoordinator(CheckpointManager(tmp_path, writer="npz"),
                              rank=rank, world=world, **kw)


def test_heartbeat_thread_decouples_liveness_from_progress(tmp_path):
    """The background heart beats without any step_boundary call — a
    rank wedged in a long compile stays alive in the peers' eyes."""
    c = _coord(tmp_path, 0, 1).install()
    try:
        hb_path = c._path("hb_r0.json")
        assert os.path.isfile(hb_path)
        t1 = json.load(open(hb_path))["wall_time"]
        time.sleep(0.15)
        t2 = json.load(open(hb_path))["wall_time"]
        assert t2 > t1                       # beat with no boundary
    finally:
        c.uninstall()


def test_slow_peer_is_waited_for_not_killed(tmp_path):
    """A peer whose heart beats but whose boundary lags (compile skew)
    is WAITED for — death is silence, never slowness."""
    c0 = _coord(tmp_path, 0, 2).install()
    c1 = _coord(tmp_path, 1, 2,
                install_signals=False)
    c1.install()
    try:
        out = {}

        def sync():
            out["ev"] = c0.step_boundary(0)

        t = threading.Thread(target=sync)
        t.start()
        time.sleep(0.6)        # > peer_timeout_s: c1 beats, no boundary
        assert t.is_alive()    # still waiting, no false death
        c1.step_boundary(0)
        t.join(timeout=5)
        assert out["ev"] is None
    finally:
        c0.uninstall()
        c1.uninstall()


def test_rank_death_on_stale_heartbeat(tmp_path):
    """Silence IS death: a peer whose heart stopped (process gone) is
    declared dead after peer_timeout_s and named in the event."""
    c0 = _coord(tmp_path, 0, 2).install()
    c1 = _coord(tmp_path, 1, 2).install()
    c1._write_heartbeat(0)     # peer reaches boundary 0 (its sync is
    c0.step_boundary(0)        # a barrier — driven by file, not nested)
    c1.uninstall()             # heart stops; hb file left stale
    try:
        t0 = time.monotonic()
        ev = c0.step_boundary(1)
        assert ev == {"kind": "rank_death", "ranks": [1], "step": 1,
                      "timeout_s": c0.peer_timeout_s}
        assert time.monotonic() - t0 >= 0.3   # waited the timeout out
        assert monitor.snapshot()["counters"][
            "resilience.elastic_rank_deaths"] >= 1
    finally:
        c0.uninstall()


def test_leave_intent_beats_the_timeout(tmp_path):
    """An announced departure (drain/preempt) is seen IMMEDIATELY —
    survivors never wait out the dead-peer window for a polite
    leaver."""
    c0 = _coord(tmp_path, 0, 2).install()
    c1 = _coord(tmp_path, 1, 2).install()
    c1._write_heartbeat(0)
    c0.step_boundary(0)
    c1.leave_intent(1, "drain")
    c1.uninstall()
    try:
        t0 = time.monotonic()
        ev = c0.step_boundary(1)
        assert ev["kind"] == "rank_leave" and ev["ranks"] == [1]
        assert ev["reasons"] == {1: "drain"}
        assert time.monotonic() - t0 < 0.3    # no timeout paid
    finally:
        c0.uninstall()


def test_join_intent_deferred_until_after_step(tmp_path):
    c = _coord(tmp_path, 0, 1).install()
    try:
        elastic.request_join(str(tmp_path), 1, after_step=3)
        assert c.step_boundary(0) is None
        assert c.step_boundary(2) is None
        ev = c.step_boundary(3)
        assert ev["kind"] == "rank_join" and ev["ranks"] == [1]
    finally:
        c.uninstall()


def test_drain_signal_self_leave(tmp_path):
    """SIGUSR1's flag (request_drain) turns into a self_leave event +
    leave intent, distinct from preemption, and is consumed."""
    c = _coord(tmp_path, 0, 1).install()
    try:
        resilience.request_drain()
        ev = c.step_boundary(5)
        assert ev == {"kind": "self_leave", "reason": "drain", "step": 5}
        assert not resilience.drain_requested()       # consumed
        assert os.path.isfile(c._path("leave_r0.json"))
        assert json.load(open(c._path("leave_r0.json")))["reason"] \
            == "drain"
    finally:
        c.uninstall()


def test_preemption_self_leave_keeps_flag(tmp_path):
    """SIGTERM's flag also posts the leave intent, but the PREEMPTION
    flag itself stays up — the training loop's save-and-exit path owns
    consuming it."""
    c = _coord(tmp_path, 0, 1).install()
    try:
        resilience.request_preemption()
        ev = c.step_boundary(2)
        assert ev["kind"] == "self_leave" and ev["reason"] == "preempt"
        assert resilience.preemption_requested()
    finally:
        c.uninstall()
        resilience.clear_preemption()


def test_preemption_handler_drain_signal_opt_in():
    """PreemptionHandler(drain_signal=SIGUSR1): the drain signal
    raises the DRAIN flag, not the preemption flag."""
    import signal

    with resilience.PreemptionHandler(drain_signal=signal.SIGUSR1):
        assert not resilience.drain_requested()
        os.kill(os.getpid(), signal.SIGUSR1)
        for _ in range(100):
            if resilience.drain_requested():
                break
            time.sleep(0.01)
        assert resilience.drain_requested()
        assert not resilience.preemption_requested()
    resilience.clear_drain()


def test_transition_window_drives_healthz_and_metrics(tmp_path):
    """Between begin_transition and commit_transition /healthz is 503
    with reason=elastic_transition; /metrics always carries
    fleet_process_count and elastic_transitions_total."""
    c = _coord(tmp_path, 0, 2).install()
    try:
        before = elastic.transitions_total()
        ok, checks = exporter.health()
        assert ok
        c.begin_transition("shrink", 3, 1, ranks=[1])
        ok, checks = exporter.health()
        assert not ok and checks["elastic_transition"]
        assert exporter._health_reason(checks) == "elastic_transition"
        srv = exporter.start(0, host="127.0.0.1")
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/healthz")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 503
            body = json.loads(ei.value.read().decode())
            assert body["reason"] == "elastic_transition"
            c.commit_transition([0], 3)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics",
                    timeout=10) as r:
                text = r.read().decode()
            parsed = exporter.parse_prometheus(text)
            assert parsed[("paddle_tpu_fleet_process_count", ())] == 1.0
            assert parsed[("paddle_tpu_elastic_transitions_total",
                           ())] == float(before + 1)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/healthz",
                    timeout=10) as r:
                assert r.status == 200
        finally:
            exporter.stop()
    finally:
        c.uninstall()


def test_shrink_in_process_restores_on_local_mesh(tmp_path):
    """The single-survivor shrink: force-save rides in, the state
    comes back replicated on the LOCAL mesh, the topology generation
    advances, and the dead rank's control files are swept."""
    c = _coord(tmp_path, 0, 2).install()
    try:
        c1 = _coord(tmp_path, 1, 2)
        c1._write_heartbeat(0)
        state = _state_on(_mesh(2), seed=4)
        st, step, mesh = c.shrink(state, 6, dead=[1],
                                  save_state=_host(state))
        assert step == 6
        assert c.world == 1 and c.members == [0] and c.gen == 2
        assert not os.path.isfile(c._path("hb_r1.json"))
        assert set(st["w"].sharding.device_set) \
            == set(mesh.devices.flat)
        assert np.array_equal(_host({"w": st["w"]})["w"],
                              _host(state)["w"])
        topo = json.load(open(c._path("topology.json")))
        assert topo["world"] == 1 and topo["gen"] == 2
        cnt = monitor.snapshot()["counters"]
        assert cnt["resilience.elastic_shrinks"] == 1
        assert cnt["resilience.elastic_reshards"] == 1
        assert cnt["resilience.elastic_force_saves"] == 1
        assert elastic.transition_in_flight() is None
    finally:
        c.uninstall()


def test_multi_survivor_shrink_requires_relaunch(tmp_path):
    """With >1 survivor the jax world must re-rendezvous: shrink
    commits the topology and raises TopologyChanged(relaunch)."""
    c = _coord(tmp_path, 1, 3).install()
    try:
        with pytest.raises(TopologyChanged) as ei:
            c.shrink({"w": np.zeros(2, np.float32)}, 4, dead=[2],
                     save_state={"w": np.zeros(2, np.float32)})
        assert ei.value.action == "relaunch"
        assert c.members == [0, 1] and c.world == 2
    finally:
        c.uninstall()


def test_grow_commits_and_raises_relaunch(tmp_path):
    c = _coord(tmp_path, 0, 1).install()
    try:
        elastic.request_join(str(tmp_path), 1)
        ev = c.step_boundary(1)
        assert ev["kind"] == "rank_join"
        with pytest.raises(TopologyChanged) as ei:
            c.grow(1, ev["ranks"],
                   save_state={"w": np.ones(3, np.float32)})
        assert ei.value.action == "relaunch"
        assert c.world == 2 and c.members == [0, 1]
        assert not os.path.isfile(c._path("join_r1.json"))  # consumed
        cnt = monitor.snapshot()["counters"]
        assert cnt["resilience.elastic_grows"] == 1
        assert cnt["resilience.elastic_rank_joins"] == 1
        assert c.manager.latest_step() == 1
        assert c.manager.load_topology(1)["world"] == 1  # pre-grow stamp
    finally:
        c.uninstall()


def test_resume_adopts_committed_topology(tmp_path):
    c = _coord(tmp_path, 0, 1).install()
    try:
        elastic.request_join(str(tmp_path), 1)
        with pytest.raises(TopologyChanged):
            c.grow(2, [1])
    finally:
        c.uninstall()
    # the relaunched fleet: a fresh coordinator reads topology.json
    c2 = _coord(tmp_path, 1, None)
    assert c2.world == 2 and c2.members == [0, 1] and c2.gen == 2
    c2.leave_intent(0, "stale")       # pretend a stale intent survived
    c2.resume(step=2)
    assert not os.path.isfile(c2._path("leave_r1.json"))
    assert monitor.snapshot()["counters"][
        "resilience.elastic_resumes"] == 1


# ---------------------------------------------------------------------
# taxonomy / retry agreement on "a rank died"
# ---------------------------------------------------------------------

def test_dispatch_error_classification(tmp_path):
    """on_dispatch_error: preemption-shaped failures become rank_death
    events naming the stale peer; programming errors are not the
    elastic layer's to handle."""
    c0 = _coord(tmp_path, 0, 2).install()
    c1 = _coord(tmp_path, 1, 2).install()
    c1._write_heartbeat(0)
    c1.uninstall()                       # heart stops
    time.sleep(0.5)                      # let the heartbeat go stale
    try:
        assert c0.on_dispatch_error(TypeError("bug")) is None
        exc = RuntimeError("FAILED_PRECONDITION: Buffer Definition "
                           "Event: Gloo all-reduce failed: Read error "
                           "[127.0.0.1]:1: Connection reset by peer")
        assert taxonomy.classify(exc) == taxonomy.PREEMPTION
        ev = c0.on_dispatch_error(exc, step=3)
        assert ev["kind"] == "rank_death" and ev["ranks"] == [1]
    finally:
        c0.uninstall()


def test_dispatch_blip_with_live_peers_is_not_a_death(tmp_path):
    """Review regression: a preemption-shaped transport blip while
    EVERY peer's heart still beats must return None (back to the
    retry/propagation path), not a fleet-wide rank_death — shrinking
    around live peers split-brains the store."""
    monitor.enable()
    # a heart that a loaded machine starves for 0.4 s is not dead: the
    # staleness threshold here is one no scheduler hiccup reaches
    c0 = _coord(tmp_path, 0, 2, peer_timeout_s=2.0).install()
    c1 = _coord(tmp_path, 1, 2, peer_timeout_s=2.0).install()
    try:
        ev = c0.on_dispatch_error(
            ConnectionResetError("one-off transport blip"), step=2)
        assert ev is None
        assert monitor.snapshot()["counters"][
            "resilience.elastic_blips_ignored"] == 1
    finally:
        c1.uninstall()
        c0.uninstall()


def test_taxonomy_fatal_codes_beat_broad_preemption_words():
    """Review regression: a status-coded programming error whose text
    merely MENTIONS a preemption-ish word stays FATAL — only the
    tightly-anchored dead-peer transport shapes outrank the fatal
    codes."""
    for msg in ("INVALID_ARGUMENT: heartbeat_interval must be positive",
                "INVALID_ARGUMENT: preemptible flag is not supported",
                "FAILED_PRECONDITION: worker pool exited configuration "
                "is invalid"):
        assert taxonomy.classify(RuntimeError(msg)) == taxonomy.FATAL, msg
    # ...while the observed dead-peer gloo shape still wins over its
    # FAILED_PRECONDITION prefix
    assert taxonomy.classify(RuntimeError(
        "FAILED_PRECONDITION: Gloo all-reduce failed: Connection reset "
        "by peer")) == taxonomy.PREEMPTION


def test_npz_writer_refuses_cross_process_sharded_leaves(tmp_path):
    """Review regression: the collective-free writer must fail LOUDLY
    on a leaf it cannot represent, never silently persist shard 0 of a
    sharded array.  (All meshes here are single-process, so sharded
    arrays are fully addressable and np.asarray gathers them — assert
    THAT roundtrip too.)"""
    mesh = _mesh(4)
    sharded = jax.device_put(
        np.arange(16, dtype=np.float32), NamedSharding(mesh, P("dp")))
    assert not sharded.is_fully_replicated
    mgr = CheckpointManager(tmp_path, writer="npz")
    mgr.save({"w": sharded}, 1, force=True)      # fully addressable: ok
    got, _ = mgr.restore_latest({"w": np.zeros(16, np.float32)})
    assert np.array_equal(np.asarray(got["w"]),
                          np.arange(16, dtype=np.float32))


def test_retry_defers_preemption_to_active_coordinator(tmp_path):
    """The satellite's contract: preemption-shaped failures are
    retried (historical behavior) WITHOUT a coordinator, and fail
    fast TO the coordinator with one installed."""
    calls = []

    def dying():
        calls.append(1)
        raise ConnectionResetError("peer gone")

    monitor.enable()
    pol = resilience.RetryPolicy(max_retries=2, base_delay=0.0,
                                 sleep=lambda d: None, seed=0)
    with pytest.raises(resilience.RetriesExhausted):
        resilience.call_with_retry(dying, pol)
    assert len(calls) == 3               # retried while no coordinator
    del calls[:]
    c = _coord(tmp_path, 0, 1).install()
    try:
        with pytest.raises(ConnectionResetError):
            resilience.call_with_retry(dying, pol)
        assert len(calls) == 1           # fail-fast to the coordinator
        assert monitor.snapshot()["counters"].get(
            "resilience.retry_deferred_to_elastic") == 1
    finally:
        c.uninstall()


# ---------------------------------------------------------------------
# skew policy: warn -> rebalance -> evict
# ---------------------------------------------------------------------

def _table(score, idx=1, n=2):
    ranks = [{"dp_index": i, "process_index": i,
              "wait_us_mean": 0.0, "behind_us_mean": 0.0}
             for i in range(n)]
    ranks[idx]["behind_us_mean"] = 1000.0
    return {"steps": 8, "ranks": ranks,
            "straggler": {"dp_index": idx, "process_index": idx,
                          "behind_us_mean": 1000.0,
                          "straggler_score": score}}


def test_policy_patience_hysteresis():
    """One slow window is not a policy event: the decision needs
    `patience` CONSECUTIVE over-threshold windows, and a healthy
    window resets the streak."""
    p = ElasticPolicy(on_straggler="warn", score_threshold=0.3,
                      patience=3)
    assert p.note_table(_table(0.5)) is None
    assert p.note_table(_table(0.5)) is None
    assert p.note_table(_table(0.1)) is None     # healthy: reset
    assert p.note_table(_table(0.5)) is None
    assert p.note_table(_table(0.5)) is None
    d = p.note_table(_table(0.5))
    assert d["action"] == "warn"
    assert d["straggler"]["dp_index"] == 1
    assert p.note_table(_table(0.5)) is None     # streak restarts


def test_policy_streak_tracks_one_straggler():
    """The streak is per-rank: the straggler hat moving between ranks
    must not accumulate toward one rank's eviction."""
    p = ElasticPolicy(on_straggler="warn", patience=2)
    assert p.note_table(_table(0.5, idx=0)) is None
    assert p.note_table(_table(0.5, idx=1)) is None   # different rank
    d = p.note_table(_table(0.5, idx=1))
    assert d is not None and d["straggler"]["dp_index"] == 1


def test_policy_rebalance_shifts_shares_and_quantizes():
    p = ElasticPolicy(on_straggler="rebalance", patience=1,
                      rebalance_step=0.25, min_share=0.5)
    d = p.note_table(_table(0.6))
    assert d["action"] == "rebalance"
    assert d["shares"][1] == 0.75 and d["shares"][0] == 1.25
    assert abs(sum(p.shares.values()) - 2.0) < 1e-9
    plan = p.plan_feed(16)
    assert sum(plan.values()) == 16
    assert plan[0] > plan[1]             # the straggler carries less
    assert plan == {0: 10, 1: 6}


def test_policy_plan_feed_none_before_rebalance():
    assert ElasticPolicy(on_straggler="warn").plan_feed(8) is None


def test_policy_rebalance_escalates_to_evict():
    """Acceptance (policy escalation): shares bottoming out — or the
    same rank straggling through the allowed rebalances — escalates
    into the shrink path."""
    p = ElasticPolicy(on_straggler="rebalance", patience=1,
                      rebalance_step=0.25, min_share=0.5,
                      evict_after_rebalances=2)
    assert p.note_table(_table(0.6))["action"] == "rebalance"
    assert p.note_table(_table(0.6))["action"] == "rebalance"
    d = p.note_table(_table(0.6))
    assert d["action"] == "evict"
    assert d["escalated_from"] == "rebalance"


def test_policy_evict_becomes_coordinator_event(tmp_path):
    c = _coord(tmp_path, 0, 2,
               policy=ElasticPolicy(on_straggler="evict", patience=1,
                                    score_threshold=0.3)).install()
    c1 = _coord(tmp_path, 1, 2)
    c1._write_heartbeat(0)
    try:
        ev = c.step_boundary(0, skew_table=_table(0.7))
        assert ev["kind"] == "evict" and ev["ranks"] == [1]
        assert monitor.snapshot()["counters"][
            "resilience.elastic_policy_evict"] == 1
    finally:
        c.uninstall()


def test_policy_invalid_action_rejected():
    with pytest.raises(ValueError):
        ElasticPolicy(on_straggler="panic")


# ---------------------------------------------------------------------
# executor hook + records + retarget
# ---------------------------------------------------------------------

def _train_prog():
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 4])
            y = fluid.data("y", [None, 1])
            pred = fluid.layers.fc(x, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _batches(n, batch=4):
    rng = np.random.default_rng(0)
    return [{"x": rng.standard_normal((batch, 4)).astype(np.float32),
             "y": rng.standard_normal((batch, 1)).astype(np.float32)}
            for _ in range(n)]


def test_train_from_dataset_elastic_join_raises_topology_changed(
        tmp_path):
    """The executor hook: a join intent surfacing at a boundary
    force-saves the rendezvous checkpoint, commits the grown topology,
    and raises TopologyChanged(action='relaunch') out of the loop."""
    monitor.enable()
    main, startup, loss = _train_prog()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    c = _coord(tmp_path, 0, 1)
    c.install()
    try:
        elastic.request_join(str(tmp_path), 1, after_step=2)
        with pytest.raises(TopologyChanged) as ei:
            exe.train_from_dataset(main, _batches(6), scope=sc,
                                   fetch_list=[loss], elastic=c,
                                   print_period=10 ** 6,
                                   prefetch=False)
        assert ei.value.action == "relaunch"
        assert ei.value.step == 2
        assert c.manager.latest_step() == 2      # force-saved boundary
        assert c.world == 2
    finally:
        c.uninstall()


def test_train_from_dataset_elastic_adopts_manager(tmp_path):
    """elastic= without checkpoint= adopts the coordinator's manager;
    a DIFFERENT manager is rejected (the shrink path must resume from
    the same store the loop saves into)."""
    main, startup, loss = _train_prog()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    c = _coord(tmp_path, 0, 1)
    c.install()
    try:
        other = CheckpointManager(str(tmp_path) + "_other")
        with pytest.raises(ValueError, match="same"):
            exe.train_from_dataset(main, _batches(2), scope=sc,
                                   fetch_list=[loss], elastic=c,
                                   checkpoint=other, prefetch=False)
    finally:
        c.uninstall()


def test_train_from_dataset_drain_exits_cleanly(tmp_path):
    """A drain request (SIGUSR1) exits the loop at the boundary with a
    durable checkpoint and a posted leave intent — and unlike
    preemption, consumes its flag."""
    monitor.enable()
    main, startup, loss = _train_prog()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    c = _coord(tmp_path, 0, 1)
    c.install()

    def draining():
        for i, b in enumerate(_batches(6)):
            if i == 3:
                resilience.request_drain()
            yield b

    try:
        exe.train_from_dataset(main, draining(), scope=sc,
                               fetch_list=[loss], elastic=c,
                               print_period=10 ** 6, prefetch=False)
        assert c.manager.latest_step() == 3
        assert os.path.isfile(c._path("leave_r0.json"))
        assert not resilience.drain_requested()
        cnt = monitor.snapshot()["counters"]
        assert cnt["resilience.elastic_drains"] == 1
        assert cnt["resilience.elastic_drain_exits"] == 1
        assert cnt["resilience.elastic_rank_leaves"] == 1
    finally:
        c.uninstall()


def test_elastic_records_ride_jsonl_and_report(tmp_path):
    """kind="elastic" records land on the telemetry stream and the
    report tool renders the topology history from them."""
    from paddle_tpu.monitor.jsonl_writer import read_jsonl
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    from tools.telemetry_report import summarize

    path = tmp_path / "t.jsonl"
    monitor.enable(jsonl_path=str(path))
    c = _coord(tmp_path / "ck", 0, 2)
    c.install()
    try:
        c.begin_transition("shrink", 5, 1, reason="rank_loss",
                           ranks=[1])
        c.commit_transition([0], 5)
    finally:
        c.uninstall()
        monitor.disable()
    recs = monitor.elastic_records()
    assert any(r["event"] == "transition_begin" for r in recs)
    on_disk = [r for r in read_jsonl(str(path))
               if r.get("kind") == "elastic"]
    assert any(r.get("event") == "transition_commit" for r in on_disk)
    assert all("process_index" in r for r in on_disk)  # rank-tagged
    rep = summarize(read_jsonl(str(path)))
    topo = rep["elastic_topology"]
    assert topo["transitions"][0]["transition"] == "shrink"
    assert topo["transitions"][0]["to_world"] == 1
    assert topo["current"]["world"] == 1


def test_retarget_dp_retraces_on_new_devices():
    """The compiler hook: retarget_dp onto a different device set must
    retrace (compiled-step cache keys on device identity), including a
    SAME-SIZED different set."""
    monitor.enable()
    main, startup, loss = _train_prog()
    prog = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=list(jax.devices()[:2]))
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    b = _batches(1)[0]
    exe.run(prog, feed=b, fetch_list=[loss], scope=sc)
    miss0 = monitor.snapshot()["counters"]["compiled_step.miss"]
    exe.run(prog, feed=b, fetch_list=[loss], scope=sc)
    assert monitor.snapshot()["counters"]["compiled_step.miss"] == miss0
    prog.retarget_dp(list(jax.devices()[2:4]))      # same size, new devs
    exe._check_state_placement = True
    exe.run(prog, feed=b, fetch_list=[loss], scope=sc)
    assert monitor.snapshot()["counters"]["compiled_step.miss"] \
        == miss0 + 1
    prog.retarget_dp(list(jax.devices()[:1]))       # shrink to one
    exe._check_state_placement = True
    out = exe.run(prog, feed=b, fetch_list=[loss], scope=sc)
    assert np.isfinite(np.asarray(out[0])).all()
    assert monitor.snapshot()["counters"]["compiled_step.miss"] \
        == miss0 + 2


def test_checkpointless_preempt_warning_names_the_flags():
    """Satellite: the checkpoint-less preempted-loop warning must tell
    the user WHAT to set — checkpoint= for durability, the SIGUSR1
    drain signal for elastic leaves."""
    main, startup, loss = _train_prog()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.request_preemption()
    with pytest.warns(RuntimeWarning) as rec:
        exe.train_from_dataset(main, _batches(2), scope=sc,
                               fetch_list=[loss], prefetch=False)
    resilience.clear_preemption()
    msg = "".join(str(w.message) for w in rec)
    assert "checkpoint=" in msg
    assert "SIGUSR1" in msg


# ---------------------------------------------------------------------------
# two real processes: kill one, reshard, let it rejoin
# ---------------------------------------------------------------------------

def test_a_killed_rank_is_resharded_around_and_rejoins(tmp_path):
    """A REAL 2-process CPU-mesh dp train (tests/dist_worker_elastic.py)
    in which rank 1 is KILLED at a step boundary (InjectedCrash at
    `elastic.step_boundary`), the survivor reshards 2->1 in process and
    trains on, a join intent grows the fleet 1->2 through a relaunch,
    and the pair finishes; beside it an uninterrupted reference with the
    SAME topology schedule (2 procs, 1 proc, 2 procs at the same
    boundaries, no kill, no elastic machinery), since dp arithmetic
    depends on the shard count.  One test for the whole arc: the
    processes are real and share the CPU with the other workers, so a
    run that goes wrong costs one failure, which names every statement
    about the recovery that did not hold."""
    from paddle_tpu.distributed.launch import start_procs
    from paddle_tpu.resilience.elastic import request_join

    total, kill_at, grow_at, batch = 12, 4, 8, 8
    tmp = str(tmp_path)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(repo, "tests", "dist_worker_elastic.py")

    def run_phase(run, phase, nproc, start, end, elastic_on,
                  expect_rc=None):
        out_dir = os.path.join(tmp, run)
        cfg = {"phase": phase, "ckpt_dir": os.path.join(tmp, f"ck_{run}"),
               "out_dir": out_dir, "total_steps": total,
               "kill_at": kill_at, "grow_at": grow_at, "batch": batch,
               "start_step": start, "end_step": end,
               "elastic": elastic_on, "peer_timeout_s": 8.0,
               "report": os.path.join(out_dir, "report")}
        os.makedirs(out_dir, exist_ok=True)
        cpath = os.path.join(out_dir, f"cfg_{phase}.json")
        with open(cpath, "w") as f:
            json.dump(cfg, f)
        procs, logs = start_procs(
            node_ips=["127.0.0.1"], node_ip="127.0.0.1",
            nproc_per_node=nproc, training_script=worker,
            script_args=(cpath,),
            log_dir=os.path.join(out_dir, f"logs_{phase}"),
            env_extra={"PYTHONPATH": repo + os.pathsep
                       + os.environ.get("PYTHONPATH", ""),
                       "PADDLE_RENDEZVOUS_TIMEOUT": "60"})
        deadline = time.time() + 180
        while time.time() < deadline and any(
                p.poll() is None for p in procs):
            time.sleep(0.3)
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for f in logs:
            f.close()
        rcs = [p.poll() for p in procs]
        want = expect_rc if expect_rc is not None else [0] * nproc
        ok = all((r == 0) == (w == 0) for r, w in zip(rcs, want))
        reports = {}
        for r in range(nproc):
            rp = f"{cfg['report']}.{phase}.r{r}"
            if os.path.isfile(rp):
                with open(rp) as f:
                    reports[r] = json.load(f)
        return ok, rcs, reports

    checks = {}

    # ---- chaos run: kill at kill_at, rejoin at grow_at ---------------
    request_join(os.path.join(tmp, "ck_chaos"), 1, after_step=grow_at)
    ok_a, rcs_a, rep_a = run_phase("chaos", "chaos_a", 2, 0, total, True,
                                   expect_rc=[0, 1])
    r0a = rep_a.get(0) or {}
    checks["chaos_a_procs"] = ok_a and 0 in rep_a
    checks["kill_fired"] = rcs_a[1] not in (0, None) and 1 not in rep_a
    events = r0a.get("events") or []
    death = next((e for e in events if e["kind"] == "rank_death"), None)
    checks["rank_death_named"] = (death is not None
                                  and death["ranks"] == [1]
                                  and death["step"] == kill_at)
    checks["shrunk_at_kill"] = r0a.get("shrunk_at") == kill_at
    health = r0a.get("health") or {}
    checks["healthz_503_during_transition"] = (
        (health.get("during") or {}).get("status") == 503
        and (health.get("during") or {}).get("reason")
        == "elastic_transition")
    checks["healthz_ok_after_commit"] = (
        (health.get("after") or {}).get("status") == 200
        and (health.get("after") or {}).get("ok") is True)
    checks["grow_relaunch"] = (r0a.get("exit_action") == "relaunch"
                               and r0a.get("steps_done") == grow_at
                               and r0a.get("ckpt_latest") == grow_at)
    counters = r0a.get("counters") or {}
    checks["elastic_counters"] = (
        counters.get("resilience.elastic_transitions") == 2
        and counters.get("resilience.elastic_shrinks") == 1
        and counters.get("resilience.elastic_grows") == 1
        and counters.get("resilience.elastic_rank_deaths", 0) >= 1
        and counters.get("resilience.elastic_reshards") == 1
        and counters.get("resilience.elastic_rank_joins") == 1)
    checks["process_count_gauge"] = (
        (r0a.get("gauges") or {}).get("fleet.process_count") == 2)

    ok_b, _, rep_b = run_phase("chaos", "chaos_b", 2, grow_at, total, True)
    r0b = rep_b.get(0) or {}
    checks["chaos_b_procs"] = ok_b and 0 in rep_b
    checks["rejoin_resumed"] = (
        r0b.get("restored_step") == grow_at
        and (r0b.get("counters") or {})
        .get("resilience.elastic_resumes") == 1
        and r0b.get("steps_done") == total)
    checks["topology_provenance"] = (
        (r0b.get("restored_topology") or {}).get("world") == 1)

    # ---- clean reference: same topology schedule, no kill ------------
    ok_c1, _, rep_c1 = run_phase("clean", "clean_a", 2, 0, kill_at, False)
    ok_c2, _, rep_c2 = run_phase("clean", "clean_b", 1, kill_at, grow_at,
                                 False)
    ok_c3, _, rep_c3 = run_phase("clean", "clean_c", 2, grow_at, total,
                                 False)
    checks["clean_reference_ran"] = ok_c1 and ok_c2 and ok_c3
    final_chaos = r0b.get("final_params")
    final_clean = (rep_c3.get(0) or {}).get("final_params")
    checks["params_bitwise_identical"] = (
        final_chaos is not None and final_clean is not None
        and set(final_chaos) == set(final_clean)
        and all(np.array_equal(np.asarray(final_chaos[n]),
                               np.asarray(final_clean[n]))
                for n in final_chaos))
    # the loss streams line up leg by leg too (same batches, same
    # worlds): A(0..kill) + shrunken(kill..grow) + B(grow..end)
    chaos_losses = (r0a.get("losses") or []) + (r0b.get("losses") or [])
    clean_losses = [x for rep in (rep_c1, rep_c2, rep_c3)
                    for x in (rep.get(0) or {}).get("losses") or []]
    checks["loss_stream_identical"] = (
        len(chaos_losses) == total and chaos_losses == clean_losses)

    # ---- topology history in the merged fleet report -----------------
    sys.path.insert(0, repo)
    try:
        from tools.telemetry_report import fleet_merge, summarize_fleet
    finally:
        sys.path.pop(0)
    tdir = os.path.join(tmp, "chaos", "telemetry")
    by_rank, merged = fleet_merge(sorted(
        os.path.join(tdir, p) for p in os.listdir(tdir)
        if p.endswith(".jsonl")))
    trans = (summarize_fleet(by_rank, merged).get("elastic_topology")
             or {}).get("transitions") or []
    checks["topology_history_reported"] = (
        len(trans) == 2
        and trans[0].get("transition") == "shrink"
        and trans[0].get("to_world") == 1
        and trans[1].get("transition") == "grow"
        and trans[1].get("to_world") == 2)
    assert not [name for name, held in checks.items() if not held], checks
