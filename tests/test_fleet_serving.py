"""Fleet serving tier tests (ISSUE 19): the versioned registry's
atomic publish/flip/rollback protocol (including a concurrent reader
racing a publish and a crash between payload and marker), the replica
worker's zero-drop hot-swap and AOT cold-start path, the router's
health gating + classified failover + merged-ledger identity, the
replica-kill chaos primitive, and exporter/report surfaces.

Determinism strategy: replicas run IN-PROCESS (ReplicaServer on
ephemeral loopback ports) so death is a closed socket the test
controls; the REAL process kill (os._exit) is exercised once through a
subprocess."""

import http.client
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.inference import Predictor
from paddle_tpu.resilience import faultinject, taxonomy
from paddle_tpu.serving import (FleetRouter, ModelHost, ModelRegistry,
                                NoReplicaAvailable, RegistryError,
                                ReplicaRequestError, ReplicaServer,
                                ReplicaUnavailable)
from paddle_tpu.serving.fleet import router_table
from paddle_tpu.serving.runtime import DeadlineExceeded


# ---------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------

def _build_model(dirname, hidden):
    """One tiny saved inference model; `hidden` varies the topology so
    two builds are guaranteed to predict differently."""
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 6])
            h = fluid.layers.fc(x, hidden, act="relu")
            out = fluid.layers.fc(h, 3, act="softmax")
    exe = fluid.Executor()
    exe.run(startup)
    fluid.io.save_inference_model(dirname, ["x"], [out], exe,
                                  main_program=main)
    return dirname


@pytest.fixture(scope="module")
def model_dirs(tmp_path_factory):
    """Two distinct model artifacts (the v1/v2 payloads)."""
    a = _build_model(str(tmp_path_factory.mktemp("model_a")), 8)
    b = _build_model(str(tmp_path_factory.mktemp("model_b")), 4)
    return a, b


@pytest.fixture()
def registry(model_dirs, tmp_path):
    """A registry with both models published and CURRENT -> v1."""
    reg = ModelRegistry(str(tmp_path / "reg"))
    v1 = reg.publish(model_dirs[0])
    v2 = reg.publish(model_dirs[1])
    assert (v1, v2) == (1, 2)
    reg.set_current(v1)
    return reg


@pytest.fixture(autouse=True)
def _clean_state():
    faultinject.disarm()
    monitor.disable()
    monitor.reset()
    yield
    faultinject.disarm()
    monitor.disable()
    monitor.reset()


_REPLICA_KW = {"max_batch_size": 2, "batch_window_s": 0.0}


def _feed(rows=1, seed=0):
    return {"x": np.random.default_rng(seed)
            .standard_normal((rows, 6)).astype(np.float32)}


def _label(prefix):
    return f"{prefix}-{time.perf_counter_ns()}"


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _post(port, path, doc):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("POST", path, body=json.dumps(doc).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


# ---------------------------------------------------------------------
# registry: atomic publish / flip / rollback
# ---------------------------------------------------------------------

def test_registry_publish_and_pointer(registry, model_dirs):
    assert registry.versions() == [1, 2]
    assert registry.latest() == 2
    assert registry.current() == 1
    # payload is a faithful copy: the registry version predicts
    # bitwise-identically to the source artifact
    feed = _feed(2)
    ref = Predictor(model_dirs[0]).run(feed)
    got = Predictor(registry.version_dir(1)).run(feed)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    registry.set_current(2)
    assert registry.current() == 2
    assert registry.current_dir() == registry.version_dir(2)


def test_registry_rejects_double_publish(registry, model_dirs):
    with pytest.raises(RegistryError):
        registry.publish(model_dirs[0], version=1)


def test_registry_rejects_incomplete_current(registry, tmp_path):
    # a version directory without its marker does not exist as far as
    # the pointer is concerned
    os.makedirs(registry.version_dir(7))
    with pytest.raises(RegistryError):
        registry.set_current(7)
    assert registry.versions() == [1, 2]


def test_registry_crash_before_marker_hides_version(model_dirs,
                                                    tmp_path):
    """A publisher killed between payload write and marker leaves an
    INVISIBLE version (the marker protocol's whole point), and the
    retried publish of the same version succeeds."""
    reg = ModelRegistry(str(tmp_path / "reg"))
    with pytest.raises(faultinject.InjectedCrash):
        with faultinject.plan_scope(
                crash_points={"registry.before_marker": 0}):
            reg.publish(model_dirs[0], version=1)
    assert reg.versions() == []          # payload is there, marker not
    assert reg.current() is None
    assert reg.publish(model_dirs[0], version=1) == 1
    assert reg.versions() == [1]


def test_registry_concurrent_reader_never_sees_partial(model_dirs,
                                                       tmp_path):
    """A reader listing/loading concurrently with publishes must only
    ever observe COMPLETE versions: every version it lists verifies its
    manifest and carries the full payload."""
    reg = ModelRegistry(str(tmp_path / "reg"))
    stop = threading.Event()
    failures = []

    def reader():
        while not stop.is_set():
            for v in reg.versions():
                vdir = reg.version_dir(v)
                try:
                    if not reg._is_complete(vdir):
                        failures.append(f"v{v} listed but incomplete")
                    for f in ("__model__.json", "__params__.npz"):
                        if not os.path.isfile(os.path.join(vdir, f)):
                            failures.append(f"v{v} missing {f}")
                except Exception as e:  # noqa: BLE001 — test verdict
                    failures.append(f"v{v}: {e}")
            cur = reg.current()
            if cur is not None and cur not in reg.versions():
                failures.append(f"CURRENT -> unpublished v{cur}")

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    try:
        for i in range(8):
            v = reg.publish(model_dirs[i % 2])
            reg.set_current(v)
    finally:
        stop.set()
        t.join(timeout=10)
    assert not failures, failures
    assert reg.versions() == list(range(1, 9))


def test_registry_rollback_is_bitwise(registry):
    """Version payloads are immutable, so re-flipping CURRENT back to
    v1 restores bitwise-identical predictions — rollback is the same
    atomic pointer flip pointed backwards."""
    feed = _feed(3, seed=7)
    before = [np.asarray(o)
              for o in Predictor(registry.current_dir()).run(feed)]
    registry.set_current(2)
    swapped = [np.asarray(o)
               for o in Predictor(registry.current_dir()).run(feed)]
    assert any(not np.array_equal(a, b)
               for a, b in zip(before, swapped))
    registry.set_current(1)
    after = [np.asarray(o)
             for o in Predictor(registry.current_dir()).run(feed)]
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)


def test_registry_aot_cell_idempotent(registry, tmp_path):
    calls = []

    def writer(d):
        calls.append(d)
        with open(os.path.join(d, "b1.jaxexport"), "wb") as f:
            f.write(b"artifact")
        return 1

    assert registry.publish_aot(1, "TPU v4", writer) == 1
    assert registry.has_aot(1, "TPU v4")
    # first publisher wins: a complete cell is left untouched
    assert registry.publish_aot(1, "TPU v4", writer) == 0
    assert len(calls) == 1
    # a writer that stages nothing marks nothing complete
    assert registry.publish_aot(2, "TPU v4", lambda d: 0) == 0
    assert not registry.has_aot(2, "TPU v4")
    # device kinds with spaces sanitize into distinct cells
    assert registry.aot_dir(1, "TPU v4") != registry.aot_dir(1, "TPUv4")


# ---------------------------------------------------------------------
# taxonomy: the failover class
# ---------------------------------------------------------------------

def test_is_failover_classes():
    assert taxonomy.is_failover(ConnectionResetError("peer reset"))
    assert taxonomy.is_failover(ConnectionRefusedError("refused"))
    import http.client as hc

    assert taxonomy.is_failover(hc.RemoteDisconnected("closed"))
    assert taxonomy.is_failover(
        faultinject.InjectedTransientError("RESOURCE_EXHAUSTED: x"))
    assert taxonomy.is_failover(ReplicaUnavailable("503"))
    # deadline/fatal shapes must NOT fail over: a spent budget cannot
    # be un-spent by moving replicas, a bad request fails everywhere
    assert not taxonomy.is_failover(DeadlineExceeded("late"))
    assert not taxonomy.is_failover(ValueError("bad feed"))
    assert not taxonomy.is_failover(ReplicaRequestError("fatal"))
    # chained causes are walked, like is_transient does
    try:
        try:
            raise ConnectionResetError("inner")
        except ConnectionResetError as inner:
            raise RuntimeError("wrapped") from inner
    except RuntimeError as outer:
        assert taxonomy.is_failover(outer)


# ---------------------------------------------------------------------
# faultinject: the replica-kill primitive
# ---------------------------------------------------------------------

def test_kill_point_noop_unarmed_and_unscheduled():
    faultinject.kill_point("replica.infer")       # disarmed: no-op
    with faultinject.plan_scope(kill_points={"other.point": 0}):
        faultinject.kill_point("replica.infer")   # unscheduled: no-op


def test_kill_point_exits_process_on_scheduled_hit():
    """The kill is a REAL os._exit(1): no exception, no cleanup — run
    it in a subprocess and assert the death landed on the scheduled
    (0-based) hit, not before."""
    code = (
        "from paddle_tpu.resilience import faultinject\n"
        "p = faultinject.arm(kill_points={'replica.infer': 1})\n"
        "faultinject.kill_point('replica.infer')\n"
        "print('survived-hit-0', flush=True)\n"
        "faultinject.kill_point('replica.infer')\n"
        "print('survived-hit-1', flush=True)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 1, r.stderr
    assert "survived-hit-0" in r.stdout
    assert "survived-hit-1" not in r.stdout


# ---------------------------------------------------------------------
# replica worker: serve / drain / hot-swap / AOT cold start
# ---------------------------------------------------------------------

def test_replica_server_serves_and_reports(registry):
    srv = ReplicaServer(registry, name=_label("rep"),
                        config_kw=dict(_REPLICA_KW))
    try:
        assert srv.host.version == 1      # from the CURRENT pointer
        feed = _feed(2)
        status, doc = _post(srv.port, "/infer",
                            {"feed": {k: v.tolist()
                                      for k, v in feed.items()}})
        assert status == 200 and doc["version"] == 1
        ref = Predictor(registry.version_dir(1)).run(feed)
        for r, g in zip(ref, doc["outputs"]):
            np.testing.assert_array_equal(
                np.asarray(r), np.asarray(g, dtype=np.float32))
        status, health = _get(srv.port, "/healthz")
        assert status == 200 and health["ok"] \
            and health["version"] == 1
        status, stats = _get(srv.port, "/stats")
        assert status == 200
        merged = stats["merged"]
        assert merged["requests"] == 1 \
            and merged["outcomes"]["completed"] == 1 \
            and merged["pending"] == 0
    finally:
        srv.close()


def test_replica_drain_gates_health_and_requests(registry):
    srv = ReplicaServer(registry, name=_label("rep"),
                        config_kw=dict(_REPLICA_KW))
    try:
        srv.drain()
        status, health = _get(srv.port, "/healthz")
        assert status == 503 and health["reason"] == "draining"
        status, doc = _post(srv.port, "/infer",
                            {"feed": {"x": _feed()["x"].tolist()}})
        assert status == 503 and doc["kind"] == "draining"
    finally:
        srv.close()


def test_replica_hot_swap_and_rollback_bitwise(registry):
    """Swap v1->v2->v1 over HTTP: versions flip, the per-version
    ledgers accumulate into one merged identity, and the rolled-back
    version predicts bitwise-identically to its pre-swap self."""
    srv = ReplicaServer(registry, name=_label("rep"),
                        config_kw=dict(_REPLICA_KW))
    try:
        feed_doc = {"feed": {"x": _feed(2, seed=3)["x"].tolist()}}
        _, before = _post(srv.port, "/infer", feed_doc)
        status, doc = _post(srv.port, "/swap", {"version": 2})
        assert status == 200 and doc == {"version": 2, "previous": 1}
        _, on_v2 = _post(srv.port, "/infer", feed_doc)
        assert on_v2["version"] == 2
        assert on_v2["outputs"] != before["outputs"]
        status, doc = _post(srv.port, "/swap", {"version": 1})
        assert status == 200 and doc["version"] == 1
        _, after = _post(srv.port, "/infer", feed_doc)
        assert after["version"] == 1
        for a, b in zip(before["outputs"], after["outputs"]):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))
        _, stats = _get(srv.port, "/stats")
        assert stats["swaps"] == 2
        assert [r["version"] for r in stats["merged"]["per_version"]] \
            == [1, 2, 1]
        merged = stats["merged"]
        assert merged["requests"] == 3 == merged["resolved"]
        assert merged["pending"] == 0
    finally:
        srv.close()


def test_replica_swap_under_traffic_drops_nothing(registry):
    """Zero-drop hot-swap: requests flow while the version flips
    forward and back; EVERY issued request completes (the outgoing
    runtime drains, the flip race resubmits) and the merged ledger
    resolves everything."""
    host = ModelHost(registry, name=_label("host"),
                     config_kw=dict(_REPLICA_KW))
    host.start(1)
    errors = []
    done = threading.Event()
    completed = [0]

    def traffic():
        i = 0
        while not done.is_set():
            try:
                host.run(_feed(1, seed=i))
                completed[0] += 1
            except Exception as e:  # noqa: BLE001 — test verdict
                errors.append(repr(e))
            i += 1

    threads = [threading.Thread(target=traffic, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    try:
        assert host.swap_to(2) == 1
        assert host.swap_to(1) == 2
    finally:
        done.set()
        for t in threads:
            t.join(timeout=30)
        host.close()
    assert not errors, errors[:3]
    assert completed[0] > 0
    merged = host.merged_ledger()
    assert merged["requests"] == completed[0]
    assert merged["outcomes"].get("completed", 0) == completed[0]
    assert merged["pending"] == 0          # the zero-silent-loss line


def test_aot_cache_cold_start_zero_compiles(registry):
    """The first host to warm v1 publishes per-bucket artifacts; a
    SECOND (cold) host imports them and reaches first byte with ZERO
    serving compile-ledger events — and predicts bitwise-identically."""
    warm = ModelHost(registry, name=_label("warm"),
                     config_kw=dict(_REPLICA_KW))
    warm.start(1)
    feed = _feed(2, seed=5)
    ref = warm.run(feed)
    try:
        if not warm.aot_exported:
            pytest.skip("jax.export unavailable on this jax build")
        import jax

        kind = jax.devices()[0].device_kind
        assert registry.has_aot(1, kind)
        monitor.enable()            # fresh ledger for the cold start
        cold = ModelHost(registry, name=_label("cold"),
                         config_kw=dict(_REPLICA_KW))
        cold.start(1)
        try:
            assert cold.aot_imported > 0
            got = cold.run(feed)
            serving_events = [
                e for e in monitor.compile_events()
                if str(e.get("key", "")).startswith("serving/")]
            assert serving_events == []
            doc = cold.stats_doc()
            assert doc["serving_compile_events"] == 0
            for r, g in zip(ref, got):
                np.testing.assert_array_equal(np.asarray(r),
                                              np.asarray(g))
        finally:
            cold.close()
    finally:
        warm.close()


# ---------------------------------------------------------------------
# fleet router: health gating, failover, merged ledger
# ---------------------------------------------------------------------

def _mk_fleet(registry, n=2, **kw):
    reps = [ReplicaServer(registry, name=f"r{i}",
                          config_kw=dict(_REPLICA_KW))
            for i in range(n)]
    router = FleetRouter(
        [(s.host_model.name, "127.0.0.1", s.port) for s in reps],
        label=_label("fleet"), auto_poll=False,
        request_timeout_s=10.0, **kw)
    return router, reps


def test_router_routes_and_ledger_reconciles(registry):
    router, reps = _mk_fleet(registry)
    try:
        for i in range(6):
            outs = router.run(_feed(1, seed=i))
            assert np.asarray(outs[0]).shape == (1, 3)
        router.poll_once()
        ledger = router.fleet_ledger()
        assert ledger["router"]["requests"] == 6
        assert ledger["router"]["outcomes"]["completed"] == 6
        # both replicas took traffic (round robin)
        by_rep = [r["ledger"]["requests"] for r in ledger["replicas"]]
        assert sum(by_rep) == 6 and all(n > 0 for n in by_rep)
        merged = ledger["merged"]
        assert merged["requests"] == merged["resolved"] == 12
        assert merged["unaccounted"] == 0
        assert ledger["attempts"] == {"started": 6, "resolved": 6,
                                      "unaccounted": 0}
        assert ledger["failovers"] == 0
    finally:
        router.close(emit=False)
        for s in reps:
            s.close()


def test_router_failover_absorbs_replica_death(registry):
    """Kill one replica's socket mid-fleet: the next request routed at
    it fails with a connection shape, is classified failover, retries
    on the survivor, and COMPLETES — the caller never sees the death."""
    router, reps = _mk_fleet(registry)
    try:
        reps[0].kill()                 # socket gone: resets/refusals
        completed = 0
        for i in range(4):
            outs = router.run(_feed(1, seed=i))
            completed += len(outs) and 1
        assert completed == 4
        assert router.failovers >= 1
        s = router.stats.summary()
        assert s["outcomes"]["completed"] == 4
        assert s["outcomes"].get("failed", 0) == 0
        # the dead socket was demoted inline, without waiting a poll
        dead = [r for r in router.replicas if r.name == "r0"][0]
        assert not dead.healthy
        assert router.attempts_started == router.attempts_resolved
        for _ in range(4):             # declared dead, not just stale
            router.poll_once()
        assert dead.dead
    finally:
        router.close(emit=False)
        for s in reps:
            s.close()


def test_one_trace_id_spans_the_router_hop_and_the_replica(registry):
    """With request tracing on, the router's tree (its `route:` span)
    and the tree of the replica that served the request carry ONE
    trace id: the router sends its context along, the replica joins
    it."""
    from paddle_tpu.monitor import tracing

    old = fluid.get_flags(["FLAGS_request_tracing", "FLAGS_trace_sample"])
    fluid.set_flags({"FLAGS_request_tracing": True,
                     "FLAGS_trace_sample": 1.0})
    router, reps = _mk_fleet(registry)
    try:
        router.run(_feed(1))
        label = router.label
        trees = tracing.get().retained_trees()
        router_trees = [t for t in trees if t["label"] == label]
        replica_ids = {t["trace_id"] for t in trees if t["label"] != label}
        joined = [t for t in router_trees if t["trace_id"] in replica_ids]
        assert joined
        assert any("route:" in (s.get("name") or "")
                   for t in joined for s in t["spans"])
    finally:
        router.close(emit=False)
        for s in reps:
            s.close()
        fluid.set_flags(old)


def test_router_rejects_when_no_replica_routable(registry):
    router, reps = _mk_fleet(registry)
    try:
        for rep in router.replicas:
            rep.healthy = False
        with pytest.raises(NoReplicaAvailable):
            router.run(_feed())
        s = router.stats.summary()
        # the rejection is LEDGERED: requests == sum(outcomes) holds
        assert s["requests"] == 1 == s["outcomes"]["rejected"]
    finally:
        router.close(emit=False)
        for s in reps:
            s.close()


def test_router_fatal_request_does_not_fail_over(registry):
    """A bad request (missing feed) fails identically on every replica;
    the router must NOT burn failover attempts on it."""
    router, reps = _mk_fleet(registry)
    try:
        with pytest.raises(ReplicaRequestError):
            router.run({"wrong_name": np.zeros((1, 6), np.float32)})
        assert router.failovers == 0
        assert router.stats.summary()["outcomes"]["failed"] == 1
    finally:
        router.close(emit=False)
        for s in reps:
            s.close()


def test_router_health_poll_gates_draining_replica(registry):
    router, reps = _mk_fleet(registry)
    try:
        reps[0].drain()
        router.poll_once()
        gated = [r for r in router.replicas if r.name == "r0"][0]
        assert not gated.healthy and gated.draining
        live = [r for r in router.replicas if r.name == "r1"][0]
        assert live.healthy and live.version == 1
        # traffic only reaches the survivor
        for i in range(3):
            router.run(_feed(1, seed=i))
        router.poll_once()
        ledger = router.fleet_ledger()
        rows = {r["name"]: r for r in ledger["replicas"]}
        assert rows["r1"]["ledger"]["requests"] == 3
        assert (rows["r0"]["ledger"] or {}).get("requests", 0) == 0
    finally:
        router.close(emit=False)
        for s in reps:
            s.close()


def test_router_roll_swaps_fleet_and_back_bitwise(registry):
    """roll(v) hot-swaps every replica under router traffic; rolling
    back restores bitwise-identical fleet predictions."""
    router, reps = _mk_fleet(registry)
    try:
        feed = _feed(2, seed=11)
        before = [np.asarray(o) for o in router.run(feed)]
        res = router.roll(2)
        assert all(r.get("version") == 2 for r in res.values()), res
        on_v2 = [np.asarray(o) for o in router.run(feed)]
        assert any(not np.array_equal(a, b)
                   for a, b in zip(before, on_v2))
        res = router.roll(1)
        assert all(r.get("version") == 1 for r in res.values()), res
        after = [np.asarray(o) for o in router.run(feed)]
        for a, b in zip(before, after):
            np.testing.assert_array_equal(a, b)
        router.poll_once()
        merged = router.fleet_ledger()["merged"]
        assert merged["unaccounted"] == 0
    finally:
        router.close(emit=False)
        for s in reps:
            s.close()


# ---------------------------------------------------------------------
# least-loaded routing (ISSUE 20 satellite)
# ---------------------------------------------------------------------

def _mk_offline_router(n=3, **kw):
    """A router over unreachable endpoints — _pick ordering is pure
    cached-state logic, so no sockets are needed to test it."""
    return FleetRouter(
        [(f"r{i}", "127.0.0.1", 1 + i) for i in range(n)],
        label=_label("ll"), auto_poll=False, **kw)


def _set_load(rep, depth=None, in_flight=None):
    active = {}
    if depth is not None:
        active["queue_depth"] = depth
    if in_flight is not None:
        active["in_flight"] = in_flight
    rep.last_stats = {"active": active} if active else {"active": {}}


def test_least_loaded_rejects_unknown_policy():
    with pytest.raises(ValueError):
        _mk_offline_router(policy="fastest_guess")


def test_least_loaded_picks_smallest_scraped_load():
    router = _mk_offline_router(policy="least_loaded")
    try:
        _set_load(router.replicas[0], depth=4, in_flight=1)
        _set_load(router.replicas[1], depth=0, in_flight=1)
        _set_load(router.replicas[2], depth=2, in_flight=2)
        # load is queue_depth + in_flight: r1 (1) < r2 (4) < r0 (5);
        # the pick ignores the rr rotation while loads differ
        for _ in range(4):
            assert router._pick(set()).name == "r1"
        # a failover that already tried the least-loaded replica moves
        # to the next-least-loaded, not back to rr order
        assert router._pick({"r1"}).name == "r2"
    finally:
        router.close(emit=False)


def test_least_loaded_missing_stats_sort_last():
    router = _mk_offline_router(policy="least_loaded")
    try:
        _set_load(router.replicas[0], depth=2)
        _set_load(router.replicas[1], in_flight=2)
        # r2 never produced a stats doc: unknown, NOT idle — while any
        # replica has a scraped load, the unknown one is picked last
        picks = [router._pick(set()).name for _ in range(4)]
        assert set(picks) == {"r0", "r1"}
        # ...and the r0/r1 TIE keeps rotating round-robin
        assert picks[0] != picks[1]
        assert router._pick({"r0", "r1"}).name == "r2"
    finally:
        router.close(emit=False)


def test_least_loaded_without_any_stats_is_round_robin():
    router = _mk_offline_router(policy="least_loaded")
    try:
        picks = [router._pick(set()).name for _ in range(6)]
        assert picks == ["r0", "r1", "r2", "r0", "r1", "r2"]
    finally:
        router.close(emit=False)


def test_least_loaded_end_to_end_and_record_carries_policy(registry):
    router, reps = _mk_fleet(registry, policy="least_loaded")
    try:
        router.poll_once()            # land real /stats docs
        for i in range(4):
            outs = router.run(_feed(1, seed=i))
            assert np.asarray(outs[0]).shape == (1, 3)
        s = router.stats.summary()
        assert s["outcomes"]["completed"] == 4
        rec = router.fleet_record()
        assert rec["policy"] == "least_loaded"
    finally:
        router.close(emit=False)
        for s in reps:
            s.close()


# ---------------------------------------------------------------------
# observability: exporter families + report section + telemetry record
# ---------------------------------------------------------------------

def test_exporter_fleet_families_contiguous(registry):
    from paddle_tpu.monitor import exporter

    router, reps = _mk_fleet(registry)
    try:
        router.run(_feed())
        router.poll_once()
        text = exporter.prometheus_text()
        parsed = exporter.parse_prometheus(text)

        def key(name, **labels):
            return (name, tuple(sorted(labels.items())))

        assert parsed[key("paddle_tpu_fleet_failovers_total",
                          router=router.label)] == 0.0
        assert parsed[key("paddle_tpu_fleet_attempts_unaccounted",
                          router=router.label)] == 0.0
        for rep in ("r0", "r1"):
            assert parsed[key("paddle_tpu_fleet_replica_healthy",
                              router=router.label, replica=rep)] == 1.0
            assert parsed[key("paddle_tpu_fleet_replica_version",
                              router=router.label, replica=rep)] == 1.0
            assert parsed[key("paddle_tpu_fleet_replica_breaker_open",
                              router=router.label, replica=rep)] == 0.0
        # exposition-format regression: ALL samples of one family must
        # be contiguous — interleaving families row-by-row splits them
        order = []
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            name = line.split("{", 1)[0].split(" ", 1)[0]
            if not order or order[-1] != name:
                order.append(name)
        assert len(order) == len(set(order)), (
            f"family split across the scrape: {order}")
    finally:
        router.close(emit=False)
        for s in reps:
            s.close()


def test_router_emits_fleet_serving_record(registry, tmp_path):
    jsonl = str(tmp_path / "telemetry.jsonl")
    monitor.enable(jsonl_path=jsonl)
    router, reps = _mk_fleet(registry)
    try:
        router.run(_feed())
        router.poll_once()
    finally:
        router.close()                   # emits the record
        for s in reps:
            s.close()
    recs = monitor.fleet_serving_records()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["kind"] == "fleet_serving" \
        and rec["label"] == router.label
    assert rec["merged"]["unaccounted"] == 0
    assert rec["attempts"]["unaccounted"] == 0
    monitor.disable()
    streamed = [r for r in monitor.read_jsonl(jsonl)
                if r.get("kind") == "fleet_serving"]
    assert len(streamed) == 1            # rides the JSONL stream too
    json.dumps(rec)                      # json-safe end to end


def test_report_fleet_serving_section(registry):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    monitor.enable()
    router, reps = _mk_fleet(registry)
    try:
        router.run(_feed())
        reps[0].kill()
        router.run(_feed())              # one of these two hits the
        router.run(_feed())              # dead socket -> failover
        router.poll_once()
    finally:
        router.close()
        for s in reps:
            s.close()
    records = monitor.fleet_serving_records()
    out = telemetry_report.summarize(records)
    section = out["fleet_serving"]
    assert section["routers"] == 1
    row = section["by_router"][router.label]
    assert row["requests"] == 3
    assert row["outcomes"]["completed"] == 3
    assert row["failovers"] >= 1
    assert "UNACCOUNTED" not in row      # zero silent losses
    assert row["merged_requests"] == row["merged_resolved"]
    assert set(row["replicas"]) == {"r0", "r1"}
    # a record with losses surfaces them LOUDLY
    lossy = dict(records[-1])
    lossy["merged"] = dict(lossy["merged"], unaccounted=3)
    out = telemetry_report.summarize([lossy])
    assert out["fleet_serving"]["by_router"][router.label][
        "UNACCOUNTED"] == 3


def test_router_table_reads_cached_state_only(registry):
    router, reps = _mk_fleet(registry)
    try:
        for s in reps:
            s.close()                    # sockets gone
        rows = [r for r in router_table()
                if r["label"] == router.label]
        # no I/O on the scrape path: dead sockets cannot stall it
        t0 = time.perf_counter()
        assert rows and len(rows[0]["replicas"]) == 2
        assert time.perf_counter() - t0 < 1.0
    finally:
        router.close(emit=False)
