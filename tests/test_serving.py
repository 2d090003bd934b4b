"""Hardened serving runtime tests (ISSUE 8): bucket batching
correctness (bitwise vs the unbatched predictor), deadline shedding,
backpressure rejection, the circuit-breaker state machine, watchdog
dump + escalation on injected hangs, degraded-mode fallback, and
counter/record/trace well-formedness.

Determinism strategy: batching tests drive the runtime synchronously
(auto_start=False + process_once) so bucket composition is exact;
deadline tests use an injectable fake clock; hang tests block on a
threading.Event the test releases (no wall-clock guesses)."""

import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, resilience
from paddle_tpu.inference import Predictor
from paddle_tpu.resilience import (CircuitBreaker, RetryPolicy,
                                   faultinject, taxonomy)
from paddle_tpu.resilience.retry import call_with_retry
from paddle_tpu.serving import (DeadlineExceeded, QueueFullError,
                                ServingClosedError, ServingRuntime,
                                WatchdogStall, default_buckets,
                                pick_bucket)
from paddle_tpu.serving.stats import exact_percentile


# ---------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_model(tmp_path_factory):
    """One tiny saved inference model + Predictor for the module."""
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 6])
            h = fluid.layers.fc(x, 8, act="relu")
            out = fluid.layers.fc(h, 3, act="softmax")
    exe = fluid.Executor()
    exe.run(startup)
    d = str(tmp_path_factory.mktemp("serving_model"))
    fluid.io.save_inference_model(d, ["x"], [out], exe,
                                  main_program=main)
    return d, Predictor(d)


@pytest.fixture(autouse=True)
def _clean_state():
    """Every test starts and ends with no armed faults and a clean
    monitor — serving chaos must not leak into the next test."""
    faultinject.disarm()
    monitor.disable()
    monitor.reset()
    yield
    faultinject.disarm()
    monitor.disable()
    monitor.reset()


def _feed(rows, seed=0):
    return {"x": np.random.default_rng(seed)
            .standard_normal((rows, 6)).astype(np.float32)}


def _bucket_ref(pred, feed, bucket):
    """Predictor.run at the padded bucket shape, sliced back — the
    bitwise ground truth for the batched path."""
    rows = len(feed["x"])
    padded = {"x": np.concatenate(
        [feed["x"], np.zeros((bucket - rows, 6), np.float32)])}
    return [o[:rows] for o in pred.run(padded)]


def _mk(pred, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("batch_window_s", 0.0)
    kw.setdefault("prewarm", False)
    kw.setdefault("label", f"t{time.perf_counter_ns()}")
    return ServingRuntime(pred, **kw)


# ---------------------------------------------------------------------
# taxonomy: the DEADLINE category (satellite 1)
# ---------------------------------------------------------------------

def test_deadline_classifies_distinct_from_transient():
    exc = DeadlineExceeded("request deadline exceeded after 5ms")
    assert taxonomy.classify(exc) == taxonomy.DEADLINE
    assert taxonomy.is_deadline(exc)
    # a raw XLA DEADLINE_EXCEEDED status stays transient (a collective
    # rendezvous timeout is infrastructure, retry-worthy)...
    assert taxonomy.classify(RuntimeError(
        "DEADLINE_EXCEEDED: collective timed out")) == taxonomy.TRANSIENT
    # ...but is_deadline still recognizes it on the orthogonal axis
    assert taxonomy.is_deadline(RuntimeError(
        "DEADLINE_EXCEEDED: collective timed out"))
    assert not taxonomy.is_deadline(RuntimeError("UNAVAILABLE: nope"))
    # the type check wins over transient-looking message content
    assert taxonomy.classify(DeadlineExceeded(
        "budget spent while retrying UNAVAILABLE")) == taxonomy.DEADLINE


def test_is_deadline_walks_cause_chain():
    inner = WatchdogStall("serving dispatch watchdog stall: 2s")
    outer = RuntimeError("dispatch failed")
    outer.__cause__ = inner
    assert taxonomy.is_deadline(outer)
    assert isinstance(inner, DeadlineExceeded)     # classified subtype


def test_deadline_registered_in_dump_triggers():
    assert "deadline" in taxonomy.TAXONOMY["dump_triggers"]
    assert "DeadlineExceeded" in taxonomy.TAXONOMY["deadline_types"]


def test_retry_never_retries_deadline():
    calls = []

    def fn():
        calls.append(1)
        raise DeadlineExceeded("request deadline exceeded")

    with pytest.raises(DeadlineExceeded):
        call_with_retry(fn, RetryPolicy(max_retries=3,
                                        sleep=lambda d: None))
    assert len(calls) == 1          # budget gone: no blind retries


# ---------------------------------------------------------------------
# circuit breaker (resilience/breaker.py)
# ---------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_breaker_opens_after_consecutive_failures():
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=3, cooldown_s=10, clock=clk)
    for _ in range(2):
        assert b.allow()
        b.note_failure(RuntimeError("x"))
    assert b.state == "closed"
    b.note_success()                 # success resets the streak
    for _ in range(3):
        b.note_failure(RuntimeError("x"))
    assert b.state == "open"
    assert not b.allow()             # fail fast
    assert [(t["from"], t["to"]) for t in b.summary()["transitions"]] \
        == [("closed", "open")]


def test_breaker_half_open_single_probe_then_close():
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=1, cooldown_s=5, clock=clk)
    b.note_failure(RuntimeError("x"))
    assert b.state == "open" and not b.allow()
    clk.t += 5.1
    assert b.state == "half_open"
    assert b.allow()                 # the ONE probe token
    assert not b.allow()             # everyone else still fails fast
    b.note_success()
    assert b.state == "closed"
    trans = [(t["from"], t["to"]) for t in b.summary()["transitions"]]
    assert trans == [("closed", "open"), ("open", "half_open"),
                     ("half_open", "closed")]


def test_breaker_probe_failure_reopens_and_restarts_cooldown():
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=1, cooldown_s=5, clock=clk)
    b.note_failure(RuntimeError("x"))
    clk.t += 5.1
    assert b.allow()
    b.note_failure(RuntimeError("probe failed"))
    assert b.state == "open"
    clk.t += 4.9                     # cooldown restarted: still open
    assert b.state == "open"
    clk.t += 0.2
    assert b.state == "half_open"


def test_breaker_unreported_probe_released_and_expires():
    """A half-open probe that never reports (all its waiters expired,
    the caller died) must not wedge the breaker: release_probe() hands
    the token back immediately, and an unreleased one expires after
    another cooldown period."""
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=1, cooldown_s=5, clock=clk)
    b.note_failure(RuntimeError("x"))
    clk.t += 5.1
    assert b.allow()                 # probe consumed...
    b.release_probe()                # ...but the dispatch was abandoned
    assert b.allow()                 # token handed back at once
    clk.t += 5.1                     # this probe never reports either
    assert b.allow()                 # expiry backstop re-granted it
    assert b.state == "half_open"


def test_breaker_counters_monitor_gated():
    monitor.enable()
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=1, cooldown_s=1, clock=clk)
    b.note_failure(RuntimeError("x"))
    b.allow()
    counters = monitor.snapshot()["counters"]
    assert counters.get("resilience.breaker_open") == 1
    assert counters.get("resilience.breaker_fast_fail") == 1


# ---------------------------------------------------------------------
# faultinject: stall/hang primitive (satellite 2)
# ---------------------------------------------------------------------

def test_stall_point_sleep_fires_once():
    plan = faultinject.arm(stall_points={"p": 0.01})
    t0 = time.perf_counter()
    faultinject.stall_point("p")
    assert time.perf_counter() - t0 >= 0.01
    assert plan.fired["stall"] == 1
    t0 = time.perf_counter()
    faultinject.stall_point("p")     # one-shot: disarmed
    assert time.perf_counter() - t0 < 0.01
    assert plan.fired["stall"] == 1


def test_stall_point_event_blocks_until_released():
    ev = threading.Event()
    faultinject.arm(stall_points={"p": ev})
    order = []

    def target():
        faultinject.stall_point("p")
        order.append("unblocked")

    t = threading.Thread(target=target, daemon=True)
    t.start()
    time.sleep(0.02)
    assert order == []               # honestly hanging
    order.append("released")
    ev.set()
    t.join(timeout=5)
    assert order == ["released", "unblocked"]


def test_stall_point_nth_hit_targeting():
    plan = faultinject.arm(stall_points={"p": (1, 0.0)})
    faultinject.stall_point("p")     # hit 0: no fire
    assert plan.fired["stall"] == 0
    faultinject.stall_point("p")     # hit 1: fires
    assert plan.fired["stall"] == 1


def test_transient_at_multiple_steps():
    plan = faultinject.arm(transient_at_step=[0, 1], transient_times=2)
    faultinject.on_step_feed({})
    with pytest.raises(faultinject.InjectedTransientError):
        faultinject.check_transient()
    faultinject.on_step_feed({})
    with pytest.raises(faultinject.InjectedTransientError):
        faultinject.check_transient()
    faultinject.on_step_feed({})     # step 2: not scheduled
    faultinject.check_transient()
    assert plan.fired["transient"] == 2


# ---------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------

def test_default_buckets_and_pick():
    assert default_buckets(8) == [1, 2, 4, 8]
    assert default_buckets(6) == [1, 2, 4, 6]
    assert pick_bucket([1, 2, 4], 3) == 4
    with pytest.raises(ValueError):
        pick_bucket([1, 2, 4], 5)


def test_submit_validation(served_model):
    _, pred = served_model
    rt = _mk(pred, auto_start=False)
    with pytest.raises(KeyError):
        rt.submit({})
    with pytest.raises(ValueError):
        rt.submit(_feed(5))          # exceeds largest bucket (4)
    assert rt.stats.requests == 0    # validation errors pre-admission
    rt.close()


def test_prewarm_compiles_every_bucket_no_recompile_after(served_model):
    _, pred = served_model
    monitor.enable()
    rt = _mk(pred, prewarm=True, auto_start=False)
    assert rt.prewarmed == 3         # buckets 1, 2, 4
    n0 = len(monitor.compile_events())
    for rows in (1, 2, 3, 4):
        rt.submit(_feed(rows))
        rt.process_once()
    assert len(monitor.compile_events()) == n0   # zero recompiles
    rt.close()


# ---------------------------------------------------------------------
# batching correctness (bitwise vs the unbatched predictor)
# ---------------------------------------------------------------------

def test_single_request_bitwise_equal(served_model):
    _, pred = served_model
    rt = _mk(pred, auto_start=False)
    feed = _feed(2)
    fut = rt.submit(feed)
    rt.process_once()
    res = fut.result(timeout=1)
    ref = _bucket_ref(pred, feed, 2)
    assert all(np.array_equal(a, b) for a, b in zip(res, ref))
    # and numerically the plain unbatched run
    plain = pred.run(feed)
    assert all(np.allclose(a, b, atol=1e-6)
               for a, b in zip(res, plain))
    rt.close()


def test_coalesced_batch_bitwise_equal_per_request(served_model):
    _, pred = served_model
    rt = _mk(pred, auto_start=False)
    feeds = [_feed(1, seed=1), _feed(2, seed=2), _feed(1, seed=3)]
    futs = [rt.submit(f) for f in feeds]
    rt.process_once()                # ONE batch: 4 rows -> bucket 4
    assert rt.stats.batches == 1
    assert rt.stats.summary()["buckets"] == {"4": 1}
    for f, fut in zip(feeds, futs):
        res = fut.result(timeout=1)
        ref = _bucket_ref(pred, f, 4)
        assert all(np.array_equal(a, b) for a, b in zip(res, ref))
    rt.close()


def test_padding_rows_never_leak(served_model):
    _, pred = served_model
    rt = _mk(pred, auto_start=False)
    fut = rt.submit(_feed(3))        # bucket 4: one padding row
    rt.process_once()
    res = fut.result(timeout=1)
    assert all(len(o) == 3 for o in res)
    assert rt.stats.padded_rows == 1
    rt.close()


def test_compiled_predictor_single_bucket(served_model, tmp_path):
    d, pred = served_model
    from paddle_tpu.inference import (CompiledPredictor,
                                      save_compiled_inference_model)

    path = save_compiled_inference_model(
        d, {"x": np.zeros((4, 6), np.float32)}, )
    cp = CompiledPredictor(path)
    rt = _mk(cp, auto_start=False)
    assert rt.dispatcher.buckets == [4]   # the artifact's batch dim
    feed = _feed(2)
    fut = rt.submit(feed)
    rt.process_once()
    res = fut.result(timeout=1)
    padded = {"x": np.concatenate(
        [feed["x"], np.zeros((2, 6), np.float32)])}
    ref = [o[:2] for o in cp.run(padded)]
    assert all(np.array_equal(a, b) for a, b in zip(res, ref))
    rt.close()


def test_blocking_run_api(served_model):
    _, pred = served_model
    rt = _mk(pred)                   # auto_start=True
    try:
        res = rt.run(_feed(2), timeout=30)
        assert len(res) == 1 and res[0].shape == (2, 3)
    finally:
        rt.close()


# ---------------------------------------------------------------------
# admission control: deadlines + backpressure
# ---------------------------------------------------------------------

def test_deadline_shed_in_queue(served_model):
    _, pred = served_model
    clk = FakeClock()
    rt = _mk(pred, auto_start=False, clock=clk)
    fut = rt.submit(_feed(1), deadline_s=0.05)
    clk.t += 0.1                     # budget expires in queue
    assert rt.process_once() == 1
    err = fut.exception(timeout=1)
    assert isinstance(err, DeadlineExceeded)
    assert taxonomy.classify(err) == taxonomy.DEADLINE
    assert err.budget_s == 0.05 and err.elapsed_s >= 0.05
    assert rt.stats.summary()["outcomes"]["shed"] == 1
    rt.close()


def test_sweep_expired_independent_of_batcher(served_model):
    """Budget expiry must not depend on the batcher being alive — the
    watchdog's poll tick sweeps the queue (here: called directly)."""
    _, pred = served_model
    clk = FakeClock()
    rt = _mk(pred, auto_start=False, clock=clk)
    f1 = rt.submit(_feed(1), deadline_s=0.05)
    f2 = rt.submit(_feed(1), deadline_s=50.0)
    clk.t += 0.1
    assert rt.sweep_expired() == 1
    assert isinstance(f1.exception(timeout=1), DeadlineExceeded)
    assert not f2.done()             # unexpired request untouched
    rt.process_once()
    assert f2.exception(timeout=1) is None
    rt.close()


def test_backpressure_rejects_with_queue_full(served_model):
    _, pred = served_model
    rt = _mk(pred, auto_start=False, max_queue_depth=2)
    rt.submit(_feed(1))
    rt.submit(_feed(1))
    with pytest.raises(QueueFullError) as ei:
        rt.submit(_feed(1))
    assert "backpressure" in str(ei.value)
    s = rt.stats.summary()
    assert s["outcomes"]["rejected"] == 1
    assert s["requests"] == 3        # rejected requests are accounted
    rt.close()


def test_deadline_expires_in_flight(served_model):
    """A dispatch that outlives a request's budget fails THAT request
    with a classified DeadlineExceeded while the dispatch completes."""
    _, pred = served_model
    hang = threading.Event()
    faultinject.arm(stall_points={"serving.dispatch": hang})
    rt = _mk(pred, auto_start=False, watchdog_stall_s=60.0)
    fut = rt.submit(_feed(1), deadline_s=0.05)
    done = threading.Thread(target=rt.process_once, daemon=True)
    done.start()
    err = fut.exception(timeout=10)  # resolved AT the deadline
    assert isinstance(err, DeadlineExceeded)
    assert rt.stats.summary()["outcomes"]["expired"] == 1
    hang.set()
    done.join(timeout=10)
    rt.close()


# ---------------------------------------------------------------------
# watchdog: hang detection, dump, escalation
# ---------------------------------------------------------------------

@pytest.fixture
def flight_dir(tmp_path):
    old = fluid.get_flags("FLAGS_flight_recorder_dir")
    fluid.set_flags({"FLAGS_flight_recorder_dir": str(tmp_path)})
    monitor.flight_recorder.get().clear()
    yield str(tmp_path)
    fluid.set_flags(old)


def test_watchdog_raise_policy_fails_batch_classified(served_model,
                                                      flight_dir):
    _, pred = served_model
    hang = threading.Event()
    faultinject.arm(stall_points={"serving.dispatch": hang})
    rt = _mk(pred, watchdog_stall_s=0.05, watchdog_poll_s=0.01,
             watchdog_policy="raise")
    try:
        fut = rt.submit(_feed(2))
        err = fut.exception(timeout=30)
        assert isinstance(err, WatchdogStall)
        assert taxonomy.is_deadline(err)
        assert rt.stats.watchdog_stalls == 1
        assert rt.stats.summary()["outcomes"]["stalled"] == 1
    finally:
        hang.set()
        rt.close()
        faultinject.disarm()


def test_watchdog_dump_carries_batch_meta_and_serving_record(
        served_model, flight_dir):
    _, pred = served_model
    hang = threading.Event()
    faultinject.arm(stall_points={"serving.dispatch": hang})
    rt = _mk(pred, watchdog_stall_s=0.05, watchdog_poll_s=0.01,
             watchdog_policy="raise")
    try:
        fut = rt.submit(_feed(2))
        fut.exception(timeout=30)
        path = monitor.flight_recorder.get().last_dump
        assert path and os.path.exists(path)
        assert os.path.dirname(path) == flight_dir
        records = [json.loads(line) for line in open(path)]
        stall = [r for r in records if r.get("kind") == "event"
                 and r.get("event") == "serving_stall"]
        assert stall and stall[0]["bucket"] == 2 \
            and stall[0]["rows"] == 2 and stall[0]["requests"] == 1
        serving = [r for r in records if r.get("kind") == "serving"]
        assert serving and serving[0]["requests"] >= 1
    finally:
        hang.set()
        rt.close()
        faultinject.disarm()


def test_watchdog_cancel_retry_recovers(served_model, flight_dir):
    _, pred = served_model
    hang = threading.Event()
    faultinject.arm(stall_points={"serving.dispatch": hang})
    rt = _mk(pred, watchdog_stall_s=0.05, watchdog_poll_s=0.01,
             watchdog_policy="cancel_retry")
    try:
        feed = _feed(2)
        res = rt.run(feed, timeout=30)  # stall -> abandon -> re-dispatch
        ref = _bucket_ref(pred, feed, 2)
        assert all(np.array_equal(a, b) for a, b in zip(res, ref))
        assert rt.stats.cancel_retries == 1
        assert rt.stats.watchdog_stalls >= 1
        assert rt.stats.summary()["outcomes"]["completed"] == 1
    finally:
        hang.set()
        rt.close()
        faultinject.disarm()


# ---------------------------------------------------------------------
# breaker integration + degraded mode + retry
# ---------------------------------------------------------------------

def test_retry_recovers_injected_transient(served_model):
    _, pred = served_model
    monitor.enable()
    faultinject.arm(transient_at_step=0, transient_times=1)
    rt = _mk(pred, auto_start=False,
             retry_policy=RetryPolicy(max_retries=2, base_delay=0.001,
                                      sleep=lambda d: None, seed=0))
    fut = rt.submit(_feed(1))
    rt.process_once()
    assert fut.exception(timeout=5) is None
    assert monitor.snapshot()["counters"].get("resilience.retries",
                                              0) >= 1
    assert rt.breaker.state == "closed"
    rt.close()


def test_breaker_opens_then_degraded_eager_serves(served_model):
    _, pred = served_model
    faultinject.arm(transient_at_step=[0], transient_times=1)
    rt = _mk(pred, auto_start=False, retry_policy=None,
             breaker_threshold=1, breaker_cooldown_s=30.0,
             degraded_mode="eager")
    sacrifice = rt.submit(_feed(1))
    rt.process_once()
    err = sacrifice.exception(timeout=5)
    assert resilience.classify(err) == taxonomy.TRANSIENT
    assert rt.breaker.state == "open"
    # open breaker: next request served through the eager interpreter
    feed = _feed(2)
    fut = rt.submit(feed)
    rt.process_once()
    res = fut.result(timeout=5)
    assert all(np.allclose(a, b, atol=1e-5)
               for a, b in zip(res, pred.run(feed)))
    s = rt.stats.summary()
    assert s["degraded_batches"] == 1
    assert s["breaker"]["state"] == "open"
    rt.close()


def test_breaker_half_open_probe_closes_via_runtime(served_model):
    _, pred = served_model
    clk = FakeClock()
    faultinject.arm(transient_at_step=[0], transient_times=1)
    rt = _mk(pred, auto_start=False, retry_policy=None,
             breaker_threshold=1, breaker_cooldown_s=5.0, clock=clk)
    rt.submit(_feed(1))
    rt.process_once()                # sacrifice -> breaker opens
    assert rt.breaker.state == "open"
    clk.t += 5.1                     # past cooldown: next is the probe
    fut = rt.submit(_feed(1))
    rt.process_once()
    assert fut.exception(timeout=5) is None
    assert rt.breaker.state == "closed"
    trans = [(t["from"], t["to"])
             for t in rt.breaker.summary()["transitions"]]
    assert trans == [("closed", "open"), ("open", "half_open"),
                     ("half_open", "closed")]
    rt.close()


def test_degraded_mode_fail_fails_fast_classified(served_model):
    from paddle_tpu.resilience.breaker import CircuitOpenError

    _, pred = served_model
    faultinject.arm(transient_at_step=[0], transient_times=1)
    rt = _mk(pred, auto_start=False, retry_policy=None,
             breaker_threshold=1, breaker_cooldown_s=30.0,
             degraded_mode="fail")
    rt.submit(_feed(1))
    rt.process_once()                # opens the breaker
    fut = rt.submit(_feed(1))
    rt.process_once()
    assert isinstance(fut.exception(timeout=5), CircuitOpenError)
    assert rt.stats.summary()["outcomes"]["failed"] == 2
    rt.close()


# ---------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------

def test_latency_percentiles_exact(served_model):
    _, pred = served_model
    rt = _mk(pred, auto_start=False)
    for i in range(7):
        rt.submit(_feed(1, seed=i))
        rt.process_once()
    s = rt.stats.summary()
    samples = sorted(rt.stats.samples())
    assert len(samples) == 7
    assert s["latency"]["p50_ms"] == round(
        exact_percentile(samples, 0.50) * 1e3, 3)
    assert s["latency"]["p99_ms"] == round(
        exact_percentile(samples, 0.99) * 1e3, 3)
    # nearest-rank: p99 of 7 samples IS the max sample
    assert s["latency"]["p99_ms"] == s["latency"]["max_ms"]
    rt.close()


def test_exact_percentile_nearest_rank_math():
    s = [1.0, 2.0, 3.0, 4.0]
    assert exact_percentile(s, 0.50) == 2.0
    assert exact_percentile(s, 0.99) == 4.0
    assert exact_percentile(s, 0.25) == 1.0
    assert exact_percentile([], 0.5) is None
    assert exact_percentile([7.0], 0.99) == 7.0


def test_serving_table_and_snapshot(served_model):
    _, pred = served_model
    monitor.enable()
    rt = _mk(pred, auto_start=False, label="table_test")
    rt.submit(_feed(2))
    rt.process_once()
    rows = monitor.serving_table()
    mine = [r for r in rows if r["key"] == "table_test"]
    assert mine and mine[0]["outcomes"]["completed"] == 1
    assert mine[0]["requests"] == mine[0]["resolved"]
    snap = monitor.snapshot()
    assert any(r["key"] == "table_test" for r in snap["serving"])
    counters = snap["counters"]
    assert counters.get("serving.requests") == 1
    assert counters.get("serving.completed") == 1
    rt.close()


def test_serving_record_on_jsonl_and_report(served_model, tmp_path):
    import importlib.util

    _, pred = served_model
    jl = str(tmp_path / "telemetry.jsonl")
    monitor.enable(jsonl_path=jl)
    rt = _mk(pred, auto_start=False, label="jsonl_test",
             max_queue_depth=1)
    rt.submit(_feed(1))
    with pytest.raises(QueueFullError):
        rt.submit(_feed(1))
    rt.process_once()
    rt.emit_telemetry()
    monitor.disable()
    from paddle_tpu.monitor.jsonl_writer import read_jsonl

    records = read_jsonl(jl)
    serving = [r for r in records if r.get("kind") == "serving"]
    assert serving and serving[-1]["key"] == "jsonl_test"
    assert serving[-1]["outcomes"]["rejected"] == 1
    # the report tool renders the same records (live or dump)
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    summary = mod.summarize(records)
    assert summary["serving"]["runtimes"] == 1
    entry = summary["serving"]["by_runtime"]["jsonl_test"]
    assert entry["completed"] == 1
    assert entry["events"]["rejected"] == 1
    assert "UNRESOLVED" not in entry      # nothing pending at emit
    assert "p99_ms" in entry["latency_ms"]
    rt.close()


def test_request_spans_in_profiler(served_model):
    import paddle_tpu.profiler as profiler

    _, pred = served_model
    rt = _mk(pred, auto_start=False)
    try:
        with profiler.profiler("All", profile_path=None):
            fut = rt.submit(_feed(1))
            rt.process_once()
            fut.result(timeout=5)
            names = [name for name, _, _, _ in profiler.spans()]
        assert any(n.startswith("serving.request/") for n in names)
        assert any(n.startswith("serving.dispatch/") for n in names)
    finally:
        profiler.reset_profiler()
        rt.close()


# ---------------------------------------------------------------------
# shutdown
# ---------------------------------------------------------------------

def test_close_fails_pending_classified_and_rejects_new(served_model):
    _, pred = served_model
    rt = _mk(pred, auto_start=False)
    fut = rt.submit(_feed(1))
    rt.close()
    assert isinstance(fut.exception(timeout=1), ServingClosedError)
    assert rt.stats.summary()["outcomes"]["cancelled"] == 1
    with pytest.raises(ServingClosedError):
        rt.submit(_feed(1))
    rt.close()                       # idempotent


def test_close_resolves_in_flight_behind_wedged_dispatch(served_model):
    """close() must fail IN-FLIGHT requests too, not just queued ones:
    a dispatch wedged past the close timeout (watchdog threshold not
    yet reached) would otherwise strand its futures pending forever —
    the exact silent loss the runtime exists to prevent."""
    _, pred = served_model
    hang = threading.Event()
    faultinject.arm(stall_points={"serving.dispatch": hang})
    rt = _mk(pred, watchdog_stall_s=300.0)    # watchdog won't fire
    try:
        fut = rt.submit(_feed(1))
        deadline = time.time() + 10
        while rt.stats.in_flight == 0 and time.time() < deadline:
            time.sleep(0.005)
        rt.close(timeout=0.2)                 # join times out: wedged
        assert isinstance(fut.exception(timeout=5), ServingClosedError)
        assert rt.stats.summary()["pending"] == 0
    finally:
        hang.set()
        faultinject.disarm()


def test_context_manager_drains(served_model):
    _, pred = served_model
    with _mk(pred) as rt:
        fut = rt.submit(_feed(2))
    assert fut.exception(timeout=1) is None   # drained before close
    assert rt.stats.summary()["pending"] == 0
