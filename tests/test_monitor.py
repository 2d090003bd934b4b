"""Telemetry subsystem tests (ISSUE 3): registry math, MFU/compile
ledger from FIXED fake cost/memory payloads, JSONL round-trip, the
executor integration, and the unified chrome trace."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, profiler
from paddle_tpu.monitor.compile_ledger import (
    CompileLedger, parse_cost_analysis, parse_memory_analysis)
from paddle_tpu.monitor.jsonl_writer import JsonlWriter, read_jsonl
from paddle_tpu.monitor.registry import MetricsRegistry
from paddle_tpu.monitor.session import MetricsSession


@pytest.fixture(autouse=True)
def _clean_monitor():
    """The monitor is process-global; every test starts and ends with
    it disabled and empty so executor-driven tests can't leak state."""
    monitor.disable()
    monitor.reset()
    yield
    monitor.disable()
    monitor.reset()


_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _toy_train_program(width=8):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [None, width])
        y = fluid.data("y", [None, 1])
        h = fluid.layers.fc(x, width, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _feed(batch=16, width=8):
    rng = np.random.default_rng(0)
    return {"x": rng.standard_normal((batch, width)).astype(np.float32),
            "y": rng.standard_normal((batch, 1)).astype(np.float32)}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    c.add()
    c.add(4)
    reg.gauge("width").set(8)
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == 5
    assert snap["gauges"]["width"] == 8


def test_registry_reset_keeps_handles():
    reg = MetricsRegistry()
    c = reg.counter("n")
    c.add(3)
    reg.reset()
    assert c.value == 0
    c.add(2)                      # the held handle still feeds the registry
    assert reg.snapshot()["counters"]["n"] == 2


def test_cache_hit_rate_numbers_exact():
    reg = MetricsRegistry()
    for _ in range(3):
        reg.counter("run_plan.hit").add(1)
    reg.counter("run_plan.miss").add(1)
    snap = reg.snapshot()["counters"]
    assert snap["run_plan.hit"] == 3 and snap["run_plan.miss"] == 1
    assert snap["run_plan.hit"] / (snap["run_plan.hit"]
                                   + snap["run_plan.miss"]) == 0.75


# ---------------------------------------------------------------------------
# compile ledger / MFU math from fixed fake payloads
# ---------------------------------------------------------------------------

# the shapes XLA actually returns: newer jax gives ONE dict, older a
# list of per-computation dicts
FAKE_COST_DICT = {"flops": 2.0e9, "bytes accessed": 5.0e6,
                  "utilization0{}": 1.0}
FAKE_COST_LIST = [{"flops": 1.5e9, "bytes accessed": 3.0e6},
                  {"flops": 0.5e9, "bytes accessed": 2.0e6}]


class FakeMemoryStats:
    argument_size_in_bytes = 1024
    output_size_in_bytes = 256
    temp_size_in_bytes = 4096
    alias_size_in_bytes = 128
    generated_code_size_in_bytes = 2048


def test_parse_cost_analysis_both_shapes():
    assert parse_cost_analysis(FAKE_COST_DICT) == {
        "flops": 2.0e9, "bytes_accessed": 5.0e6}
    assert parse_cost_analysis(FAKE_COST_LIST) == {
        "flops": 2.0e9, "bytes_accessed": 5.0e6}
    assert parse_cost_analysis(None)["flops"] is None


def test_parse_memory_analysis_exact_bytes():
    mem = parse_memory_analysis(FakeMemoryStats())
    assert mem == {"argument_bytes": 1024, "output_bytes": 256,
                   "temp_bytes": 4096, "alias_bytes": 128,
                   "generated_code_bytes": 2048}
    assert parse_memory_analysis(None) is None


def test_mfu_exact_from_fake_payloads():
    reg = MetricsRegistry()
    ledger = CompileLedger(reg)
    cost = parse_cost_analysis(FAKE_COST_DICT)
    ledger.record("train_step", compile_s=0.25, flops=cost["flops"],
                  bytes_accessed=cost["bytes_accessed"],
                  memory=parse_memory_analysis(FakeMemoryStats()))
    # 2e9 flops / 0.01 s / 1e12 peak == 0.2 exactly
    assert ledger.mfu(0.01, peak=1e12) == pytest.approx(0.2)
    assert ledger.mfu(0.01, key="train_step", peak=1e12) \
        == pytest.approx(0.2)
    assert ledger.mfu(0.01, key="other", peak=1e12) is None
    assert ledger.mfu(0.0, peak=1e12) is None
    summary = ledger.summary()
    assert summary["count"] == 1
    assert summary["total_compile_ms"] == pytest.approx(250.0)
    assert summary["flops"] == 2.0e9
    assert summary["memory"]["temp_bytes"] == 4096
    assert reg.snapshot()["counters"]["compile.count"] == 1
    # live-bytes gauge: arguments + temps of the latest program
    assert reg.snapshot()["gauges"]["compile.live_bytes"] == 1024 + 4096


def test_mfu_uses_latest_event_per_key():
    ledger = CompileLedger(MetricsRegistry())
    ledger.record("a", 0.1, flops=1e9)
    ledger.record("a", 0.1, flops=4e9)     # recompile: newer numbers win
    assert ledger.mfu(0.01, key="a", peak=1e12) == pytest.approx(0.4)


def test_instrument_jit_fallback_records_first_call():
    """A callable with no AOT .lower() still lands a ledger event (wall
    time of the first, compiling, call) and runs correctly after."""
    ledger = CompileLedger(MetricsRegistry())
    calls = []

    def plain(x):
        calls.append(x)
        return x * 2

    wrapped = ledger.instrument_jit(plain, key="fallback",
                                    is_enabled=lambda: True)
    assert wrapped(3) == 6 and wrapped(4) == 8
    events = ledger.events()
    assert len(events) == 1
    assert events[0]["source"] == "first_call"
    assert events[0]["key"] == "fallback"
    assert calls == [3, 4]


def test_instrument_jit_disabled_is_passthrough():
    ledger = CompileLedger(MetricsRegistry())
    wrapped = ledger.instrument_jit(lambda x: x + 1, key="k",
                                    is_enabled=lambda: False)
    assert wrapped(1) == 2
    assert ledger.events() == []


def test_instrument_jit_survives_disable_and_resignature():
    """Once compiled through the ledger, the executable keeps serving
    with telemetry OFF (no re-trace on toggle), and a changed input
    signature falls back to a fresh per-signature compile instead of
    failing."""
    import jax
    import jax.numpy as jnp

    ledger = CompileLedger(MetricsRegistry())
    enabled = [True]
    wrapped = ledger.instrument_jit(jax.jit(lambda x: x * 2), key="k",
                                    is_enabled=lambda: enabled[0])
    assert float(wrapped(jnp.ones(()))) == 2.0
    assert len(ledger.events()) == 1
    enabled[0] = False          # toggle off: same executable, no event
    assert float(wrapped(jnp.asarray(3.0))) == 6.0
    assert len(ledger.events()) == 1
    enabled[0] = True           # new signature: second ledger compile
    assert wrapped(jnp.ones((4,))).shape == (4,)
    assert len(ledger.events()) == 2


# ---------------------------------------------------------------------------
# session + JSONL round trip
# ---------------------------------------------------------------------------

def test_jsonl_round_trip_same_snapshot(tmp_path):
    """write -> parse -> the parsed records reproduce the session's
    in-process records and aggregates."""
    reg = MetricsRegistry()
    session = MetricsSession(reg, CompileLedger(reg))
    path = str(tmp_path / "t.jsonl")
    session.attach_writer(JsonlWriter(path))
    session.record_step(host_dispatch_us=100.0, examples=32,
                        feed_bytes=1024, fetch_bytes=8)
    session.record_step(host_dispatch_us=50.0, examples=32,
                        feed_bytes=1024, fetch_bytes=8)
    parsed = read_jsonl(path)
    # every serialized line is rank-stamped (ISSUE 10) — the stamp is
    # a superset of the in-process record, never a mutation of it
    from paddle_tpu.monitor import fleet

    tag = fleet.rank_tag()
    for r in parsed:
        for k, v in tag.items():
            assert r.pop(k) == v
    assert parsed == json.loads(json.dumps(session.records()))
    assert [r["step"] for r in parsed] == [1, 2]
    assert all(r["kind"] == "step" for r in parsed)
    # aggregates recomputed from the parsed rows match the snapshot
    snap = session.snapshot()
    assert snap["steps"] == 2
    assert snap["feed_bytes"] == sum(r["feed_bytes"] for r in parsed)
    assert snap["host_dispatch_us"]["mean"] == pytest.approx(
        sum(r["host_dispatch_us"] for r in parsed) / 2)


def test_read_jsonl_rejects_malformed_line(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"ok": 1}\n{truncated\n')
    with pytest.raises(ValueError, match="malformed"):
        read_jsonl(str(p))


def test_disable_detaches_jsonl_writer(tmp_path):
    """enable(path) -> disable() -> enable() must not keep appending to
    the old path (the orphaned-writer bug)."""
    path = str(tmp_path / "t.jsonl")
    monitor.enable(jsonl_path=path)
    monitor.record_step(host_dispatch_us=1.0)
    monitor.disable()
    n = len(read_jsonl(path))
    monitor.enable()                       # no path: in-process only
    monitor.record_step(host_dispatch_us=1.0)
    monitor.disable()
    assert len(read_jsonl(path)) == n
    assert monitor.jsonl_path() is None


def test_record_step_threaded_unique_ordered():
    """Concurrent recorders (producer thread + main) get unique step
    numbers and a list whose order matches timestamp order."""
    import threading

    reg = MetricsRegistry()
    session = MetricsSession(reg, CompileLedger(reg))

    def work():
        for _ in range(50):
            session.record_step(host_dispatch_us=1.0)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    records = session.records()
    assert [r["step"] for r in records] == list(range(1, 201))
    assert all(a["ts_us"] <= b["ts_us"]
               for a, b in zip(records, records[1:]))


def test_observe_steps_bulk():
    reg = MetricsRegistry()
    session = MetricsSession(reg, CompileLedger(reg))
    session.observe_steps(10, 2.0, examples=100)
    snap = session.snapshot()
    assert snap["steps"] == 10
    assert snap["step_time_s"]["last"] == pytest.approx(0.2)
    assert reg.snapshot()["counters"]["steps"] == 10


def test_warmup_steps_excluded_from_means_and_mfu():
    """A compile-paying step must not skew the steady-state aggregates:
    means and the MFU denominator cover non-warmup records only."""
    reg = MetricsRegistry()
    ledger = CompileLedger(reg)
    session = MetricsSession(reg, ledger)
    session.record_step(host_dispatch_us=5_000_000.0, warmup=True)
    for _ in range(3):
        session.record_step(host_dispatch_us=100.0)
    snap = session.snapshot()
    assert snap["steps"] == 4 and snap["warmup_steps"] == 1
    assert snap["host_dispatch_us"]["mean"] == pytest.approx(100.0)
    assert snap["step_time_s"]["mean"] < 1.0       # not the 5s warmup
    assert session.mean_step_time() < 1.0
    # all-warmup degrades gracefully rather than reporting nothing
    s2 = MetricsSession(reg, ledger)
    s2.record_step(host_dispatch_us=50.0, warmup=True)
    assert s2.snapshot()["step_time_s"]["last"] > 0


def test_jsonl_writer_retired_after_close(tmp_path):
    """close() ends the writer's life: a racing emit is dropped, the
    file is never reopened."""
    path = tmp_path / "w.jsonl"
    w = JsonlWriter(str(path))
    w.emit({"a": 1})
    w.close()
    w.emit({"a": 2})               # dropped, not appended
    assert len(read_jsonl(str(path))) == 1
    path.unlink()
    w.emit({"a": 3})               # and never recreated
    assert not path.exists()


# ---------------------------------------------------------------------------
# executor integration
# ---------------------------------------------------------------------------

def test_executor_feeds_monitor_automatically(tmp_path):
    jsonl = str(tmp_path / "steps.jsonl")
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable(jsonl_path=jsonl)
    exe.run(startup, scope=scope)
    for _ in range(4):
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    snap = monitor.snapshot()
    monitor.disable()

    counters = snap["counters"]
    assert snap["steps"] == 5                       # startup + 4 train
    assert counters["run_plan.miss"] == 2           # startup + main
    assert counters["run_plan.hit"] == 3
    assert counters["compiled_step.miss"] == 2
    assert counters["compiled_step.hit"] == 3
    assert snap["compile"]["count"] == 2
    assert snap["compile"]["total_compile_ms"] > 0
    assert snap["compile"]["flops"] > 0             # XLA cost analysis
    assert snap["compile"]["memory"]["temp_bytes"] >= 0
    assert snap["step_time_s"]["mean"] > 0
    assert snap["host_dispatch_us"]["mean"] > 0
    assert snap["examples"] == 16 * 4
    assert snap["feed_bytes"] > 0 and snap["fetch_bytes"] > 0
    assert snap["mfu"] is None                      # a CPU has no peak
    assert monitor.mfu(peak=1e11) > 0               # the inputs are there
    # the two compile-paying runs are warmup-tagged, so the means above
    # are steady-state numbers
    records = monitor.step_records()
    assert [bool(r.get("warmup")) for r in records] \
        == [True, True, False, False, False]
    assert snap["warmup_steps"] == 2
    # timestamps monotone across the run
    assert all(a["ts_us"] < b["ts_us"]
               for a, b in zip(records, records[1:]))
    # JSONL stream matches the in-process records (step-kind lines;
    # compile-time op_profile records ride the same stream, ISSUE 5)
    lines = read_jsonl(jsonl)
    assert len([r for r in lines if r.get("kind") == "step"]) \
        == len(records)
    op_lines = [r for r in lines if r.get("kind") == "op_profile"]
    assert op_lines and op_lines[-1]["scopes"]


def test_executor_disabled_records_nothing():
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    snap = monitor.snapshot()
    assert snap["steps"] == 0
    assert snap["compile"]["count"] == 0
    assert monitor.step_records() == []


def test_with_telemetry_label_keys_the_ledger():
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    compiled = fluid.CompiledProgram(main).with_telemetry("my_train")
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable()
    exe.run(startup, scope=scope)
    exe.run(compiled, feed=_feed(), fetch_list=[loss], scope=scope)
    snap = monitor.snapshot()
    monitor.disable()
    assert "my_train" in snap["compile"]["programs"]
    assert monitor.mfu(0.01, key="my_train", peak=1e12) is not None


def test_eager_executor_records_steps():
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable()
    exe.run(startup, scope=scope)
    fluid.set_flags({"FLAGS_eager_executor": True})
    try:
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    finally:
        fluid.set_flags({"FLAGS_eager_executor": False})
    snap = monitor.snapshot()
    monitor.disable()
    assert snap["steps"] == 2
    # the eager interpreter EXECUTES inline: its record carries no
    # host_dispatch_us (that aggregate means "dispatch", not "run")
    assert "host_dispatch_us" not in monitor.step_records()[-1]


def test_export_with_explicit_events_is_a_pure_filter(tmp_path):
    """export_chrome_tracing(path, events) exports exactly those host
    spans — no ambient monitor step/counter tracks mixed in."""
    monitor.enable()
    monitor.record_step(host_dispatch_us=10.0, examples=4)
    path = profiler.export_chrome_tracing(
        str(tmp_path / "subset.json"),
        [{"name": "only_span", "ts": 1.0, "dur": 2.0, "tid": 7}])
    monitor.disable()
    events = json.load(open(path))["traceEvents"]
    assert {e["name"] for e in events} == {"only_span"}


# ---------------------------------------------------------------------------
# unified chrome trace
# ---------------------------------------------------------------------------

def test_merged_trace_has_spans_and_counter_tracks(tmp_path):
    """One exported trace carries host RecordEvent spans, step spans,
    compile spans, and >= 2 counter tracks with metadata naming the
    processes — the Perfetto acceptance shape."""
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable()
    with profiler.profiler(state="CPU",
                           profile_path=str(tmp_path / "prof")):
        with profiler.RecordEvent("outer_span"):
            exe.run(startup, scope=scope)
            for _ in range(3):
                exe.run(main, feed=_feed(), fetch_list=[loss],
                        scope=scope)
    path = profiler.export_chrome_tracing(str(tmp_path / "trace.json"))
    monitor.disable()
    doc = json.load(open(path))
    events = doc["traceEvents"]
    by_ph = {}
    for e in events:
        by_ph.setdefault(e["ph"], []).append(e)
    x_names = {e["name"] for e in by_ph["X"]}
    assert "outer_span" in x_names                  # host span
    assert "executor.run.dispatch" in x_names       # dispatch span
    assert "step" in x_names                        # step-boundary span
    assert "xla_compile" in x_names                 # compile span
    counter_tracks = {e["name"] for e in by_ph.get("C", [])}
    assert len(counter_tracks) >= 2
    assert {"examples/s", "cache"} <= counter_tracks
    meta = {(e["name"], e.get("pid")) for e in by_ph.get("M", [])}
    assert ("process_name", 0) in meta and ("process_name", 1) in meta
    # steps and host spans share one clock: the step spans overlap the
    # time range the host spans cover
    host_ts = [e["ts"] for e in by_ph["X"] if e.get("cat") == "host"]
    step_ts = [e["ts"] for e in by_ph["X"] if e.get("cat") == "step"]
    assert min(step_ts) <= max(host_ts) and max(step_ts) >= min(host_ts)
    # every event json-serializable scalar args (Perfetto requirement)
    json.dumps(events)


def test_parse_xplane_reads_merged_trace(tmp_path):
    """tools/parse_xplane.py accepts the merged chrome trace (satellite:
    the two trace paths must not silently diverge)."""
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable()
    with profiler.profiler(state="CPU",
                           profile_path=str(tmp_path / "prof")):
        exe.run(startup, scope=scope)
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    path = profiler.export_chrome_tracing(str(tmp_path / "trace.json"))
    monitor.disable()
    tool = os.path.join(_TOOLS, "parse_xplane.py")
    r = subprocess.run([sys.executable, tool, path],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "counter" in r.stdout and "track" in r.stdout


def test_parse_xplane_tolerates_foreign_chrome_trace(tmp_path):
    """A trace from another producer (metadata without args, bare
    events) parses instead of crashing with a KeyError."""
    foreign = tmp_path / "foreign.json"
    foreign.write_text(json.dumps({"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 3},
        {"ph": "X", "name": "op", "ts": 1.0, "dur": 2.0, "pid": 3},
        # two same-name counter samples at the SAME integer ts: the
        # sort must key on ts, not compare the args dicts
        {"ph": "C", "name": "ctr", "ts": 5, "args": {"v": 1}},
        {"ph": "C", "name": "ctr", "ts": 5, "args": {"v": 2}},
        "not-a-dict",
    ]}))
    tool = os.path.join(_TOOLS, "parse_xplane.py")
    r = subprocess.run([sys.executable, tool, str(foreign)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "op" in r.stdout


def test_parse_xplane_names_expected_formats_on_garbage(tmp_path):
    bad = tmp_path / "bad.bin"
    bad.write_bytes(b"\x00\x01garbage")
    tool = os.path.join(_TOOLS, "parse_xplane.py")
    r = subprocess.run([sys.executable, tool, str(bad)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "xplane.pb" in r.stderr and "chrome-trace" in r.stderr


def test_telemetry_report_tool(tmp_path):
    reg = MetricsRegistry()
    session = MetricsSession(reg, CompileLedger(reg))
    path = str(tmp_path / "t.jsonl")
    session.attach_writer(JsonlWriter(path))
    for _ in range(5):
        session.record_step(host_dispatch_us=10.0, examples=4)
    tool = os.path.join(_TOOLS, "telemetry_report.py")
    r = subprocess.run([sys.executable, tool, path],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "step_time_ms" in r.stdout


# ---------------------------------------------------------------------------
# end to end: a data-parallel train loop with telemetry on
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def telemetry_scenario(tmp_path_factory):
    """Eight steps of a small fc train program through the PUBLIC
    Executor.run, data-parallel over the test mesh, telemetry on and
    streaming to a JSONL file; run once, each well-formedness check of
    the snapshot is a case below.  Nothing here hand-codes a FLOP
    count: the numbers come from XLA's cost and memory analysis."""
    import jax

    steps, batch = 8, 64
    jsonl = str(tmp_path_factory.mktemp("telemetry") / "telemetry.jsonl")
    monitor.reset()
    monitor.enable(jsonl_path=jsonl)
    try:
        with fluid.unique_name.guard():
            main, startup, loss = _toy_train_program(width=64)
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=len(jax.devices())
        ).with_telemetry("telemetry_scenario")
        exe = fluid.Executor()
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        feed = _feed(batch, width=64)
        for _ in range(steps):
            exe.run(prog, feed=feed, fetch_list=[loss], scope=scope,
                    return_numpy=False)
        snap = monitor.snapshot()
        records = monitor.step_records()
        counters = snap.get("counters", {})
        compile_ = snap["compile"]
        last_step_s = (snap.get("step_time_s") or {}).get("last")
        return {
            # startup run + train steps all recorded
            "steps_recorded": snap.get("steps", 0) >= steps,
            "timestamps_monotone": all(
                a["ts_us"] < b["ts_us"]
                for a, b in zip(records, records[1:])),
            "step_time_present": bool(
                (snap.get("step_time_s") or {}).get("mean")),
            "host_dispatch_present": bool(
                (snap.get("host_dispatch_us") or {}).get("mean")),
            "cache_hits": counters.get("run_plan.hit", 0) > 0
            and counters.get("compiled_step.hit", 0) > 0,
            "cache_misses": counters.get("run_plan.miss", 0) > 0
            and counters.get("compiled_step.miss", 0) > 0,
            "compile_counted": compile_.get("count", 0) >= 1
            and compile_.get("total_compile_ms", 0) > 0,
            "memory_bytes": (compile_.get("memory") or {})
            .get("temp_bytes") is not None,
            # the MFU numerator; the ratio itself needs a device with
            # a peak on record (monitor.peak_flops: a CPU has none)
            "flops_from_cost_analysis": (compile_.get("flops") or 0) > 0,
            "no_mfu_without_a_peak": bool(last_step_s)
            and monitor.mfu(step_time_s=last_step_s) is None,
            # step-kind lines match the in-process records (op_profile
            # records from the compile ledger ride the same stream)
            "jsonl_round_trip": len(
                [r for r in read_jsonl(jsonl)
                 if r.get("kind") == "step"]) == len(records),
        }
    finally:
        monitor.disable()
        monitor.reset()


@pytest.mark.parametrize("check", [
    "steps_recorded", "timestamps_monotone", "step_time_present",
    "host_dispatch_present", "cache_hits", "cache_misses",
    "compile_counted", "memory_bytes", "flops_from_cost_analysis",
    "no_mfu_without_a_peak", "jsonl_round_trip"])
def test_telemetry_of_a_data_parallel_train_loop(telemetry_scenario, check):
    assert telemetry_scenario[check], telemetry_scenario
