"""Request-scoped distributed tracing tests (ISSUE 18): W3C
traceparent ingest/emit, EXACT integer-ns tail-latency attribution
(recomputed from raw spans with ``==``, never allclose), span-tree /
outcome-ledger reconciliation across the serving runtime and the
decode engine (including the chaos detours: breaker requeue on the
SAME trace, shed/expired/rejected trees closed, engine-broken drain),
SLO burn-rate + violator-exemplar retention, and the export surfaces
(/metrics family contiguity, Chrome-trace request tracks, flight-dump
trace lines, the report tool's tracing section).

Determinism strategy mirrors test_serving/test_decode_serving: the
runtime is driven synchronously (auto_start=False + process_once), the
decode engine by step(), budgets and breaker cooldowns ride injectable
fake clocks, and head-sampling is asserted via the deterministic
keep-rule — no wall-clock guesses anywhere."""

import collections
import glob
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.inference import Predictor
from paddle_tpu.monitor import tracing
from paddle_tpu.monitor.tracing import (COMPONENTS, RequestTrace,
                                        components_of,
                                        format_traceparent,
                                        parse_traceparent,
                                        tree_problems)
from paddle_tpu.resilience import faultinject
from paddle_tpu.serving import QueueFullError, ServingRuntime
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine


# ---------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_model(tmp_path_factory):
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 6])
            h = fluid.layers.fc(x, 8, act="relu")
            out = fluid.layers.fc(h, 3, act="softmax")
    exe = fluid.Executor()
    exe.run(startup)
    d = str(tmp_path_factory.mktemp("tracing_model"))
    fluid.io.save_inference_model(d, ["x"], [out], exe,
                                  main_program=main)
    return d, Predictor(d)


@pytest.fixture(scope="module")
def dense_model():
    from paddle_tpu.models.gpt import GPT, GPTConfig

    np.random.seed(21)
    cfg = GPTConfig(vocab_size=61, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=24, dropout=0.0)
    return GPT(cfg)


@pytest.fixture(autouse=True)
def _clean_state():
    """Tracing flags are process-global: every test restores them, and
    the store/monitor reset so chaos never leaks forward."""
    old = fluid.get_flags(["FLAGS_request_tracing",
                           "FLAGS_serving_slo_ms",
                           "FLAGS_trace_sample", "FLAGS_trace_buffer"])
    faultinject.disarm()
    monitor.disable()
    monitor.reset()
    yield
    fluid.set_flags(old)
    faultinject.disarm()
    monitor.disable()
    monitor.reset()


def _tracing_on(slo_ms=0.0, sample=1.0):
    fluid.set_flags({"FLAGS_request_tracing": True,
                     "FLAGS_serving_slo_ms": slo_ms,
                     "FLAGS_trace_sample": sample})


def _feed(rows, seed=0):
    return {"x": np.random.default_rng(seed)
            .standard_normal((rows, 6)).astype(np.float32)}


def _mk(pred, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("batch_window_s", 0.0)
    kw.setdefault("prewarm", False)
    kw.setdefault("label", f"tr{time.perf_counter_ns()}")
    return ServingRuntime(pred, **kw)


def _engine(model, clock=time.monotonic, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 24)
    kw.setdefault("buckets", (8,))
    kw.setdefault("watchdog_stall_s", 30.0)
    kw.setdefault("label", f"dtr{time.perf_counter_ns()}")
    return DecodeEngine(model, config=DecodeConfig(clock=clock, **kw),
                        auto_start=False)


def _drain(eng, futs, max_steps=300):
    for _ in range(max_steps):
        if all(f.done() for f in futs):
            return
        eng.step()
    raise AssertionError("engine did not drain")


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------
# W3C trace context
# ---------------------------------------------------------------------

def test_traceparent_roundtrip_and_rejection():
    tid, sid = "a" * 32, "b" * 16
    hdr = format_traceparent(tid, sid)
    assert hdr == f"00-{tid}-{sid}-01"
    assert parse_traceparent(hdr) == (tid, sid)
    assert parse_traceparent("  " + hdr.upper() + " ") == (tid, sid)
    # per spec: malformed / version ff / all-zero ids are ABSENT
    assert parse_traceparent(None) is None
    assert parse_traceparent("not-a-header") is None
    assert parse_traceparent(f"ff-{tid}-{sid}-01") is None
    assert parse_traceparent(f"00-{'0' * 32}-{sid}-01") is None
    assert parse_traceparent(f"00-{tid}-{'0' * 16}-01") is None
    assert parse_traceparent(f"00-{tid[:-1]}-{sid}-01") is None


def test_trace_emits_parseable_traceparent():
    tr = RequestTrace("r")
    got = parse_traceparent(tr.traceparent())
    assert got == (tr.trace_id, tr.root.span_id)
    tr.finish("completed")


# ---------------------------------------------------------------------
# exact attribution
# ---------------------------------------------------------------------

def test_attribution_exact_nested_deepest_wins():
    """Hand-built tree with known ns boundaries: attribution is the
    deepest-categorized-span partition, the uncovered remainder lands
    in "other", and the sum is INTEGER-equal to the total."""
    tr = RequestTrace("r")
    tr.root.start_ns = 1000
    q = tr.child("queue", "queue", start_ns=1000)
    tr.end(q, end_ns=4000)
    d = tr.child("dispatch", "dispatch", start_ns=4000)
    # retry nested under dispatch: its interval must be charged to
    # retry (deeper), NOT double-counted under dispatch
    r = tr.child("retry", "retry", parent=d, start_ns=5000)
    tr.end(r, end_ns=6500)
    tr.end(d, end_ns=9000)
    tr.finish("completed", end_ns=10000)
    comp = components_of(tr)
    assert comp["queue"] == 3000
    assert comp["dispatch"] == 3500       # 4000..9000 minus the retry
    assert comp["retry"] == 1500
    assert comp["other"] == 1000          # 9000..10000 uncovered
    assert sum(comp.values()) == 9000     # == total, exact
    # the tree-dict path recomputes identically (bench/report contract)
    tree = tr.to_record()
    assert components_of(tree) == comp
    assert tree["components_ns"] == comp
    assert tree_problems(tree) == []


def test_attribution_force_closed_spans_still_sum():
    """finish() force-closes open spans at the root end — attribution
    still sums exactly (the zero-orphan contract under chaos)."""
    tr = RequestTrace("r")
    tr.root.start_ns = 0
    tr.child("queue", "queue", start_ns=0)       # never ended
    tr.finish("stalled", end_ns=5000)
    tree = tr.to_record()
    assert tree_problems(tree) == []
    assert tree["components_ns"]["queue"] == 5000
    assert sum(tree["components_ns"].values()) == tree["total_ns"]


def test_head_sampling_deterministic():
    keep = tracing.TraceStore._head_keep
    assert [keep(n, 1.0) for n in range(1, 5)] == [True] * 4
    assert [keep(n, 0.0) for n in range(1, 5)] == [False] * 4
    kept = [keep(n, 0.5) for n in range(1, 11)]
    assert sum(kept) == 5                  # exactly the rate
    assert kept == [False, True] * 5       # and deterministic


# ---------------------------------------------------------------------
# serving runtime: trees reconcile with the ledger
# ---------------------------------------------------------------------

def test_runtime_traces_reconcile_with_ledger(served_model):
    _, pred = served_model
    _tracing_on()
    rt = _mk(pred, auto_start=False)
    futs = [rt.submit(_feed(1, seed=i)) for i in range(4)]
    rt.process_once()                      # one bucket-4 batch
    assert all(f.exception(timeout=5) is None for f in futs)
    rt.close()
    store = tracing.get()
    label = rt.config.label
    trees = store.retained_trees(label)
    assert len(trees) == 4
    ledger = rt.stats.summary()["outcomes"]
    got = collections.Counter(t["outcome"] for t in trees)
    assert got == collections.Counter(
        {k: v for k, v in ledger.items() if v})
    for t in trees:
        assert tree_problems(t) == []                  # orphan-free
        assert components_of(t) == t["components_ns"]  # exact, ==
        assert sum(t["components_ns"].values()) == t["total_ns"]
        names = [s["name"] for s in t["spans"]]
        assert "queue" in names
        assert any(n.startswith("dispatch/b") for n in names)
    assert store.active_traces(label) == []            # all closed


def test_a_watchdog_stall_is_charged_to_the_victims_tree(served_model):
    """A dispatch that hangs past the watchdog is abandoned and the
    request re-dispatched: it is served, and its tree charges the time
    lost to the hang to `stall`, with the components still summing
    exactly to the total."""
    _, pred = served_model
    _tracing_on()
    hang = threading.Event()
    # prewarmed: the re-dispatch must not meet a compile under the
    # watchdog
    rt = _mk(pred, prewarm=True, watchdog_stall_s=0.1,
             watchdog_poll_s=0.02, watchdog_policy="cancel_retry")
    try:
        faultinject.arm(stall_points={"serving.dispatch": hang})
        rt.run(_feed(2), timeout=30)   # stall -> abandon -> re-dispatch
        assert rt.stats.watchdog_stalls >= 1
        assert rt.stats.summary()["outcomes"]["completed"] == 1
    finally:
        hang.set()
        rt.close()
        faultinject.disarm()
    (tree,) = tracing.get().retained_trees(rt.config.label)
    assert tree["outcome"] == "completed"
    assert tree["components_ns"].get("stall", 0) > 0
    assert tree_problems(tree) == []
    assert components_of(tree) == tree["components_ns"]
    assert sum(tree["components_ns"].values()) == tree["total_ns"]


def test_runtime_joins_external_traceparent(served_model):
    _, pred = served_model
    _tracing_on()
    rt = _mk(pred, auto_start=False)
    hdr = format_traceparent("c" * 32, "d" * 16)
    fut = rt.submit(_feed(1), traceparent=hdr)
    rt.process_once()
    fut.result(timeout=5)
    rt.close()
    trees = tracing.get().retained_trees(rt.config.label)
    assert [t["trace_id"] for t in trees] == ["c" * 32]
    root = [s for s in trees[0]["spans"] if s["depth"] == 0][0]
    assert root["parent_id"] == "d" * 16   # child of the caller's span


def test_tracing_off_is_absent_not_broken(served_model):
    """Flag off: no trace objects, no store state, requests unaffected
    — the gate-free contract's observable half."""
    _, pred = served_model
    assert not tracing.get().enabled
    assert tracing.get().start_request("r") is None
    rt = _mk(pred, auto_start=False)
    fut = rt.submit(_feed(1))
    rt.process_once()
    fut.result(timeout=5)
    rt.close()
    assert tracing.get().labels() == []


def test_shed_and_rejected_close_trees(served_model):
    """Admission-edge outcomes close the tree too: a queue-shed
    request's trace finishes "shed", a backpressure rejection finishes
    "rejected" — the outcome multiset reconciles exactly."""
    _, pred = served_model
    _tracing_on()
    clk = FakeClock()
    rt = _mk(pred, auto_start=False, clock=clk, max_queue_depth=2)
    f1 = rt.submit(_feed(1), deadline_s=0.05)
    f2 = rt.submit(_feed(1), deadline_s=50.0)
    with pytest.raises(QueueFullError):
        rt.submit(_feed(1))                # depth 2: tree -> rejected
    clk.t += 0.1                           # f1's budget expires
    rt.process_once()                      # sheds f1, serves f2
    assert f1.exception(timeout=5) is not None
    assert f2.exception(timeout=5) is None
    rt.close()
    store = tracing.get()
    label = rt.config.label
    trees = store.retained_trees(label)
    got = collections.Counter(t["outcome"] for t in trees)
    ledger = rt.stats.summary()["outcomes"]
    assert got == collections.Counter(
        {k: v for k, v in ledger.items() if v})
    assert got["shed"] == 1 and got["rejected"] == 1 \
        and got["completed"] == 1
    for t in trees:
        assert tree_problems(t) == []
    rej = [t for t in trees if t["outcome"] == "rejected"][0]
    assert any("queue full" in a[1]
               for a in rej["spans"][0].get("annotations", ()))
    assert store.active_traces(label) == []


# ---------------------------------------------------------------------
# decode engine: requeue / broken-drain semantics
# ---------------------------------------------------------------------

def test_decode_breaker_requeue_reuses_same_trace(dense_model):
    """A breaker-open requeue is a DETOUR of the same request: the
    trace id survives, the requeue is a point annotation, the queue
    span keeps accruing, and the final tree still sums exactly."""
    clk = FakeClock()
    _tracing_on()
    eng = _engine(dense_model, clock=clk, breaker_threshold=1,
                  breaker_cooldown_s=5.0, retry_policy=None)
    eng.breaker.note_failure(RuntimeError("induced"))   # OPEN
    fut = eng.submit(np.arange(4) % 61, 3)
    tid0 = list(tracing.get().active_traces(eng.config.label))
    assert len(tid0) == 1
    eng.step()                              # breaker open -> requeue
    assert not fut.done()
    clk.t += 10.0                           # past cooldown: half-open
    _drain(eng, [fut])
    assert fut.exception(timeout=5) is None
    eng.close()
    trees = tracing.get().retained_trees(eng.config.label)
    assert [t["trace_id"] for t in trees] == tid0      # SAME trace
    t = trees[0]
    assert tree_problems(t) == []
    root = [s for s in t["spans"] if s["depth"] == 0][0]
    assert any(a[1] == "breaker_requeue"
               for a in root.get("annotations", ()))
    names = [s["name"] for s in t["spans"]]
    assert names.count("queue") == 1        # one span, kept open across
    assert any(n.startswith("prefill/b") for n in names)
    assert "decode" in names
    assert sum(t["components_ns"].values()) == t["total_ns"]


def test_decode_broken_engine_drains_all_traces(dense_model):
    """_mark_broken cancels EVERY unresolved request — queued and
    slot-resident — so no future or trace stays open behind a dead
    engine, and the ledger/trace multisets still reconcile."""
    _tracing_on()
    eng = _engine(dense_model, slots=1)
    f1 = eng.submit(np.arange(5) % 61, 8)
    for _ in range(50):                     # drive f1 slot-resident
        eng.step()
        if eng._slot_req[0] is not None:
            break
    assert eng._slot_req[0] is not None and not f1.done()
    f2 = eng.submit(np.arange(3) % 61, 4)   # still queued
    assert len(tracing.get().active_traces(eng.config.label)) == 2
    eng._mark_broken("induced by test")
    assert f1.exception(timeout=5) is not None
    assert f2.exception(timeout=5) is not None
    s = eng.summary()
    assert s["outcomes"]["cancelled"] == 2
    assert s["requests"] == sum(s["outcomes"].values())
    store = tracing.get()
    assert store.active_traces(eng.config.label) == []
    trees = store.retained_trees(eng.config.label)
    got = collections.Counter(t["outcome"] for t in trees)
    assert got == collections.Counter(cancelled=2)
    for t in trees:
        assert tree_problems(t) == []       # decode span force-closed
    eng.close()


def test_decode_trace_has_token_annotations(dense_model):
    _tracing_on()
    eng = _engine(dense_model)
    fut = eng.submit(np.arange(4) % 61, 5)
    _drain(eng, [fut])
    fut.result(timeout=5)
    eng.close()
    t = tracing.get().retained_trees(eng.config.label)[0]
    assert tree_problems(t) == []
    dec = [s for s in t["spans"] if s["name"] == "decode"][0]
    toks = [a for a in dec.get("annotations", ())
            if a[1].startswith("token ")]
    assert len(toks) == 4                   # tokens 2..5 (1st=prefill)
    pre = [s for s in t["spans"] if s["name"].startswith("prefill/")][0]
    assert any(a[1] == "first_token"
               for a in pre.get("annotations", ()))


# ---------------------------------------------------------------------
# SLO + exemplars + /metrics
# ---------------------------------------------------------------------

def test_slo_violator_retained_under_zero_sampling(served_model):
    """FLAGS_trace_sample=0 drops every head-sampled tree, but SLO
    violators are ALWAYS retained with their full tree — the exemplar
    contract.  Attribution rows are recorded for everyone."""
    _, pred = served_model
    _tracing_on(slo_ms=0.0001, sample=0.0)   # everything violates
    rt = _mk(pred, auto_start=False)
    futs = [rt.submit(_feed(1, seed=i)) for i in range(3)]
    rt.process_once()
    assert all(f.exception(timeout=5) is None for f in futs)
    rt.close()
    store = tracing.get()
    label = rt.config.label
    trees = store.retained_trees(label)
    assert len(trees) == 3                   # violators beat sample=0
    assert all(t["violation"] for t in trees)
    assert len(store.component_rows(label)) == 3
    slo = store.slo_table(label)
    assert slo["violations_total"] == 3 and slo["eligible"] == 3
    assert slo["burn_rate"] == 1.0 and slo["attainment"] == 0.0
    # flip: no SLO, sample=0 -> nothing retained, rows still recorded
    fluid.set_flags({"FLAGS_serving_slo_ms": 0.0})
    rt2 = _mk(pred, auto_start=False)
    f = rt2.submit(_feed(1))
    rt2.process_once()
    f.result(timeout=5)
    rt2.close()
    assert tracing.get().retained_trees(rt2.config.label) == []
    assert len(tracing.get().component_rows(rt2.config.label)) == 1


def test_attribution_table_rows_recompute_from_trees(served_model):
    """The p99 row of attribution_table is ONE actual request's
    decomposition: its components re-derive from that trace's retained
    raw spans with integer equality."""
    _, pred = served_model
    _tracing_on()
    rt = _mk(pred, auto_start=False)
    for i in range(5):
        f = rt.submit(_feed(1, seed=i))
        rt.process_once()
        f.result(timeout=5)
    rt.close()
    store = tracing.get()
    label = rt.config.label
    table = store.attribution_table(label)
    assert table["count"] == 5
    by_id = {t["trace_id"]: t for t in store.retained_trees(label)}
    for key in ("p50", "p99"):
        row = table[key]
        tree = by_id[row["trace_id"]]
        assert components_of(tree) == row["components_ns"]
        assert sum(row["components_ns"].values()) == row["total_ns"]
        assert row["total_ns"] == tree["total_ns"]


def test_slo_metrics_exported_and_families_contiguous(served_model,
                                                      dense_model):
    """/metrics carries the SLO counter+gauge per traced label, and —
    the regression this PR must not introduce — EVERY family in the
    exposition stays contiguous (one # HELP/# TYPE block, all its
    samples together; Prometheus rejects interleaved families)."""
    from paddle_tpu.monitor import exporter

    _, pred = served_model
    _tracing_on(slo_ms=0.0001)
    monitor.enable()
    rt = _mk(pred, auto_start=False)
    futs = [rt.submit(_feed(1, seed=i)) for i in range(2)]
    rt.process_once()
    [f.result(timeout=5) for f in futs]
    # a decode runtime rides the same exposition: its families must
    # not split the serving ones (nor vice versa)
    eng = _engine(dense_model)
    df = eng.submit(np.arange(4) % 61, 3)
    _drain(eng, [df])
    text = exporter.prometheus_text()
    rt.close()
    eng.close()
    parsed = exporter.parse_prometheus(text)
    lab = (("runtime", rt.config.label),)
    assert parsed[("paddle_tpu_serving_slo_violations_total", lab)] == 2
    assert parsed[("paddle_tpu_serving_slo_burn_rate", lab)] == 1.0
    # generic contiguity scan over the whole exposition
    seen_done = set()
    current = None
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name = line.split("{", 1)[0].split(" ", 1)[0]
        if name != current:
            assert name not in seen_done, \
                f"family {name} split into non-contiguous blocks"
            if current is not None:
                seen_done.add(current)
            current = name
    help_names = [ln.split(" ")[2] for ln in text.splitlines()
                  if ln.startswith("# HELP")]
    assert len(help_names) == len(set(help_names))


# ---------------------------------------------------------------------
# export: chrome trace, flight dump, report tool
# ---------------------------------------------------------------------

def test_chrome_trace_request_tracks(served_model):
    from paddle_tpu.monitor.trace import (merged_trace_events,
                                          request_trace_events)

    _, pred = served_model
    _tracing_on()
    rt = _mk(pred, auto_start=False)
    f = rt.submit(_feed(1))
    rt.process_once()
    f.result(timeout=5)
    rt.close()
    trees = tracing.get().retained_trees(rt.config.label)
    evs = request_trace_events(trees)
    procs = [e for e in evs if e["name"] == "process_name"]
    assert procs and procs[0]["pid"] == 2
    assert procs[0]["args"]["name"] == "requests"
    xs = [e for e in evs if e.get("ph") == "X"]
    assert {e["pid"] for e in xs} == {2}
    root_tree = trees[0]
    by_name = {e["name"]: e for e in xs}
    assert root_tree["name"] in by_name
    root_ev = by_name[root_tree["name"]]
    # same clock as the profiler: span ns -> trace clock μs
    assert root_ev["ts"] == root_tree["start_ns"] / 1e3
    assert root_ev["dur"] == root_tree["total_ns"] / 1e3
    assert by_name["queue"]["args"]["category"] == "queue"
    ann = [e for e in evs if e.get("ph") == "i"]
    assert any(a["name"].startswith("batch_join") for a in ann)
    # and they ride the merged timeline
    merged = merged_trace_events([], trace_trees=trees)
    assert any(e.get("pid") == 2 and e.get("ph") == "X"
               for e in merged)


def test_flight_dump_carries_trace_lines(served_model, tmp_path):
    """A flight dump carries the retained trees as kind="trace" lines
    and names still-open traces in a kind="trace_active" line — the
    stall post-mortem join surface."""
    from paddle_tpu.monitor import flight_recorder

    _, pred = served_model
    old = fluid.get_flags("FLAGS_flight_recorder_dir")
    fluid.set_flags({"FLAGS_flight_recorder_dir": str(tmp_path)})
    try:
        _tracing_on()
        rt = _mk(pred, auto_start=False)
        f = rt.submit(_feed(1))
        rt.process_once()
        f.result(timeout=5)
        done_tid = tracing.get().retained_trees(
            rt.config.label)[0]["trace_id"]
        open_fut = rt.submit(_feed(1))      # still queued at dump time
        open_tid = tracing.get().active_traces(rt.config.label)[0]
        flight_recorder.dump("tracing_test")
        paths = glob.glob(str(tmp_path / "*.jsonl"))
        assert paths
        lines = []
        for p in paths:
            with open(p) as fh:
                lines += [json.loads(ln) for ln in fh if ln.strip()]
        trace_lines = [ln for ln in lines if ln.get("kind") == "trace"]
        assert done_tid in {ln["trace_id"] for ln in trace_lines}
        for ln in trace_lines:
            assert tree_problems(ln) == []  # dump == stream shape
        act = [ln for ln in lines if ln.get("kind") == "trace_active"]
        assert act and open_tid in act[0]["active"][rt.config.label]
        rt.process_once()
        open_fut.result(timeout=5)
        rt.close()
    finally:
        fluid.set_flags(old)


def test_report_tool_tracing_section(served_model):
    """kind="serving" records embed the tracing rollup and kind="trace"
    records carry the trees; the report tool renders SLO attainment,
    the p99 breakdown, and the slowest-traces table from them."""
    from tools.telemetry_report import _tracing_section

    _, pred = served_model
    _tracing_on(slo_ms=0.0001)
    monitor.enable()
    rt = _mk(pred, auto_start=False)
    futs = [rt.submit(_feed(1, seed=i)) for i in range(3)]
    rt.process_once()
    [f.result(timeout=5) for f in futs]
    rt.emit_telemetry()
    rt.close()
    records = monitor.serving_records() + monitor.trace_records()
    sec = _tracing_section(records)
    entry = sec["by_label"][rt.config.label]
    assert entry["finished"] == 3
    assert entry["slo"]["violations"] == 3
    assert entry["slo"]["attainment"] == 0.0
    assert entry["p99_breakdown_ms"]
    assert entry["p99_dominant"] in COMPONENTS + ("other",)
    assert sec["trees"] == 3
    assert len(sec["slowest"]) == 3
    assert sec["slowest"][0]["total_ms"] >= sec["slowest"][-1]["total_ms"]
    assert all(r["violation"] for r in sec["slowest"])
    assert all(r["dominant"] for r in sec["slowest"])


# ---------------------------------------------------------------------
# stats honesty (satellite): eviction counters
# ---------------------------------------------------------------------

def test_stats_sample_windows_count_evictions():
    """The bounded latency/TTFT/token rings admit they are windows:
    once full, every push increments a samples_dropped counter and the
    summaries surface it — percentiles silently "improving" because
    slow old samples fell out is no longer invisible."""
    from paddle_tpu.serving.stats import DecodeStats, ServingStats

    st = ServingStats("drop_t", register=False)
    cap = st._samples.maxlen
    for i in range(cap + 7):
        st.note_outcome("completed", latency_s=0.001)
    lat = st.latency()
    assert st.samples_dropped == 7
    assert lat["samples_dropped"] == 7
    assert lat["count"] == cap
    ds = DecodeStats("drop_dec_t", slots=1, register=False)
    tcap = ds._tok_lat.maxlen
    for _ in range(tcap + 3):
        ds.note_token_latency(0.001)
    for _ in range(ds._ttft.maxlen + 2):
        ds.note_prefill(ttft_s=0.001)
    d = ds.decode_summary()
    assert d["token_latency"]["samples_dropped"] == 3
    assert d["ttft"]["samples_dropped"] == 2
