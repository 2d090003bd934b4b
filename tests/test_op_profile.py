"""Per-op attribution + flight recorder tests (ISSUE 5): exact
split math on FIXED fake payloads, HLO-text parsing, scope-name
stability across recompiles, the sampling mode, the sorted_key
satellite, gauge counter tracks, and the flight-recorder dump after an
InjectedCrash."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, profiler, resilience
from paddle_tpu.framework.executor import op_scope_names, op_scopes
from paddle_tpu.monitor import flight_recorder, op_profile
from paddle_tpu.monitor.op_profile import (
    UNATTRIBUTED, parse_hlo_instruction_costs, scope_of, split_by_scope)


@pytest.fixture(autouse=True)
def _clean_monitor():
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
# attribution math on fixed fake payloads
# ---------------------------------------------------------------------------

def test_split_by_scope_exact_on_fake_payload():
    """The acceptance invariant verbatim: per-scope FLOPs/bytes from a
    FIXED fake per-instruction payload sum EXACTLY (==, not approx) to
    the fake cost_analysis totals, proportions preserved."""
    rows = [
        {"scope": "fwd0/conv2d_0", "flops": 600.0, "bytes_accessed": 30.0},
        {"scope": "fwd0/conv2d_0", "flops": 200.0, "bytes_accessed": 10.0},
        {"scope": "fwd0/relu_1", "flops": 100.0, "bytes_accessed": 40.0},
        {"scope": "update/sgd_2", "flops": 100.0, "bytes_accessed": 10.0},
        {"scope": None, "flops": 0.0, "bytes_accessed": 10.0},
    ]
    totals = {"flops": 2000.0, "bytes_accessed": 400.0}
    split = split_by_scope(rows, totals)
    scopes = split["scopes"]
    # proportions: conv owns 800/1000 of model flops -> 1600 of 2000
    assert scopes["fwd0/conv2d_0"]["flops"] == 1600.0
    assert scopes["fwd0/relu_1"]["flops"] == 200.0
    assert scopes["update/sgd_2"]["flops"] == 200.0
    assert split["unattributed"]["flops"] == 0.0
    # bytes: unattributed keeps its 10/100 share -> 40 of 400
    assert split["unattributed"]["bytes_accessed"] == 40.0
    flops_sum = sum(d["flops"] for d in scopes.values()) \
        + split["unattributed"]["flops"]
    bytes_sum = sum(d["bytes_accessed"] for d in scopes.values()) \
        + split["unattributed"]["bytes_accessed"]
    assert flops_sum == totals["flops"]          # exact, not approx
    assert bytes_sum == totals["bytes_accessed"]
    assert scopes["fwd0/conv2d_0"]["flops_pct"] == 80.0
    assert scopes["fwd0/conv2d_0"]["instructions"] == 2


def test_split_by_scope_remainder_lands_exactly():
    """Scale factors that don't divide evenly still sum exactly: the
    float remainder is assigned, not lost."""
    rows = [{"scope": f"main/op_{i}", "flops": 1.0, "bytes_accessed": 1.0}
            for i in range(3)]
    totals = {"flops": 1000.0, "bytes_accessed": 10.0}
    split = split_by_scope(rows, totals)
    assert sum(d["flops"] for d in split["scopes"].values()) \
        + split["unattributed"]["flops"] == 1000.0
    assert sum(d["bytes_accessed"] for d in split["scopes"].values()) \
        + split["unattributed"]["bytes_accessed"] == 10.0


def test_split_by_scope_remainder_never_negative():
    """The rounding remainder goes to the LARGEST group: a near-zero
    group placed last must not absorb the drift and go negative."""
    rows = [{"scope": "main/a_0", "flops": 1.0, "bytes_accessed": 0.0},
            {"scope": "main/b_1", "flops": 1.0, "bytes_accessed": 0.0},
            {"scope": "main/c_2", "flops": 1.0, "bytes_accessed": 0.0},
            {"scope": "main/tiny_3", "flops": 1e-6,
             "bytes_accessed": 0.0}]
    split = split_by_scope(rows, {"flops": 2.0, "bytes_accessed": None})
    assert all(d["flops"] >= 0.0 for d in split["scopes"].values())
    assert sum(d["flops"] for d in split["scopes"].values()) == 2.0


def test_split_by_scope_modelless_total_is_loud_residual():
    """XLA reports cost but the model saw nothing costable: the whole
    total lands in the unattributed bucket instead of vanishing."""
    rows = [{"scope": "main/copy_0", "flops": 0.0, "bytes_accessed": 0.0}]
    split = split_by_scope(rows, {"flops": 500.0, "bytes_accessed": None})
    assert split["unattributed"]["flops"] == 500.0
    assert split["unattributed"]["flops_pct"] == 100.0


def test_parse_hlo_costs_fixed_text():
    """Deterministic parse of a hand-written HLO module: dot FLOPs use
    the contracting dim, fused inner instructions count FLOPs but not
    bytes, to_apply regions are skipped (the reduce call site covers
    them), and entry instructions count operand+output bytes."""
    hlo = """HloModule jit_step, entry_computation_layout={(f32[8,16]{1,0})->f32[16]{0}}

%region_0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(f32[] %a, f32[] %b), metadata={op_name="jit(step)/main/mean_1/reduce_sum"}
}

%fused_computation (p: f32[8,16]) -> f32[8,16] {
  %p = f32[8,16]{1,0} parameter(0)
  %c = f32[] constant(0)
  %bc = f32[8,16]{1,0} broadcast(f32[] %c), dimensions={}
  ROOT %max.1 = f32[8,16]{1,0} maximum(f32[8,16]{1,0} %p, f32[8,16]{1,0} %bc), metadata={op_name="jit(step)/main/relu_0/max"}
}

ENTRY %main.10 (Arg_0.1: f32[8,16]) -> f32[16] {
  %Arg_0.1 = f32[8,16]{1,0} parameter(0)
  %w = f32[16,16]{1,0} constant({...})
  %dot.2 = f32[8,16]{1,0} dot(f32[8,16]{1,0} %Arg_0.1, f32[16,16]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/main/matmul_2/dot_general"}
  %fusion.1 = f32[8,16]{1,0} fusion(f32[8,16]{1,0} %dot.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/main/relu_0/max"}
  %zero = f32[] constant(0)
  ROOT %reduce.3 = f32[16]{0} reduce(f32[8,16]{1,0} %fusion.1, f32[] %zero), dimensions={0}, to_apply=%region_0, metadata={op_name="jit(step)/main/mean_1/reduce_sum"}
}
"""
    rows = parse_hlo_instruction_costs(hlo)
    by_scope = {}
    for r in rows:
        by_scope.setdefault(r["scope"], []).append(r)
    # dot: 2 * out(8*16) * K(16) = 4096 flops; entry bytes = lhs 512 +
    # rhs 1024 + out 512
    (dot,) = [r for r in rows if r["opcode"] == "dot"]
    assert dot["flops"] == 4096.0
    assert dot["bytes_accessed"] == 512 + 1024 + 512
    assert dot["scope"] == "main/matmul_2"
    # the fused maximum counts flops (128) but no bytes (register op);
    # the fusion call site counts bytes (in 512 + out 512), no flops
    maxes = [r for r in rows if r["opcode"] == "maximum"]
    assert [m["flops"] for m in maxes] == [128.0]
    assert maxes[0]["bytes_accessed"] == 0.0
    (fusion,) = [r for r in rows if r["opcode"] == "fusion"]
    assert fusion["flops"] == 0.0 and fusion["bytes_accessed"] == 1024.0
    # reduce: in_elems (128) flops; the region add must NOT also appear
    assert not [r for r in rows
                if r["opcode"] == "add"], "to_apply region was counted"
    (reduce_,) = [r for r in rows if r["opcode"] == "reduce"]
    assert reduce_["flops"] == 128.0
    assert reduce_["scope"] == "main/mean_1"


def test_parse_hlo_inheritance_and_call_regions():
    """Metadata-less instructions inherit a dataflow-neighbor scope:
    the weight-grad convolution (this jax drops its op_name) must land
    on ITS conv via the family search even when the direct operand is
    someone else's cotangent; and a plain `call` to_apply body (XLA:CPU
    parallel fusion) IS costed while a reduce comparator is not."""
    hlo = """HloModule jit_step

%region_0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.r = f32[] add(f32[] %a, f32[] %b)
}

%parallel_fusion (p: f32[4,4]) -> f32[4,4] {
  %p = f32[4,4]{1,0} parameter(0)
  ROOT %exp.1 = f32[4,4]{1,0} exponential(f32[4,4]{1,0} %p), metadata={op_name="jit(step)/fwd0/relu_1/exp"}
}

ENTRY %main (Arg_0.1: f32[4,4]) -> f32[4,4] {
  %Arg_0.1 = f32[4,4]{1,0} parameter(0)
  %w = f32[4,4]{1,0} constant({...})
  %conv.fwd = f32[4,4]{1,0} convolution(f32[4,4]{1,0} %Arg_0.1, f32[4,4]{1,0} %w), dim_labels=bf_io->bf, metadata={op_name="jit(step)/jvp(fwd0/conv2d_0)/conv_general_dilated"}
  %cot = f32[4,4]{1,0} multiply(f32[4,4]{1,0} %conv.fwd, f32[4,4]{1,0} %conv.fwd), metadata={op_name="jit(step)/transpose(jvp(fwd0/batch_norm_1))/mul"}
  %mid = f32[4,4]{1,0} add(f32[4,4]{1,0} %cot, f32[4,4]{1,0} %cot)
  %conv.wgrad = f32[4,4]{1,0} convolution(f32[4,4]{1,0} %mid, f32[4,4]{1,0} %mid), dim_labels=bf_io->bf
  %zero = f32[] constant(0)
  %red = f32[] reduce(f32[4,4]{1,0} %conv.wgrad, f32[] %zero), dimensions={0,1}, to_apply=%region_0, metadata={op_name="jit(step)/fwd0/mean_2/reduce_sum"}
  ROOT %par = f32[4,4]{1,0} call(f32[4,4]{1,0} %conv.wgrad), to_apply=%parallel_fusion
}
"""
    rows = parse_hlo_instruction_costs(hlo)
    # the bare add inherits its operand's scope (plain 1-hop)
    (mid,) = [r for r in rows if r["opcode"] == "add"
              and r["scope"] is not None]
    assert mid["scope"] == "fwd0/batch_norm_1" and mid["inherited"]
    # the bare weight-grad conv skips the cotangent's batch_norm scope
    # and finds the conv two hops away (family BFS)
    wgrad = [r for r in rows if r["opcode"] == "convolution"
             and r.get("inherited")]
    assert len(wgrad) == 1
    assert wgrad[0]["scope"] == "fwd0/conv2d_0"
    # reduce comparator region excluded; call to_apply body counted
    assert not [r for r in rows if r["opcode"] == "add"
                and r["scope"] is None]       # region add not parsed
    (exp,) = [r for r in rows if r["opcode"] == "exponential"]
    assert exp["flops"] == 16.0 and exp["scope"] == "fwd0/relu_1"


def test_scope_of_extraction_paths():
    known = {"fwd0/conv2d_3", "update/sgd_1"}
    # forward, jvp-wrapped, transpose(jvp(..)) backward, parenthesized
    assert scope_of("jit(step)/jit(main)/fwd0/conv2d_3/conv") \
        == "fwd0/conv2d_3"
    assert scope_of("jit(step)/jvp(fwd0/conv2d_3)/conv") == "fwd0/conv2d_3"
    assert scope_of(
        "jit(step)/transpose(jvp(fwd0/conv2d_3))/transpose") \
        == "fwd0/conv2d_3"
    assert scope_of("jit(step)/jit(main)/update/sgd_1/sub") \
        == "update/sgd_1"
    # known-set filtering rejects lookalikes
    assert scope_of("user/fwd0/conv2d_9/op", known) is None
    assert scope_of("x", known) is None
    assert scope_of(None) is None


# ---------------------------------------------------------------------------
# scope naming + stability across recompiles
# ---------------------------------------------------------------------------

def test_op_scope_names_sections_and_tail():
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    pairs = op_scope_names(main, [loss.name])
    scopes = [s for s, _ in pairs]
    # every op has a scope; names embed the op type and position
    assert len(scopes) == len(set(scopes)) == \
        len(main.global_block().ops)
    for i, (s, op) in enumerate(pairs):
        assert s.endswith(f"{op.type}_{i}")
    # forward ops live in fwd0, optimizer ops in update
    assert scopes[0].startswith("fwd0/")
    assert scopes[-1].startswith("update/")
    # a section-less (inference) clone gets main/ scopes
    test_prog = main.clone(for_test=True)
    t_scopes = [s for s, _ in op_scope_names(test_prog, [loss.name])]
    assert t_scopes and all(s.startswith("main/") for s in t_scopes)


def test_scope_names_stable_across_recompiles():
    """Two compiles of the SAME program (different batch sizes force a
    fresh jit signature) emit IDENTICAL scope sets — attribution keys
    must survive recompiles or per-op history is useless."""
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable()
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feed(16), fetch_list=[loss], scope=scope)
    exe.run(main, feed=_feed(32), fetch_list=[loss], scope=scope)
    events = [e for e in monitor.compile_events() if e.get("op_profile")]
    assert len(events) >= 2
    sets = [frozenset(e["op_profile"]["scopes"]) for e in events[-2:]]
    assert sets[0] == sets[1]
    # and they are exactly the program's own ops
    expected = {s for s, _ in op_scope_names(main, [loss.name])}
    assert sets[0] == expected


def test_compiled_attribution_sums_exactly_and_covers_ops():
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable()
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    split = monitor.op_profile_split()
    assert split is not None
    tot = split["totals"]
    flops_sum = sum(d["flops"] for d in split["scopes"].values()) \
        + split["unattributed"]["flops"]
    assert tot["flops"] and flops_sum == tot["flops"]
    expected = {s for s, _ in op_scope_names(main, [loss.name])}
    assert expected <= set(split["scopes"])
    # snapshot carries the merged rows, json-safe
    snap = monitor.snapshot()
    assert snap["op_profile"]
    json.dumps(snap["op_profile"])


# ---------------------------------------------------------------------------
# sampling mode (eager/dygraph per-op host timing)
# ---------------------------------------------------------------------------

def test_sampling_mode_times_each_op():
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    with op_profile.sampling() as s:
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    rows = s.rows()
    expected = {sc for sc, _ in op_scope_names(main, [loss.name])}
    assert expected <= set(rows)
    for r in rows.values():
        assert r["calls"] == 1
        assert r["total_us"] > 0
        assert r["min_us"] <= r["ave_us"] <= r["max_us"]
    # the eager flag was restored
    assert not fluid.get_flags("FLAGS_eager_executor")[
        "FLAGS_eager_executor"]
    # finished samples stay readable for op_table until cleared
    assert set(op_profile.sampled_rows()) == set(rows)
    table = monitor.op_table()
    assert {r["scope"] for r in table} >= expected
    timed = {r["scope"]: r for r in table if "total_us" in r}
    assert expected <= set(timed)
    assert abs(sum(r["time_pct"] for r in timed.values()) - 100.0) < 0.1


def test_sampling_never_records_jit_staging():
    """A sampler left active around a COMPILED-path run (sampling(
    force_eager=False)) must not record the jit trace's per-op host
    times as measurements — trace time is not device time."""
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    with op_profile.sampling(force_eager=False) as s:
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    assert s.rows() == {}


def test_dygraph_layer_sampling():
    import paddle_tpu.dygraph as dygraph

    with dygraph.guard():
        fc = dygraph.Linear(8, 4)
        x = dygraph.to_variable(np.ones((2, 8), np.float32))
        with op_profile.sampling(force_eager=False) as s:
            fc(x)
    rows = s.rows()
    assert any(k.startswith("dygraph/") for k in rows)


# ---------------------------------------------------------------------------
# stop_profiler satellite: sorting + min/ave columns + per-op section
# ---------------------------------------------------------------------------

def test_stop_profiler_sorted_key_and_min_ave(capsys):
    profiler.start_profiler("CPU")
    with profiler.RecordEvent("alpha"):
        pass
    for _ in range(3):
        with profiler.RecordEvent("beta"):
            pass
    table = profiler.stop_profiler(sorted_key="calls", profile_path=None)
    out = capsys.readouterr().out
    assert table["beta"]["calls"] == 3
    for row in table.values():
        assert row["min_us"] <= row["ave_us"] <= row["max_us"]
        assert row["ave_us"] == pytest.approx(row["total_us"]
                                              / row["calls"])
    # calls-sorted: beta (3 calls) prints before alpha (1)
    assert out.index("beta") < out.index("alpha")
    assert "Min(us)" in out and "Ave(us)" in out


@pytest.mark.parametrize("key", ["max", "min", "ave", "total", "calls"])
def test_stop_profiler_sort_keys_accepted(key):
    profiler.start_profiler("CPU")
    with profiler.RecordEvent("span"):
        pass
    assert "span" in profiler.stop_profiler(sorted_key=key,
                                            profile_path=None)


def test_stop_profiler_rejects_unknown_sort_key():
    profiler.start_profiler("CPU")
    with pytest.raises(ValueError, match="sorted_key"):
        profiler.stop_profiler(sorted_key="bogus", profile_path=None)
    profiler.reset_profiler()


def test_stop_profiler_prints_op_table_when_attributed(capsys):
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable()
    exe.run(startup, scope=scope)
    profiler.start_profiler("CPU")
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    profiler.stop_profiler(profile_path=None)
    out = capsys.readouterr().out
    assert "Per-op attribution" in out
    assert "fwd0/" in out and "update/" in out


# ---------------------------------------------------------------------------
# gauge time-series -> chrome counter tracks (satellite)
# ---------------------------------------------------------------------------

def test_gauge_series_become_counter_tracks(tmp_path):
    monitor.enable()
    g = monitor.gauge("resilience.last_save_s")
    g.set(0.25)
    g.set(0.5)
    monitor.gauge("textual").set("not-a-number")   # must be skipped
    path = profiler.export_chrome_tracing(str(tmp_path / "t.json"))
    monitor.disable()
    events = json.load(open(path))["traceEvents"]
    track = [e for e in events
             if e["ph"] == "C" and e["name"] == "resilience.last_save_s"]
    assert [e["args"]["last_save_s"] for e in track] == [0.25, 0.5]
    assert [e for e in track if e["ts"] <= 0] == []
    assert not [e for e in events
                if e["ph"] == "C" and e["name"] == "textual"]
    json.dumps(events)


def test_registry_reset_clears_gauge_series():
    g = monitor.gauge("some.gauge")
    g.set(1.0)
    assert g.samples()
    monitor.reset()
    assert g.samples() == []


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

@pytest.fixture
def _flight_dir(tmp_path):
    old = fluid.get_flags("FLAGS_flight_recorder_dir")
    fluid.set_flags({"FLAGS_flight_recorder_dir": str(tmp_path)})
    fr = flight_recorder.get()
    fr.clear()
    yield str(tmp_path)
    fr.clear()
    fluid.set_flags(old)


def test_flight_recorder_dump_after_injected_crash(_flight_dir):
    """The acceptance scenario: steps run (telemetry OFF — the recorder
    is always-on), an InjectedCrash fires from the fault-injection
    harness, and the dump contains the last K step records + resilience
    counters."""
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    for _ in range(3):
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    with resilience.plan_scope(crash_points={"test.crash_point": 0}):
        with pytest.raises(resilience.InjectedCrash):
            resilience.faultinject.crash_point("test.crash_point")
    path = flight_recorder.get().last_dump
    assert path and path.startswith(_flight_dir)
    records = monitor.read_jsonl(path)
    kinds = {}
    for r in records:
        kinds[r.get("kind")] = kinds.get(r.get("kind"), 0) + 1
    assert kinds.get("step", 0) >= 4          # startup + 3 train steps
    (meta,) = [r for r in records if r["kind"] == "meta"]
    assert meta["reason"] == "injected_crash:test.crash_point"
    (counters,) = [r for r in records if r["kind"] == "counters"]
    assert counters["recorder"]["injected_crash"] == 1
    # the chrome-trace sibling exists and loads
    trace = path.replace(".jsonl", ".trace.json")
    assert os.path.exists(trace)
    doc = json.load(open(trace))
    assert any(e.get("name") == "step" for e in doc["traceEvents"])


def test_flight_recorder_ring_is_bounded(_flight_dir):
    fr = flight_recorder.FlightRecorder(capacity=4)
    for i in range(10):
        fr.note_step(None, host_dispatch_us=float(i))
    snap = fr.snapshot()
    assert len(snap["steps"]) == 4
    assert snap["step_seq"] == 10
    assert snap["steps"][-1]["step"] == 10
    # minimal records carry a derived step_time_s after the first
    assert "step_time_s" in snap["steps"][-1]


def test_flight_recorder_dump_on_guard_escalation(_flight_dir):
    """Anomaly-guard escalation is a taxonomy dump point: the
    AnomalyError raise leaves a post-mortem even though callers
    typically catch it."""
    fr = flight_recorder.get()
    fr.note_step(None, host_dispatch_us=1.0)
    with resilience.anomaly_guard(policy="skip_step",
                                  max_consecutive=1) as g:
        g.note_anomaly()
        with pytest.raises(resilience.AnomalyError):
            g.note_anomaly()
    path = fr.last_dump
    assert path is not None
    (meta,) = [r for r in monitor.read_jsonl(path)
               if r["kind"] == "meta"]
    assert "anomaly_guard" in meta["reason"]


def test_flight_recorder_disabled_flag_is_total(_flight_dir):
    fr = flight_recorder.FlightRecorder()
    fr.enabled = False
    fr.note_step(None, host_dispatch_us=1.0)
    fr.note_event("anomaly", severe=True)
    assert fr.snapshot()["steps"] == []
    assert fr.dump("reason") is None


def test_flight_recorder_shares_session_records(_flight_dir):
    """With telemetry ON the ring holds the SAME record dicts the
    session keeps — no duplicate bookkeeping on the hot path."""
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable()
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    ring = flight_recorder.get().snapshot()["steps"]
    session = monitor.step_records()
    assert ring[-1] is session[-1]
    # a dump's op_profile record has the SAME shape as the telemetry
    # stream's (top-level scopes), so telemetry_report reads both
    path = monitor.flight_dump("test")
    (op_rec,) = [r for r in monitor.read_jsonl(path)
                 if r["kind"] == "op_profile"]
    assert op_rec["scopes"]


# ---------------------------------------------------------------------------
# tools
# ---------------------------------------------------------------------------

def test_telemetry_report_op_and_resilience_sections(tmp_path):
    import subprocess
    import sys


    jsonl = str(tmp_path / "t.jsonl")
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable(jsonl_path=jsonl)
    exe.run(startup, scope=scope)
    monitor.counter("resilience.retries").add(2)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    monitor.disable()
    tool = os.path.join(_TOOLS, "telemetry_report.py")
    r = subprocess.run([sys.executable, tool, jsonl],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "op_profile" in r.stdout
    assert "resilience" in r.stdout and "retries" in r.stdout


def test_parse_xplane_groups_sampled_trace_by_scope(tmp_path):
    import subprocess
    import sys


    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    profiler.start_profiler("CPU")
    with op_profile.sampling():
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    path = str(tmp_path / "prof") + ".json"
    profiler.stop_profiler(profile_path=str(tmp_path / "prof"))
    tool = os.path.join(_TOOLS, "parse_xplane.py")
    r = subprocess.run([sys.executable, tool, path],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "per-op attribution" in r.stdout
    assert "fwd0/" in r.stdout


# ---------------------------------------------------------------------------
# end to end: attribution of a data-parallel train loop
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def attribution_scenario():
    """Six steps of a small fc train program through the PUBLIC
    Executor.run, data-parallel over the test mesh, telemetry on; run
    once, each attribution invariant is a case below."""
    import jax

    monitor.reset()
    monitor.enable()
    try:
        with fluid.unique_name.guard():
            main, startup, loss = _toy_train_program(width=64)
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=len(jax.devices())
        ).with_telemetry("attribution_scenario")
        exe = fluid.Executor()
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        feed = _feed(64, width=64)
        for _ in range(6):
            exe.run(prog, feed=feed, fetch_list=[loss], scope=scope,
                    return_numpy=False)
        split = monitor.op_profile_split()
        snap = monitor.snapshot()
        expected = {s for s, _ in op_scope_names(prog, [loss.name])}
        checks = {"split_present": split is not None}
        if split is None:
            return checks
        scopes = split["scopes"]
        flops_sum = sum(d["flops"] for d in scopes.values()) \
            + split["unattributed"]["flops"]
        bytes_sum = sum(d["bytes_accessed"] for d in scopes.values()) \
            + split["unattributed"]["bytes_accessed"]
        checks.update({
            # exact: split_by_scope assigns the float remainder, so
            # == (not approx) is the contract under test
            "flops_sum_exact": flops_sum == split["totals"]["flops"]
            and split["totals"]["flops"] > 0,
            "bytes_sum_exact":
                bytes_sum == split["totals"]["bytes_accessed"],
            # every ProgramDesc op of the compiled section under its own
            # scope name; framework-inserted dp-sync collectives carry
            # scopes of their own on top
            "all_ops_scoped": expected <= set(scopes),
            "residual_under_1pct":
                split["unattributed"]["flops_pct"] <= 1.0,
            "snapshot_rows": bool(snap.get("op_profile"))
            and json.dumps(snap["op_profile"]) is not None,
        })
        return checks
    finally:
        monitor.disable()
        monitor.reset()


@pytest.mark.parametrize("check", [
    "split_present", "flops_sum_exact", "bytes_sum_exact",
    "all_ops_scoped", "residual_under_1pct", "snapshot_rows"])
def test_attribution_of_a_data_parallel_train_loop(attribution_scenario,
                                                   check):
    assert attribution_scenario.get(check), attribution_scenario
