"""HBM memory observability tests (ISSUE 6): exact liveness/peak math
on FIXED fake HLO text (donated-input aliasing, remainder assignment
summing exactly, the residual bucket), variable-class attribution,
the OOM post-mortem end-to-end via the fault-injection harness, JSONL
round-trip, and trace-track well-formedness."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, profiler, resilience
from paddle_tpu.monitor import flight_recorder, mem_profile
from paddle_tpu.monitor.mem_profile import (
    build_mem_profile, mem_table, parse_hlo_liveness)
from paddle_tpu.monitor.op_profile import UNATTRIBUTED, scale_groups_exact
from paddle_tpu.resilience.taxonomy import is_oom


@pytest.fixture(autouse=True)
def _clean_monitor():
    monitor.disable()
    monitor.reset()
    yield
    monitor.disable()
    monitor.reset()


@pytest.fixture
def _flight_dir(tmp_path):
    old = fluid.get_flags("FLAGS_flight_recorder_dir")
    fluid.set_flags({"FLAGS_flight_recorder_dir": str(tmp_path)})
    fr = flight_recorder.get()
    fr.clear()
    yield str(tmp_path)
    fr.clear()
    fluid.set_flags(old)


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


# A hand-written scheduled module with every shape the parser must
# handle: arg-name metadata on parameters, a donated output
# (input_output_alias), a fusion, a backward (transpose(jvp)) value, a
# metadata-less instruction that must inherit its neighbor's scope,
# and a skipped constant.
_FAKE_HLO = """HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias) }, entry_computation_layout={(f32[8,8]{1,0}, f32[4,8]{1,0})->(f32[8,8]{1,0}, f32[])}

%fused_computation (p: f32[4,8]) -> f32[4,8] {
  %p = f32[4,8]{1,0} parameter(0)
  ROOT %e = f32[4,8]{1,0} exponential(f32[4,8]{1,0} %p), metadata={op_name="jit(step)/jit(main)/fwd0/relu_1/exp"}
}

ENTRY %main.10 (Arg_0.1: f32[8,8], Arg_1.2: f32[4,8]) -> (f32[8,8], f32[]) {
  %Arg_0.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="state[\\'w\\']"}
  %Arg_1.2 = f32[4,8]{1,0} parameter(1), metadata={op_name="feeds[\\'x\\']"}
  %dot.3 = f32[4,8]{1,0} dot(f32[4,8]{1,0} %Arg_1.2, f32[8,8]{1,0} %Arg_0.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jit(main)/fwd0/fc_0/dot_general"}
  %fusion.4 = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %dot.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jit(main)/fwd0/relu_1/exp"}
  %mul.5 = f32[4,8]{1,0} multiply(f32[4,8]{1,0} %fusion.4, f32[4,8]{1,0} %fusion.4), metadata={op_name="jit(step)/transpose(jvp(fwd0/fc_0))/mul"}
  %bare.6 = f32[4,8]{1,0} add(f32[4,8]{1,0} %mul.5, f32[4,8]{1,0} %mul.5)
  %wnew.7 = f32[8,8]{1,0} subtract(f32[8,8]{1,0} %Arg_0.1, f32[8,8]{1,0} %Arg_0.1), metadata={op_name="jit(step)/jit(main)/update/sgd_2/sub"}
  %c = f32[] constant(0)
  %red.8 = f32[] reduce(f32[4,8]{1,0} %bare.6, f32[] %c), dimensions={0,1}, to_apply=%region_0, metadata={op_name="jit(step)/jit(main)/fwd0/mean_3/reduce_sum"}
  ROOT %tuple.9 = (f32[8,8]{1,0}, f32[]) tuple(f32[8,8]{1,0} %wnew.7, f32[] %red.8)
}
"""

_VAR_INFO = {"params": frozenset({"w"}), "persist": frozenset({"w"})}


def _fake_parsed():
    return parse_hlo_liveness(_FAKE_HLO, var_info=_VAR_INFO)


# ---------------------------------------------------------------------------
# liveness on fixed fake HLO
# ---------------------------------------------------------------------------

def test_parse_liveness_fixed_text():
    parsed = _fake_parsed()
    by = {b["name"]: b for b in parsed["buffers"]}
    assert parsed["positions"] == 9        # constant excluded
    # arguments: caller-owned (alloc 0), live for the whole program,
    # classed through the var maps / arg-path metadata
    w = by["Arg_0.1"]
    assert w["arg"] and w["bytes"] == 256 and w["alloc_bytes"] == 0
    assert (w["def"], w["end"]) == (0, 8)
    assert w["class"] == "parameter" and w["arg_name"] == "state['w']"
    assert by["Arg_1.2"]["class"] == "activation"
    # computed buffers: def at their position, end at last use
    dot = by["dot.3"]
    assert (dot["def"], dot["end"]) == (2, 3)
    assert dot["alloc_bytes"] == 128
    assert dot["scope"] == "fwd0/fc_0" and dot["class"] == "activation"
    assert (by["fusion.4"]["def"], by["fusion.4"]["end"]) == (3, 4)
    # backward value: transpose(jvp(..)) -> gradient, scoped to ITS op
    mul = by["mul.5"]
    assert mul["class"] == "gradient" and mul["scope"] == "fwd0/fc_0"
    # the metadata-less add inherits its operand's scope
    bare = by["bare.6"]
    assert bare["scope"] == "fwd0/fc_0" and bare.get("inherited")
    assert (bare["def"], bare["end"]) == (5, 7)
    # root operands live to the end; the tuple itself allocates nothing
    assert by["red.8"]["end"] == 8
    assert by["tuple.9"]["alloc_bytes"] == 0


def test_donated_alias_not_double_counted():
    """The output aliased onto the donated parameter reuses its
    storage: zero new allocation, class donated_reuse, live to end."""
    parsed = _fake_parsed()
    by = {b["name"]: b for b in parsed["buffers"]}
    wnew = by["wnew.7"]
    assert wnew["donated"] and wnew["alloc_bytes"] == 0
    assert wnew["class"] == "donated_reuse"
    assert wnew["end"] == 8
    # ...and the non-aliased output (the loss) still allocates
    assert by["red.8"]["alloc_bytes"] == 4


def test_peak_and_timeline_fixed_text():
    """Hand-computed curve: args baseline 384, temp peak 256 at
    positions 3..5 (argmax reports the first), timeline monotone and
    exact at every position."""
    prof = build_mem_profile(_fake_parsed(), memory=None)
    assert prof["peak"]["pos"] == 3
    assert prof["peak"]["model_alloc_bytes"] == 256
    assert prof["peak"]["model_bytes"] == 640
    assert prof["totals"]["model_args_bytes"] == 384
    expected = [[0, 384], [1, 384], [2, 512], [3, 640], [4, 640],
                [5, 640], [6, 512], [7, 516], [8, 388]]
    assert prof["timeline"] == expected
    assert all(a[0] < b[0] for a, b in zip(prof["timeline"],
                                           prof["timeline"][1:]))


def test_peak_scope_scaling_exact_and_classes():
    """Per-scope peak contributions scale EXACTLY (==, any summation
    order) to memory_analysis temp+output; the class split at the peak
    names parameters and activations."""
    memory = {"temp_bytes": 900, "output_bytes": 100,
              "argument_bytes": 384, "alias_bytes": 256}
    prof = build_mem_profile(_fake_parsed(), memory=memory)
    scopes = prof["scopes"]
    # live at peak pos 3: dot (fwd0/fc_0, 128) + fusion (fwd0/relu_1,
    # 128) -> 500 / 500 of the 1000 temp+output bytes
    assert scopes["fwd0/fc_0"]["peak_bytes"] == 500.0
    assert scopes["fwd0/relu_1"]["peak_bytes"] == 500.0
    total = sum(d["peak_bytes"] for d in scopes.values()) \
        + prof["unattributed"]["peak_bytes"]
    assert total == 1000.0
    assert prof["totals"]["attributed_bytes"] == 1000
    assert prof["peak"]["hbm_bytes"] == 384 + 100 + 900
    classes = prof["classes"]
    assert classes["parameter"]["peak_bytes"] == 256
    assert classes["activation"]["peak_bytes"] == 384   # x + dot + fusion
    # peak snapshot table: ranked by resident bytes, w first
    top = prof["top_buffers"]
    assert top[0]["var"] == "state['w']" and top[0]["bytes"] == 256
    assert top[0]["pct_of_peak"] == pytest.approx(256 / 640 * 100, abs=0.01)
    assert prof["donated"] == ["wnew.7"]


def test_donated_buffer_visible_in_classes_at_peak():
    """A donated output live at the peak shows up in the classes split
    and the peak table as donated_reuse (zero resident bytes) instead
    of being silently dropped — and contributes nothing to the scaled
    per-scope attribution."""
    parsed = {"buffers": [
        {"name": "t", "opcode": "multiply", "scope": "fwd0/mul_0",
         "class": "activation", "shape": "f32[4]", "bytes": 16,
         "alloc_bytes": 16, "def": 0, "end": 1, "arg": False,
         "donated": False},
        {"name": "wnew", "opcode": "subtract", "scope": "update/sgd_1",
         "class": "donated_reuse", "shape": "f32[4]", "bytes": 16,
         "alloc_bytes": 0, "def": 0, "end": 1, "arg": False,
         "donated": True}], "positions": 2}
    prof = build_mem_profile(parsed, memory={"temp_bytes": 100,
                                             "output_bytes": 0})
    assert prof["classes"]["donated_reuse"]["buffers"] == 1
    assert prof["classes"]["donated_reuse"]["peak_bytes"] == 0
    assert any(b["name"] == "wnew" and b.get("donated")
               for b in prof["top_buffers"])
    # donation contributes NO scaled scope bytes
    assert "update/sgd_1" not in prof["scopes"]
    assert prof["scopes"]["fwd0/mul_0"]["peak_bytes"] == 100.0


def test_scale_remainder_lands_exactly():
    """Scale factors that don't divide evenly still sum exactly; the
    remainder goes to the LARGEST group so nothing can go negative."""
    per = {f"s{i}": {"peak_bytes": 1.0} for i in range(3)}
    per["big"] = {"peak_bytes": 5.0}
    assert scale_groups_exact(per, "peak_bytes", 1000.0)
    assert sum(d["peak_bytes"] for d in per.values()) == 1000.0
    assert all(d["peak_bytes"] >= 0 for d in per.values())
    # modelless: untouched, reported False
    empty = {"a": {"peak_bytes": 0.0}}
    assert not scale_groups_exact(empty, "peak_bytes", 10.0)


def test_modelless_total_is_loud_residual():
    """XLA reports temp+output bytes but no buffer is live at the
    model's peak: the whole total lands in the unattributed bucket."""
    parsed = {"buffers": [
        {"name": "a", "opcode": "tuple", "scope": None, "class": "temp",
         "shape": "f32[2]", "bytes": 8, "alloc_bytes": 0, "def": 0,
         "end": 0, "arg": False, "donated": False}], "positions": 1}
    prof = build_mem_profile(parsed, memory={"temp_bytes": 500,
                                             "output_bytes": 0})
    assert prof["unattributed"]["peak_bytes"] == 500.0
    assert prof["unattributed"]["peak_pct"] == 100.0
    assert prof["scopes"] == {}


def test_mem_table_rows_ordered_residual_last():
    memory = {"temp_bytes": 900, "output_bytes": 100}
    prof = build_mem_profile(_fake_parsed(), memory=memory)
    rows = mem_table(prof)
    assert rows and rows[0]["peak_bytes"] >= rows[-2]["peak_bytes"]
    assert all(set(r) >= {"scope", "peak_bytes", "peak_pct", "buffers"}
               for r in rows)
    assert mem_table(None) == []


# ---------------------------------------------------------------------------
# compiled end-to-end (public Executor path)
# ---------------------------------------------------------------------------

def test_compiled_mem_profile_sums_exactly():
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable()
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    prof = monitor.mem_profile_split()
    assert prof is not None
    total = sum(d["peak_bytes"] for d in prof["scopes"].values()) \
        + prof["unattributed"]["peak_bytes"]
    assert prof["totals"]["attributed_bytes"] > 0
    assert total == prof["totals"]["attributed_bytes"]
    # entry arguments resolved through the executor's var maps: the fc
    # weights are class parameter, the feeds activations
    classes = {b["class"] for b in prof["top_buffers"]}
    assert "parameter" in classes or "activation" in classes
    # surfaces agree and are json-safe
    snap = monitor.snapshot()
    assert snap["mem_profile"]["peak"] == prof["peak"]
    json.dumps(snap["mem_profile"])
    assert monitor.mem_table()
    assert monitor.peak_breakdown()["scopes"] == monitor.mem_table()


def test_mem_profile_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable(jsonl_path=path)
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    monitor.disable()
    records = monitor.read_jsonl(path)
    mems = [r for r in records if r.get("kind") == "mem_profile"]
    assert mems
    rec = mems[-1]
    assert rec["scopes"] and rec["timeline"] and rec["key"]
    # the record round-trips the in-process structure verbatim
    prof = monitor.mem_profile_split()
    assert rec["peak"] == prof["peak"]
    assert rec["timeline"] == prof["timeline"]


def test_trace_carries_hbm_track_and_single_live_bytes_source(tmp_path):
    """The merged trace renders the mem-profile timeline as the
    hbm_live_bytes counter track (monotone ts, numeric args), and the
    live-bytes watermark appears ONLY as the compile.live_bytes gauge
    track — the per-compile-event duplicate is gone (dedupe
    satellite)."""
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
    events = json.load(open(path))["traceEvents"]
    hbm = [e for e in events if e.get("ph") == "C"
           and e["name"] == "hbm_live_bytes"]
    assert len(hbm) >= 2
    ts = [e["ts"] for e in hbm]
    assert ts == sorted(ts)
    assert all(isinstance(e["args"]["bytes"], (int, float))
               for e in hbm)
    counter_names = {e["name"] for e in events if e.get("ph") == "C"}
    assert "compile.live_bytes" in counter_names
    assert "live_bytes" not in counter_names     # the old duplicate


# ---------------------------------------------------------------------------
# OOM classification + post-mortem
# ---------------------------------------------------------------------------

def test_is_oom_classification():
    assert is_oom(RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert is_oom(RuntimeError("Out of memory allocating 5 bytes"))
    assert is_oom(MemoryError())
    # the chain is walked: RetriesExhausted wrapping an OOM reads as one
    inner = RuntimeError("RESOURCE_EXHAUSTED: oom")
    outer = resilience.RetriesExhausted(3, inner)
    assert is_oom(outer)
    assert not is_oom(RuntimeError("INVALID_ARGUMENT: bad shape"))
    assert not is_oom(None)
    # it is a registered dump trigger in the inspectable taxonomy
    assert "oom" in resilience.TAXONOMY["dump_triggers"]


def test_parse_requested_bytes():
    parse = flight_recorder._parse_requested_bytes
    assert parse("while trying to allocate 123456 bytes") == 123456
    assert parse("Attempting to allocate 1.91G. That was not "
                 "possible.") == int(1.91 * 2 ** 30)
    assert parse("failed to allocate 512.0KiB there") == 512 * 1024
    assert parse("no sizes here") is None
    assert parse("") is None


def test_oom_dump_end_to_end(_flight_dir):
    """The acceptance scenario: a synthetic RESOURCE_EXHAUSTED raised
    inside a compiled Executor step (fault-injection harness, retry
    off) produces a flight-recorder dump containing the peak-HBM table
    and the live-bytes timeline BEFORE the error propagates."""
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable()
    exe.run(startup, scope=scope)
    for _ in range(2):
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    with resilience.plan_scope(transient_at_step=0):
        with pytest.raises(resilience.InjectedTransientError):
            exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    path = flight_recorder.get().last_dump
    assert path and path.startswith(_flight_dir)
    records = monitor.read_jsonl(path)
    (meta,) = [r for r in records if r["kind"] == "meta"]
    assert meta["reason"].startswith("oom:")
    # the peak table + timeline rode along
    (mem,) = [r for r in records if r["kind"] == "mem_profile"]
    assert mem["scopes"] and mem["timeline"] and mem["top_buffers"]
    # the oom record carries the parsed requested bytes
    (oom,) = [r for r in records if r["kind"] == "oom"]
    assert "RESOURCE_EXHAUSTED" in oom["error"]
    assert oom["requested_bytes"] == 1073741824
    # last-K steps are in the window, and the counter moved
    assert sum(1 for r in records if r.get("kind") == "step") >= 3
    assert monitor.snapshot()["counters"]["resilience.oom_events"] == 1


def test_oom_with_retry_recovers_without_dump(_flight_dir):
    """With retry enabled a transient RESOURCE_EXHAUSTED is retried
    and the run continues — recovery wins, no OOM dump."""
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    resilience.enable_retry(resilience.RetryPolicy(
        max_retries=3, base_delay=0.0, jitter=0.0, sleep=lambda s: None))
    try:
        with resilience.plan_scope(transient_at_step=0,
                                   transient_times=1):
            exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    finally:
        resilience.disable_retry()
    assert flight_recorder.get().last_dump is None


def test_flight_recorder_disabled_no_oom_dump(_flight_dir):
    fr = flight_recorder.FlightRecorder()
    fr.enabled = False
    assert fr.dump_oom(RuntimeError("RESOURCE_EXHAUSTED")) is None


# ---------------------------------------------------------------------------
# tools + profiler surfaces
# ---------------------------------------------------------------------------

def test_stop_profiler_prints_peak_hbm(capsys):
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable()
    profiler.start_profiler("CPU")
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    profiler.stop_profiler(profile_path=None)
    out = capsys.readouterr().out
    assert "Peak HBM" in out
    assert "classes:" in out and "parameter=" in out


def test_telemetry_report_memory_section(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with fluid.unique_name.guard():
        main, startup, loss = _toy_train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    monitor.enable(jsonl_path=path)
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    monitor.disable()
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "telemetry_report.py")
    r = subprocess.run([sys.executable, tool, path],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "memory" in r.stdout
    assert "top_peak_scopes" in r.stdout


def test_parse_xplane_memory_track_table(tmp_path):
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
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "parse_xplane.py")
    r = subprocess.run([sys.executable, tool, path],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "memory counter tracks" in r.stdout
    assert "hbm_live_bytes" in r.stdout


# ---------------------------------------------------------------------------
# end to end: peak-memory attribution of a data-parallel train loop
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def peak_memory_scenario():
    """Six steps of a small fc train program through the PUBLIC
    Executor.run, data-parallel over the test mesh, telemetry on; run
    once, each peak-memory invariant is a case below."""
    import jax

    monitor.reset()
    monitor.enable()
    try:
        with fluid.unique_name.guard():
            main, startup, loss = _toy_train_program(width=64)
        ndev = len(jax.devices())
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=ndev
        ).with_telemetry("peak_memory_scenario")
        exe = fluid.Executor()
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        # 128 examples PER DEVICE, whatever the mesh: the <=1% residual
        # bound is an attribution-coverage assertion on real working
        # buffers — a shrinking per-device batch would turn XLA's
        # constant-size parameter-plumbing copies (the honest residual)
        # into bound-breaking noise
        feed = _feed(128 * ndev, width=64)
        for _ in range(6):
            exe.run(prog, feed=feed, fetch_list=[loss], scope=scope,
                    return_numpy=False)
        prof = monitor.mem_profile_split()
        snap = monitor.snapshot()
        checks = {"profile_present": prof is not None}
        if prof is None:
            return checks
        peak_sum = sum(d["peak_bytes"] for d in prof["scopes"].values()) \
            + prof["unattributed"]["peak_bytes"]
        tl = prof["timeline"]
        checks.update({
            # exact: scale_groups_exact assigns the float remainder,
            # so == (not approx) is the contract
            "peak_sum_exact":
                peak_sum == prof["totals"]["attributed_bytes"]
                and (prof["totals"]["attributed_bytes"] or 0) > 0,
            "residual_under_1pct":
                prof["unattributed"]["peak_pct"] <= 1.0,
            "timeline_monotone": len(tl) >= 2 and all(
                tl[i][0] < tl[i + 1][0] for i in range(len(tl) - 1)),
            "timeline_covers_peak": any(
                p == prof["peak"]["pos"] for p, _ in tl),
            "peak_table_nonempty": bool(prof["top_buffers"]),
            "classes_name_params":
                "parameter" in (prof.get("classes") or {}),
            "snapshot_rows": bool(snap.get("mem_profile"))
            and json.dumps(snap["mem_profile"]) is not None,
            "peak_bytes_positive": (prof["peak"].get("hbm_bytes")
                                    or prof["peak"]["model_bytes"]) > 0,
        })
        return checks
    finally:
        monitor.disable()
        monitor.reset()


@pytest.mark.parametrize("check", [
    "profile_present", "peak_sum_exact", "residual_under_1pct",
    "timeline_monotone", "timeline_covers_peak", "peak_table_nonempty",
    "classes_name_params", "snapshot_rows", "peak_bytes_positive"])
def test_peak_memory_of_a_data_parallel_train_loop(peak_memory_scenario,
                                                   check):
    assert peak_memory_scenario.get(check), peak_memory_scenario
