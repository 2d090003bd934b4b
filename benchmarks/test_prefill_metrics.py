"""Tests of the two per-layer metrics that read what the decode engine
spends on its prefills (ISSUE 38): `prefill_device_share_pct.serve`,
from the device trace's program runs, and `prefill_padding_pct.serve`,
from the engine's spans; on the CPU, about half a minute.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_prefill_metrics.py -q

1. Their arithmetic: the share on a two-program trace reduced as the
   benchmark reduces one, the padding on a handful of made-up spans.
2. Each reads nothing, and does not raise, where there is nothing to
   read: no trace, or no program in it; no spans, or spans without
   the attributes, or a profiler without `spans` at all.
3. A rehearsal of the GPT serve cell prints the padding with `--trace 1`
   and neither with `--trace 0` (off the chip the trace has no device
   plane, so the share reads nothing there).
"""

import json
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks import run as R  # noqa: E402
from benchmarks import trace_reduce as T  # noqa: E402
from paddle_tpu import profiler  # noqa: E402

SERVE = "gpt2-medium.serve-closed-c64"
NAMES = ("prefill_device_share_pct.serve", "prefill_padding_pct.serve")


class FakeRun:
    trace = None
    result = {}


def spans_of(monkeypatch, spans):
    def fake(prefix=None):
        return [s for s in spans
                if prefix is None or s[0].startswith(prefix)]

    monkeypatch.setattr(profiler, "spans", fake)


def test_share_reads_the_prefill_programs_runs():
    ms = 1e6
    trace = {
        "/device:TPU:0": {
            "XLA Ops": [["%fusion.1 = f32[8]{0} fusion()", 0, 100 * ms]],
            "XLA Modules": [["jit_decode_step(11)", 0, 30 * ms],
                            ["jit_prefill_b64(12)", 30 * ms, 2 * ms],
                            ["jit_decode_step(11)", 32 * ms, 30 * ms],
                            ["jit_prefill_b256(13)", 62 * ms, 8 * ms],
                            ["jit_decode_step(11)", 70 * ms, 30 * ms]]},
        "/host:CPU": {"python3": [["bench:window", 0, 100 * ms]]},
    }
    run = FakeRun()
    run.trace = T.reduce_trace(trace)
    read = R.load_reader(NAMES[0]).read
    assert read(run, NAMES[0]) == pytest.approx(100.0 * 10 / 100)
    # a stretch that met no prefill reads 0, not nothing
    run.trace = {"module_runs": {"jit_decode_step(11)": [0.03, 0.03]}}
    assert read(run, NAMES[0]) == 0.0


def test_padding_reads_the_prefill_spans(monkeypatch):
    spans_of(monkeypatch, [
        ("engine.prefill_wait", 0, 1, {"bucket": 64, "true_len": 40,
                                       "device_s": 0.002}),
        ("engine.decode_wait", 1, 2, {"active": 3, "device_s": 0.030,
                                      "behind_s": 0.002, "behind": 1}),
        ("engine.prefill_wait", 2, 3, {"bucket": 256, "true_len": 200,
                                       "device_s": 0.008}),
        # other phases and spans carry no such attributes
        ("engine.prefill_host", 4, 5, {}),
        ("host.gc", 6, 7, {"generation": 0, "collected": 0}),
    ])
    padding = R.load_reader(NAMES[1]).read(FakeRun(), NAMES[1])
    assert padding == pytest.approx(100.0 * (320 - 240) / 320)


def test_share_reads_nothing_without_a_trace_or_a_program():
    read = R.load_reader(NAMES[0]).read
    run = FakeRun()
    assert read(run, NAMES[0]) is None            # untraced, or no TPU
    run.trace = {"module_runs": {}}
    assert read(run, NAMES[0]) is None


def test_padding_reads_nothing_where_the_program_records_nothing(
        monkeypatch):
    read = R.load_reader(NAMES[1]).read
    spans_of(monkeypatch, [])
    assert read(FakeRun(), NAMES[1]) is None
    # the parent's spans: the same names, without the new attributes
    spans_of(monkeypatch, [
        ("engine.prefill_wait", 0, 1, {"bucket": 64, "slot": 0, "rid": 1,
                                       "queue_wait_s": 0.1,
                                       "turnaround_s": 0.01,
                                       "late": False}),
        ("engine.decode_wait", 1, 2, {"active": 3, "ahead": True})])
    assert read(FakeRun(), NAMES[1]) is None
    monkeypatch.delattr(profiler, "spans")     # a profiler without spans
    assert read(FakeRun(), NAMES[1]) is None


def test_both_are_declared_for_the_four_serve_cells():
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    serve = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_output_tokens_per_s")["workloads"]
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, source in zip(NAMES, ("device_trace", "program_span")):
        m = declared[name]
        assert m["workloads"] == serve and len(serve) == 4
        assert (m["source"], m["layer"], m["moves"], m["unit"],
                m["better"]) == (source, "entry: decode server",
                                 "serve_output_tokens_per_s", "%", "lower")


def test_rehearsal_prints_the_padding_only_when_traced(capsys, tmp_path):
    lines = {}
    for trace in ("1", "0"):
        code = R.main(["--workload", SERVE, "--seed", "3800000038",
                       "--seconds", "2", "--rehearse", "--trace", trace,
                       "--out", str(tmp_path)])
        assert code == 0
        lines[trace] = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        profiler.reset_profiler()
    traced, plain = lines["1"], lines["0"]
    assert traced["correct"] and plain["correct"]
    padding = traced["metrics"][NAMES[1]]
    assert padding["unit"] == "%"
    # the rehearsal's prompts of 4 to 32 in buckets of 16 and 32
    assert 0 <= padding["value"] < 100
    assert NAMES[0] not in traced["metrics"]   # no device plane here
    assert not set(NAMES) & set(plain["metrics"])
