"""Tests of the per-layer metrics that read the program's own spans
(`paddle_tpu.profiler.spans`); on the CPU, a minute or two.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_span_metrics.py -q

1. A rehearsal of each cell prints its span metrics with `--trace 1` (a
   profiler session runs on the CPU too) and none of them with
   `--trace 0`; `decode_program_ms_p50.serve` needs the device's plane
   and is left out of a rehearsal.
2. The readers' arithmetic on a handful of made-up spans, and that each
   reads nothing, and does not raise, where the program records no spans
   or has no `spans` at all (the parent of the PR that brought them).
"""

import json
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks import run as R  # noqa: E402
from paddle_tpu import profiler  # noqa: E402

TRAIN = "gpt2-medium.train-seq1024"
SERVE = "gpt2-medium.serve-closed-c64"
SPAN_METRICS = {
    SERVE: {"engine_host_ms_per_step.serve", "queue_wait_ms_p50.serve",
            "prefill_turnaround_ms_p50.serve"},
    TRAIN: {"reader_host_ms_per_batch.train"},
}
ALL = set().union(*SPAN_METRICS.values()) | {"decode_program_ms_p50.serve"}


@pytest.mark.parametrize("workload", [SERVE, TRAIN])
def test_rehearsal_prints_the_span_metrics_only_when_traced(
        capsys, tmp_path, workload):
    lines = {}
    for trace in ("1", "0"):
        code = R.main(["--workload", workload, "--seed", "4000000027",
                       "--seconds", "2", "--rehearse", "--trace", trace,
                       "--out", str(tmp_path)])
        assert code == 0
        lines[trace] = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        if trace == "0":
            assert profiler.spans() == []
        profiler.reset_profiler()
    traced, plain = lines["1"], lines["0"]
    assert traced["correct"] and plain["correct"]
    assert ALL & set(traced["metrics"]) == SPAN_METRICS[workload]
    for name in SPAN_METRICS[workload]:
        assert traced["metrics"][name]["value"] > 0
        assert traced["metrics"][name]["unit"] == "ms"
    assert not ALL & set(plain["metrics"])
    assert traced["notes"]["compiled_in_window"] == 0


def test_every_span_metric_is_declared_for_one_cell():
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for cell, names in SPAN_METRICS.items():
        for name in names | ({"decode_program_ms_p50.serve"}
                             if cell == SERVE else set()):
            assert declared[name]["workloads"] == [cell]
            assert hasattr(R.load_reader(name), "read")


class FakeRun:
    trace = None
    result = {}


def spans_of(monkeypatch, spans):
    def fake(prefix=None):
        return [s for s in spans
                if prefix is None or s[0].startswith(prefix)]

    monkeypatch.setattr(profiler, "spans", fake)


def test_engine_host_counts_whole_iterations_and_leaves_waits_out(
        monkeypatch):
    ms = 1_000_000
    spans_of(monkeypatch, [
        # a phase of an iteration begun before the session: no step span
        ("engine.emit", 0, 1 * ms, {}),
        # a whole iteration: host 1 + 2 + 3 + 4 = 10 ms, waits 50 ms
        ("engine.step", 10 * ms, 80 * ms, {}),
        ("engine.sweep", 10 * ms, 11 * ms, {}),
        ("engine.prefill_host", 11 * ms, 13 * ms, {}),
        ("engine.prefill_wait", 13 * ms, 23 * ms, {}),
        ("engine.decode_host", 23 * ms, 26 * ms, {}),
        ("engine.decode_wait", 26 * ms, 66 * ms, {"active": 3}),
        ("engine.emit", 66 * ms, 70 * ms, {}),
        # one cut short by the session's end: not counted
        ("engine.step", 90 * ms, 99 * ms, {}),
        ("engine.sweep", 90 * ms, 95 * ms, {}),
        ("reader.source", 0, 5 * ms, {}),
    ])
    read = R.load_reader("engine_host_ms_per_step.serve").read
    assert read(FakeRun(), "engine_host_ms_per_step.serve") == \
        pytest.approx(10.0)


def test_first_token_split_and_reader_arithmetic(monkeypatch):
    ms = 1_000_000
    spans_of(monkeypatch, [
        ("engine.prefill_wait", 0, 1, {"queue_wait_s": q, "turnaround_s": t})
        for q, t in ((0.1, 0.003), (0.3, 0.001), (0.2, 0.002))
    ] + [("reader.source", 0, 3 * ms, {}),
         ("reader.device_put", 3 * ms, 4 * ms, {}),
         ("reader.source", 4 * ms, 6 * ms, {}),
         ("reader.device_put", 6 * ms, 8 * ms, {}),
         ("reader.source", 8 * ms, 9 * ms, {})])
    run = FakeRun()
    assert R.load_reader("queue_wait_ms_p50.serve").read(
        run, "queue_wait_ms_p50.serve") == pytest.approx(200.0)
    assert R.load_reader("prefill_turnaround_ms_p50.serve").read(
        run, "prefill_turnaround_ms_p50.serve") == pytest.approx(2.0)
    assert R.load_reader("reader_host_ms_per_batch.train").read(
        run, "reader_host_ms_per_batch.train") == pytest.approx(4.5)


def test_decode_program_is_picked_by_its_name():
    read = R.load_reader("decode_program_ms_p50.serve").read
    run = FakeRun()
    assert read(run, "decode_program_ms_p50.serve") is None
    run.trace = {"module_runs": {"jit_decode_step(123)": [0.2, 0.1, 0.3],
                                 "jit_prefill_b64(9)": [0.9],
                                 "jit__unknown(5)": [0.5, 0.5]}}
    assert read(run, "decode_program_ms_p50.serve") == pytest.approx(200.0)
    run.trace = {"module_runs": {"jit__unknown(5)": [0.5, 0.5]}}
    assert read(run, "decode_program_ms_p50.serve") is None


@pytest.mark.parametrize("name", sorted(ALL - {"decode_program_ms_p50.serve"}))
def test_readers_read_nothing_where_the_program_has_no_spans(
        monkeypatch, name):
    read = R.load_reader(name).read
    spans_of(monkeypatch, [])
    assert read(FakeRun(), name) is None
    monkeypatch.delattr(profiler, "spans")     # the parent's profiler
    assert read(FakeRun(), name) is None
