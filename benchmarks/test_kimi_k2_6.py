"""Tests of the configuration `kimi-k2.6` and its cell, on the CPU at the
rehearsal size (two or three minutes).

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_kimi_k2_6.py -q

1. The cell's rehearsal runs and is correct, its fp8 control is not, and
   it prints the span metrics of the expert layer when traced.
2. Planted faults in the reference's place (the shared expert left out;
   the selection bias used in the weights) fail the rehearsal's limit.
3. The four new readers' arithmetic on a stored reduction, and that each
   reads nothing, and does not raise, where its kernel or its spans are
   missing (the parent of the PR that brought them).
"""

import json
import os
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks import run as R  # noqa: E402
from paddle_tpu import profiler  # noqa: E402

CELL = "kimi-k2.6.serve-closed-c256"
NEW = ["mla_decode_roofline_pct.serve", "moe_experts_roofline_pct.serve",
       "expert_tokens_per_step.serve", "expert_load_max_over_mean.serve"]


def _cell_data():
    cfg = R.load_json("configs", "kimi-k2.6.json")
    cfg.update(cfg["rehearse"])
    job = R.load_json("traffic", "serve-closed-c256.json")
    job.update(job["rehearse"])
    cfg.update(job["rehearse_config"])
    limits = R.load_json("limits", CELL + ".json")
    return cfg, job, limits["rehearse_limits"]["token_logit_gap"]


def test_rehearsal_is_correct_and_the_control_is_not(capsys, tmp_path):
    code = R.main(["--workload", CELL, "--seed", "4000000029", "--seconds",
                   "2", "--rehearse", "--trace", "1", "--control", "1",
                   "--out", str(tmp_path)])
    assert code == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    profiler.reset_profiler()
    assert line["correct"] and line["failed"] == 0
    gap = line["checks"]["token_logit_gap"]
    assert gap["value"] <= gap["limit"] < line["notes"]["control_fp8"]
    assert line["notes"]["compiled_in_window"] == 0
    m = line["metrics"]
    # 4 slots x top 2 of 16 with 4 held, 2 expert layers: half an
    # assignment an expert, a layer, a step when every slot decodes
    assert 0 < m["expert_tokens_per_step.serve"]["value"] <= 0.5 * 4
    assert m["expert_load_max_over_mean.serve"]["value"] >= 1
    # the device's metrics need the device's plane
    assert "mla_decode_roofline_pct.serve" not in m
    assert "moe_experts_roofline_pct.serve" not in m


@pytest.fixture(scope="module")
def served():
    """What the program serves at the rehearsal size: (cfg, limit,
    reference, [(prompt, tokens)])."""
    cfg, job, limit = _cell_data()
    config = R.load_module("configs", "kimi-k2.6")
    engine = config.build_engine(cfg, job, 29, time.monotonic)
    rng = np.random.default_rng(29)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (5, 9, 14, 20, 27, 31, 8, 17, 23, 12, 30, 6)]
    outs = []
    try:
        # the queue is as deep as the mix has callers
        for i in range(0, len(prompts), job["clients"]):
            futs = [engine.submit(p, 24)
                    for p in prompts[i:i + job["clients"]]]
            outs += [f.result(timeout=300) for f in futs]
    finally:
        engine.close()
    ref = config.ReferenceLM(cfg, 29, job["engine"]["max_len"])
    ref.PAD_TO = 16
    return limit, ref, list(zip(prompts, outs))


def _widest(ref, pairs, **kw):
    return max(float(ref.token_gaps(p, t, **kw).max()) for p, t in pairs)


def test_the_program_passes_the_rehearsals_limit(served):
    limit, ref, pairs = served
    assert _widest(ref, pairs) <= limit


@pytest.mark.parametrize("judge", [{"fault": "no_shared_expert"},
                                   {"fault": "bias_in_weights"},
                                   {"control": True}])
def test_a_planted_fault_fails_the_rehearsals_limit(served, judge):
    limit, ref, pairs = served
    assert _widest(ref, pairs, **judge) > limit


def test_the_reference_reports_what_it_compared(served, capsys):
    """Under the control the reference reads the planted faults too, and
    its report holds every judge's distribution and the routings that a
    bfloat16 rounding changes: the line a limit is set from."""
    _, ref, pairs = served
    prompt, tokens = pairs[0]
    ref.token_gaps(prompt, tokens)
    ref.token_gaps(prompt, tokens, control=True)
    said = capsys.readouterr().err.strip().splitlines()[-1]
    assert said.startswith("kimi-k2.6 reference, so far: ")
    report = json.loads(said.split(": ", 1)[1])
    assert report == ref.report()
    assert {"served", "fp8", "no_shared_expert",
            "bias_in_weights"} <= set(report)
    assert report["served"]["max"] <= report["fp8"]["max"]
    assert report["served"]["share_not_first"] \
        < report["no_shared_expert"]["share_not_first"]
    routings = report["routings_under_bfloat16"]
    # 2 expert layers; every compared request is counted once
    assert routings["compared"] % 2 == 0 and routings["compared"] >= 2 * (
        len(prompt) + len(tokens))
    assert routings["in_an_expert_held_here"] \
        <= routings["chose_differently"] <= routings["compared"]


# ---------------------------------------------------------------------
# the readers on a stored reduction
# ---------------------------------------------------------------------

class StoredRun:
    """What a traced run of the cell leaves for the readers: eight
    decode steps and two prefills of a v5e."""

    def __init__(self):
        self.cfg = R.load_json("configs", "kimi-k2.6.json")
        self.config = R.load_module("configs", "kimi-k2.6")
        self.trace = {
            "device_ops": [
                ["fusion x900 largest bf16[256,36864]", 0.080],
                ["moe_grouped_mm x80 largest f32[2048,7168] mosaic", 0.060],
                ["mla_decode x40 largest bf16[256,64,512] mosaic", 0.030],
                ["latent_append x40 largest bf16[5,256,576,4096] mosaic",
                 0.002]],
            "module_runs": {"jit_decode_step(11)": [0.02] * 8,
                            "jit_prefill_b1024(12)": [0.05],
                            "jit_prefill_b512(13)": [0.03]},
        }
        # the driver's list runs on past the trace's end: twelve entries
        self.result = {"traced_steps": [(float(i), 256, 256, 400_000)
                                        for i in range(12)]}

    def chip_peaks(self):
        return {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def spans_of(monkeypatch, spans):
    monkeypatch.setattr(
        profiler, "spans",
        lambda prefix=None: [s for s in spans
                             if prefix is None or s[0].startswith(prefix)])


STEPS = [("engine.decode_wait", i, i + 1,
          {"active": 256, "expert_tokens": 240 + 4 * i,
           "expert_load_max": 30 + i}) for i in range(8)]
PREFILLS = [("engine.prefill_wait", 20 + i, 21 + i,
             {"bucket": 1024, "expert_tokens": 1000, "expert_load_max": 120})
            for i in range(2)]


def test_the_new_readers_arithmetic(monkeypatch):
    run = StoredRun()
    spans_of(monkeypatch, STEPS + PREFILLS + [
        ("engine.decode_wait", 40, 41, {"active": 3})])
    read = {n: R.load_reader(n).read(run, n) for n in NEW}
    # 8 traced steps x 400,000 cached positions x 5 layers x 1,152 B over
    # 819 GB/s (the bytes bound: 139,264 operations a position a layer
    # over 197 TFLOP/s is less), over mla_decode + latent_append
    floor = 8 * 400_000 * 5 * 576 * 2 / 819e9
    assert floor > 8 * 400_000 * 5 * 64 * 2 * (2 * 512 + 64) / 197e12
    assert read[NEW[0]] == pytest.approx(100 * floor / 0.032)
    # 10 program runs x 4 layers x 12 experts x 3 x 7168 x 2048 x 2 B
    runs_bytes = 10 * 4 * 12 * 3 * 7168 * 2048 * 2
    assert read[NEW[1]] == pytest.approx(
        100 * (runs_bytes / 819e9) / 0.060)
    assert read[NEW[2]] == pytest.approx((240 + 4 * 3.5) / (12 * 4))
    assert read[NEW[3]] == pytest.approx(np.mean(
        [(30 + i) * 12 / (240 + 4 * i) for i in range(8)]))
    assert 0 < read[NEW[0]] < 100 and 0 < read[NEW[1]] < 100


def test_many_assignments_bind_the_experts_by_operations(monkeypatch):
    run = StoredRun()
    spans_of(monkeypatch, [("engine.prefill_wait", 0, 1,
                            {"expert_tokens": 2_000_000,
                             "expert_load_max": 400_000})])
    ops = 2_000_000 * 2 * 3 * 7168 * 2048 / 197e12
    assert R.load_reader(NEW[1]).read(run, NEW[1]) == pytest.approx(
        100 * ops / 0.060)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_reads_nothing(monkeypatch, name):
    read = R.load_reader(name).read
    run = StoredRun()
    # the parent's program: no such kernels in the trace, spans without
    # the attributes, or no `spans` at all
    run.trace["device_ops"] = run.trace["device_ops"][:1]
    spans_of(monkeypatch, [("engine.decode_wait", 0, 1, {"active": 3})])
    assert read(run, name) is None
    monkeypatch.delattr(profiler, "spans")
    assert read(run, name) is None
    run.trace = None
    assert read(run, name) is None
