"""Tests of the configuration `trinity-mini` and its cell, on the CPU at
the rehearsal size (two or three minutes).

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_trinity_mini.py -q

1. The cell's rehearsal runs and is correct, its fp8 control is not, and
   it prints the span metrics of the two caches and of the expert layer
   when traced.
2. Planted faults in the reference's place (the window left out of the
   window layers; rotary applied in the full layers; the output gate
   left out; the shared expert left out) fail the rehearsal's limit.
3. The three new readers' arithmetic on a stored reduction, and that
   each reads nothing, and does not raise, where its kernel or its spans
   are missing (the parent of the PR that brought them).
"""

import json
import os
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks import run as R  # noqa: E402
from paddle_tpu import profiler  # noqa: E402

CELL = "trinity-mini.serve-closed-mixed-c64"
NEW = ["swa_decode_roofline_pct.serve", "swa_prefill_roofline_pct.serve",
       "window_cache_read_pct.serve"]
SHARED = ["moe_experts_roofline_pct.serve", "expert_tokens_per_step.serve",
          "expert_load_max_over_mean.serve"]


def _cell_data():
    cfg = R.load_json("configs", "trinity-mini.json")
    cfg.update(cfg["rehearse"])
    job = R.load_json("traffic", "serve-closed-mixed-c64.json")
    job.update(job["rehearse"])
    cfg.update(job["rehearse_config"])
    limits = R.load_json("limits", CELL + ".json")
    return cfg, job, limits["rehearse_limits"]["token_logit_gap"]


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini", "serve-closed-mixed-c64", 1)
    job = R.load_json("traffic", "serve-closed-mixed-c64.json")
    assert job["engine"] == {"slots": 64, "max_len": 9728,
                             "buckets": [2048, 4096, 8192]}
    assert (job["clients"], job["request_pairs"]) == (64, 64)
    assert job["prompt_len"] == [1024, 8192]
    assert job["output_len"] == [512, 1536]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "serve_output_tokens_per_s"
        assert hasattr(R.load_reader(name), "read")
    for name in SHARED:
        assert CELL in per_layer[name]["workloads"]
    # the catalog's numbers, but for what `reduced` lists
    config = next(c for c in bench["configs"] if c["name"] == "trinity-mini")
    cfg = R.load_json("configs", "trinity-mini.json")
    assert set(config["reduced"]) == set(cfg["reduced_why"]) \
        == set(cfg["published"])
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["sliding_window"]) == (
        2048, 32, 4, 128, 6144, 1024, 8, 2048)
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 16
    # the longest prompt and the longest answer fit a slot
    assert job["prompt_len"][1] + job["output_len"][1] \
        == job["engine"]["max_len"] == cfg["max_position_embeddings"]


def test_rehearsal_is_correct_and_the_control_is_not(capsys, tmp_path):
    code = R.main(["--workload", CELL, "--seed", "4000000034", "--seconds",
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
    # 4 slots x top 2 of 16 with 4 held, 7 expert layers: half an
    # assignment an expert, a layer, a step when every slot decodes
    assert 0 < m["expert_tokens_per_step.serve"]["value"] <= 0.5 * 4
    assert m["expert_load_max_over_mean.serve"]["value"] >= 1
    # 6 rings of 16 against 2 full caches of up to 64: between the
    # share of a context of 16 (3/4) and of one of 64 (3/7)
    assert 100 * 3 / 7 < m["window_cache_read_pct.serve"]["value"] < 75
    # the device's metrics need the device's plane
    for name in ("swa_decode_roofline_pct.serve",
                 "swa_prefill_roofline_pct.serve",
                 "moe_experts_roofline_pct.serve"):
        assert name not in m


@pytest.fixture(scope="module")
def served():
    """What the program serves at the rehearsal size: (limit, reference,
    [(prompt, tokens)]), prompts either side of the window of 16."""
    cfg, job, limit = _cell_data()
    config = R.load_module("configs", "trinity-mini")
    engine = config.build_engine(cfg, job, 34, time.monotonic)
    rng = np.random.default_rng(34)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (5, 9, 14, 20, 27, 31, 8, 17, 23, 12, 40, 6)]
    outs = []
    try:
        # the queue is as deep as the mix has callers
        for i in range(0, len(prompts), job["clients"]):
            futs = [engine.submit(p, 24)
                    for p in prompts[i:i + job["clients"]]]
            outs += [f.result(timeout=300) for f in futs]
    finally:
        engine.close()
    ref = config.ReferenceLM(cfg, 34, job["engine"]["max_len"])
    return limit, ref, list(zip(prompts, outs))


def _compared(ref, pairs, **kw):
    """What `drivers/serve_closed.py` holds to the limit."""
    return max(float(ref.token_gaps(p, t, **kw).max()) for p, t in pairs)


def test_the_program_passes_the_rehearsals_limit(served):
    limit, ref, pairs = served
    assert _compared(ref, pairs) <= limit


def test_what_is_compared_is_the_worst_requests_mean_gap(served):
    _, ref, pairs = served
    judge = {"fault": "no_gate"}
    means = [float(ref.gaps(p, t, **judge).mean()) for p, t in pairs]
    for (p, t), mean in zip(pairs, means):
        assert ref.gaps(p, t, **judge).shape == (len(t),)
        assert ref.token_gaps(p, t, **judge).tolist() == [mean]
    assert _compared(ref, pairs, **judge) == max(means) > 0
    assert ref.report()["no_gate"]["request_mean_max"] == max(means)


@pytest.mark.parametrize("judge", [{"fault": "no_window"},
                                   {"fault": "rope_in_full_layers"},
                                   {"fault": "no_gate"},
                                   {"fault": "no_shared_expert"},
                                   {"control": True}])
def test_a_planted_fault_fails_the_rehearsals_limit(served, judge):
    limit, ref, pairs = served
    assert _compared(ref, pairs, **judge) > limit


def test_the_reference_reports_what_it_compared(served, capsys):
    """Under the control the reference reads the planted faults too, and
    its report holds every judge's distribution and the routings that a
    bfloat16 rounding changes: the line a limit is set from."""
    _, ref, pairs = served
    prompt, tokens = pairs[0]
    ref.token_gaps(prompt, tokens)
    ref.token_gaps(prompt, tokens, control=True)
    said = capsys.readouterr().err.strip().splitlines()[-1]
    assert said.startswith("trinity-mini reference, so far: ")
    report = json.loads(said.split(": ", 1)[1])
    assert report == ref.report()
    assert {"served", "fp8", "no_window", "rope_in_full_layers", "no_gate",
            "no_shared_expert"} <= set(report)
    assert report["served"]["max"] <= report["fp8"]["max"]
    routings = report["routings_under_bfloat16"]
    # 7 expert layers; every compared request is counted once
    assert routings["compared"] % 7 == 0 and routings["compared"] >= 7 * (
        len(prompt) + len(tokens))
    assert routings["in_an_expert_held_here"] \
        <= routings["chose_differently"] <= routings["compared"]


# ---------------------------------------------------------------------
# the readers on a stored reduction
# ---------------------------------------------------------------------

class StoredRun:
    """What a traced run of the cell leaves for the readers: eight
    decode steps and three prefills of a v5e."""

    def __init__(self):
        self.cfg = R.load_json("configs", "trinity-mini.json")
        self.config = R.load_module("configs", "trinity-mini")
        self.trace = {
            "device_ops": [
                ["fusion x900 largest bf16[64,12288]", 0.080],
                ["moe_grouped_mm x176 largest f32[512,2048] mosaic", 0.030],
                ["gqa_decode x96 largest bf16[64,32,128] mosaic", 0.060],
                ["kv_append x96 largest bf16[9,64,4,128,2048] mosaic",
                 0.004],
                ["flash_fwd x36 largest bf16[32,8192,128] mosaic", 0.120]],
            "module_runs": {"jit_decode_step(11)": [0.012] * 8,
                            "jit_prefill_b8192(12)": [0.4],
                            "jit_prefill_b2048(13)": [0.08, 0.08]},
        }
        self.result = {}

    def chip_peaks(self):
        return {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def spans_of(monkeypatch, spans):
    monkeypatch.setattr(
        profiler, "spans",
        lambda prefix=None: [s for s in spans
                             if prefix is None or s[0].startswith(prefix)],
        raising=False)


# ten spans for eight traced runs: the session's edges
STEPS = [("engine.decode_wait", i, i + 1,
          {"active": 64, "live_full": 250_000 + 1000 * i,
           "live_window": 120_000, "expert_tokens": 700,
           "expert_load_max": 9}) for i in range(10)]


def test_the_new_readers_arithmetic(monkeypatch):
    run = StoredRun()
    spans_of(monkeypatch, STEPS + [
        ("engine.prefill_wait", 20, 21, {"bucket": 8192, "live_full": 7000,
                                         "live_window": 2048}),
        ("engine.decode_wait", 40, 41, {"active": 3})])
    read = {n: R.load_reader(n).read(run, n) for n in NEW}
    # 8 traced steps at the mean step's reads: 254,500 positions in each
    # of 3 full layers and 120,000 in each of 9 rings, 2,048 B each,
    # over 819 GB/s (the bytes bound: 16,384 operations a position over
    # 197 TFLOP/s is less), over gqa_decode + kv_append
    positions = 8 * (3 * 254_500 + 9 * 120_000)
    floor = positions * 2048 / 819e9
    assert floor > positions * 16384 / 197e12
    assert read[NEW[0]] == pytest.approx(100 * floor / 0.064)
    # one prefill at 8,192 and two at 2,048: the triangle in 3 layers,
    # the band in 9, 16,384 operations a pair
    def pairs(b):
        return 3 * b * (b + 1) // 2 + 9 * (
            2048 * 2049 // 2 + (b - 2048) * 2048)
    ops = 16384 * (pairs(8192) + 2 * pairs(2048))
    assert read[NEW[1]] == pytest.approx(100 * ops / 197e12 / 0.120)
    ring = 9 * 10 * 120_000
    assert read[NEW[2]] == pytest.approx(
        100 * ring / (ring + 3 * sum(250_000 + 1000 * i for i in range(10))))
    assert 0 < read[NEW[0]] < 100 and 0 < read[NEW[1]] < 100


def test_the_accepted_expert_readers_read_the_new_configuration(monkeypatch):
    """They read `n_routed_experts`: the configuration gives its held
    experts under that key too."""
    run = StoredRun()
    spans_of(monkeypatch, STEPS)
    # 700 assignments over 16 held experts in 11 expert layers
    assert R.load_reader(SHARED[1]).read(run, SHARED[1]) == pytest.approx(
        700 / (16 * 11))
    assert R.load_reader(SHARED[2]).read(run, SHARED[2]) == pytest.approx(
        9 * 16 / 700)
    # 11 program runs x 11 layers x 16 experts x 3 x 2048 x 1024 x 2 B
    runs_bytes = 11 * 11 * 16 * 3 * 2048 * 1024 * 2
    assert R.load_reader(SHARED[0]).read(run, SHARED[0]) == pytest.approx(
        100 * (runs_bytes / 819e9) / 0.030)


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
    # another configuration's run: its module has no such counts
    other = StoredRun()
    other.config = R.load_module("configs", "kimi-k2.6")
    spans_of(monkeypatch, STEPS)
    assert read(other, name) is None
