"""Tests of the configuration `brumby-14b` and its cell, on the CPU at
the rehearsal size (a minute or two).

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_brumby_14b.py -q

1. The cell's rehearsal runs and is correct, its fp8 control is not, and
   it prints the span metric of the state's traffic when traced.
2. Planted faults in the reference's place (the state not carried from
   one chunk to the next; the gate left out; the divisor left out; a
   bucket's padding fed into the state; rotary left out) fail the
   rehearsal's limit.
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

CELL = "brumby-14b.serve-closed-longdoc-c16"
NEW = ["retention_decode_roofline_pct.serve",
       "retention_prefill_roofline_pct.serve", "state_rw_gb_per_step.serve"]


def _cell_data():
    cfg = R.load_json("configs", "brumby-14b.json")
    cfg.update(cfg["rehearse"])
    job = R.load_json("traffic", "serve-closed-longdoc-c16.json")
    job.update(job["rehearse"])
    cfg.update(job["rehearse_config"])
    limits = R.load_json("limits", CELL + ".json")
    return cfg, job, limits["rehearse_limits"]["token_logit_gap"]


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b", "serve-closed-longdoc-c16", 1)
    job = R.load_json("traffic", "serve-closed-longdoc-c16.json")
    assert job["engine"] == {"slots": 16, "max_len": 10240,
                             "buckets": [6144, 8192]}
    assert (job["clients"], job["request_pairs"]) == (16, 16)
    assert job["prompt_len"] == [4096, 8192]
    assert job["output_len"] == [1024, 2048]
    assert job["warm_completions"] == job["clients"]
    assert job["check_requests"] >= 4
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "serve_output_tokens_per_s"
        assert hasattr(R.load_reader(name), "read")
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "serve_output_tokens_per_s")
    assert rate["workloads"][-1] == CELL
    # the catalog's numbers, but for what `reduced` lists
    config = next(c for c in bench["configs"] if c["name"] == "brumby-14b")
    cfg = R.load_json("configs", "brumby-14b.json")
    assert config["source"] == cfg["source"]
    assert set(config["reduced"]) == set(cfg["reduced_why"]) \
        == set(cfg["published"]) == {"num_hidden_layers",
                                     "max_position_embeddings"}
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"], cfg["max_window_layers"]) == (
        5120, 40, 8, 128, 17408, 151936, 8, 40)
    assert (cfg["rope_theta"], cfg["rms_norm_eps"], cfg["attention_bias"],
            cfg["tie_word_embeddings"], cfg["hidden_act"]) == (
        1000000, 1e-06, False, False, "silu")
    for item in ("power", "state", "divisor", "qk_norm", "rotary", "gate",
                 "gate_bias", "gate_half_life", "state_dtype"):
        assert item in cfg["assumed"]
    assert "five pipeline stages" in cfg["deployment"]
    # nothing in the file reaches the program's precision
    assert "program" not in cfg and cfg["assumed"]["state_dtype"] == "float32"
    # the longest prompt and the longest answer fit the positions
    assert job["prompt_len"][1] + job["output_len"][1] \
        == job["engine"]["max_len"] == cfg["max_position_embeddings"]


def test_the_sizes_leave_a_buckets_padding_never_empty():
    """At the cell's sizes and at the rehearsal's: no prompt is as long
    as its bucket, and every prompt spans several chunks."""
    from benchmarks.drivers.serve_closed import size_pairs
    from paddle_tpu.kernels.retention import retention_tiling

    job = R.load_json("traffic", "serve-closed-longdoc-c16.json")
    for mix in (job, dict(job, **job["rehearse"])):
        buckets = mix["engine"]["buckets"]
        for prompt, out in size_pairs(mix):
            bucket = next(b for b in buckets if b >= prompt)
            assert prompt < bucket
            assert prompt > 4 * 0 + retention_tiling(128, bucket).chunk
            assert prompt + out <= mix["engine"]["max_len"]


def test_rehearsal_is_correct_and_the_control_is_not(capsys, tmp_path):
    code = R.main(["--workload", CELL, "--seed", "4000000036", "--seconds",
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
    # 4 slots of 3 layers x 2 K/V heads x (9 + 1) x 16 x 16 float32, read
    # and written by the slots that were active
    slot = 3 * 2 * 10 * 16 * 16 * 4
    assert m["state_rw_gb_per_step.serve"]["value"] * 1e9 == pytest.approx(
        2 * slot * 4 * m["slot_occupancy_pct.serve"]["value"] / 100,
        rel=0.1)
    # the device's metrics need the device's plane
    for name in NEW[:2]:
        assert name not in m


@pytest.fixture(scope="module")
def served():
    """What the program serves at the rehearsal size: (limit, reference,
    [(prompt, tokens)]), prompts of two and three chunks in both
    buckets."""
    cfg, job, limit = _cell_data()
    config = R.load_module("configs", "brumby-14b")
    engine = config.build_engine(cfg, job, 36, time.monotonic)
    rng = np.random.default_rng(36)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (269, 511, 513, 700, 300, 767)]
    outs = []
    try:
        # the queue is as deep as the mix has callers
        for i in range(0, len(prompts), job["clients"]):
            futs = [engine.submit(p, 40)
                    for p in prompts[i:i + job["clients"]]]
            outs += [f.result(timeout=300) for f in futs]
    finally:
        engine.close()
    ref = config.ReferenceLM(cfg, 36, job["engine"]["max_len"])
    return limit, ref, list(zip(prompts, outs))


def _compared(ref, pairs, **kw):
    """What `drivers/serve_closed.py` holds to the limit."""
    return max(float(ref.token_gaps(p, t, **kw).max()) for p, t in pairs)


def test_the_program_passes_the_rehearsals_limit(served):
    limit, ref, pairs = served
    assert _compared(ref, pairs) <= limit


@pytest.mark.parametrize("judge", [{"fault": "no_carry"},
                                   {"fault": "no_gate"},
                                   {"fault": "no_divisor"},
                                   {"fault": "padding_in_state"},
                                   {"fault": "no_rotary"},
                                   {"control": True}])
def test_a_planted_fault_fails_the_rehearsals_limit(served, judge):
    limit, ref, pairs = served
    assert _compared(ref, pairs, **judge) > limit


def test_the_reference_reports_what_it_compared(served, capsys):
    """Under the control the reference reads the planted faults too, and
    its report holds every judge's distribution: the line a limit is set
    from."""
    _, ref, pairs = served
    prompt, tokens = pairs[0]
    ref.token_gaps(prompt, tokens)
    ref.token_gaps(prompt, tokens, control=True)
    said = capsys.readouterr().err.strip().splitlines()[-1]
    assert said.startswith("brumby-14b reference, so far: ")
    report = json.loads(said.split(": ", 1)[1])
    assert report == ref.report()
    config = R.load_module("configs", "brumby-14b")
    assert {"served", "fp8", *config.FAULTS} <= set(report)
    assert report["served"]["max"] <= report["fp8"]["max"]


# ---------------------------------------------------------------------
# the readers on a stored reduction
# ---------------------------------------------------------------------

class StoredRun:
    """What a traced run of the cell leaves for the readers: a hundred
    decode steps and two prefills of a v5e."""

    def __init__(self):
        self.cfg = R.load_json("configs", "brumby-14b.json")
        self.config = R.load_module("configs", "brumby-14b")
        self.trace = {
            "device_ops": [
                ["fusion x9000 largest bf16[16,34816]", 1.4],
                ["retention_decode x800 largest f32[8,16,8,128,8320] "
                 "mosaic", 1.6],
                ["retention_prefill x16 largest f32[8,16,8,128,8320] "
                 "mosaic", 0.25]],
            "module_runs": {"jit_decode_step(11)": [0.030] * 100,
                            "jit_prefill_b8192(12)": [0.55],
                            "jit_prefill_b6144(13)": [0.42]},
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


# what the program counts a slot: 8 layers x 8 K/V heads x (65 + 1) x
# 128 x 128 float32, read and written
SLOT_RW = 2 * 8 * 8 * 66 * 128 * 128 * 4
# 102 spans for 100 traced runs: the session's edges
STEPS = [("engine.decode_wait", i, i + 1,
          {"active": 16 - (i % 2), "ahead": True,
           "state_bytes": (16 - (i % 2)) * SLOT_RW}) for i in range(102)]


def test_the_new_readers_arithmetic(monkeypatch):
    run = StoredRun()
    spans_of(monkeypatch, STEPS + [
        ("engine.prefill_wait", 200, 201, {"bucket": 8192, "chunks": 32,
                                           "state_bytes": SLOT_RW // 2}),
        ("engine.decode_wait", 400, 401, {"active": 3})])
    read = {n: R.load_reader(n).read(run, n) for n in NEW}
    # 100 traced steps at the mean step's 15.5 slots, their published
    # states each way, over 819 GB/s (the bytes bound: 12 operations for
    # 8 bytes is far under the chip's 240 a byte), over the kernel's
    # 1.6 s
    floor = run.config.retention_decode_bytes(run.cfg, 1550) / 819e9
    assert floor < 100 * 15.5 * SLOT_RW / 819e9 < 1.02 * floor
    assert floor > run.config.retention_decode_flops(run.cfg, 1550) / 197e12
    assert read[NEW[0]] == pytest.approx(100 * floor / 1.6)
    ops = run.config.retention_prefill_flops(run.cfg, 8192) \
        + run.config.retention_prefill_flops(run.cfg, 6144)
    assert read[NEW[1]] == pytest.approx(100 * ops / 197e12 / 0.25)
    assert read[NEW[2]] == pytest.approx(15.5 * SLOT_RW / 1e9)
    assert 0 < read[NEW[0]] < 100 and 0 < read[NEW[1]] < 100
    # the acceptance's band: the program's layout moves within 2% of the
    # published state's 8.72 GB a full step
    assert read[NEW[2]] == pytest.approx(
        run.config.retention_decode_bytes(run.cfg, 15.5) / 1e9, rel=0.02)


def test_the_shares_cannot_pass_100_by_their_counts():
    """A kernel that moved the state at the chip's whole bandwidth, or
    computed the recurrence at its whole peak, reads 100 and no more: the
    bytes are what is moved once each way, the operations the cheaper
    form's."""
    run = StoredRun()
    cfg = run.cfg
    assert run.config.retention_decode_bytes(cfg, 16) == 2 * 16 \
        * run.config.slot_state_bytes(cfg)
    per_position = run.config.retention_flops_per_position(cfg)
    for bucket in (6144, 8192):
        assert run.config.retention_prefill_flops(cfg, bucket) \
            < bucket * cfg["num_hidden_layers"] * per_position


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
    # another configuration's run: its module has no such counts, its
    # spans no such attribute
    other = StoredRun()
    other.config = R.load_module("configs", "trinity-mini")
    spans_of(monkeypatch, [("engine.decode_wait", 0, 1, {"active": 3})])
    assert read(other, name) is None
