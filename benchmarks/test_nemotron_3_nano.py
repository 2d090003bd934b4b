"""Tests of the configuration `nemotron-3-nano-30b-a3b` and its cell, on
the CPU at the rehearsal size (a minute or two).

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_nemotron_3_nano.py -q

1. The cell is declared as its issue names it, and its rehearsal runs
   and is correct, its fp8 control is not.
2. The fp8 control, a bfloat16 SSD state and each planted fault in the
   reference's place (the state not carried from one chunk to the next;
   the conv window of the prompt's end zeroed; D left out; the z gate
   left out; B and C read by h % groups; rotary applied; the shared
   expert left out) fail the rehearsal's limit; the probe of the slot's
   states holds what the program left within the tolerance, and each
   control and fault's states fall outside it where that fault lies.
3. The two new readers' arithmetic on a stored reduction, and that each
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

CELL = "nemotron-3-nano-30b-a3b.serve-closed-chat-c512"
CONFIG = "nemotron-3-nano-30b-a3b"
TRAFFIC = "serve-closed-chat-c512"
NEW = ["ssd_decode_roofline_pct.serve", "ssd_prefill_roofline_pct.serve"]
# accepted metrics whose lists the cell joins: their readers find here
# what they find in the other expert or serve cells
JOINED = ["moe_experts_roofline_pct.serve", "expert_tokens_per_step.serve",
          "expert_load_max_over_mean.serve",
          "prefill_device_share_pct.serve", "prefill_padding_pct.serve",
          "state_rw_gb_per_step.serve", "expert_rows_kept_pct.serve"]


def _cell_data():
    cfg = R.load_json("configs", CONFIG + ".json")
    cfg.update(cfg["rehearse"])
    job = R.load_json("traffic", TRAFFIC + ".json")
    job.update(job["rehearse"])
    cfg.update(job["rehearse_config"])
    limits = R.load_json("limits", CELL + ".json")
    return cfg, job, limits["rehearse_limits"]["token_logit_gap"]


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    job = R.load_json("traffic", TRAFFIC + ".json")
    assert job["engine"] == {"slots": 512, "max_len": 3072,
                             "buckets": [256, 512, 1024, 2048]}
    assert (job["clients"], job["request_pairs"]) == (512, 512)
    assert job["prompt_len"] == [128, 2048]
    assert job["output_len"] == [128, 1024]
    assert (job["trace_seconds"], job["check_requests"]) == (4, 8)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "serve_output_tokens_per_s"
        assert per_layer[name]["layer"] == "kernels"
        assert hasattr(R.load_reader(name), "read")
    for name in JOINED:
        assert CELL in per_layer[name]["workloads"]
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "serve_output_tokens_per_s")
    assert CELL in rate["workloads"]
    # the catalog's numbers, but for what `reduced` lists
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = R.load_json("configs", CONFIG + ".json")
    assert config["source"] == cfg["source"]
    assert set(config["reduced"]) == set(cfg["reduced_why"]) \
        == set(cfg["published"]) == {
            "num_hidden_layers", "hybrid_override_pattern",
            "n_routed_experts", "vocab_size", "max_position_embeddings"}
    assert cfg["published"]["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert (cfg["hidden_size"], cfg["mamba_num_heads"],
            cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"],
            cfg["conv_kernel"], cfg["chunk_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == (
        2688, 64, 64, 8, 128, 4, 128, 32, 2, 128, 1856, 3712, 6, 2.5)
    assert (cfg["n_routed_experts"], cfg["n_routed_experts_deployment"],
            cfg["vocab_size"], cfg["num_hidden_layers"]) == (
        16, 128, 16384, 13)
    for item in ("d_inner", "no_rotary", "ssm_state_dtype", "conv_state",
                 "mixer_init", "initializer_range", "expert_storage"):
        assert item in cfg["assumed"]
    assert cfg["assumed"]["ssm_state_dtype"] == "float32"
    # the longest prompt and the longest answer fit the positions
    assert job["prompt_len"][1] + job["output_len"][1] \
        == job["engine"]["max_len"] == cfg["max_position_embeddings"]


def test_the_sizes_leave_a_buckets_padding_and_fit_the_positions():
    """At the cell's sizes and at the rehearsal's: every prompt fits a
    bucket and every request its positions; at the rehearsal's no
    prompt is as long as its bucket."""
    from benchmarks.drivers.serve_closed import size_pairs

    job = R.load_json("traffic", TRAFFIC + ".json")
    for mix in (job, dict(job, **job["rehearse"])):
        buckets = mix["engine"]["buckets"]
        for prompt, out in size_pairs(mix):
            assert prompt <= buckets[-1]
            assert prompt + out <= mix["engine"]["max_len"]
            if mix is not job:
                assert prompt < next(b for b in buckets if b >= prompt)


def test_rehearsal_is_correct_and_the_control_is_not(capsys, tmp_path):
    code = R.main(["--workload", CELL, "--seed", "4100000041", "--seconds",
                   "2", "--rehearse", "--trace", "1", "--control", "1",
                   "--out", str(tmp_path)])
    assert code == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    profiler.reset_profiler()
    assert line["correct"] and line["failed"] == 0
    gap = line["checks"]["token_logit_gap"]
    assert gap["value"] <= gap["limit"] < line["notes"]["control_fp8"]
    assert line["notes"]["compiled_in_window"] == 0
    # the device's metrics need the device's plane
    for name in NEW:
        assert name not in line["metrics"]


@pytest.fixture(scope="module")
def served():
    """What the program serves at the rehearsal size: (limit, reference,
    [(prompt, tokens)]), prompts of one to six chunks in both buckets,
    one of 2."""
    cfg, job, limit = _cell_data()
    config = R.load_module("configs", CONFIG)
    engine = config.build_engine(cfg, job, 41, time.monotonic)
    rng = np.random.default_rng(41)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (21, 63, 65, 100, 2, 47, 90, 33)]
    outs = []
    try:
        # the queue is as deep as the mix has callers
        for i in range(0, len(prompts), job["clients"]):
            futs = [engine.submit(p, 40)
                    for p in prompts[i:i + job["clients"]]]
            outs += [f.result(timeout=300) for f in futs]
    finally:
        engine.close()
    ref = config.ReferenceLM(cfg, 41, job["engine"]["max_len"])
    return limit, ref, list(zip(prompts, outs))


def _compared(ref, pairs, **kw):
    """What `drivers/serve_closed.py` holds to the limit."""
    return max(float(ref.token_gaps(p, t, **kw).max()) for p, t in pairs)


def test_the_program_passes_the_rehearsals_limit(served):
    limit, ref, pairs = served
    assert _compared(ref, pairs) <= limit


@pytest.mark.parametrize("judge", [
    {"fault": f} for f in R.load_module("configs", CONFIG).FAULTS]
    + [{"fault": "bf16_state"}, {"control": True}])
def test_a_planted_fault_fails_the_rehearsals_limit(served, judge):
    limit, ref, pairs = served
    assert _compared(ref, pairs, **judge) > limit


def test_the_reference_reports_what_it_compared(served, capsys):
    """Under the control the reference reads the bfloat16 state and the
    planted faults too, and its report holds every judge's distribution:
    the line a limit is set from."""
    _, ref, pairs = served
    prompt, tokens = pairs[0]
    ref.token_gaps(prompt, tokens)
    ref.token_gaps(prompt, tokens, control=True)
    said = capsys.readouterr().err.strip().splitlines()[-1]
    assert said.startswith(CONFIG + " reference, so far: ")
    report = json.loads(said.split(": ", 1)[1])
    assert report == ref.report()
    config = R.load_module("configs", CONFIG)
    assert {"served", "fp8", "bf16_state", *config.FAULTS} <= set(report)
    assert report["served"]["max"] <= report["fp8"]["max"]
    states = report["slot_states"]
    assert {"served", *config.JUDGES, "tolerance"} <= set(states)


def test_the_probe_holds_what_the_request_left_in_its_slot(served):
    """`build_engine` served one request alone and read its slot back:
    the first Mamba layer's SSD state and conv window, the first
    attention layer's K and V over the positions fed; they lie within
    the rehearsal's tolerance of the reference's."""
    _, ref, _ = served
    cfg, _, _ = _cell_data()
    check, probe = cfg["state_check"], ref.probe
    fed = check["prompt_len"] + check["new_tokens"] - 1
    assert probe["start"] == check["prompt_len"] and len(probe["ids"]) == fed
    assert probe["ssd"].shape == (8, 16, 16)
    assert probe["conv"].shape == (3, 8 * 16 + 2 * 2 * 16)
    assert probe["k"].shape == probe["v"].shape == (fed, 2, 16)
    errors = ref.state_errors()
    assert ref.state_holds() and set(errors) == set(check["tolerance"])
    assert all(0 <= e < 1e-5 for e in errors.values())


@pytest.mark.parametrize("judge,array", [
    ("bf16_state", "ssd"), ("fp8", "ssd"), ("fp8", "conv"),
    ("no_carry", "ssd"), ("conv_window_zeroed", "ssd"), ("bc_by_mod", "ssd"),
    ("rotary", "kv"), ("no_D", "kv"), ("no_z_gate", "kv"),
    ("no_shared_expert", "kv")])
def test_a_judges_states_fail_where_its_fault_lies(served, judge, array):
    """Each control and planted fault leaves states outside the
    tolerance in the array it alters: a bfloat16 SSD state fails by its
    state alone, whatever its tokens read."""
    _, ref, _ = served
    tol = ref.cfg["state_check"]["tolerance"]
    assert ref.state_errors(judge)[array] > 10 * tol[array]
    assert not ref.state_holds(judge)


def test_without_the_engines_probe_nothing_is_correct(served):
    """A reference with no probe of its seed cannot hold the slot's
    states to anything: the number compared is infinite."""
    limit, ref, pairs = served
    config = R.load_module("configs", CONFIG)
    bare = config.ReferenceLM(ref.cfg, 7, ref.max_len, params=ref.p)
    assert bare.probe is None and bare.state_errors() is None
    prompt, tokens = pairs[0]
    assert ref.token_gaps(prompt, tokens).max() <= limit
    assert bare.token_gaps(prompt, tokens).max() == np.inf


# ---------------------------------------------------------------------
# the readers on a stored reduction
# ---------------------------------------------------------------------

class StoredRun:
    """What a traced run of the cell leaves for the readers: a hundred
    decode steps and three prefills of a v5e."""

    def __init__(self):
        self.cfg = R.load_json("configs", CONFIG + ".json")
        self.config = R.load_module("configs", CONFIG)
        self.trace = {
            "device_ops": [
                ["fusion x9000 largest bf16[512,10304]", 0.4],
                ["ssd_decode x600 largest f32[6,512,4096,128] mosaic", 1.9],
                ["ssd_prefill x18 largest f32[6,512,4096,128] mosaic",
                 0.05]],
            "module_runs": {"jit_decode_step(11)": [0.025] * 100,
                            "jit_prefill_b2048(12)": [0.05],
                            "jit_prefill_b256(13)": [0.01, 0.01]},
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


# what the program counts a slot: 6 Mamba layers of 64 x 64 x 128
# float32 and 3 x 6,144 bfloat16, read and written
SLOT_RW = 2 * 6 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
# 102 spans for 100 traced runs: the session's edges
STEPS = [("engine.decode_wait", i, i + 1,
          {"active": 512 - (i % 2), "ahead": True,
           "state_bytes": (512 - (i % 2)) * SLOT_RW}) for i in range(102)]


def test_the_new_readers_arithmetic(monkeypatch):
    run = StoredRun()
    spans_of(monkeypatch, STEPS + [
        ("engine.prefill_wait", 200, 201, {"bucket": 2048, "chunks": 16,
                                           "state_bytes": SLOT_RW // 2}),
        ("engine.decode_wait", 400, 401, {"active": 3})])
    read = {n: R.load_reader(n).read(run, n) for n in NEW}
    # 100 traced steps at the mean step's 511.5 slots, their published
    # SSD states each way, over 819 GB/s (the bytes bound: 5 operations
    # for 8 bytes), over the kernel's 1.9 s
    floor = run.config.ssd_decode_bytes(run.cfg, 51150) / 819e9
    assert floor == pytest.approx(100 * 511.5 * 2 * 6 * 2 ** 21 / 819e9)
    assert floor > run.config.ssd_decode_flops(run.cfg, 51150) / 197e12
    assert read[NEW[0]] == pytest.approx(100 * floor / 1.9)
    ops = run.config.ssd_prefill_flops(run.cfg, 2048) \
        + 2 * run.config.ssd_prefill_flops(run.cfg, 256)
    assert read[NEW[1]] == pytest.approx(100 * ops / 197e12 / 0.05)
    assert 0 < read[NEW[0]] < 100 and 0 < read[NEW[1]] < 100


def test_the_joined_readers_read_the_cell(monkeypatch):
    """The expert and prefill metrics the cell joins read it: the held
    experts at the published 1,856 (not the 1,920 stored), up and down
    without a gate, read once by each of the 103 program runs."""
    run = StoredRun()
    run.trace["device_ops"].append(
        ["moe_grouped_mm x1600 largest f32[768,1920] mosaic", 0.3])
    load = [{"active": 512, "expert_tokens": 5 * 3072,
             "expert_load_max": 5 * 260, "expert_layers_kept": 5,
             "expert_layers": 5} for _ in range(102)]
    spans_of(monkeypatch, [
        ("engine.decode_wait", i, i + 1, a) for i, a in enumerate(load)] + [
        ("engine.prefill_wait", 200, 201, {"bucket": 2048, "true_len": 1500,
                                           "expert_tokens": 5 * 9000})])
    read = {n: R.load_reader(n).read(run, n) for n in JOINED}
    assert run.config.expert_bytes(run.cfg) == 5 * 16 * 2 * 2688 * 1856 * 2
    assert read["moe_experts_roofline_pct.serve"] == pytest.approx(
        100 * 103 * run.config.expert_bytes(run.cfg) / 819e9 / 0.3)
    assert read["expert_tokens_per_step.serve"] == pytest.approx(
        5 * 3072 / (16 * 5))
    assert read["expert_load_max_over_mean.serve"] == pytest.approx(
        260 * 16 / 3072)
    assert read["prefill_device_share_pct.serve"] == pytest.approx(
        100 * 0.07 / 2.57)
    assert read["prefill_padding_pct.serve"] == pytest.approx(
        100 * 548 / 2048)
    assert read["expert_rows_kept_pct.serve"] == 100.0
    spans_of(monkeypatch, STEPS)
    assert R.load_reader("state_rw_gb_per_step.serve").read(
        run, "state_rw_gb_per_step.serve") == pytest.approx(
            511.5 * SLOT_RW / 1e9, rel=1e-3)


def test_the_shares_cannot_pass_100_by_their_counts():
    """A kernel that moved the state at the chip's whole bandwidth reads
    100 and no more: the bytes are the published state's, once each
    way, under the program's, which also moves the conv window; the
    chunked form's operations are what its products compute at
    most."""
    run = StoredRun()
    cfg = run.cfg
    assert run.config.ssd_decode_bytes(cfg, 512) == 2 * 512 \
        * run.config.ssd_state_bytes(cfg) < 512 * SLOT_RW
    # a chunk of 128 at 64 heads of 64 x 128 and 8 groups, 6 layers
    per_chunk = 8 * 2 * 128 * 128 * 128 + 64 * (
        128 * 128 + 2 * 128 * 128 * 64 + 4 * 128 * 64 * 128 + 64 * 128)
    assert run.config.ssd_prefill_flops(cfg, 2048) == 6 * 16 * per_chunk


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
    # another configuration's run: its module has no such counts (the
    # Brumby cell's spans carry `state_bytes` too)
    other = StoredRun()
    other.config = R.load_module("configs", "brumby-14b")
    spans_of(monkeypatch, STEPS)
    assert read(other, name) is None
