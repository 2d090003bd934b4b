"""chip_smoke.py end to end on the CPU, tiny: the same phase functions
main() runs at full width on the chip (Pallas kernels in interpret
mode here), so the command is known to run before chip time is spent on
it; plus the contract of main() on a host with no TPU."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GPT_TOY = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=2,
               max_seq_len=256, dtype="float32")
STATIC_TOY = dict(t=16, d=32, heads=4, vocab=128)


@pytest.fixture(scope="module")
def log():
    return chip_smoke.CompileLog()


def test_device_phase_names_platform_and_cache():
    dev = chip_smoke.phase_device()
    assert dev["platform"] == "cpu" and dev["count"] == len(jax.devices())
    assert dev["jax"] == jax.__version__
    assert dev["compile_cache_dir"]


def test_train_layer_phase():
    r = chip_smoke.phase_train_layer(GPT_TOY, batch=2, seq=64, steps=5)
    assert len(r["losses"]) == 5 and r["losses"][-1] < r["losses"][0]
    # below the flash crossover and off the TPU: the XLA composition
    assert r["mosaic_calls_in_hlo"] == 0


def test_train_executor_phase():
    r = chip_smoke.phase_train_executor(STATIC_TOY, batch=4, steps=5)
    assert r["loss_last"] < r["loss_first"]
    assert "fused_attention" in r["fused_ops"]


def test_serve_phase_is_token_exact(log):
    # one bucket below and one above the prompts' midpoint, more
    # requests than slots so slots are refilled mid-decode
    lens = [5, 40, 17, 90, 33, 5]
    r = chip_smoke.phase_serve(GPT_TOY, lens, 6, (32, 128), log, slots=3,
                               max_len=256, exact_vs_generate=True)
    assert r["requests"] == 6 and r["tokens_returned"] == 36
    assert r["programs_prewarmed"] == 3
    assert r["compiles_under_traffic"] == 0
    assert r["tokens_equal_to_generate"] == "6/6"
    assert not any(r["resilience"].values())


def test_kernels_phase_interpreted():
    r = chip_smoke.phase_kernels(
        attn=(2, 2, 256, 64), decode=(4, 2, 256, 64),
        decode_lengths=[1, 40, 128, 256], ln=(64, 128), topk_n=5000,
        dtype=jnp.float32, tol=1e-4)
    assert set(r) >= {"flash_fwd", "flash_bwd_dq", "flash_bwd_dk",
                      "flash_bwd_dv", "flash_decode", "kv_append",
                      "flash_decode_resident", "layer_norm_fwd",
                      "layer_norm_bwd_dx", "topk_threshold"}
    assert r["topk_threshold"]["histogram_max_count_diff"] == 0


def test_k2_phase_at_the_rehearsal_size():
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kimi-k2.6.json")) as f:
        hf = json.load(f)
    # the rehearsal's toy widths, but a latent the kernels tile
    hf.update(hf["rehearse"])
    hf.update(dtype="float32", kv_lora_rank=128, qk_rope_head_dim=64)
    r = chip_smoke.phase_k2(
        hf, slots=2, max_len=128, buckets=(16, 64),
        prompt_lens=[5, 40, 17, 5], new_tokens=6,
        decode_lengths=[1, 40, 128], tol=1e-4, gap_tol=1e-4)
    assert r["requests"] == 4
    assert r["tokens_equal_to_forward"] == "24/24"
    assert r["cache"]["kind"].startswith("latent")
    assert r["experts"]["tokens_total"] > 0


def test_afmoe_phase_at_the_rehearsal_size():
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "trinity-mini.json")) as f:
        hf = json.load(f)
    # the rehearsal's toy widths, but heads and a window the kernels tile
    hf.update(hf["rehearse"])
    hf.update(dtype="float32", head_dim=64, sliding_window=128)
    r = chip_smoke.phase_afmoe(
        hf, slots=2, max_len=256, buckets=(64, 256),
        prompt_lens=[5, 120, 200, 17], new_tokens=12,
        decode_lengths=[1, 40, 128, 129, 256], prefill_seq=256, tol=1e-4,
        gap_tol=1e-4)
    assert r["requests"] == 4
    assert r["tokens_equal_to_forward"] == "48/48"
    assert [a["depth"] for a in r["cache"]["arrays"]] == [256, 256, 128, 128]
    assert r["experts"]["tokens_total"] > 0
    # the kernel alone on both caches, at the tiles `gqa_tiling` names:
    # one of 256 a slot of the full cache, one of 128 of the ring
    for name, walked in (("full", 5), ("ring", 5)):
        k = r[f"gqa_decode_{name}"]
        assert k["written_column"] == "exact" and k["rel_to_max"] < 1e-4
        assert (k["tiles_walked"], k["tiles_of_the_grid"]) == (walked, 5)
        assert k["ms_a_call"] > 0


def test_brumby_phase_at_the_rehearsal_size():
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "brumby-14b.json")) as f:
        hf = json.load(f)
    # the rehearsal's toy widths, but heads the kernels tile: both run
    # in the interpreter, alone, against their XLA mathematics
    hf.update(hf["rehearse"])
    hf.update(dtype="float32", head_dim=128, num_attention_heads=5,
              num_key_value_heads=1, num_hidden_layers=2)
    r = chip_smoke.phase_brumby(
        hf, slots=2, max_len=128, buckets=(32, 64), prompt_lens=[5, 40, 60],
        new_tokens=12, kernel_slots=3, kernel_bucket=32, tol=2e-2,
        state_tol=8e-3, gap_tol=1e-3)
    assert r["requests"] == 3
    assert r["tokens_equal_to_forward"] == "36/36"
    assert [a["kind"] for a in r["cache"]["arrays"]] == ["state", "state"]
    assert r["cache"]["chunks"] == 3 and r["cache"]["state_bytes"] > 0
    assert max(r["retention_decode_tiled"]["rel_to_max"]) < 1e-4
    # as served, on bfloat16 operands, and beside it on float32 ones
    fill = r["retention_prefill"]
    assert 1e-4 < max(fill["rel_to_max"]) < 2e-2
    assert max(fill["rel_to_max_float32_operands"]) < 1e-4


def test_nemotron_phase_at_the_rehearsal_size():
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        hf = json.load(f)
    # the rehearsal's toy widths, but Mamba heads of 64 that the kernels
    # tile (2 a row of lanes): both run in the interpreter, alone,
    # against the XLA mathematics and the recurrence
    hf.update(hf["rehearse"])
    hf.update(dtype="float32", mamba_head_dim=64)
    r = chip_smoke.phase_nemotron(
        hf, slots=2, max_len=128, buckets=(32, 64), prompt_lens=[5, 40, 2],
        new_tokens=12, kernel_slots=3, kernel_bucket=32, tol=1e-4,
        state_tol=1e-4, gap_tol=1e-3)
    assert r["requests"] == 3
    assert r["tokens_equal_to_forward"] == "36/36"
    assert [a["kind"] for a in r["cache"]["arrays"]] == [
        "state", "state", "depth", "depth"]
    assert r["cache"]["chunks"] > 0 and r["cache"]["state_bytes"] > 0
    assert r["ssd_prefill"]["chunks"] == 2


def test_four_chips_phase_on_the_cpu_mesh():
    """The CPU-mesh twin of the four-chip phase: shards on four distinct
    devices, half a tensor-parallel leaf on each, first-step losses
    equal to the unsharded runs."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    layer = chip_smoke.phase_train_layer(GPT_TOY, batch=4, seq=64, steps=2)
    executor = chip_smoke.phase_train_executor(STATIC_TOY, batch=8,
                                               steps=1)
    r = chip_smoke.phase_four_chips(
        GPT_TOY, 4, 64, 2, layer["losses"][0], STATIC_TOY, 8,
        executor["loss_first"], rtol=1e-4)
    assert set(r) == {"layer_dp2_tp2", "executor_dp4", "executor_dp2_mp2"}
    assert len(r["layer_dp2_tp2"]["devices"]) == 4


def test_main_fails_without_a_tpu(capsys):
    """No accelerator: non-zero, the platform named on stderr, and not
    a line of result on stdout."""
    assert chip_smoke.main() == 1
    cap = capsys.readouterr()
    assert cap.out == ""
    assert "'cpu'" in cap.err


def test_main_fails_alone_in_a_directory(tmp_path):
    """chip_smoke.py and nothing else of the repo: it must not pass."""
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert r.stdout == ""
