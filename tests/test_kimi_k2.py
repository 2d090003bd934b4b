"""Kimi-K2 (DeepSeek-V3's layer) behind the decode engine's seam (ISSUE
29), on the CPU at toy widths with seeded random weights, each test
against the plain reference of `benchmarks/configs/kimi-k2.6.py` (which
imports nothing of paddle_tpu) or a few lines of numpy.

The toy model is float32, so what separates program and reference is
re-association only: the absorbed attention (`q_nope W_uk^T` against the
latent) against K and V built per head, the grouped product against
every expert over every token, XLA's fusions.  Logits of size one agree
to a few 1e-6; the tolerances below leave a hundred times that."""

import glob
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor, profiler
from paddle_tpu.distributed.moe import routed_experts, sigmoid_top_k
from paddle_tpu.kernels import flash_attention, grouped_mm, mla
from paddle_tpu.kernels.attention import resident_mla_attention
from paddle_tpu.models import kimi_k2
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

from moe_reference import dense_moe as _dense_moe  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "kimi-k2.6")
TOL = 2e-4


def _module():
    spec = importlib.util.spec_from_file_location("kimi_k2_6_config",
                                                  CONFIG + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _toy_cfg(**over):
    with open(CONFIG + ".json") as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    cfg.update(dtype="float32", **over)
    return cfg


class Toy:
    def __init__(self, seed=29, max_len=64, **over):
        self.M = _module()
        self.cfg = _toy_cfg(**over)
        self.kcfg = kimi_k2.K2Cfg.from_hf(self.cfg, max_seq_len=max_len)
        with jax.default_matmul_precision("highest"):
            self.flat = self.M.init_params(self.cfg, seed)
        self.params = kimi_k2.K2Params.from_flat(self.kcfg, self.flat)
        self.ref = self.M.ReferenceLM(self.cfg, seed, max_len,
                                      params=self.flat)
        self.ref.PAD_TO = 16

    def engine(self, **kw):
        kw.setdefault("slots", 2)
        kw.setdefault("max_len", self.kcfg.max_seq_len)
        kw.setdefault("buckets", (16, 32))
        kw.setdefault("watchdog_stall_s", 60.0)
        kw.setdefault("label", f"k2_{time.time_ns() % 1000000}")
        return DecodeEngine(self.params, config=DecodeConfig(**kw),
                            auto_start=False)


@pytest.fixture(scope="module")
def toy():
    return Toy()


@pytest.fixture(autouse=True)
def _clean_state():
    monitor.disable()
    monitor.reset()
    profiler.reset_profiler()
    yield
    monitor.disable()
    monitor.reset()
    profiler.reset_profiler()


def _drain(eng, futs, max_steps=600):
    for _ in range(max_steps):
        if all(f.done() for f in futs):
            return
        eng.step()
    raise AssertionError("engine did not drain")


# ---------------------------------------------------------------------
# the model's side of the seam against the reference
# ---------------------------------------------------------------------

def test_param_shapes_are_the_benchmarks(toy):
    want = {n: tuple(s) for n, s, _ in toy.M.param_specs(toy.cfg)}
    have = {n: tuple(s) for n, (s, _) in
            kimi_k2.param_shapes(toy.kcfg).items()}
    assert want == have
    mine = kimi_k2.init_params(toy.kcfg, jax.random.PRNGKey(0))
    assert {n: v.shape for n, v in mine.items()} == want
    assert mine["layers.1.router_bias"].dtype == jnp.float32
    assert float(jnp.abs(mine["layers.1.router_bias"]).max()) > 0


def test_yarn_frequencies_are_the_references(toy):
    got = kimi_k2.yarn_inv_freq(toy.kcfg.qk_rope_head_dim,
                                toy.kcfg.rope_theta, toy.kcfg.yarn)
    np.testing.assert_allclose(got, toy.M.yarn_frequencies(toy.cfg),
                               rtol=1e-6)
    # the published sizes: the fastest lanes keep their frequency, the
    # slowest are divided by the factor
    with open(CONFIG + ".json") as f:
        full = kimi_k2.K2Cfg.from_hf(json.load(f))
    inv = kimi_k2.yarn_inv_freq(64, full.rope_theta, full.yarn)
    plain = full.rope_theta ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(inv[-1], plain[-1] / 64, rtol=1e-6)
    assert full.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)


def test_trees_hold_the_attention_laid_out_beside_the_published_shapes(
        toy):
    """`param_shapes` publishes `kv_b [rank, heads * (nope + v)]`; the
    trees hold its two halves instead, `w_uk [heads, nope, rank]` and
    `w_uv [heads, v, rank]`, the same numbers and no more of them, and
    the other leaves under their published names and shapes."""
    k = toy.kcfg
    shapes = kimi_k2.param_shapes(k)
    width = k.qk_nope_head_dim + k.v_head_dim
    assert shapes["layers.0.kv_b"] == ((k.kv_lora_rank, k.num_heads * width),
                                       "matrix")
    for i, lp in enumerate(toy.params.trees["layers"]):
        assert "kv_b" not in lp
        pre = f"layers.{i}."
        assert {n: v.shape for n, v in lp.items()
                if n not in ("w_uk", "w_uv")} == {
            n[len(pre):]: s for n, (s, _) in shapes.items()
            if n.startswith(pre) and n != pre + "kv_b"}
        w = np.asarray(toy.flat[pre + "kv_b"]).reshape(
            k.kv_lora_rank, k.num_heads, width).transpose(1, 2, 0)
        np.testing.assert_array_equal(lp["w_uk"], w[:, :k.qk_nope_head_dim])
        np.testing.assert_array_equal(lp["w_uv"], w[:, k.qk_nope_head_dim:])
    assert sum(v.size for v in jax.tree.leaves(toy.params.trees)) == sum(
        v.size for v in toy.flat.values())


def _published_rope(cfg, x, pos):
    """DeepSeek-V3's rotation of the published lanes, pairs (2i, 2i+1)
    strided: the pairs' first halves, then their second halves."""
    factor, _, _, _, mscale, all_dim = cfg.yarn
    m = kimi_k2.yarn_mscale(factor, mscale) / kimi_k2.yarn_mscale(
        factor, all_dim)
    angle = pos.astype(jnp.float32)[..., None] * kimi_k2.yarn_inv_freq(
        cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn)
    cos, sin = jnp.cos(angle) * m, jnp.sin(angle) * m
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@pytest.mark.parametrize("rope", [8, 64])
def test_rotary_on_the_laid_out_trees_is_the_published_bit_for_bit(rope):
    """`_queries_and_latent` over the trees, whose rotary columns are in
    halves, against the published rotation of strided pairs over the
    flat weights: the queries and the latent written to the cache are
    the same bit for bit, at the toy's rotary width and the published
    64."""
    toy = Toy(qk_rope_head_dim=rope) if rope != 8 else Toy()
    k = toy.kcfg
    assert k.qk_rope_head_dim == rope
    h = jnp.asarray(np.random.default_rng(4).standard_normal(
        (9, k.hidden_size)), jnp.float32)
    pos = jnp.asarray([0, 1, 2, 5, 17, 30, 31, 47, 63], jnp.int32)
    lp, pre = toy.params.trees["layers"][1], "layers.1."
    flat = {n[len(pre):]: v for n, v in toy.flat.items()
            if n.startswith(pre)}

    @jax.jit
    def published(h, pos):
        cq = kimi_k2.rms_norm(k, h @ flat["q_a"], flat["q_a_norm"])
        q = (cq @ flat["q_b"]).reshape(h.shape[0], k.num_heads, -1)
        kv = h @ flat["kv_a"]
        c_kv = kimi_k2.rms_norm(k, kv[:, :k.kv_lora_rank], flat["kv_a_norm"])
        k_rope = _published_rope(k, kv[:, k.kv_lora_rank:], pos)
        return (q[..., :k.qk_nope_head_dim],
                _published_rope(k, q[..., k.qk_nope_head_dim:],
                                pos[:, None]),
                jnp.concatenate([c_kv, k_rope], axis=-1))

    got = jax.jit(lambda h, pos: kimi_k2._queries_and_latent(
        k, lp, h, pos))(h, pos)
    want = published(h, pos)
    for name, g, w in zip(("q_nope", "q_rope", "latent"), got, want):
        assert g.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_absorbed_decode_through_the_latent_cache_equals_the_full_forward(
        toy):
    """Prefill (first form of attention, latent written into the slot),
    then twelve steps of the absorbed form through the cache, fed the
    reference's own tokens: every step's logits against the reference's
    full forward pass over the whole sequence."""
    k, trees = toy.kcfg, toy.params.trees
    rng = np.random.default_rng(0)
    ids = rng.integers(0, k.vocab_size, 11 + 12).astype(np.int32)
    want = np.asarray(toy.ref.logits(np.pad(ids, (0, 32 - ids.size))))
    cache = k.cache_arrays(3, 64)
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :11] = ids[:11]
    with jax.default_matmul_precision("highest"):
        cache, hidden, counters = jax.jit(k.prefill)(
            trees, cache, prompt, np.int32(11), np.int32(1))
        got = [np.asarray(k.head(trees, hidden))[0]]
        assert int(counters["expert_counts"].sum()) > 0
        step = jax.jit(k.decode)
        for i in range(11, ids.size - 1):
            token = np.zeros(3, np.int32)
            token[1] = ids[i]
            pos = np.array([0, i, 0], np.int32)
            cache, hidden, _ = step(trees, cache, token, pos)
            got.append(np.asarray(k.head(trees, hidden))[1])
    np.testing.assert_allclose(np.stack(got), want[10:ids.size - 1],
                               atol=TOL, rtol=0)
    # the slot holds [c_kv ; k_rope] of its positions, depth minor, and
    # the other slots' prompt regions were left alone
    latent = np.asarray(cache["latent"])
    assert latent.shape == (3, 3, 32 + 8, 64)
    assert np.abs(latent[:, 1, :, :ids.size - 1]).min(axis=1).max() > 0
    assert not latent[:, 0, :, 1:].any() and not latent[:, 2, :, 1:].any()


def test_engine_serves_what_the_reference_computes(toy):
    """Five requests through two slots, so three of them join a slot
    that another has released, mid-stream: every served token is the
    reference's first choice over prompt and served tokens together."""
    eng = toy.engine()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, toy.kcfg.vocab_size, n).astype(np.int32)
               for n in (5, 20, 9, 30, 12)]
    try:
        futs = [eng.submit(p, 10 + 3 * i) for i, p in enumerate(prompts)]
        _drain(eng, futs)
        summary = eng.summary()
    finally:
        eng.close()
    for p, f in zip(prompts, futs):
        served = f.result(timeout=0)
        assert toy.ref.token_gaps(p, served).max() <= TOL
    assert summary["decode"]["prefill_steps"] == 5
    assert summary["decode"]["cache"] == {
        "kind": toy.kcfg.cache_kind, "bytes": 3 * 2 * 40 * 64 * 4,
        "arrays": [{"name": "latent", "kind": "depth", "layers": 3,
                    "depth": 64, "bytes": 3 * 2 * 40 * 64 * 4}]}


def test_loop_thread_one_step_ahead_serves_what_the_reference_computes(
        toy):
    """The same five requests through two slots with the loop thread
    running one decode step ahead of its host (each step's four results
    reach the host 10 ms after its launch): requests join a slot that
    another released while a step that still carried the old tenant is
    queued, and every served token is the reference's first choice."""
    from engine_fakes import Gate, hold_steps

    eng = toy.engine()
    hold_steps(eng, Gate(delay_s=0.01))
    eng.start()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, toy.kcfg.vocab_size, n).astype(np.int32)
               for n in (5, 20, 9, 30, 12)]
    try:
        futs = [eng.submit(p, 10 + 3 * i) for i, p in enumerate(prompts)]
        served = [f.result(timeout=120) for f in futs]
    finally:
        eng.close()
    summary = eng.summary()
    for p, out, i in zip(prompts, served, range(5)):
        assert out.size == 10 + 3 * i
        assert toy.ref.token_gaps(p, out).max() <= TOL
    look = summary["decode"]["lookahead"]
    assert look["steps"] == summary["decode"]["decode_steps"]
    assert look["ahead"] >= look["steps"] // 2 > 0
    assert look["in_time"] + look["late"] == \
        summary["decode"]["prefill_steps"] == 5
    # the experts' counts come with every answer, ahead or not
    assert summary["decode"]["experts"]["programs"] == 5 + look["steps"]
    assert summary["requests"] == sum(summary["outcomes"].values()) == 5


def test_gpt_runs_through_the_same_seam():
    np.random.seed(29)
    model = GPT(GPTConfig(vocab_size=97, hidden_size=48, num_layers=2,
                          num_heads=4, max_seq_len=32, dropout=0.0))
    eng = DecodeEngine(model, config=DecodeConfig(
        slots=2, max_len=32, buckets=(8,), label="k2_gpt_seam"),
        auto_start=False)
    try:
        fut = eng.submit(np.arange(5), 4)
        _drain(eng, [fut])
        decode = eng.summary()["decode"]
    finally:
        eng.close()
    # the model's cache beside the engine's own per-slot vectors
    assert set(eng._state) == {"k", "v", "pos", "active", "token", "stop",
                               "eos", "temp", "key"}
    assert fut.result(timeout=0).size == 4
    assert decode["cache"]["kind"].startswith("kv [")
    assert decode["cache"]["bytes"] == 2 * 2 * 2 * 48 * 32 * 4
    assert "experts" not in decode


def test_a_cache_array_may_not_take_one_of_the_engines_names(toy):
    """The engine keeps the model's cache arrays beside its own per-slot
    vectors in one donated dict: a model whose cache is called `pos`
    would silently lose it."""
    class Clash(kimi_k2.K2Cfg):
        def cache_arrays(self, slots, max_len):
            return {"pos": super().cache_arrays(slots, max_len)["latent"]}

    params = kimi_k2.K2Params(toy.params.trees, Clash(*toy.kcfg))
    with pytest.raises(ValueError, match="names the engine keeps"):
        DecodeEngine(params, config=DecodeConfig(
            slots=2, max_len=64, buckets=(16,), prewarm=False,
            label="k2_clash"), auto_start=False)


@pytest.mark.parametrize("mscale, all_dim", [(0.707, 1.0), (1.0, 0.5)])
def test_rotary_scale_is_the_published_ratio(mscale, all_dim):
    """cos and sin are scaled by yarn_get_mscale(factor, mscale) /
    yarn_get_mscale(factor, mscale_all_dim), as the reference has it; K2's
    own two are equal, so only a configuration where they differ shows a
    wrong formula."""
    base = _toy_cfg()["rope_scaling"]
    t = Toy(rope_scaling=dict(base, mscale=mscale, mscale_all_dim=all_dim))
    ids = np.random.default_rng(4).integers(
        0, t.kcfg.vocab_size, 16).astype(np.int32)
    want = np.asarray(t.ref.logits(ids))[12]
    with jax.default_matmul_precision("highest"):
        _, hidden, _ = jax.jit(t.kcfg.prefill)(
            t.params.trees, t.kcfg.cache_arrays(2, 64), ids[None],
            np.int32(13), np.int32(0))
        got = np.asarray(t.kcfg.head(t.params.trees, hidden))[0]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


# ---------------------------------------------------------------------
# the router and the expert layer that is told its experts
# ---------------------------------------------------------------------

def test_router_chooses_by_score_plus_bias_and_weighs_by_score():
    h = jnp.eye(3, dtype=jnp.float32)
    logits = np.array([[2.0, 1.0, 0.0, -1.0, -2.0],
                       [0.0, 0.0, 0.0, 3.0, -3.0],
                       [1.0, 1.1, 1.2, 1.3, 1.4]], np.float32)
    bias = np.array([-0.5, 0.0, 0.3, 0.0, 0.0], np.float32)
    experts, weights = sigmoid_top_k(h, jnp.asarray(logits),
                                     jnp.asarray(bias), 2, 2.827)
    sig = 1.0 / (1.0 + np.exp(-logits))
    for row in range(3):
        want = np.argsort(-(sig[row] + bias))[:2]
        assert sorted(np.asarray(experts[row])) == sorted(want)
        chosen = sig[row][np.asarray(experts[row])]
        np.testing.assert_allclose(weights[row],
                                   2.827 * chosen / chosen.sum(), rtol=1e-6)
    # row 0: the bias changed the choice (by score alone: experts 0, 1),
    # and the weights are of the scores, not of score + bias
    assert sorted(np.asarray(experts[0])) == [1, 2]
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 2.827,
                               rtol=1e-6)


def _layer_weights(rng, d=24, f=16, experts=16):
    return dict(
        router=jnp.asarray(rng.standard_normal((d, experts)), jnp.float32),
        bias=jnp.asarray(0.3 * rng.standard_normal(experts), jnp.float32),
        gate_up=jnp.asarray(rng.standard_normal((experts, d, 2 * f))
                            / np.sqrt(d), jnp.float32),
        down=jnp.asarray(rng.standard_normal((experts, f, d))
                         / np.sqrt(f), jnp.float32))


def test_routed_experts_computes_its_own_experts_part_only():
    rng = np.random.default_rng(3)
    w = _layer_weights(rng)
    h = jnp.asarray(rng.standard_normal((13, 24)), jnp.float32)
    valid = jnp.arange(13) < 11
    with jax.default_matmul_precision("highest"):
        y, counts, _ = jax.jit(
            lambda h, valid: routed_experts(
                h, w["router"], w["bias"], (w["gate_up"][4:8],
                                            w["down"][4:8]),
                4, 16, 3, 2.5, valid=valid))(h, valid)
    want, want_counts = _dense_moe(h[:11], w["router"], w["bias"],
                                   w["gate_up"][4:8], w["down"][4:8],
                                   3, 2.5, first=4)
    np.testing.assert_allclose(np.asarray(y)[:11], want, atol=1e-5)
    assert not np.asarray(y)[11:].any()       # padding makes no assignment
    assert list(np.asarray(counts)) == list(want_counts)
    with pytest.raises(ValueError):
        routed_experts(h, w["router"], w["bias"],
                       (w["gate_up"][:4], w["down"][:4]), 14, 16, 3, 2.5)


def test_no_token_is_dropped_at_a_planted_imbalance():
    """The bias sends every token to held expert 2 first: GShard's
    capacity would cut all but a few; here each one is computed."""
    rng = np.random.default_rng(4)
    w = _layer_weights(rng)
    bias = w["bias"].at[2].set(10.0)
    h = jnp.asarray(rng.standard_normal((64, 24)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, counts, _ = routed_experts(h, w["router"], bias,
                                      (w["gate_up"][:4], w["down"][:4]),
                                      0, 16, 2, 2.827)
    want, want_counts = _dense_moe(h, w["router"], bias, w["gate_up"][:4],
                                   w["down"][:4], 2, 2.827)
    assert int(counts[2]) == 64 and list(np.asarray(counts)) == \
        list(want_counts)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 routed experts in 4 shares of 4: the four chips' routed parts,
    with the shared expert counted once, are the reference's whole
    layer (the reference holding all 16)."""
    M = _module()
    cfg = _toy_cfg(n_routed_experts=16, n_routed_experts_deployment=16)
    rng = np.random.default_rng(5)
    w = _layer_weights(rng, d=cfg["hidden_size"],
                       f=cfg["moe_intermediate_size"])
    f = cfg["moe_intermediate_size"]
    shared_gu = jnp.asarray(rng.standard_normal((cfg["hidden_size"], 2 * f))
                            / 8, jnp.float32)
    shared_down = jnp.asarray(rng.standard_normal((f, cfg["hidden_size"]))
                              / 4, jnp.float32)
    h = jnp.asarray(rng.standard_normal((37, cfg["hidden_size"])),
                    jnp.float32)
    mm = M._product("float32")
    whole, _ = M._moe(cfg, {"router": w["router"], "router_bias": w["bias"],
                            "experts_gate_up": w["gate_up"],
                            "experts_down": w["down"],
                            "shared_gate_up": shared_gu,
                            "shared_down": shared_down}, h, mm, None)
    with jax.default_matmul_precision("highest"):
        parts, counts, _ = zip(*[routed_experts(
            h, w["router"], w["bias"],
            (w["gate_up"][s:s + 4], w["down"][s:s + 4]), s, 16,
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"])
            for s in (0, 4, 8, 12)])
        shared = kimi_k2.swiglu(h, shared_gu, shared_down)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=1e-5)
    # every assignment fell on exactly one share
    assert sum(int(c.sum()) for c in counts) == \
        37 * cfg["num_experts_per_tok"]
    # and one share alone is the reference's share
    one, flips = M._moe(
        dict(cfg, n_routed_experts=4, first_expert=8),
        {"router": w["router"], "router_bias": w["bias"],
         "experts_gate_up": w["gate_up"][8:12],
         "experts_down": w["down"][8:12],
         "shared_gate_up": shared_gu, "shared_down": shared_down},
        h, mm, "no_shared_expert")
    np.testing.assert_allclose(np.asarray(parts[2]), np.asarray(one),
                               atol=1e-5)
    # the reference's count of routings that a bfloat16 rounding of the
    # router's input changes: a token's held experts change only where
    # its chosen set does
    flips = np.asarray(flips)
    assert flips.shape == (37, 2) and not (flips[:, 1] & ~flips[:, 0]).any()


# ---------------------------------------------------------------------
# the kernels, interpreted, against the XLA mathematics
# ---------------------------------------------------------------------

def _latent_step(rng, pos, heads=8, rank=128, rope=64, t=512, layers=2):
    s = len(pos)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (rand(s, heads, rank), rand(s, heads, rope), rand(s, rank + rope),
            rand(layers, s, rank + rope, t), jnp.asarray(pos, jnp.int32))


# a tile of 128 here: lengths (pos + 1) of 1, of one less than, exactly
# and one more than a tile (and a part of 256, of which a tile of 512
# has two), of the whole depth, and long slots beside short ones, so
# that the copies in flight belong to other slots than the one computed
WALKS = {
    "one_position": [0, 0, 0],
    "around_a_tile": [126, 127, 128],
    "around_a_part": [254, 255, 256, 257],
    "the_whole_depth": [511, 511],
    "long_after_short": [3, 500, 7, 383, 0, 255],
    "short_after_long": [500, 3, 383, 7, 255, 0],
    "issue_29s": [5, 130, 383],
}


@pytest.mark.parametrize("block_k", [128, 256, 512])
@pytest.mark.parametrize("walk", list(WALKS))
def test_mla_decode_equals_its_xla_mathematics(walk, block_k):
    """The kernel, interpreted, against `resident_mla_attention`'s XLA
    mathematics: what the heads attend to, and the cache after the step,
    which differs from the cache before it in exactly each slot's column
    `pos` of that layer."""
    ql, qr, new, latent, pos = _latent_step(np.random.default_rng(6),
                                            WALKS[walk])
    want_o, want_latent = resident_mla_attention(
        ql, qr, new, latent, 1, pos, 0.07, use_kernel=False)
    got_o, got_latent = mla.mla_decode(ql, qr, new, latent, 1, pos, 0.07,
                                       block_k=block_k)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got_latent),
                                  np.asarray(want_latent))
    changed = np.asarray(got_latent != latent)
    assert not changed[0].any()
    for i, p in enumerate(WALKS[walk]):
        assert changed[1, i, :, p].all()
    assert changed.sum() == len(WALKS[walk]) * new.shape[1]


def test_mla_decode_at_the_tile_its_tiling_names():
    """A depth of 1,024 is one tile of four parts: slots whose last tile
    holds one, two, three and four live parts, in both orders."""
    pos = [0, 255, 256, 600, 1023, 767, 511, 3]
    ql, qr, new, latent, pos = _latent_step(np.random.default_rng(8), pos,
                                            heads=4, t=1024)
    assert mla.mla_tiling(192, 1024) == (1024, 256)
    want_o, want_latent = resident_mla_attention(
        ql, qr, new, latent, 1, pos, 0.07, use_kernel=False)
    got_o, got_latent = mla.mla_decode(ql, qr, new, latent, 1, pos, 0.07)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got_latent),
                                  np.asarray(want_latent))


def test_a_stale_position_writes_the_slots_own_last_column():
    """An inactive slot's pos may lie past the depth: the dispatch clamps
    it, and the kernel (at the tile `mla_tiling` names: 128 at a depth of
    384) writes and reads inside the slot."""
    ql, qr, new, latent, pos = _latent_step(
        np.random.default_rng(7), [5, 384, 9000, 383], t=384)
    want_o, want_latent = resident_mla_attention(
        ql, qr, new, latent, 0, pos, 0.07, use_kernel=False)
    got_o, got_latent = resident_mla_attention(
        ql, qr, new, latent, 0, pos, 0.07, use_kernel=True)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got_latent),
                                  np.asarray(want_latent))
    changed = np.asarray(got_latent != latent)
    assert changed[0, 1:3, :, 383].all() and changed.sum() == 4 * 192


@pytest.mark.parametrize("width, depth, tile, part", [
    (576, 4096, 1024, 256),       # the K2 cell's latent
    (576, 2048, 1024, 256),       # chip_smoke.py's
    (192, 512, 512, 256), (192, 384, 128, 128), (192, 256, 256, 256),
    (192, 128, 128, 128),
    (2304, 4096, 256, 256),       # four times the rows: a quarter the tile
])
def test_mla_tiling_over_the_shapes_in_use(width, depth, tile, part):
    assert mla.mla_tiling(width, depth) == (tile, part)
    assert mla.mla_tiling(width, depth, block_k=128) == (128, 128)
    lengths = [1, tile - 1, tile, min(tile + 1, depth), depth]
    tiles = [1, 1, 1, min(2, depth // tile), depth // tile]
    assert flash_attention.tiles_walked(lengths, tile) == sum(tiles)
    first, slot = mla._visits(jnp.asarray(lengths, jnp.int32), depth, tile)
    assert list(np.asarray(first)) == [0] + list(np.cumsum(tiles))
    assert list(np.asarray(slot)[:sum(tiles)]) == \
        [i for i, n in enumerate(tiles) for _ in range(n)]


@pytest.mark.parametrize("depth, block_k", [(384, 256), (100, None),
                                            (512, 64)])
def test_mla_tiling_refuses_what_does_not_tile(depth, block_k):
    with pytest.raises(ValueError):
        mla.mla_tiling(192, depth, block_k=block_k)


@pytest.mark.parametrize("counts", [
    [5, 0, 130, 1, 0, 7],         # a group over a row-tile boundary
    [100, 28, 1, 1, 126, 0],      # a row tile shared by five groups
    [256, 0, 0, 0, 0, 0],         # every row on one group
    [0, 0, 0, 0, 0, 0]])          # no assignment: no visit, no write
def test_moe_grouped_mm_equals_ragged_dot(counts):
    rng = np.random.default_rng(8)
    m, k, n = 256, 256, 384
    rows = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    weights = jnp.asarray(rng.standard_normal((len(counts), k, n)),
                          jnp.float32)
    c = jnp.asarray(counts, jnp.int32)
    got = jax.jit(grouped_mm.moe_grouped_mm)(rows, weights, c)
    with jax.default_matmul_precision("highest"):
        want = jax.lax.ragged_dot(rows, weights, c)
    live = sum(counts)
    # float32 sums over 256 terms of size one, in another order
    np.testing.assert_allclose(np.asarray(got)[:live],
                               np.asarray(want)[:live], atol=2e-4)
    with pytest.raises(ValueError):
        grouped_mm.moe_grouped_mm(rows[:100], weights, c)


def test_routed_experts_through_the_kernel_equals_the_dense_layer():
    """64 tokens x top 2 = 128 rows, one row tile, widths of whole
    lanes: `routed_experts` on `moe_grouped_mm` (interpreted), at a
    planted imbalance too."""
    rng = np.random.default_rng(9)
    w = _layer_weights(rng, d=128, f=128)
    h = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    for bias in (w["bias"], w["bias"].at[5].set(10.0)):
        fn = jax.jit(lambda h, bias: routed_experts(
            h, w["router"], bias, (w["gate_up"][4:8], w["down"][4:8]),
            4, 16, 2, 2.827, use_kernel=True))
        assert "name=moe_grouped_mm" in str(jax.make_jaxpr(fn)(h, bias))
        y, counts, _ = fn(h, bias)
        want, want_counts = _dense_moe(h, w["router"], bias,
                                       w["gate_up"][4:8], w["down"][4:8],
                                       2, 2.827, first=4)
        assert list(np.asarray(counts)) == list(want_counts)
        np.testing.assert_allclose(np.asarray(y), want, atol=2e-4)


def test_engine_through_the_kernels_serves_the_references_tokens(
        monkeypatch):
    """The engine's decode step on `mla_decode` (interpreted), the one
    Pallas call a layer's attention makes, at a latent the kernel tiles
    (rank 128, rope 64, depth 128): still the reference's tokens, also
    in a refilled slot."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH_DECODE", "1")
    toy = Toy(max_len=128, kv_lora_rank=128, qk_rope_head_dim=64,
              num_hidden_layers=2, max_position_embeddings=128)
    eng = toy.engine(slots=1, buckets=(16,))
    step = str(jax.make_jaxpr(toy.kcfg.decode)(
        toy.params.trees, jax.eval_shape(
            lambda: toy.kcfg.cache_arrays(1, 128)),
        np.zeros(1, np.int32), np.zeros(1, np.int32)))
    assert step.count("name=mla_decode") == 2 and "append" not in step
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, toy.kcfg.vocab_size, n).astype(np.int32)
               for n in (12, 5)]
    try:
        futs = [eng.submit(p, 6) for p in prompts]
        _drain(eng, futs)
    finally:
        eng.close()
    for p, f in zip(prompts, futs):
        assert toy.ref.token_gaps(p, f.result(timeout=0)).max() <= TOL


# ---------------------------------------------------------------------
# tracing: the expert counts on the engine's spans and in its stats
# ---------------------------------------------------------------------

def test_wait_spans_and_summary_carry_the_expert_counts(toy, tmp_path):
    eng = toy.engine()
    rng = np.random.default_rng(8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        futs = [eng.submit(rng.integers(0, 211, size=6 + i), 5)
                for i in range(3)]
        _drain(eng, futs)
    finally:
        jax.profiler.stop_trace()
    summary = eng.summary()["decode"]
    eng.close()
    assert glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)
    spans = profiler.spans("engine.")
    held, k = toy.kcfg.experts_held, toy.kcfg.num_experts_per_tok
    expert_layers = toy.kcfg.num_layers - toy.kcfg.first_k_dense
    waits = {name: [a for n, _, _, a in spans if n == name]
             for name in ("engine.decode_wait", "engine.prefill_wait")}
    assert len(waits["engine.prefill_wait"]) == 3
    assert len(waits["engine.decode_wait"]) == summary["decode_steps"]
    for name, attrs in waits.items():
        for a in attrs:
            assert 0 <= a["expert_load_max"] <= a["expert_tokens"]
            assert a["expert_tokens"] <= held * a["expert_load_max"]
    for a in waits["engine.decode_wait"]:
        # every slot routes, live or not: at most slots x top_k x layers
        assert a["expert_tokens"] <= 2 * k * expert_layers
        assert "active" in a
    for a, fut in zip(waits["engine.prefill_wait"], futs):
        assert {"bucket", "slot", "rid", "queue_wait_s"} <= set(a)
    # the toy's programs (2 slots, buckets of 16 and 32) have no kept
    # case: each says so, and none ran over kept rows
    for attrs in waits.values():
        for a in attrs:
            assert a["expert_layers_kept"] == a["expert_layers"] == 0
    total = sum(a["expert_tokens"] for attrs in waits.values()
                for a in attrs)
    assert summary["experts"] == {
        "programs": 3 + summary["decode_steps"], "tokens_total": total,
        "load_max": max(a["expert_load_max"] for attrs in waits.values()
                        for a in attrs),
        "layers_kept": 0, "layers": 0}
    assert total > 0


@pytest.mark.parametrize("planted", [False, True],
                         ids=["usual", "planted_bias"])
def test_wait_spans_count_the_expert_layers_over_kept_rows(planted,
                                                           tmp_path):
    """A prefill at a bucket of 128 (256 rows, the 4 held of 16 experts
    expecting 64: 128 kept) runs both expert layers over the kept rows,
    and says so on `engine.prefill_wait` and in the summary beside the
    two layers its shape gives the case; a planted bias that sends every
    token to the held experts (200 assignments of 100 tokens a layer)
    makes both run every row, and the counter drops to 0 of 2.  The
    decode steps of 2 slots have no such case."""
    toy = Toy(max_len=128, max_position_embeddings=128)
    layers = toy.kcfg.num_layers - toy.kcfg.first_k_dense
    assert toy.kcfg.expert_layers(128) == layers == 2
    assert toy.kcfg.expert_layers(2) == 0
    params = toy.params
    if planted:
        held = slice(toy.kcfg.first_expert,
                     toy.kcfg.first_expert + toy.kcfg.experts_held)
        params = kimi_k2.K2Params(dict(params.trees, layers=[
            dict(lp, router_bias=lp["router_bias"].at[held].add(10.0))
            if "router_bias" in lp else lp
            for lp in params.trees["layers"]]), params.cfg)
    eng = DecodeEngine(params, config=DecodeConfig(
        slots=2, max_len=128, buckets=(128,), watchdog_stall_s=60.0,
        label=f"k2_kept_{planted}"), auto_start=False)
    prompt = np.random.default_rng(10).integers(0, 211, size=100)
    jax.profiler.start_trace(str(tmp_path))
    try:
        fut = eng.submit(prompt, 3)
        _drain(eng, [fut])
    finally:
        jax.profiler.stop_trace()
    summary = eng.summary()["decode"]
    eng.close()
    fill, = [a for n, _, _, a in profiler.spans("engine.prefill_wait")]
    steps = [a for n, _, _, a in profiler.spans("engine.decode_wait")]
    assert fill["expert_layers"] == layers
    if planted:
        assert fill["expert_tokens"] == 200 * layers
    assert fill["expert_layers_kept"] == (0 if planted else layers)
    assert steps and all(a["expert_layers_kept"] == a["expert_layers"] == 0
                         for a in steps)
    assert summary["experts"]["layers_kept"] == (0 if planted else layers)
    assert summary["experts"]["layers"] == layers


def test_decode_wait_and_summary_count_the_tiles_walked(monkeypatch,
                                                         tmp_path):
    """`latent_tiles` of `latent_grid` on `engine.decode_wait` and their
    totals in the summary are what the requests' lengths give at the tile
    `mla_tiling` names (128 at a depth of 384), the second request
    crossing into its second tile on the way; and the decode program
    answers with three results of the engine's and the experts'
    counters: their counts and the layers over kept rows."""
    from paddle_tpu.serving import decode as D

    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH_DECODE", "1")
    toy = Toy(max_len=384, kv_lora_rank=128, qk_rope_head_dim=64,
              num_hidden_layers=2, max_position_embeddings=384)
    assert toy.kcfg.cache_walk([1, 128, 129], 4, 384) == {
        "latent_tiles": 4, "latent_grid": 12}
    eng = toy.engine(slots=2, buckets=(16, 128))
    state = jax.eval_shape(eng._fresh_state)
    answer = jax.eval_shape(
        lambda st: D._decode_step_impl(st, toy.params.trees,
                                       np.zeros(2, bool), toy.kcfg), state)
    assert len(jax.tree.leaves(answer[1:])) == 5
    assert set(answer[-1]) == {"expert_counts", "expert_layers_kept"}
    rng = np.random.default_rng(9)
    sizes = (12, 126)
    jax.profiler.start_trace(str(tmp_path))
    try:
        futs = [eng.submit(rng.integers(0, 211, size=n), 5) for n in sizes]
        _drain(eng, futs)
    finally:
        jax.profiler.stop_trace()
    summary = eng.summary()["decode"]
    eng.close()
    walks = [a for n, _, _, a in profiler.spans("engine.decode_wait")]
    # a request's first token is its prefill's; decode step j reads its
    # prompt and j tokens
    want = [sum(-(-(n + j) // 128) for n in sizes) for j in range(1, 5)]
    assert want == [2, 2, 3, 3]
    assert [a["latent_tiles"] for a in walks] == want
    assert {a["latent_grid"] for a in walks} == {2 * 3}
    assert summary["cache"]["latent_tiles"] == sum(want)
    assert summary["cache"]["latent_grid"] == 6 * len(want)
    assert summary["decode_steps"] == len(want)
