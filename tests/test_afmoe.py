"""AFMoE (Arcee Trinity's layer) behind the decode engine's seam (ISSUE
34), on the CPU at toy widths with seeded random weights, each test
against the plain reference of `benchmarks/configs/trinity-mini.py`
(which imports nothing of paddle_tpu) or a few lines of numpy.

The toy model is float32, so what separates program and reference is
re-association only: grouped heads read in place against K and V
repeated, a ring against a mask, the grouped product against every
expert over every token.  Logits of size one agree to a few 1e-6; the
tolerances below leave a hundred times that."""

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
from paddle_tpu.distributed.moe import routed_experts
from paddle_tpu.kernels import attention, flash_attention
from paddle_tpu.models import afmoe
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "trinity-mini")
TOL = 2e-4


def _module():
    spec = importlib.util.spec_from_file_location("trinity_mini_config",
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
    """The rehearsal's widths: window 16, 4 query heads over 2 K/V heads
    of 16, 8 layers (6 window + 2 full; 1 dense + 7 expert), 4 of 16
    experts held, top 2."""

    def __init__(self, seed=34, max_len=64, **over):
        self.M = _module()
        self.cfg = _toy_cfg(**over)
        self.acfg = afmoe.AfmoeCfg.from_hf(self.cfg, max_seq_len=max_len)
        with jax.default_matmul_precision("highest"):
            self.flat = self.M.init_params(self.cfg, seed)
        self.params = afmoe.AfmoeParams.from_flat(self.acfg, self.flat)
        self.ref = self.M.ReferenceLM(self.cfg, seed, max_len,
                                      params=self.flat)

    def engine(self, auto_start=False, **kw):
        kw.setdefault("slots", 2)
        kw.setdefault("max_len", self.acfg.max_seq_len)
        kw.setdefault("buckets", (16, 32, 64))
        kw.setdefault("watchdog_stall_s", 60.0)
        kw.setdefault("label", f"afmoe_{time.time_ns() % 1000000}")
        return DecodeEngine(self.params, config=DecodeConfig(**kw),
                            auto_start=auto_start)


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


def _drain(eng, futs, max_steps=900):
    for _ in range(max_steps):
        if all(f.done() for f in futs):
            return
        eng.step()
    raise AssertionError("engine did not drain")


def _rand(rng, shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)


# ---------------------------------------------------------------------
# the model's side of the seam against the reference
# ---------------------------------------------------------------------

def test_param_shapes_are_the_benchmarks(toy):
    want = {n: tuple(s) for n, s, _ in toy.M.param_specs(toy.cfg)}
    have = {n: tuple(s) for n, (s, _) in
            afmoe.param_shapes(toy.acfg).items()}
    assert want == have
    mine = afmoe.init_params(toy.acfg, jax.random.PRNGKey(0))
    assert {n: v.shape for n, v in mine.items()} == want
    assert mine["layers.1.expert_bias"].dtype == jnp.float32
    assert "layers.0.gate_up" in mine and "layers.1.router" in mine


def test_the_published_sizes_are_the_issues_count():
    """The cut of configs/trinity-mini.json: 1,646.9 M parameters (the
    arithmetic of ISSUE 34), two caches of 47,616 columns a slot."""
    M = _module()
    with open(CONFIG + ".json") as f:
        cfg = json.load(f)
    assert M.param_count(cfg) == pytest.approx(1646.9e6, rel=1e-3)
    acfg = afmoe.AfmoeCfg.from_hf(cfg)
    cache = jax.eval_shape(lambda: acfg.cache_arrays(64, 9728))
    assert {n: a.shape for n, a in cache.items()} == {
        "k_full": (3, 64, 4, 128, 9728), "v_full": (3, 64, 4, 128, 9728),
        "k_window": (9, 64, 4, 128, 2048),
        "v_window": (9, 64, 4, 128, 2048)}
    assert sum(a.size * 2 for a in cache.values()) \
        == 64 * 47616 * M.kv_bytes_per_position(cfg)
    assert M.kv_bytes_per_position(cfg) == 2048
    assert M.attention_flops_per_position(cfg) == 16384
    # the band of a 4,096 prefill: a triangle of 2,048 and 2,048 rows
    # of 2,048 keys, in 9 layers; the whole triangle in 3
    assert M.swa_prefill_flops(cfg, 4096) == 16384 * (
        3 * 4096 * 4097 // 2 + 9 * (2048 * 2049 // 2 + 2048 * 2048))


def test_full_logits_equal_the_references(toy):
    ids = np.random.default_rng(1).integers(
        0, toy.acfg.vocab_size, 48).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = afmoe.full_logits(toy.acfg, toy.params.trees, jnp.asarray(ids))
        want = toy.ref.logits(ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


@pytest.mark.parametrize("fault", _module().FAULTS)
def test_each_mechanism_moves_the_references_logits(toy, fault):
    """The window, the missing rotation of full layers, the gate and the
    shared expert each matter at the toy size: leaving one out moves the
    logits by far more than program and reference differ."""
    ids = np.random.default_rng(2).integers(
        0, toy.acfg.vocab_size, 48).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        moved = np.abs(np.asarray(toy.ref.logits(ids, fault=fault))
                       - np.asarray(toy.ref.logits(ids))).max()
    assert moved > 100 * TOL


def test_engine_serves_what_the_reference_computes(toy):
    """Prefill and decode through both caches: prompts shorter and
    longer than the window (16), of lengths that are no multiple of a
    bucket, decoded for more than one window so that every ring wraps,
    a request joining a slot another has left."""
    eng = toy.engine()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, toy.acfg.vocab_size, n).astype(np.int32)
               for n in (5, 19, 37, 26, 16, 33)]
    try:
        futs = [eng.submit(p, 24) for p in prompts]
        _drain(eng, futs)
        summary = eng.summary()
    finally:
        eng.close()
    for p, f in zip(prompts, futs):
        out = f.result(timeout=0)
        assert out.shape == (24,)
        assert toy.ref.gaps(p, out).max() <= TOL
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(afmoe.full_logits(
                toy.acfg, toy.params.trees,
                jnp.asarray(np.concatenate([p, out]))))
        rows = logits[p.size - 1:p.size - 1 + 24]
        assert (rows.max(axis=1) - rows[np.arange(24), out]).max() <= TOL
    assert summary["requests"] == sum(summary["outcomes"].values()) == 6


def test_loop_thread_one_step_ahead_serves_what_the_reference_computes(toy):
    eng = toy.engine(auto_start=True, slots=3)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, toy.acfg.vocab_size, n).astype(np.int32)
               for n in (7, 40, 21, 30, 12)]
    try:
        outs = [f.result(timeout=300)
                for f in [eng.submit(p, 20) for p in prompts]]
        ahead = eng.summary()["decode"]["lookahead"]
    finally:
        eng.close()
    for p, out in zip(prompts, outs):
        assert toy.ref.gaps(p, out).max() <= TOL
    assert ahead["steps"] > 0


@pytest.mark.parametrize("n", [1, 5, 16, 17, 31, 32, 33, 47, 48])
def test_ring_columns_after_a_padded_prefill(toy, n):
    """A prompt of n tokens in a bucket of 48 leaves in every ring the
    positions max(0, n - 16) .. n - 1, position p in column p mod 16,
    whatever the bucket's padding holds."""
    acfg = toy.acfg
    ids = np.random.default_rng(5).integers(1, acfg.vocab_size, 48).astype(
        np.int32)
    ids[n:] = 0
    cache = acfg.cache_arrays(2, 64)
    with jax.default_matmul_precision("highest"):
        got, _, counters = acfg.prefill(
            toy.params.trees, cache, jnp.asarray(ids[None]), jnp.int32(n),
            jnp.int32(1))
        _, kvs, _ = afmoe._forward(acfg, toy.params.trees,
                                   jnp.asarray(ids), None)
    window = [kv for kv, t in zip(kvs, acfg.layer_types)
              if t == afmoe.WINDOW]
    full = [kv for kv, t in zip(kvs, acfg.layer_types) if t == afmoe.FULL]
    assert len(window) == 6 and len(full) == 2
    for p in range(max(0, n - 16), n):
        for l, (k, v) in enumerate(window):
            np.testing.assert_array_equal(got["k_window"][l, 1, :, :, p % 16],
                                          k[:, :, p])
            np.testing.assert_array_equal(got["v_window"][l, 1, :, :, p % 16],
                                          v[:, :, p])
    for l, (k, v) in enumerate(full):
        np.testing.assert_array_equal(got["k_full"][l, 1, :, :, :n],
                                      k[:, :, :n])
    # the other slot is untouched; the program answers with its experts'
    # counters alone (the reads are the host's: `cache_reads`)
    assert not np.asarray(got["k_window"][:, 0]).any()
    assert set(counters) == {"expert_counts", "expert_layers_kept"}
    assert toy.acfg.cache_reads([n]) == {"live_full": n,
                                         "live_window": min(n, 16)}


def test_the_rotation_is_hf_rotate_half(toy):
    """Lane i pairs with lane i + d / 2, angle pos * theta ** (-2i/d)."""
    x = _rand(np.random.default_rng(6), (5, 3, 16))
    pos = jnp.asarray([0, 1, 7, 100, 900], jnp.int32)
    got = np.asarray(afmoe.rope(toy.acfg, x, pos))
    inv = 10000.0 ** (-np.arange(8) / 8)
    ang = np.asarray(pos)[:, None, None] * inv
    a, b = np.asarray(x)[..., :8], np.asarray(x)[..., 8:]
    want = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                           b * np.cos(ang) + a * np.sin(ang)], axis=-1)
    np.testing.assert_allclose(got, want, atol=3e-4)
    np.testing.assert_array_equal(got[0], np.asarray(x)[0])


# ---------------------------------------------------------------------
# grouped heads, windows and rings in kernels/attention.py
# ---------------------------------------------------------------------

def _masked_attention(q, k, v, window):
    """softmax(q k^T / sqrt(d)) v, causal, inside the window; numpy."""
    s = q.shape[-2]
    sc = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (j <= i) & ((j > i - window) if window else True)
    sc = np.where(seen, sc, -np.inf)
    p = np.exp(sc - sc.max(axis=-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(axis=-1, keepdims=True), v)


@pytest.mark.parametrize("window", [None, 128])
def test_grouped_heads_equal_k_and_v_repeated_8_times(window):
    rng = np.random.default_rng(7)
    q, k, v = (_rand(rng, (1, h, 256, 64)) for h in (16, 2, 2))
    want = _masked_attention(np.asarray(q), np.repeat(np.asarray(k), 8, 1),
                             np.repeat(np.asarray(v), 8, 1), window)
    for use_flash in (False, True):
        got = attention.dot_product_attention(
            q, k, v, is_causal=True, training=False, window=window,
            use_flash=use_flash)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)


@pytest.mark.parametrize("seq,window,block_q,streamed", [
    (512, 256, 128, False), (512, 128, 128, False), (1024, 256, None, False),
    (1024, 256, 64, True), (1024, 512, 128, True), (2048, 512, 512, True)])
def test_window_flash_fwd_equals_the_xla_mask(monkeypatch, seq, window,
                                              block_q, streamed):
    """The band's two edges inside one resident block of keys, and (a
    small budget of VMEM makes K and V stream in blocks) in different
    ones; chunks smaller than a q block where the tiling gives them."""
    if streamed:
        monkeypatch.setattr(flash_attention, "_STRIP_BYTES", 64 * 1024)
    rng = np.random.default_rng(8)
    q, k, v = (_rand(rng, (1, h, seq, 64)) for h in (4, 2, 2))
    tiling = flash_attention.flash_tiling(seq, 64, True, block_q, None,
                                          window)
    assert (tiling.block_major < seq) == streamed
    assert tiling.tiles_visited < flash_attention.flash_tiling(
        seq, 64, True, tiling.block_q).tiles_visited
    got = flash_attention.flash_attention_fwd(q, k, v, window=window,
                                              block_q=block_q)
    want = attention.dot_product_attention(
        q, k, v, is_causal=True, training=False, window=window,
        use_flash=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_window_tiles_visited_counts_the_band():
    """At the cell's widest bucket: 512-row q blocks in chunks of 256
    against 2,048-key blocks; a q block's diagonal and edge tiles are
    3 chunk tiles each, the 3 whole tiles between them 4 each."""
    t = flash_attention.flash_tiling(8192, 128, True, window=2048)
    assert (t.block_q, t.chunk_q, t.block_major) == (512, 256, 2048)
    assert t.tiles_visited == sum(
        3 + 4 * min(i, 3) + (3 if i >= 4 else 0) for i in range(16))
    assert t.tiles_visited < flash_attention.flash_tiling(
        8192, 128, True).tiles_visited / 2
    with pytest.raises(ValueError, match="window"):
        flash_attention.flash_tiling(512, 64, False, window=128)


def _resident(rng, layers, slots, kvh, d, depth):
    return (_rand(rng, (layers, slots, kvh, d, depth)),
            _rand(rng, (layers, slots, kvh, d, depth)))


@pytest.mark.parametrize("ring,pos", [
    (False, [0, 39, 127, 128, 255, 77]),
    (True, [0, 39, 127, 128, 255, 1000])])
def test_ring_decode_kernels_equal_their_xla_mathematics(monkeypatch, ring,
                                                         pos):
    """`resident_decode_attention` through `gqa_decode` (interpreted,
    at the tile the tiling names; tests/test_resident_kv_cache.py walks
    the kernel itself over several tiles) against the XLA path of the
    same call and against numpy: 8 query heads over 2 K/V heads, a full
    cache 256 deep and a ring 128 deep at positions before and after it
    wraps."""
    rng = np.random.default_rng(9)
    s, depth = len(pos), 128 if ring else 256
    q, k_new, v_new = (_rand(rng, (s, h, 1, 64)) for h in (8, 2, 2))
    kc, vc = _resident(rng, 2, s, 2, 64, depth)
    pos = jnp.asarray(pos, jnp.int32)
    outs = {}
    for kernel in ("1", "0"):
        monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH_DECODE", kernel)
        outs[kernel] = attention.resident_decode_attention(
            q, k_new, v_new, kc, vc, 1, pos, ring=ring)
    for a, b in zip(outs["1"][1:], outs["0"][1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(outs["1"][0]),
                               np.asarray(outs["0"][0]), atol=1e-5)
    # numpy: slot i attends its first `live` columns after the write
    k, v = np.asarray(outs["0"][1][1]), np.asarray(outs["0"][2][1])
    np.testing.assert_array_equal(np.asarray(outs["0"][1][0]),
                                  np.asarray(kc[0]))
    for i, p in enumerate(np.asarray(pos)):
        col = p % depth if ring else p
        live = min(p + 1, depth) if ring else p + 1
        np.testing.assert_array_equal(k[i, :, :, col],
                                      np.asarray(k_new[i, :, 0]))
        for h in range(8):
            sc = np.asarray(q[i, h, 0]) @ k[i, h // 4, :, :live] / 8.0
            w = np.exp(sc - sc.max())
            want = v[i, h // 4, :, :live] @ (w / w.sum())
            np.testing.assert_allclose(np.asarray(outs["0"][0][i, h, 0]),
                                       want, atol=1e-5)


@pytest.mark.parametrize("force", ["0", "1"])
def test_equal_heads_and_no_ring_give_the_parents_result_bit_for_bit(
        monkeypatch, force):
    """`kv_heads == heads`, no window: `resident_decode_attention` is
    the call it was (the GPT family's path), the same jaxpr and so the
    same bits; and a full-window `dot_product_attention` is the causal
    one."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH_DECODE", force)
    rng = np.random.default_rng(10)
    q, k_new, v_new = (_rand(rng, (3, 4, 1, 64)) for _ in range(3))
    kc, vc = _resident(rng, 2, 3, 4, 64, 128)
    pos = jnp.asarray([0, 50, 127], jnp.int32)
    got = attention.resident_decode_attention(q, k_new, v_new, kc, vc, 1,
                                              pos)
    text = str(jax.make_jaxpr(lambda *a: attention.resident_decode_attention(
        *a, 1, pos))(q, k_new, v_new, kc, vc))
    assert "gqa_decode" not in text
    assert ("name=flash_decode" in text) == (force == "1")
    if force == "0":
        # the parent's mathematics, written out: write, then
        # decode_attention over the layer
        slots = jnp.arange(3)
        k2 = kc.at[1, slots, :, :, pos].set(k_new[:, :, 0])
        v2 = vc.at[1, slots, :, :, pos].set(v_new[:, :, 0])
        want = attention.decode_attention(
            q, jnp.swapaxes(k2[1], -1, -2), jnp.swapaxes(v2[1], -1, -2),
            pos=pos, use_flash=False)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(k2))
    qs, ks, vs = (_rand(rng, (1, 2, 256, 64)) for _ in range(3))
    np.testing.assert_array_equal(
        np.asarray(attention.dot_product_attention(
            qs, ks, vs, is_causal=True, training=False, use_flash=False)),
        np.asarray(attention._xla_attention(
            qs, ks, vs, None, 0.125, True, 0.0, False, None)))


def test_engine_through_the_kernels_serves_the_references_tokens(
        monkeypatch):
    """The engine's decode step on `gqa_decode` (interpreted) at shapes
    the kernel tiles (heads of 64, a window of 128 in a max_len of 256):
    one call a layer and no `kv_append`, the visit tables built once for
    the full cache and once for the rings, not once a layer; still the
    reference's tokens, across the ring's wrap."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH_DECODE", "1")
    toy = Toy(max_len=256, head_dim=64, sliding_window=128,
              num_hidden_layers=4, layer_types=_toy_cfg()["layer_types"][:4],
              max_position_embeddings=256)
    eng = toy.engine(slots=1, buckets=(64, 128))
    step = str(jax.make_jaxpr(toy.acfg.decode)(
        toy.params.trees, jax.eval_shape(
            lambda: toy.acfg.cache_arrays(1, 256)),
        np.zeros(1, np.int32), np.zeros(1, np.int32)))
    assert step.count("name=gqa_decode") == 4
    assert "name=kv_append" not in step
    assert step.count("name=cumsum") == 2
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, toy.acfg.vocab_size, n).astype(np.int32)
               for n in (120, 9)]
    try:
        futs = [eng.submit(p, 12) for p in prompts]
        _drain(eng, futs)
    finally:
        eng.close()
    for p, f in zip(prompts, futs):
        assert toy.ref.gaps(p, f.result(timeout=0)).max() <= TOL


# ---------------------------------------------------------------------
# the chip's share of the expert layer
# ---------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """128 routed experts in 8 shares of 16 (`first_expert` 0, 16, ...
    112), toy widths: the eight chips' routed parts, with the shared
    expert counted once, are the reference's whole layer (the reference
    holding all 128)."""
    M = _module()
    d, f, routed, k = 32, 16, 128, 8
    cfg = _toy_cfg(hidden_size=d, moe_intermediate_size=f,
                   num_experts=routed, num_experts_deployment=routed,
                   num_experts_per_tok=k)
    rng = np.random.default_rng(12)
    w = {"router": _rand(rng, (d, routed), 0.5),
         "expert_bias": _rand(rng, (routed,), 0.05),
         "experts_gate_up": _rand(rng, (routed, d, 2 * f), 0.2),
         "experts_down": _rand(rng, (routed, f, d), 0.2),
         "shared_gate_up": _rand(rng, (d, 2 * f), 0.2),
         "shared_down": _rand(rng, (f, d), 0.2)}
    h = _rand(rng, (37, d))
    mm = M._product("float32")
    whole, _ = M._moe(cfg, w, h, mm, None)
    with jax.default_matmul_precision("highest"):
        parts, counts, _ = zip(*[routed_experts(
            h, w["router"], w["expert_bias"],
            (w["experts_gate_up"][s:s + 16], w["experts_down"][s:s + 16]),
            s, routed, k, cfg["route_scale"]) for s in range(0, routed, 16)])
        shared = afmoe.swiglu(h, w["shared_gate_up"], w["shared_down"])
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=1e-5)
    # every assignment fell on exactly one share
    assert sum(int(c.sum()) for c in counts) == 37 * k
    # and one share alone is the reference's share
    one, _ = M._moe(
        dict(cfg, num_experts=16, first_expert=32),
        dict(w, experts_gate_up=w["experts_gate_up"][32:48],
             experts_down=w["experts_down"][32:48]),
        h, mm, "no_shared_expert")
    np.testing.assert_allclose(np.asarray(parts[2]), np.asarray(one),
                               atol=1e-5)


def test_rows_kept_are_whole_row_tiles_and_under_half_of_all(toy):
    """`_rows_kept` at the cells' shapes: twice the rows the held
    experts expect, in whole row tiles, where that is at most half of
    all rows (K2: 12 of 384 held, Trinity: 16 of 128, both top 8); the
    rehearsal's toys (4 of 16 held, top 2, a few tokens) round up to a
    row tile that is more than half of their rows and have no such
    case, so the toy decode step holds no `cond`."""
    from paddle_tpu.distributed.moe import _rows_kept

    k2, trinity = 12 / 384, 16 / 128
    # K2's decode step of 256 slots, its prefills at 256 to 2,048
    assert _rows_kept(256 * 8, k2) == 128
    assert [_rows_kept(b * 8, k2) for b in (256, 512, 1024, 2048)] == \
        [128, 256, 512, 1024]
    # Trinity's decode step of 64 slots, its prefills at 2,048 to 8,192
    assert _rows_kept(64 * 8, trinity) == 128
    assert [_rows_kept(b * 8, trinity) for b in (2048, 4096, 8192)] == \
        [4096, 8192, 16384]
    assert _rows_kept(300 * 8, 4 / 32) == 640         # 600 in tiles of 128
    assert _rows_kept(64 * 2, 4 / 16) is None
    assert _rows_kept(16 * 8, trinity) is None        # 32: a tile of 128
    assert toy.acfg.expert_layers(2) == 0
    assert toy.acfg.expert_layers(64) == 0
    step = str(jax.make_jaxpr(toy.acfg.decode)(
        toy.params.trees, toy.acfg.cache_arrays(2, 64),
        np.zeros(2, np.int32), np.zeros(2, np.int32)))
    assert "scatter-add" not in step and "cond[" not in step


# ---------------------------------------------------------------------
# tracing: both caches in the summary, their reads on the spans
# ---------------------------------------------------------------------

def test_summary_lists_both_caches(toy):
    eng = toy.engine(slots=3)
    cache = eng.summary()["decode"]["cache"]
    eng.close()
    assert cache["kind"] == toy.acfg.cache_kind
    assert cache["arrays"] == [
        {"name": "k_full", "kind": "depth", "layers": 2, "depth": 64,
         "bytes": 2 * 3 * 2 * 16 * 64 * 4},
        {"name": "v_full", "kind": "depth", "layers": 2, "depth": 64,
         "bytes": 2 * 3 * 2 * 16 * 64 * 4},
        {"name": "k_window", "kind": "depth", "layers": 6, "depth": 16,
         "bytes": 6 * 3 * 2 * 16 * 16 * 4},
        {"name": "v_window", "kind": "depth", "layers": 6, "depth": 16,
         "bytes": 6 * 3 * 2 * 16 * 16 * 4}]
    assert cache["bytes"] == sum(a["bytes"] for a in cache["arrays"])


class _DeviceReads(afmoe.AfmoeCfg):
    """The model as it was before ISSUE 38: its decode step also answers
    with the cached positions each slot read, `pos + 1`, counted on the
    device."""

    def decode(self, trees, cache, token, pos, active=None):
        cache, hidden, counters = super().decode(trees, cache, token, pos,
                                                 active)
        return cache, hidden, dict(counters, device_reads=pos + 1)


@pytest.mark.parametrize("loop", [False, True], ids=["by_hand", "loop"])
def test_wait_spans_carry_the_reads_of_each_cache(toy, tmp_path, loop):
    """`live_full` / `live_window` of every `engine.decode_wait`, which
    the host counts from the lengths it holds, equal what the program
    counted on the device before ISSUE 38 (each active slot's `pos + 1`;
    in a ring no more than the window), step by step, by hand and with
    the loop thread one step ahead; a prefill's are those of its
    prompt."""
    eng = DecodeEngine(
        afmoe.AfmoeParams(toy.params.trees, _DeviceReads(*toy.acfg)),
        config=DecodeConfig(slots=2, max_len=toy.acfg.max_seq_len,
                            buckets=(16, 32, 64), watchdog_stall_s=60.0,
                            label=f"afmoe_reads_{loop}"),
        auto_start=loop)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 211, size=n).astype(np.int32)
               for n in (6, 30, 19)]
    want = []
    answer = eng._answer

    def counting(flight):
        now = answer(flight)
        if flight.op == "decode":
            _, was_active, _, counters = flight.results
            ctx = counters["device_reads"][was_active]
            want.append((int(ctx.sum()),
                         int(np.minimum(ctx, 16).sum())))
        return now

    eng._answer = counting
    jax.profiler.start_trace(str(tmp_path))
    try:
        futs = [eng.submit(p, 14) for p in prompts]
        if loop:
            for f in futs:
                f.result(timeout=120)
        else:
            _drain(eng, futs)
    finally:
        jax.profiler.stop_trace()
    summary = eng.summary()["decode"]
    eng.close()
    assert glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)
    spans = profiler.spans("engine.")
    steps = [a for n, _, _, a in spans if n == "engine.decode_wait"]
    fills = [a for n, _, _, a in spans if n == "engine.prefill_wait"]
    assert len(steps) == summary["decode_steps"] == len(want)
    assert [(a["live_full"], a["live_window"]) for a in steps] == want
    assert max(a["live_full"] for a in steps) > 2 * 16
    assert [(a["live_full"], a["live_window"]) for a in fills] == [
        (p.size, min(p.size, 16)) for p in prompts]
    for a in steps + fills:
        assert 0 <= a["expert_load_max"] <= a["expert_tokens"]
        assert isinstance(a["live_full"], int)
        # the toy's programs have no kept case: each says so
        assert a["expert_layers_kept"] == a["expert_layers"] == 0
    assert summary["experts"]["layers_kept"] == \
        summary["experts"]["layers"] == 0
