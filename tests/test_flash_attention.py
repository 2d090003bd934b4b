"""Flash-attention kernel numerics vs the XLA reference composition.

The OpTest pattern (op_test.py:1261 analytic-vs-numeric) applied to the
fused kernel: forward and all three input grads must match the unfused
softmax(QK^T)V composition. Runs in pallas interpret mode on CPU.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels.attention import _xla_attention
from paddle_tpu.kernels.flash_attention import flash_attention, flash_tiling


def _inputs(b=1, h=2, s=256, d=64, seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(r.normal(size=(b, h, s, d)) * 0.5, dtype=dtype)
    return mk(), mk(), mk()


def _assert_forward(q, k, v, causal, **blocks):
    scale = 1.0 / math.sqrt(q.shape[-1])
    out = flash_attention(q, k, v, causal=causal, **blocks)
    ref = _xla_attention(q, k, v, None, scale, causal, 0.0, False, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _assert_grads(q, k, v, causal, **blocks):
    scale = 1.0 / math.sqrt(q.shape[-1])
    r = np.random.default_rng(7)
    w = jnp.asarray(r.normal(size=q.shape), jnp.float32)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, **blocks) * w).sum()

    def f_ref(q, k, v):
        return (_xla_attention(q, k, v, None, scale, causal, 0.0, False,
                               None) * w).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name} mismatch (causal={causal})")


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_xla(causal):
    _assert_forward(*_inputs(), causal)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_xla(causal):
    q, k, v = _inputs(s=256, d=64)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal) ** 2).sum()

    def f_ref(q, k, v):
        return (_xla_attention(q, k, v, None, scale, causal, 0.0, False,
                               None) ** 2).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_multi_block_seq():
    # seq spanning several q/k blocks: the diagonal met in each of them
    _assert_forward(*_inputs(s=384, d=64), True, block_q=128, block_k=128)


def test_bf16_inputs():
    q, k, v = _inputs(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = _xla_attention(q, k, v, None, 1.0 / 8.0, True, 0.0, False, None)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_rejects_unaligned_seq():
    q, k, v = _inputs(s=96)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=64, block_k=64)


@pytest.mark.parametrize("causal", [False, True])
def test_with_lse_outputs_and_grads(causal):
    """flash_attention_with_lse: lse equals logsumexp of the score rows,
    and grads flow correctly through BOTH outputs (the dlse path folds
    into delta — checked against a pure-jnp reference)."""
    from paddle_tpu.kernels.flash_attention import flash_attention_with_lse

    q, k, v = _inputs(s=128, d=16)
    scale = 1.0 / math.sqrt(q.shape[-1])
    out, lse = flash_attention_with_lse(q, k, v, causal=causal)

    s_mat = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        sq = q.shape[2]
        mask = jnp.tril(jnp.ones((sq, sq), bool))
        s_mat = jnp.where(mask, s_mat, -1e30)
    ref_lse = jax.nn.logsumexp(s_mat, axis=-1)
    ref_out = jnp.einsum("bhqk,bhkd->bhqd",
                         jax.nn.softmax(s_mat, axis=-1), v)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-5, atol=2e-5)

    # a loss touching BOTH outputs exercises the dlse cotangent
    r = np.random.default_rng(3)
    wo = jnp.asarray(r.normal(size=out.shape), jnp.float32)
    wl = jnp.asarray(r.normal(size=lse.shape), jnp.float32)

    def loss_kernel(q, k, v):
        o, l = flash_attention_with_lse(q, k, v, causal=causal)
        return (o * wo).sum() + (l * wl).sum()

    def loss_ref(q, k, v):
        s_mat = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            sq = q.shape[2]
            mask = jnp.tril(jnp.ones((sq, sq), bool))
            s_mat = jnp.where(mask, s_mat, -1e30)
        l = jax.nn.logsumexp(s_mat, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd",
                       jax.nn.softmax(s_mat, axis=-1), v)
        return (o * wo).sum() + (l * wl).sum()

    g_kernel = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gk, gr, name in zip(g_kernel, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gk), np.asarray(gr), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name}")


# --------------------------------------------------------------------------
# the tiling: shapes the benchmark runs, the strips the kernels walk
# --------------------------------------------------------------------------

# (seq, head_dim, with gradients): the train cell's shape, the widest
# prefill bucket of the K2 cell, a multiple of 128 that 256 does not
# divide, and sequences below one tile
SHAPES = [(1024, 64, True), (2048, 256, False), (384, 64, True),
          (64, 64, True), (96, 32, True)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq,head_dim,grads", SHAPES)
def test_shapes_match_xla(seq, head_dim, grads, causal):
    q, k, v = _inputs(b=1, h=1, s=seq, d=head_dim, seed=seq)
    _assert_forward(q, k, v, causal)
    if grads:
        _assert_grads(q, k, v, causal)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k", [(256, 256), (256, 128),
                                             (128, 256)])
def test_streamed_operand_matches_xla(monkeypatch, block_q, block_k, causal):
    """Where K and V (q and dO) do not fit, they arrive in blocks through
    the grid and the running statistics pass through scratch: forced
    here by a small budget, the same kernels at seq 1024 in 4 blocks."""
    monkeypatch.setattr(fa, "_STRIP_BYTES", 256 * 256 * 4)
    assert flash_tiling(1024, 64, causal, block_q, block_k).block_major == 256
    q, k, v = _inputs(b=1, h=2, s=1024, d=64, seed=5)
    blocks = dict(block_q=block_q, block_k=block_k)
    _assert_forward(q, k, v, causal, **blocks)
    _assert_grads(q, k, v, causal, **blocks)


def test_with_lse_shape_is_batch_heads_seq():
    from paddle_tpu.kernels.flash_attention import flash_attention_with_lse

    q, k, v = _inputs(b=2, h=3, s=256, d=64)
    out, lse = flash_attention_with_lse(q, k, v, causal=True)
    assert out.shape == (2, 3, 256, 64)
    assert lse.shape == (2, 3, 256) and lse.dtype == jnp.float32


def _strip_area(strips, rows_from_row0, block):
    """Score elements the strips of one grid step compute."""
    return sum((block - a if rows_from_row0 else a) * width
               for a, _, width, _ in strips)


@pytest.mark.parametrize("seq,head_dim", [(1024, 64), (2048, 256),
                                          (384, 64), (8192, 128)])
def test_tiling_visits_the_triangle_only(seq, head_dim):
    """The counter that says the causal pruning engages: the tiling
    function's count is the triangle's, and the strips the kernels walk
    (their static loop bounds) cover exactly that many elements."""
    t = flash_tiling(seq, head_dim, True)
    n = seq // t.chunk_q
    assert (t.tiles_visited, t.tiles_total) == (n * (n + 1) // 2, n * n)
    full = flash_tiling(seq, head_dim, False)
    assert full.tiles_visited == full.tiles_total == n * n
    assert full[:5] == t[:5]        # the mask changes no block size

    # flash_fwd / flash_dq: every q block against every resident K
    area = 0
    for q0 in range(0, seq, t.block_q):
        for k0 in range(0, seq, t.block_major):
            if k0 + t.block_major <= q0:          # wholly before: all live
                strips = fa._key_strips(None, t.block_q, t.chunk_q,
                                        t.block_major)
            elif k0 <= q0:                        # the diagonal is in it
                strips = fa._key_strips((q0 - k0) // t.block_q, t.block_q,
                                        t.chunk_q, t.block_major)
            else:
                continue                          # wholly after: dead
            area += _strip_area(strips, True, t.block_q)
    assert area == t.tiles_visited * t.chunk_q ** 2
    # flash_dkv: every k block against every resident q, mirrored
    m = seq // t.chunk_k
    area = 0
    for k0 in range(0, seq, t.block_k):
        for q0 in range(0, seq, t.block_major):
            if q0 >= k0 + t.block_k:
                strips = fa._query_strips(None, t.block_k, t.chunk_k,
                                          t.block_major)
            elif q0 <= k0 < q0 + t.block_major:
                strips = fa._query_strips((k0 - q0) // t.block_k, t.block_k,
                                          t.chunk_k, t.block_major)
            else:
                continue
            area += _strip_area(strips, False, t.block_k)
    assert area == m * (m + 1) // 2 * t.chunk_k ** 2


def test_tiling_at_the_benchmarks_shapes():
    """What the three cells run (BENCHMARK.json): tiles of 256 visit 10
    of 16 at the train cell's shape; K and V stay resident in all."""
    t = flash_tiling(1024, 64, True)
    assert (t.tiles_visited, t.tiles_total) == (10, 16)
    assert t.block_major == 1024
    assert flash_tiling(2048, 256, True).block_major == 2048
    assert flash_tiling(8192, 128, True).block_major < 8192


def test_tiling_rejects_blocks_that_do_not_divide():
    with pytest.raises(ValueError):
        flash_tiling(96, 64, True, 64, 64)


@pytest.mark.parametrize("strips", ["_key_strips", "_query_strips"])
def test_unmasked_diagonal_is_caught(monkeypatch, strips):
    """Mutation check: with the diagonal tiles left unmasked (in the
    forward's strips, in the backward's) the comparisons above fail."""
    real = getattr(fa, strips)
    monkeypatch.setattr(fa, strips, lambda *a: tuple(
        (a0, c0, w, False) for a0, c0, w, _ in real(*a)))
    q, k, v = _inputs(b=1, h=1, s=512, d=64)
    # the kernels' callers are jitted: no trace from before the mutation
    # may answer here, and none of the mutant's may stay behind
    jax.clear_caches()
    try:
        with pytest.raises(AssertionError):
            if strips == "_key_strips":
                _assert_forward(q, k, v, True)
            else:
                _assert_grads(q, k, v, True)
    finally:
        jax.clear_caches()
