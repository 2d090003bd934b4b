"""Pallas TPU flash attention (forward + custom-VJP backward).

The native-kernel tier of the attention stack: replaces the reference's
hand-fused CUDA attention (/root/reference/paddle/fluid/operators/fused/
multihead_matmul_op.cu, operators/math/bert_encoder_functor.cu) with an
online-softmax tiled kernel that never materialises the [S, S] score
matrix in HBM.

Structure (canonical TPU pipelining shape): the grid is
(batch*heads, q blocks, k blocks) with the k axis innermost and marked
"arbitrary" so Mosaic double-buffers the k/v block DMAs against compute.
Softmax statistics (running max m, running sum l) and the output
accumulator live in VMEM scratch that persists across the k steps of one
q block; the causal triangle prunes dead (qi, ki) tiles with pl.when.
Matmuls run in the input dtype (bf16 → full-rate MXU) accumulating f32
via preferred_element_type.

Backward recomputes scores blockwise from the saved logsumexp (no S×S
residual): one kernel for dq (grid k-innermost) and one for dk/dv (grid
q-innermost) — the flash-attention-2 decomposition.

On non-TPU backends the same kernels run in interpret mode, which is how
tests/test_flash_attention.py checks numerics vs the XLA composition.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret

# 1024x1024 tiles: measured fastest on v5e (r4 flash_block_ab2,
# b8 h16 s2048 d64 fwd+bwd chained): 512x512 17.48ms, 1024x512 16.62,
# 2048x512 17.07, 1024x1024 14.80 (2048x1024 fails to compile).  The
# f32 score block is 4 MB — fits Mosaic's default 16MB scoped budget
# (this file sets no vmem_limit_bytes, unlike fused_bottleneck); shorter
# k loops beat the extra DMA overlap the 512 tiling bought.  Override
# per-call via flash_attention(block_q=..., block_k=...) or globally
# via PADDLE_TPU_FLASH_BLOCK=<q>x<k> for on-chip A/B runs.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
_LANES = 128
NEG_INF = -1e30


def _env_blocks():
    import os

    v = os.environ.get("PADDLE_TPU_FLASH_BLOCK")
    if not v:
        return None
    try:
        bq, _, bk = v.partition("x")
        return int(bq), int(bk or bq)
    except ValueError:
        raise ValueError(
            f"PADDLE_TPU_FLASH_BLOCK={v!r} is malformed; expected "
            f"'<block_q>x<block_k>' (e.g. 512x512) or a single size"
        ) from None


def _vmem_spec(*args):
    return pl.BlockSpec(*args, memory_space=pltpu.VMEM)


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _causal_mask(s, qi, ki, block_q, block_k):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sm_scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile(masked):
        q = q_ref[0]                                      # [bq, d] native
        k_blk = k_ref[0]                                  # [bk, d]
        v_blk = v_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk] f32
        if masked:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        m_prev = m_ref[:, 0]                              # [bq]
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + p.sum(axis=-1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    if causal:
        # only tiles straddling the diagonal pay the iota/mask passes;
        # tiles fully below it run the unmasked fast path
        live = (qi + 1) * block_q > ki * block_k
        full = qi * block_q >= (ki + 1) * block_k

        @pl.when(live & full)
        def _fast():
            _tile(masked=False)

        @pl.when(live & jnp.logical_not(full))
        def _diag():
            _tile(masked=True)
    else:
        _tile(masked=False)

    @pl.when(ki == num_k - 1)
    def _finalize():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
        # stats get a trailing singleton axis: TPU block shapes need the
        # last two dims (8,128)-aligned or equal to the array dims
        lse_ref[0] = (m_ref[:, 0] + jnp.log(l_safe))[:, None]


def _fwd(q, k, v, sm_scale, causal, block_q, block_k):
    b, h, s, d = q.shape
    grid = (b * h, s // block_q, s // block_k)
    q3 = q.reshape(b * h, s, d)
    k3 = k.reshape(b * h, s, d)
    v3 = v.reshape(b * h, s, d)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            _vmem_spec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            _vmem_spec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            _vmem_spec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            _vmem_spec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            _vmem_spec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q, d)),
            _scratch((block_q, _LANES)),
            _scratch((block_q, _LANES)),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret(),
        name="flash_fwd",
    )
    with jax.named_scope("flash_fwd"):
        out, lse = call(q3, k3, v3)
    return out.reshape(b, h, s, d), lse.reshape(b, h, s)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc_ref, *, sm_scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    live = ((qi + 1) * block_q > ki * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]                                      # [bq, d]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]                            # [bq]
        delta = delta_ref[0][:, 0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        s = sm_scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp(s - lse[:, None])                     # [bq, bk]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_acc_ref[:] = dq_acc_ref[:] + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, sm_scale, causal, block_q, block_k):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    live = ((qi + 1) * block_q > ki * block_k) if causal else True

    @pl.when(live)
    def _compute():
        k_blk = k_ref[0]                                  # [bk, d]
        v_blk = v_ref[0]
        q = q_ref[0]                                      # [bq, d]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]
        delta = delta_ref[0][:, 0]
        s = sm_scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk]
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp(s - lse[:, None])
        dv_acc_ref[:] = dv_acc_ref[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_acc_ref[:] = dk_acc_ref[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    do = g
    # delta = rowsum(dO * O), [b,h,s] — plain XLA, fuses into one pass
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    return _bwd_core(sm_scale, causal, block_q, block_k, q, k, v, do,
                     lse, delta)


def _bwd_core(sm_scale, causal, block_q, block_k, q, k, v, do, lse,
              delta):
    """Shared FA-2 backward given a precomputed delta row vector.

    The (out, lse)-output variant folds its lse cotangent in here:
    ds = p*(dp - delta + dlse) = p*(dp - (delta - dlse)), so the caller
    just passes delta - dlse and the kernels stay byte-identical."""
    b, h, s, d = q.shape
    q3 = q.reshape(b * h, s, d)
    k3 = k.reshape(b * h, s, d)
    v3 = v.reshape(b * h, s, d)
    do3 = do.reshape(b * h, s, d)
    lse3 = lse.reshape(b * h, s, 1)
    delta3 = delta.reshape(b * h, s, 1)

    grid_dq = (b * h, s // block_q, s // block_k)
    call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid_dq,
        in_specs=[
            _vmem_spec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            _vmem_spec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            _vmem_spec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            _vmem_spec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            _vmem_spec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
            _vmem_spec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=_vmem_spec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d))],
        compiler_params=_compiler_params(),
        interpret=interpret(),
        name="flash_dq",
    )
    with jax.named_scope("flash_dq"):
        dq = call(q3, k3, v3, do3, lse3, delta3)

    grid_kv = (b * h, s // block_k, s // block_q)
    call = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid_kv,
        in_specs=[
            _vmem_spec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            _vmem_spec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            _vmem_spec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            _vmem_spec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            _vmem_spec((1, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
            _vmem_spec((1, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            _vmem_spec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            _vmem_spec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s, d), v.dtype),
        ],
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        compiler_params=_compiler_params(),
        interpret=interpret(),
        name="flash_dkv",
    )
    with jax.named_scope("flash_dkv"):
        dk, dv = call(q3, k3, v3, do3, lse3, delta3)

    return (dq.reshape(b, h, s, d), dk.reshape(b, h, s, d),
            dv.reshape(b, h, s, d))


# --------------------------------------------------------------------------
# single-query decode forward
# --------------------------------------------------------------------------
#
# Two callers, one online-softmax body (`_decode_kernel`):
#
# - `flash_decode` takes K and V as arrays `[B, H, T, D]` (the cohort
#   decoder of models/generate.py, ops/fused_ops.py): a tile is
#   `[block_k, D]`, scores contract D of q with D of the tile.
# - `flash_decode_resident` reads the decode engine's stacked cache
#   `[L, S, H, D, T]` (serving/decode.py) where it lies: the layer index
#   rides scalar prefetch beside the lengths and picks the layer in the
#   BlockSpec, so no layer is sliced out; a tile is `[D, block_k]`, with
#   T on the lanes, and the output contracts p with the V tile over
#   block_k (the q @ k^T form of the forward kernel above).  T minor is
#   the order the device holds such an array in anyway (a minor
#   dimension of 64 would be padded to 128 lanes), so neither XLA nor the
#   Mosaic call has a reason to re-lay the cache.
#
# `kv_append` is the engine's only writer inside the decode step: per
# slot, the 128-lane tile that holds column `pos[s]` comes in, the new
# column is merged under an iota mask, the same tile goes out, on the
# cache aliased to the call's output.  (An XLA scatter or
# dynamic_update_slice of one column re-lays the whole stacked cache
# around the write: the update's minor dimension of 1 pulls the operand's
# layout with it.)

def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, sm_scale, block_k, heads, t_minor):
    ki = pl.program_id(1)
    num_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # per-row lengths ride scalar prefetch (SMEM holds the whole [B]
    # vector; a (1, 1) SMEM block per grid step does not lower)
    length = len_ref[pl.program_id(0) // heads]
    # which axis of a K tile holds head_dim, and of a V tile the columns:
    # tiles are [block_k, D], or [D, block_k] from a cache kept T minor
    k_d, v_t = (0, 1) if t_minor else (1, 0)

    # k blocks entirely past the live prefix contribute nothing; skip
    # their DMA'd compute outright (the ragged-length win: a slot at
    # pos 40 in a 2048-deep cache touches 1 block, not 16)
    @pl.when(ki * block_k < length)
    def _tile():
        q = q_ref[...]                                    # [1, d]
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (k_d,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [1, bk] f32
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_ref[0, 0]
        l_prev = l_ref[0, 0]
        m_cur = jnp.maximum(m_prev, s.max())
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[:] = jnp.broadcast_to(l_prev * alpha + p.sum(), l_ref.shape)
        acc_ref[0:1] = acc_ref[0:1] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (v_t,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)

    @pl.when(ki == num_k - 1)
    def _finalize():
        l = l_ref[0, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[0:1] / l_safe).astype(o_ref.dtype)


def _decode_block_k(t, block_k):
    if block_k is None:
        cand = 512
        while cand > 64 and (cand > t or t % cand):
            cand //= 2
        block_k = cand if (cand <= t and t % cand == 0) else t
    if t % block_k:
        raise ValueError(
            f"cache depth {t} must be divisible by block_k {block_k}")
    return block_k


def _decode_call(kernel, rows, heads, t, d, block_k, dtype, n_prefetch,
                 kv_spec):
    """The pallas_call both decode entry points share: grid
    (rows * heads, T // block_k), one [1, D] query and output a row,
    `kv_spec` the BlockSpec of a K (and V) tile."""
    q_spec = _vmem_spec((None, 1, d), lambda bh, ki, *_: (bh, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(rows * heads, t // block_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                # 8-row scratch (f32 sublane tile) though only row 0 is
                # used: sub-tile scratch shapes are not portable on TPU
                _scratch((8, d)),
                _scratch((8, _LANES)),
                _scratch((8, _LANES)),
            ]),
        out_shape=jax.ShapeDtypeStruct((rows * heads, 1, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret(),
        name="flash_decode",
    )


def flash_decode(q, k, v, lengths, sm_scale=None, block_k=None):
    """Single-query flash attention for the decode phase.

    q: [B, H, 1, D] (one new token per row), k/v: [B, H, T, D] (the KV
    cache), lengths: int32 [B] or scalar — live prefix length per row
    (pos + 1); cache positions >= length are masked out.  The grid is
    (B*H, T//block_k) with the k axis "arbitrary" so the running
    (m, l, acc) online-softmax state persists across k blocks, and
    blocks past the live prefix are pruned with pl.when — cost scales
    with the ragged lengths, not the cache depth.  T must be divisible
    by block_k (auto-shrunk power of two <= 512)."""
    b, h, q_len, d = q.shape
    if q_len != 1:
        raise ValueError(f"flash_decode needs q_len == 1, got {q_len}")
    t = k.shape[-2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    block_k = _decode_block_k(t, block_k)
    lengths = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))
    call = _decode_call(
        functools.partial(_decode_kernel, sm_scale=float(sm_scale),
                          block_k=block_k, heads=h, t_minor=False),
        b, h, t, d, block_k, q.dtype, 1,
        _vmem_spec((None, block_k, d), lambda bh, ki, lens: (bh, ki, 0)))
    with jax.named_scope("flash_decode"):
        out = call(lengths, q.reshape(b * h, 1, d),
                   k.reshape(b * h, t, d), v.reshape(b * h, t, d))
    return out.reshape(b, h, 1, d)


def flash_decode_resident(q, k_cache, v_cache, layer, lengths,
                          sm_scale=None):
    """`flash_decode` over one layer of the decode engine's resident
    cache, read where it lies.

    q: [S, H, 1, D]; k_cache/v_cache: the stacked caches [L, S, H, D, T]
    (K and V stored transposed, T minor); layer: int32 scalar, traced;
    lengths: int32 [S].  Same grid, same online softmax and the same
    pruning as `flash_decode`; a tile is [D, block_k]."""
    s, h, q_len, d = q.shape
    if q_len != 1:
        raise ValueError(f"flash_decode needs q_len == 1, got {q_len}")
    t = k_cache.shape[-1]
    if k_cache.shape[1:] != (s, h, d, t) or v_cache.shape != k_cache.shape:
        raise ValueError(
            f"resident caches must be [L, {s}, {h}, {d}, T], got "
            f"{k_cache.shape} and {v_cache.shape}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    block_k = _decode_block_k(t, None)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(s)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    kernel = functools.partial(_decode_kernel, sm_scale=float(sm_scale),
                               block_k=block_k, heads=h, t_minor=True)
    call = _decode_call(
        lambda len_ref, layer_ref, *refs: kernel(len_ref, *refs),
        s, h, t, d, block_k, q.dtype, 2,
        _vmem_spec((None, None, None, d, block_k),
                   lambda bh, ki, lens, layer: (layer[0], bh // h, bh % h,
                                                0, ki)))
    with jax.named_scope("flash_decode"):
        out = call(lengths, layer, q.reshape(s * h, 1, d), k_cache, v_cache)
    return out.reshape(s, h, 1, d)


def _append_kernel(pos_ref, layer_ref, kc_ref, vc_ref, kn_ref, vn_ref,
                   ko_ref, vo_ref):
    # one slot a grid step: tiles [H, D, 128], new columns [D, H]
    col = pos_ref[pl.program_id(0)] % _LANES
    hit = jax.lax.broadcasted_iota(jnp.int32, kc_ref.shape[1:], 1) == col
    for new_ref, in_ref, out_ref in ((kn_ref, kc_ref, ko_ref),
                                     (vn_ref, vc_ref, vo_ref)):
        new = new_ref[...]
        for h in range(in_ref.shape[0]):
            out_ref[h] = jnp.where(hit, new[:, h:h + 1], in_ref[h])


def kv_append(k_cache, v_cache, k_new, v_new, layer, pos):
    """Write one new column per slot into one layer of the resident
    caches, in place.

    k_cache/v_cache: [L, S, H, D, T] with T a multiple of 128;
    k_new/v_new: [S, H, D]; layer: int32 scalar, traced; pos: int32 [S],
    each inside [0, T).  Slot s gets column pos[s] of `layer`; nothing
    else is touched: the caches are aliased to the outputs and only the
    128-lane tile around each column passes through VMEM."""
    n_layers, s, h, d, t = k_cache.shape
    if t % _LANES:
        raise ValueError(f"cache depth {t} must be a multiple of {_LANES}")
    pos = jnp.asarray(pos, jnp.int32).reshape(s)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    tile = _vmem_spec(
        (None, None, h, d, _LANES),
        lambda i, pos, layer: (layer[0], i, 0, 0, pos[i] // _LANES))
    # the new columns arrive [S, D, H]: head_dim on the sublanes, as in
    # the tile, so a head's column is one lane of the block, broadcast
    column = _vmem_spec((None, d, h), lambda i, pos, layer: (i, 0, 0))
    call = pl.pallas_call(
        _append_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s,),
            in_specs=[tile, tile, column, column],
            out_specs=[tile, tile]),
        out_shape=[jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        # operand numbers count the two scalar-prefetch arguments
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret(),
        name="kv_append",
    )
    with jax.named_scope("kv_append"):
        return call(pos, layer, k_cache, v_cache,
                    jnp.swapaxes(k_new, 1, 2).astype(k_cache.dtype),
                    jnp.swapaxes(v_new, 1, 2).astype(v_cache.dtype))


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, sm_scale, causal, block_q, block_k):
    out, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k)
    return out


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, sm_scale, causal, block_q, block_k):
    return _fwd(q, k, v, sm_scale, causal, block_q, block_k)


def _flash_lse_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(sm_scale, causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    do, dlse = g
    # dlse rides the same kernels: ds gains +p*dlse, i.e. delta -> delta
    # - dlse (see _bwd_core)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1) - dlse.astype(jnp.float32)
    return _bwd_core(sm_scale, causal, block_q, block_k, q, k, v, do,
                     lse, delta)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _resolve(q, sm_scale, block_q, block_k):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s = q.shape[-2]

    def _auto_block(default):
        # largest power-of-two tile <= default that divides seq, so any
        # 128-multiple seq (1920, 2176, ...) gets a valid tiling; the
        # ladder always descends to 64 regardless of where the default
        # starts (raising the default to 1024 must not lift the floor —
        # a seq divisible by 64 but not 128 would otherwise fall back
        # to one full-seq tile and blow the score block's VMEM)
        cand = default
        while cand >= 64:
            if cand <= s and s % cand == 0:
                return cand
            cand //= 2
        return s

    env = _env_blocks()
    dq, dk = env if env else (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    block_q = block_q or _auto_block(dq)
    block_k = block_k or _auto_block(dk)
    block_q, block_k = min(block_q, s), min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(
            f"seq {s} must be divisible by block sizes ({block_q},{block_k})")
    return float(sm_scale), block_q, block_k


def flash_attention(q, k, v, causal=False, sm_scale=None,
                    block_q=None, block_k=None):
    """Tiled attention over [batch, heads, seq, head_dim] inputs.

    seq must be a multiple of the block sizes (default DEFAULT_BLOCK_Q/
    DEFAULT_BLOCK_K = 1024, auto-shrunk to a power-of-two divisor of
    seq); head_dim should be an MXU-friendly 64/128/256. Returns the same
    shape/dtype as q.
    """
    sm_scale, block_q, block_k = _resolve(q, sm_scale, block_q, block_k)
    return _flash(q, k, v, sm_scale, bool(causal), block_q, block_k)


def flash_attention_with_lse(q, k, v, causal=False, sm_scale=None,
                             block_q=None, block_k=None):
    """flash_attention that ALSO returns the per-row logsumexp
    [batch, heads, seq] (f32), fully differentiable through both
    outputs — the building block for ring attention's (out, lse) block
    combine (distributed/ring_attention.py): partial attentions over kv
    shards merge exactly via softmax-weighted averaging of normalized
    outputs."""
    sm_scale, block_q, block_k = _resolve(q, sm_scale, block_q, block_k)
    return _flash_lse(q, k, v, sm_scale, bool(causal), block_q, block_k)
