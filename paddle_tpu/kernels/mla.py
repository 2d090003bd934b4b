"""Pallas TPU kernels for latent attention (MLA) over a resident latent
cache: `mla_decode` and `latent_append`.

The decode engine's latent cache (models/kimi_k2.py) is one array
`[layers, slots, rank + rope, max_len]`, depth minor: per token and layer
the normalised compressed latent `c_kv` (rank) over the rotated shared
key `k_rope` (rope).  It is key (all rank + rope rows) and value (its
first rank rows) of every head at once, so a decode step is, for each
slot, all heads' absorbed queries `[heads, rank + rope]` against that
slot's latent up to its length: no existing kernel computes that
(`flash_decode` is one query row against one head's own K and V, and
broadcasting the latent to the heads would read it heads times).

`mla_decode`: grid (slots, depth blocks), the depth axis "arbitrary" so
the online-softmax state of the slot's heads persists across blocks.  A
latent tile `[rank + rope, block_k]` is fetched once for all the heads;
blocks past a slot's length are neither computed (`pl.when`) nor fetched
(their index map names the slot's last live block again, and a block
whose index did not change is not copied).

`latent_append`: one new column per slot into one layer, in place, as
`flash_attention.kv_append` writes K and V: the 128-lane tile around the
column passes through VMEM and the cache is aliased to the output.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret
from .flash_attention import NEG_INF, _LANES, _scratch, _vmem_spec

DEFAULT_BLOCK_K = 512


def _mla_kernel(len_ref, ql_ref, qr_ref, c_ref, o_ref, acc_ref, m_ref,
                l_ref, *, sm_scale, block_k, rank):
    ki = pl.program_id(1)
    length = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * block_k < length)
    def _tile():
        c = c_ref[0:rank, :]                               # [rank, bk]
        s = jax.lax.dot_general(
            ql_ref[...], c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(
            qr_ref[...], c_ref[rank:, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        s *= sm_scale                                      # [heads, bk]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_ref[:, 0:1]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, 0:1] * alpha + p.sum(axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [heads, rank]
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _block_k(t, block_k):
    block_k = min(block_k or DEFAULT_BLOCK_K, t)
    if t % block_k or block_k % _LANES:
        raise ValueError(f"cache depth {t} must be a multiple of the "
                         f"block {block_k}, and that of {_LANES}")
    return block_k


def mla_decode(q_latent, q_rope, latent, layer, lengths, sm_scale,
               block_k=None):
    """All heads of every slot against the slot's latent, read where it
    lies.

    q_latent [S, H, rank]: the queries absorbed into the latent space
    (`q_nope @ W_uk^T`); q_rope [S, H, rope]: their rotated part; latent:
    the resident cache [L, S, rank + rope, T]; layer: a Python int;
    lengths: int32 [S], live positions of each slot, in [1, T].  Returns
    softmax((q_latent . c_kv + q_rope . k_rope) * sm_scale) . c_kv,
    [S, H, rank] in q_latent's type, float32 scores and accumulation."""
    s, h, rank = q_latent.shape
    rope = q_rope.shape[-1]
    t = latent.shape[-1]
    if latent.shape[1:] != (s, rank + rope, t):
        raise ValueError(f"the latent cache must be [L, {s}, {rank + rope},"
                         f" T], got {latent.shape}")
    block_k = _block_k(t, block_k)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(s)

    def tile(i, ki, lens):
        return layer, i, 0, jnp.minimum(ki, (lens[i] - 1) // block_k)

    call = pl.pallas_call(
        functools.partial(_mla_kernel, sm_scale=float(sm_scale),
                          block_k=block_k, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, t // block_k),
            in_specs=[
                _vmem_spec((None, h, rank), lambda i, ki, lens: (i, 0, 0)),
                _vmem_spec((None, h, rope), lambda i, ki, lens: (i, 0, 0)),
                _vmem_spec((None, None, rank + rope, block_k), tile)],
            out_specs=_vmem_spec((None, h, rank),
                                 lambda i, ki, lens: (i, 0, 0)),
            scratch_shapes=[_scratch((h, rank)), _scratch((h, _LANES)),
                            _scratch((h, _LANES))]),
        out_shape=jax.ShapeDtypeStruct((s, h, rank), q_latent.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret(),
        name="mla_decode",
    )
    with jax.named_scope("mla_decode"):
        return call(lengths, q_latent, q_rope.astype(q_latent.dtype),
                    latent)


def _append_kernel(pos_ref, tile_ref, new_ref, out_ref):
    # one slot a grid step: tile [rank + rope, 128], new column [.., 1]
    col = pos_ref[pl.program_id(0)] % _LANES
    hit = jax.lax.broadcasted_iota(jnp.int32, tile_ref.shape, 1) == col
    out_ref[...] = jnp.where(hit, new_ref[...], tile_ref[...])


def latent_append(latent, new, layer, pos):
    """Write one new column per slot into one layer of the resident
    latent cache, in place.

    latent [L, S, rank + rope, T] with T a multiple of 128; new
    [S, rank + rope]; layer: a Python int; pos: int32 [S], each inside
    [0, T).  Nothing but slot s's column pos[s] of `layer` changes: the
    cache is aliased to the output and only the 128-lane tile around
    each column passes through VMEM."""
    _, s, width, t = latent.shape
    if t % _LANES:
        raise ValueError(f"cache depth {t} must be a multiple of {_LANES}")
    pos = jnp.asarray(pos, jnp.int32).reshape(s)
    tile = _vmem_spec((None, None, width, _LANES),
                      lambda i, pos: (layer, i, 0, pos[i] // _LANES))
    call = pl.pallas_call(
        _append_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s,),
            in_specs=[tile, _vmem_spec((None, width, 1),
                                       lambda i, pos: (i, 0, 0))],
            out_specs=tile),
        out_shape=jax.ShapeDtypeStruct(latent.shape, latent.dtype),
        # operand numbers count the scalar-prefetch argument
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret(),
        name="latent_append",
    )
    with jax.named_scope("latent_append"):
        return call(pos, latent, new.astype(latent.dtype)[:, :, None])
