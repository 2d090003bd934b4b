"""Pallas TPU kernel for latent attention (MLA) over a resident latent
cache: `mla_decode`.

The decode engine's latent cache (models/kimi_k2.py) is one array
`[layers, slots, rank + rope, max_len]`, depth minor: per token and layer
the normalised compressed latent `c_kv` (rank) over the rotated shared
key `k_rope` (rope).  It is key (all rank + rope rows) and value (its
first rank rows) of every head at once, so a decode step is, for each
slot, all heads' absorbed queries `[heads, rank + rope]` against that
slot's latent up to its length: no existing kernel computes that
(`flash_decode` is one query row against one head's own K and V, and
broadcasting the latent to the heads would read it heads times).

`mla_decode` writes this step's column of every slot and attends, in one
call.  **The walk has as many steps as the slots have live tiles.**  A
visit is one (slot, tile) pair that holds a live position; `_visits`
(flash_attention.py: `gqa_decode` walks by the same tables) lists them
in XLA from the lengths (each slot's first visit, the slot of each
visit), and the tables are scalar-prefetched.  The grid is
`(slots,)`: a grid step brings the slot's queries and takes its result
through BlockSpecs, and its body loops over the slot's visits only.  The
latent stays where it lies (`pl.ANY`) and the kernel copies a visit's
tile `[rank + rope, tile]` into one of `_BUFFERS` VMEM buffers itself,
always `_BUFFERS - 1` visits ahead of the one it computes, whatever slot
those belong to: the first tiles of the next slots are in flight while
this slot's last one is computed, and a copy's latency hides behind the
copies before it.  Visit v lives in buffer v mod `_BUFFERS`, so no turn
is carried from one grid step to the next.  A tile is copied in parts
(`MlaTiling.part` columns each), and of a slot's last tile only the
parts that hold a live position: what lies past them in the buffer is
an earlier visit's, finite and masked.

The new column is written by the kernel that reads it: on a slot's last
visit the 128 lanes around column `length - 1` of the tile in VMEM take
this step's `[c_kv ; k_rope]` before the scores are computed, and the
same 128 lanes go back to the cache, which is aliased to the result,
through a BlockSpec.  Nothing else of the cache is written.

`mla_tiling` is the only place a tile is chosen.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret
from .flash_attention import (NEG_INF, _LANES, _scratch, _visits,
                              _vmem_spec)

# tiles in VMEM: the one computed and those on their way
_BUFFERS = 4
# elements of the latent those buffers may hold together: 8 MiB in
# bfloat16, half of what a kernel is given on the v5e
_BUFFER_ELEMS = 4 << 20


class MlaTiling(NamedTuple):
    """How `mla_decode` walks one slot's latent."""
    tile: int       # columns of a visit: one step of the online softmax
    part: int       # columns of one copy; a tile is whole parts


def mla_tiling(width, depth, block_k=None):
    """The tiling of `mla_decode` over a latent `[width, depth]`.  The
    tile is the largest power-of-two multiple of 128, at most 1024, that
    divides the depth and whose `_BUFFERS` buffers of `width` rows fit
    `_BUFFER_ELEMS` (`block_k` overrides it: tests and the interpreter,
    for small depths), and it is copied in parts of 256 columns where
    those divide it.

    Measured on the v5e at the K2 cell's `[5, 256, 576, 4096]`, 64 heads
    and lengths drawn as its (mean 1,492; PERF.md section 6, PR 35), ms a
    call.  Tiles copied whole: 1.17 at 256 columns (the softmax's fixed
    work a visit bounds it), 0.89 at 512, 0.95 at 1024 and 1.11 at 2048
    (the dead half of a slot's last tile is copied and computed).
    Copied in live parts of 256 a tile of 1024 takes 0.80, which is what
    its copies alone take (685 GB/s): the computation, 0.71 alone, hides
    behind them; parts of 128 take 0.82, a tile of 512 in parts 0.87.
    Four buffers of 1024 columns of 576 rows are 4.7 MB of VMEM."""
    tile = block_k
    if tile is None:
        tile = 1024
        while tile > _LANES and (
                depth % tile or _BUFFERS * width * tile > _BUFFER_ELEMS):
            tile //= 2
    if depth % tile or tile % _LANES:
        raise ValueError(f"cache depth {depth} must be a multiple of the "
                         f"tile {tile}, and that of {_LANES}")
    return MlaTiling(tile, _LANES if tile % (2 * _LANES) else 2 * _LANES)


def _mla_kernel(len_ref, first_ref, slot_ref, ql_ref, qr_ref, new_ref,
                latent_ref, o_ref, lanes_ref, buf_ref, sem_ref, acc_ref,
                m_ref, l_ref, *, layer, sm_scale, tile, part, rank):
    i = pl.program_id(0)
    length = len_ref[i]
    begin, end = first_ref[i], first_ref[i + 1]
    visits = first_ref[pl.num_programs(0)]
    parts = tile // part

    def copy(v, j, slot=0, column=0):
        return pltpu.make_async_copy(
            latent_ref.at[layer, slot, :, pl.ds(column + j * part, part)],
            buf_ref.at[v % _BUFFERS, :, pl.ds(j * part, part)],
            sem_ref.at[v % _BUFFERS, j])

    def live_parts(v, slot):
        left = len_ref[slot] - (v - first_ref[slot]) * tile
        return jnp.minimum((left + part - 1) // part, parts)

    def fetch(v):
        @pl.when(v < visits)
        def _start():
            slot = slot_ref[v]
            column = pl.multiple_of((v - first_ref[slot]) * tile, tile)
            live = live_parts(v, slot)
            for j in range(parts):
                pl.when(j < live)(copy(v, j, slot, column).start)

    @pl.when(i == 0)
    def _prime():
        # no part of a buffer is ever read before something was put there
        buf_ref[...] = jnp.zeros_like(buf_ref)
        for v in range(_BUFFERS - 1):
            fetch(v)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def visit(v, last):
        fetch(v + _BUFFERS - 1)
        live = live_parts(v, i) if last else None
        for j in range(parts):
            if last:
                pl.when(j < live)(copy(v, j).wait)
            else:
                copy(v, j).wait()
        buf = buf_ref.at[v % _BUFFERS]
        if last:
            # this step's column into the 128 lanes around it: the slot's
            # own lane of its 128 slots' columns, spread over the lanes
            at = length - 1 - (v - begin) * tile
            around = pl.ds(pl.multiple_of(at // _LANES * _LANES, _LANES),
                           _LANES)
            lane = jax.lax.broadcasted_iota(jnp.int32, lanes_ref.shape, 1)
            new = jnp.where(lane == i % _LANES,
                            new_ref[...].astype(jnp.float32), 0.0)
            lanes = jnp.where(
                lane == at % _LANES,
                new.sum(axis=1, keepdims=True).astype(lanes_ref.dtype),
                buf[:, around])
            buf[:, around] = lanes
            lanes_ref[...] = lanes
        c = buf[0:rank, :]                                 # [rank, tile]
        s = jax.lax.dot_general(
            ql_ref[...], c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(
            qr_ref[...], buf[rank:, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        s *= sm_scale                                      # [heads, tile]
        if last:
            k_pos = (v - begin) * tile + jax.lax.broadcasted_iota(
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

    jax.lax.fori_loop(begin, end - 1,
                      lambda v, _: visit(v, last=False), None)
    visit(end - 1, last=True)
    o_ref[...] = (acc_ref[...] / l_ref[:, 0:1]).astype(o_ref.dtype)


def mla_decode(q_latent, q_rope, new, latent, layer, pos, sm_scale,
               block_k=None):
    """Write each slot's new latent column, then all heads of every slot
    against the slot's latent up to and with it, read where it lies.

    q_latent [S, H, rank]: the queries absorbed into the latent space
    (`q_nope @ W_uk^T`); q_rope [S, H, rope]: their rotated part; new
    [S, rank + rope]: this step's `[c_kv ; k_rope]`; latent: the resident
    cache [L, S, rank + rope, T], T a multiple of 128; layer: a Python
    int; pos: int32 [S], each slot's position, inside [0, T).  Returns
    (softmax((q_latent . c_kv + q_rope . k_rope) * sm_scale) . c_kv over
    columns 0 .. pos, [S, H, rank] in q_latent's type, float32 scores and
    accumulation; the cache with slot s's column pos[s] of `layer`
    written and nothing else changed: it is aliased to the result, and
    only the 128 lanes around each column are written)."""
    s, h, rank = q_latent.shape
    rope = q_rope.shape[-1]
    width, t = rank + rope, latent.shape[-1]
    if latent.shape[1:] != (s, width, t):
        raise ValueError(f"the latent cache must be [L, {s}, {width}, T], "
                         f"got {latent.shape}")
    tile, part = mla_tiling(width, t, block_k)
    pos = jnp.asarray(pos, jnp.int32).reshape(s)
    first, slot = _visits(pos + 1, t, tile)
    # the slots' columns side by side, 128 slots a block: a slot's column
    # is one lane of its block
    blocks = -(-s // _LANES)
    columns = jnp.pad(new.astype(latent.dtype),
                      ((0, blocks * _LANES - s), (0, 0)))
    columns = columns.reshape(blocks, _LANES, width).swapaxes(1, 2)

    def per_slot(i, *_):
        return i, 0, 0

    call = pl.pallas_call(
        functools.partial(_mla_kernel, layer=layer,
                          sm_scale=float(sm_scale), tile=tile, part=part,
                          rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s,),
            in_specs=[
                _vmem_spec((None, h, rank), per_slot),
                _vmem_spec((None, h, rope), per_slot),
                _vmem_spec((None, width, _LANES),
                           lambda i, *_: (i // _LANES, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                _vmem_spec((None, h, rank), per_slot),
                _vmem_spec((None, None, width, _LANES),
                           lambda i, lens, *_:
                           (layer, i, 0, (lens[i] - 1) // _LANES))],
            scratch_shapes=[
                pltpu.VMEM((_BUFFERS, width, tile), latent.dtype),
                pltpu.SemaphoreType.DMA((_BUFFERS, tile // part)),
                _scratch((h, rank)), _scratch((h, _LANES)),
                _scratch((h, _LANES))]),
        out_shape=[jax.ShapeDtypeStruct((s, h, rank), q_latent.dtype),
                   jax.ShapeDtypeStruct(latent.shape, latent.dtype)],
        # operand numbers count the scalar-prefetch arguments
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret(),
        name="mla_decode",
    )
    with jax.named_scope("mla_decode"):
        o, latent = call(pos + 1, first, slot, q_latent,
                         q_rope.astype(q_latent.dtype), columns, latent)
    return o, latent
