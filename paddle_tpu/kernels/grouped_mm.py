"""Pallas TPU kernel for the routed experts' grouped product:
`moe_grouped_mm`.

`rows [M, K]` are sorted by group and group `g` owns the next
`counts[g]` of them; the product is `rows[of g] @ weights[g]` for every
group, `[M, N]`.  Rows past `sum(counts)` belong to no group and their
part of the result is never written.

The expert layer of a served decoder (distributed/moe.py
`routed_experts`) makes few rows a group: a decode step of 256 slots
leaves about five on each held expert, so the product is bound by
reading each expert's weights once.  `jax.lax.ragged_dot` as XLA lowers
it for the v5e reads them at a third of the chip's bandwidth (2.41 ms
for 12 experts of 7168 x 4096 where 0.86 ms is the floor); this kernel
follows the megablox design (jax.experimental.pallas.ops.tpu.megablox)
and reads them at four fifths (1.05 ms):

- the grid is (N tiles, visits, K tiles).  A visit is one (row tile,
  group) pair that holds rows: a row tile shared by several groups is
  visited once for each, a group without rows is never visited (its
  weights are not read), and the number of visits is a traced scalar,
  at most `M / tm + groups - 1`;
- the visits' row tile and group come from scalar-prefetched tables, so
  the index maps fetch the group's `[tk, tn]` weight tile and the row
  tile's `[tm, tk]` rows; the float32 accumulator lives in VMEM across
  the K tiles, and the last K tile stores the rows of the row tile that
  belong to the visit's group and leaves the others as they are.
"""

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret
from .flash_attention import _LANES, _scratch, _vmem_spec

ROW_TILE = 128


def _tile(n, largest):
    """The largest power-of-two multiple of 128, at most `largest`, that
    divides n; where that is 128 alone (n / 128 odd: nemotron_h's 2,688
    and its experts' 1,920), the largest multiple of 128 at most
    `largest` that divides n, so that a weight's tile is not a sliver of
    128 x 128; None where 128 does not divide n."""
    t = largest
    while t >= _LANES:
        if n % t == 0:
            break
        t //= 2
    if t < _LANES:
        return None
    if t == _LANES:
        t = max(c for c in range(_LANES, largest + 1, _LANES) if n % c == 0)
    return t


_REFUSED = set()


def refusal(m, k, n):
    """Why `moe_grouped_mm` does not tile rows [m, k] against weights
    [G, k, n], or None where it does: whole row tiles and whole lanes."""
    why = [f"{name} {v} is not a multiple of {unit}" for name, v, unit in
           (("rows", m, ROW_TILE), ("K", k, _LANES), ("N", n, _LANES))
           if v % unit]
    return "; ".join(why) or None


def takes_kernel(m, k, n):
    """Shapes `moe_grouped_mm` tiles: whole row tiles and whole lanes.
    A shape it refuses is logged once, with the reason (a model whose
    expert width is not whole lanes pads its stored experts, as
    models/nemotron_h.py does)."""
    why = refusal(m, k, n)
    if why is not None and (m, k, n) not in _REFUSED:
        _REFUSED.add((m, k, n))
        logging.getLogger(__name__).info(
            "moe_grouped_mm refuses [%d, %d] x [G, %d, %d] (%s): "
            "jax.lax.ragged_dot instead", m, k, k, n, why)
    return why is None


def _visits(counts, m, tm):
    """counts int32 [G] -> (group of each visit, row tile of each visit,
    first row of each group, row past each group, number of visits):
    group g visits the row tiles that hold its rows [start, end)."""
    g = counts.shape[0]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = starts // tm
    tiles = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    tile_ends = jnp.cumsum(tiles)
    v = jnp.arange(m // tm + g - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(tile_ends, v, side="right"), g - 1).astype(jnp.int32)
    tile = first[group] + v - (tile_ends - tiles)[group]
    # entries past the last visit are never run; keep them in range
    tile = jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32)
    return (group, tile, starts.astype(jnp.int32), ends.astype(jnp.int32),
            tile_ends[-1].astype(jnp.int32))


def _kernel(group_ref, tile_ref, start_ref, end_ref, x_ref, w_ref, o_ref,
            acc_ref, *, tm):
    v, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _store():
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= start_ref[g]) & (row < end_ref[g])
        o_ref[...] = jnp.where(mine, acc_ref[...],
                               o_ref[...].astype(jnp.float32)).astype(
                                   o_ref.dtype)


def moe_grouped_mm(rows, weights, counts, out_dtype=jnp.float32):
    """rows [M, K] sorted by group, weights [G, K, N], counts int32 [G]
    (sum at most M) -> [M, N] in `out_dtype`, float32 accumulation: row
    r of group g is `rows[r] @ weights[g]`; rows past the groups' are
    left unwritten (whatever the buffer held)."""
    m, k = rows.shape
    g, _, n = weights.shape
    if weights.shape[1] != k or not takes_kernel(m, k, n):
        raise ValueError(f"moe_grouped_mm takes rows [M, K] with M a "
                         f"multiple of {ROW_TILE} and weights [G, K, N] "
                         f"with K and N multiples of {_LANES}; got "
                         f"{rows.shape} and {weights.shape}")
    tm, tk, tn = ROW_TILE, _tile(k, 1024), _tile(n, 2048)
    group, tile, starts, ends, visits = _visits(
        jnp.asarray(counts, jnp.int32), m, tm)

    call = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, visits, k // tk),
            in_specs=[
                _vmem_spec((tm, tk),
                           lambda ni, v, ki, grp, til, st, en: (til[v], ki)),
                _vmem_spec((None, tk, tn),
                           lambda ni, v, ki, grp, til, st, en:
                           (grp[v], ki, ni))],
            out_specs=_vmem_spec(
                (tm, tn), lambda ni, v, ki, grp, til, st, en: (til[v], ni)),
            scratch_shapes=[_scratch((tm, tn))]),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret(),
        name="moe_grouped_mm",
    )
    with jax.named_scope("moe_grouped_mm"):
        return call(group, tile, starts, ends, rows,
                    weights.astype(rows.dtype))
