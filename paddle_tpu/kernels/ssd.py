"""The Mamba-2 state-space recurrence (SSD) over a resident state:
`ssd_decode` and `ssd_prefill` (Pallas on the TPU, the same mathematics
in XLA elsewhere, one dispatch predicate).

The layer (Dao, Gu, "Transformers are SSMs", arXiv:2405.21060), for head
h of `heads`, which reads the B and C of group g = h // (heads / groups),
with dt_t >= 0 and A_h < 0 a scalar each:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T      S: [head_dim, state]
    y_t = S_t C_t                                     (the D x_t term and
                                                       the gate are the
                                                       model's)

and in the chunked form a prefill walks (chunks of Q positions, b the
running sum of dt A inside a chunk):

    y_t = sum_{s <= t in the chunk} (C_t . B_s) exp(b_t - b_s) dt_s x_s
          + exp(b_t) S_before C_t
    S_after = exp(b_last) S_before + sum_s exp(b_last - b_s) dt_s x_s B_s^T

**The layout.**  The state of a layer and slot is `[heads / pack x
state, pack x head_dim]` float32: `pack` heads of one group side by side
on a row of lanes (`ssd_tiling`: 2 heads of 64 fill 128 lanes), the
state's index down the sublanes, so entry `[k state + n, i head_dim + p]`
is S[head k pack + i][p, n].  A decode step's update is then the row of
`dt x` of the pack's heads across the lanes times their group's B down
the sublanes, its answer a sum down the sublanes against C, and no head
of 64 lanes is padded to 128.  The resident array is `[layers, slots,
heads / pack x state, pack x head_dim]`, written in place: aliased input
to output, a decode step through BlockSpecs whose index names, for a
slot that is not active, the block visited last again (no copy in, none
out), a prefill into the slot's own region, which it holds in VMEM from
the prompt's first chunk to its last.

`ssd_tiling` is the only place a tile or a chunk is chosen.
"""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend
from .flash_attention import _LANES, _vmem_spec

__all__ = ["SsdTiling", "ssd_tiling", "ssd_recurrent", "pack_state",
           "unpack_state", "ssd_decode", "ssd_prefill"]

_HIGHEST = jax.lax.Precision.HIGHEST
_SUBLANES = 8
# a decode grid step's state tile, in bytes, at most
_DECODE_TILE_BYTES = 1 << 20


class SsdTiling(NamedTuple):
    """How the two kernels lay out and walk a state."""
    pack: int          # heads side by side on a row of lanes
    block_heads: int   # heads of a decode grid step
    chunk: int         # positions of a prefill grid step


def ssd_tiling(heads, head_dim, state, groups, chunk=None):
    """The layout and tiling for a shape.

    `pack`: the heads of one group that fill a row of 128 lanes (2 at a
    head_dim of 64), 1 where the head_dim does not divide the lanes.
    `block_heads`: a decode grid step's heads, whole groups, the most
    whose float32 state is at most 1 MiB (32 heads of 64 x 128: 4
    groups; in and out, double-buffered, 4 MiB of VMEM).  `chunk`: the
    model's chunk (Mamba-2's `chunk_size`), or 128."""
    group = heads // groups
    pack = math.gcd(group, _LANES // head_dim) \
        if _LANES % head_dim == 0 else 1
    fits = [g * group for g in range(1, groups + 1)
            if groups % g == 0
            and g * group * head_dim * state * 4 <= _DECODE_TILE_BYTES]
    return SsdTiling(pack, max(fits or [group]), chunk or 128)


def _takes_kernel(heads, head_dim, state, groups, use_kernel=None):
    """The one predicate that picks the Pallas kernels over the XLA
    mathematics: a row of packed heads that is 128 lanes, a state of
    whole sublane tiles and, unless `use_kernel` says otherwise, a
    TPU."""
    pack = ssd_tiling(heads, head_dim, state, groups).pack
    if pack * head_dim != _LANES or state % _SUBLANES:
        return False
    if use_kernel is None:
        use_kernel = backend.is_tpu_backend()
    return bool(use_kernel)


def pack_state(s, pack):
    """[..., heads, head_dim, state] -> the resident layout [...,
    heads / pack x state, pack x head_dim]."""
    *lead, h, p, n = s.shape
    s = s.reshape(*lead, h // pack, pack, p, n)
    s = jnp.moveaxis(s, -1, -3)                      # [.., h/pack, n, pack, p]
    return s.reshape(*lead, h // pack * n, pack * p)


def unpack_state(s, heads, pack):
    """The resident layout -> [..., heads, head_dim, state]."""
    *lead, rows, lanes = s.shape
    n, p = rows // (heads // pack), lanes // pack
    s = s.reshape(*lead, heads // pack, n, pack, p)
    s = jnp.moveaxis(s, -3, -1)                      # [.., h/pack, pack, p, n]
    return s.reshape(*lead, heads, p, n)


def _by_head(z, heads):
    """[..., groups, n] -> [..., heads, n]: each head its group's."""
    return jnp.repeat(z, heads // z.shape[-2], axis=-2)


def ssd_recurrent(x, dt, a, b, c):
    """The recurrence as written, a position at a time, float32: x [T,
    H, P], dt [T, H], a [H] (negative), b, c [T, G, N], from a zero
    state.  Returns (y [T, H, P], the last state [H, P, N]).  What the
    kernels and the chunked form are held against."""
    f32 = jnp.float32
    t, h, p = x.shape
    n = b.shape[-1]

    def step(s, xs):
        x, dt, b, c = xs
        s = jnp.exp(dt * a)[:, None, None] * s \
            + (dt[:, None] * x)[:, :, None] * _by_head(b, h)[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, _by_head(c, h),
                             precision=_HIGHEST)

    s, y = jax.lax.scan(step, jnp.zeros((h, p, n), f32), tuple(
        z.astype(f32) for z in (x, dt, b, c)))
    return y, s


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _decode_xla(x, dt, a, b, c, state, layer, active, pack):
    f32 = jnp.float32
    s, h, _ = x.shape
    old = state[layer]
    st = unpack_state(old, h, pack)
    dt = dt.astype(f32)
    new = jnp.exp(dt * a)[..., None, None] * st \
        + (dt[..., None] * x.astype(f32))[..., None] \
        * _by_head(b.astype(f32), h)[:, :, None, :]
    y = jnp.einsum("shpn,shn->shp", new, _by_head(c.astype(f32), h),
                   precision=_HIGHEST)
    live = active[:, None, None]
    state = state.at[layer].set(jnp.where(live, pack_state(new, pack), old))
    return jnp.where(live, y, 0.0), state


def _decode_kernel(layer_ref, mode_ref, src_ref, ua_ref, bc_ref, s_ref,
                   y_ref, s_out, *, groups_a_block, rows_a_group, n):
    del layer_ref, src_ref
    i, j = pl.program_id(0), pl.program_id(1)
    live = mode_ref[i] == 0
    lanes = s_ref.shape[-1]

    @pl.when(jnp.logical_and(jnp.logical_and(i == 0, j == 0),
                             jnp.logical_not(live)))
    def _pass_along():
        # the block this step names is written back whatever happens: it
        # goes back as it came
        s_out[...] = s_ref[...]

    def down_sublanes(row):
        """[1, n] -> [n, lanes], entry [e, l] = row[e]."""
        return jnp.broadcast_to(row, (lanes, n)).T

    @pl.when(live)
    def _step():
        for gi in range(groups_a_block):
            b = down_sublanes(bc_ref[gi:gi + 1, :])
            c = down_sublanes(bc_ref[groups_a_block + gi:
                                     groups_a_block + gi + 1, :])
            for k in range(gi * rows_a_group, (gi + 1) * rows_a_group):
                rows, cols = slice(k * n, (k + 1) * n), \
                    slice(k * lanes, (k + 1) * lanes)
                new = ua_ref[1:2, cols] * s_ref[rows, :] \
                    + b * ua_ref[0:1, cols]
                s_out[rows, :] = new
                y_ref[:, cols] = jnp.sum(new * c, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnums=(8,), inline=True)
def _decode_call(layer, mode, src, x, dt, a, b, c, tiling, state):
    # jitted so that the layers share one trace of the kernel (the layer
    # is data), inlined so that the caller's program holds the call
    # itself, aliases and all
    f32 = jnp.float32
    s, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    group = h // g
    hb = tiling.block_heads
    blocks, gb = h // hb, hb // group
    rows_a_block, lanes = hb // tiling.pack * n, tiling.pack * p
    dt = dt.astype(f32)
    # a slot's row of dt x and its row of exp(dt A), head by head
    ua = jnp.stack([(dt[..., None] * x.astype(f32)).reshape(s, h * p),
                    jnp.repeat(jnp.exp(dt * a), p, axis=1)], axis=1)
    # B of the block's groups, then C of them
    bc = jnp.concatenate([b.astype(f32).reshape(s, blocks, gb, n),
                          c.astype(f32).reshape(s, blocks, gb, n)], axis=2)

    def s_map(i, j, layer, mode, src):
        m = mode[i]
        return (layer[0], src[i],
                jnp.where(m == 0, j, jnp.where(m == 1, blocks - 1, 0)), 0)

    s_spec = _vmem_spec((None, None, rows_a_block, lanes), s_map)
    call = pl.pallas_call(
        functools.partial(_decode_kernel, groups_a_block=gb,
                          rows_a_group=group // tiling.pack, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s, blocks),
            in_specs=[
                _vmem_spec((None, 2, hb * p), lambda i, j, *_: (i, 0, j)),
                _vmem_spec((None, None, 2 * gb, n),
                           lambda i, j, *_: (i, j, 0, 0)),
                s_spec],
            out_specs=[
                _vmem_spec((None, 1, hb * p), lambda i, j, *_: (i, 0, j)),
                s_spec]),
        out_shape=[jax.ShapeDtypeStruct((s, 1, h * p), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand numbers count the scalar-prefetch arguments
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=backend.interpret(),
        name="ssd_decode",
    )
    with jax.named_scope("ssd_decode"):
        y, state = call(layer, mode, src, ua, bc, state)
    return y.reshape(s, h, p), state


def ssd_decode(x, dt, a, b, c, state, layer, active, use_kernel=None):
    """One position of every active slot: decay the slot's state by
    exp(dt A), add dt x B^T, and answer S C.

    x [S, H, P]; dt float32 [S, H] (after the softplus); a float32 [H]
    (negative); b, c [S, G, N]; state float32, the resident array
    [L, S, H / pack x N, pack x P] (`pack_state`); layer: int32 scalar,
    traced or not; active bool [S].  Returns (y float32 [S, H, P], the D
    term and the gate left to the caller; state): layer `layer` of the
    active slots advanced by one position, everything else as it was (a
    slot that is not active answers 0).  All of it float32 on the vector
    unit, about 5 operations for the 8 bytes of a state's entry read and
    written.  On the kernel's path the state is aliased to the result and
    a slot that is not active is neither fetched nor written."""
    s, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    tiling = ssd_tiling(h, p, n, g)
    if not _takes_kernel(h, p, n, g, use_kernel):
        return _decode_xla(x, dt, a, b, c, state, layer, active,
                           tiling.pack)
    # a slot that is not active names the block visited last again (the
    # last of the active slot before it), or, before any active slot,
    # the first block of the first one (slot 0's where none is active)
    idx = jnp.arange(s, dtype=jnp.int32)
    act = active.astype(bool)
    before = jax.lax.cummax(jnp.where(act, idx, -1))
    first = jnp.argmax(act).astype(jnp.int32)
    mode = jnp.where(act, 0, jnp.where(before >= 0, 1, 2)).astype(jnp.int32)
    src = jnp.where(act, idx, jnp.where(before >= 0, before, first))
    return _decode_call(jnp.asarray(layer, jnp.int32).reshape(1), mode,
                        src.astype(jnp.int32), x, dt, a, b, c, tiling, state)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _masked(dt, true_len):
    """Positions at or past `true_len` get dt = 0: no decay and no
    input, so they leave every state as it is."""
    valid = jnp.arange(dt.shape[0]) < true_len
    return jnp.where(valid[:, None], dt.astype(jnp.float32), 0.0)


def _prefill_xla(x, dt, a, b, c, chunk):
    """The chunked form over one prompt (dt already masked): (y [T, H,
    P] float32, the last state [H, P, N])."""
    f32 = jnp.float32
    t, h, p = x.shape
    n, q = b.shape[-1], chunk
    m = t // q
    u = (dt[..., None] * x.astype(f32)).reshape(m, q, h, p)
    bh = _by_head(b.astype(f32), h).reshape(m, q, h, n)
    ch = _by_head(c.astype(f32), h).reshape(m, q, h, n)
    cum = jnp.cumsum((dt * a).reshape(m, q, h), axis=1)
    seen = jnp.tril(jnp.ones((q, q), bool))

    def one(st, xs):
        u, b, c, cum = xs
        decay = jnp.where(seen[None], jnp.exp(jnp.minimum(
            cum.T[:, :, None] - cum.T[:, None, :], 0.0)), 0.0)  # [h, t, s]
        cb = jnp.einsum("thn,shn->hts", c, b, precision=_HIGHEST)
        y = jnp.einsum("hts,shp->thp", cb * decay, u, precision=_HIGHEST) \
            + jnp.exp(cum)[..., None] * jnp.einsum(
                "thn,hpn->thp", c, st, precision=_HIGHEST)
        left = jnp.exp(cum[-1][None] - cum)                   # [s, h]
        st = jnp.exp(cum[-1])[:, None, None] * st + jnp.einsum(
            "shp,shn->hpn", u * left[..., None], b, precision=_HIGHEST)
        return st, y

    st, y = jax.lax.scan(one, jnp.zeros((h, p, n), f32), (u, bh, ch, cum))
    return y.reshape(t, h, p), st


def _prefill_kernel(layer_ref, slot_ref, u_ref, b_ref, c_ref, bq_ref, bk_ref,
                    s_any, y_ref, s_out, *, group, pack, head_dim, chunk):
    del layer_ref, slot_ref, s_any
    f32 = jnp.float32
    n = b_ref.shape[-1]
    lanes = pack * head_dim

    @pl.when(pl.program_id(1) == 0)
    def _fresh():
        # the slot's state is this prompt's alone
        s_out[...] = jnp.zeros_like(s_out)

    def mm(lhs, rhs, contract):
        return jax.lax.dot_general(
            lhs, rhs, (((contract[0],), (contract[1],)), ((), ())),
            precision=_HIGHEST, preferred_element_type=f32)

    b, c = b_ref[...].astype(f32), c_ref[...].astype(f32)     # [Q, N]
    cb = mm(c, b, (1, 1))                                     # [Q, Q]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    head_of = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // head_dim
    for k in range(group // pack):
        cols = slice(k * lanes, (k + 1) * lanes)
        u = u_ref[:, cols]                                    # [Q, lanes]
        weights, rhs = [], []
        at_t = jnp.zeros((chunk, lanes), f32)       # exp(b_t), lane's head
        to_end = jnp.zeros((chunk, lanes), f32)     # exp(b_last - b_t)
        whole = jnp.zeros((1, lanes), f32)          # exp(b_last)
        for i in range(pack):
            hh = k * pack + i
            bcol = bq_ref[:, hh:hh + 1]                       # [Q, 1]
            brow = bk_ref[hh:hh + 1, :]                       # [1, Q]
            last = brow[:, chunk - 1:chunk]                   # [1, 1]
            weights.append(jnp.where(
                col <= row, cb * jnp.exp(jnp.minimum(bcol - brow, 0.0)), 0.0))
            mine = head_of == i
            rhs.append(jnp.where(mine, u, 0.0))
            at_t = jnp.where(mine, jnp.exp(bcol), at_t)
            to_end = jnp.where(mine, jnp.exp(last - bcol), to_end)
            whole = jnp.where(mine, jnp.exp(last), whole)
        rows = slice(k * n, (k + 1) * n)
        st = s_out[rows, :]                                   # [N, lanes]
        # inside the chunk, every head of the row in one product against
        # its own lanes; against the state of the chunks before
        y_ref[:, cols] = mm(jnp.concatenate(weights, axis=1),
                            jnp.concatenate(rhs, axis=0), (1, 0)) \
            + at_t * mm(c, st, (1, 0))
        s_out[rows, :] = whole * st + mm(b, u * to_end, (0, 0))


@functools.partial(jax.jit, static_argnums=(8,), inline=True)
def _prefill_call(layer, slot, x, dt, a, b, c, state, tiling):
    f32 = jnp.float32
    t, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    group, pack, q = h // g, tiling.pack, tiling.chunk
    m = t // q
    u = (dt[..., None] * x.astype(f32)).reshape(t, h * p)
    cum = jnp.cumsum((dt * a).reshape(m, q, h), axis=1)
    bq = cum.reshape(t, g, group).transpose(1, 0, 2)              # [G, T, gp]
    bk = cum.reshape(m, q, g, group).transpose(2, 3, 0, 1).reshape(
        g, group, t)                                              # [G, gp, T]
    call = pl.pallas_call(
        functools.partial(_prefill_kernel, group=group, pack=pack,
                          head_dim=p, chunk=q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(g, m),
            in_specs=[
                _vmem_spec((q, group * p), lambda j, i, *_: (i, j)),
                _vmem_spec((q, n), lambda j, i, *_: (i, j)),
                _vmem_spec((q, n), lambda j, i, *_: (i, j)),
                _vmem_spec((None, q, group), lambda j, i, *_: (j, i, 0)),
                _vmem_spec((None, group, q), lambda j, i, *_: (j, 0, i)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                _vmem_spec((q, group * p), lambda j, i, *_: (i, j)),
                _vmem_spec((None, None, group // pack * n, pack * p),
                           lambda j, i, layer, slot:
                           (layer[0], slot[0], j, 0))]),
        out_shape=[jax.ShapeDtypeStruct((t, h * p), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand numbers count the scalar-prefetch arguments
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=backend.interpret(),
        name="ssd_prefill",
    )
    with jax.named_scope("ssd_prefill"):
        y, state = call(layer, slot, u, b.reshape(t, g * n),
                        c.reshape(t, g * n), bq, bk, state)
    return y.reshape(t, h, p), state


def ssd_prefill(x, dt, a, b, c, true_len, state, layer, slot, chunk=None,
                use_kernel=None):
    """One prompt into one slot, chunk by chunk.

    x [T, H, P]; dt float32 [T, H] (after the softplus); a float32 [H];
    b, c [T, G, N]: a bucket's shape, of which the first `true_len`
    positions are the prompt; state the resident array (`ssd_decode`);
    layer, slot: int32 scalars, traced or not.  Returns (y float32
    [T, H, P], rows at or past `true_len` of no meaning; state): the
    slot's state of `layer` is that of position `true_len - 1` and
    replaces whatever the slot held; the bucket's padding does not touch
    it (dt = 0 there).  On the kernel's path a group's state lives in
    VMEM from the first chunk to the last and goes to the slot's region
    of the aliased array once; every product takes float32 operands and
    sums in float32."""
    t, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    tiling = ssd_tiling(h, p, n, g, chunk=chunk)
    if t % tiling.chunk:
        raise ValueError(f"chunk {tiling.chunk} does not divide bucket {t}")
    dt = _masked(dt, true_len)
    a = a.astype(jnp.float32)
    if _takes_kernel(h, p, n, g, use_kernel):
        i32 = jnp.int32
        return _prefill_call(jnp.asarray(layer, i32).reshape(1),
                             jnp.asarray(slot, i32).reshape(1),
                             x, dt, a, b, c, state, tiling)
    y, st = _prefill_xla(x, dt, a, b, c, tiling.chunk)
    zero = jnp.zeros((), jnp.int32)
    state = jax.lax.dynamic_update_slice(
        state, pack_state(st, tiling.pack)[None, None].astype(state.dtype),
        (jnp.asarray(layer, jnp.int32), jnp.asarray(slot, jnp.int32),
         zero, zero))
    return y, state
