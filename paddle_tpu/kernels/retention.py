"""Gated power retention of degree 2 over a resident recurrent state:
`retention_decode` and `retention_prefill` (Pallas on the TPU, the same
mathematics in XLA elsewhere, one dispatch predicate).

The layer (Buckman, Gelada, Zhang, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239), for query head i reading K/V head
j = i // group, with `G` the running sum of head j's `log g`:

    attention form   a[t, s] = (q_t . k_s)^2 exp(G_t - G_s),  s <= t
                     o_t = sum_s a[t, s] v_s / sum_s a[t, s]
    recurrent form   S_t = g_t S_{t-1} + phi(k_t) v_t^T,
                     z_t = g_t z_{t-1} + phi(k_t),
                     o_t = phi(q_t)^T S_t / phi(q_t)^T z_t

with `phi(q) . phi(k) = (q . k)^2`: the monomials `w_a w_b`, a <= b,
those with a < b weighing 2 between the two sides.

**The layout of phi.**  The monomials are laid out by the distance
between their lanes: row r of `phi(w)` is `w * roll(w, r)`, lane a
holding `w_a w_(a - r mod d)`, for r = 0 .. d / 2, and a pair's weight is
all on the key's side (`phi_k` = c_r x that, `phi_q` bare: the product is
what the published map's sqrt 2 on either side gives).  Row 0 holds the
squares (c = 1); rows 1 .. d / 2 - 1 hold each pair of that distance
once (c = 2); row d / 2 holds each of its pairs twice (c = 1, the two
halves carrying the pair's 2 between them).  That is the d (d + 1) / 2
monomials of the published map, the d / 2 pairs of the last row stored
twice: d / 2 + 1 rows of d lanes (65 x 128 = 8,320 for 8,256 at d = 128,
0.8% more), and the same function.  Why: a row is one lane rotation and
one product of whole vector registers, built in VMEM from q and k, and
never a gather; a triangle packed tightly has no such form.

**The state**, per layer, slot and K/V head: `state` float32 `[d, rows
d]`, entry `[e, r d + a]` the sum over positions of (decay) `v_e
phi_k[r, a]`: the value's lane e on the sublanes, the monomials on the
lanes, so that a decode step's update is `v` down the sublanes times
`phi_k` across the lanes (two broadcasts), and a run of rows is a run of
lanes, which a prefill contracts in one product.  And `norm` float32 `[d,
d]`: the divisor's state `z` as the matrix `sum (decay) k k^T`, so that
`phi(q)^T z = q^T norm q` is one small product and no walk over `phi`
(each pair twice, as a symmetric matrix holds it: 16,384 for 8,256, of
a state 129 times that).  Both arrays are `[layers, slots, kv_heads,
...]` and are written in place: aliased input to output, a decode step
through BlockSpecs whose index names, for a slot that is not active, the
block that was last visited again (no copy in, none out), a prefill into
the slot's own region, which it holds in VMEM from the prompt's first
chunk to its last.

`retention_tiling` is the only place a tile or a chunk is chosen.
"""

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend
from .flash_attention import _LANES, _scratch, _vmem_spec

__all__ = ["phi_q", "phi_k", "phi_rows", "recurrent_form",
           "retention_tiling", "retention_decode", "retention_prefill"]

# under this a divisor is no divisor: a row that saw no key (a bucket's
# padding before any true position) answers 0, not 0 / 0
_TINY = 1e-30
_HIGHEST = jax.lax.Precision.HIGHEST
_SUBLANES = 8


def _mm(a, b, contract, operands):
    """a . b over `contract` (an axis of each), accumulated in float32:
    `operands` "float32" multiplies the float32 values as they are (the
    MXU's passes of a full-precision product), "bfloat16" rounds both
    sides first (one pass)."""
    if operands == "bfloat16":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        precision = None
    else:
        precision = _HIGHEST
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


def phi_rows(head_dim):
    return head_dim // 2 + 1


def _pair_weight(rows):
    """c_r: what a pair of lanes r apart weighs in `phi_k`."""
    c = [2.0] * rows
    c[0] = c[-1] = 1.0
    return c


def _rolled(w, weights):
    w = w.astype(jnp.float32)
    out = jnp.stack([c * w * jnp.roll(w, r, axis=-1)
                     for r, c in enumerate(weights)], axis=-2)
    return out.reshape(w.shape[:-1] + (-1,))


def phi_q(w):
    """w [..., d] -> float32 [..., rows d], the layout above."""
    return _rolled(w, [1.0] * phi_rows(w.shape[-1]))


def phi_k(w):
    """As `phi_q`, each row times its pairs' weight."""
    return _rolled(w, _pair_weight(phi_rows(w.shape[-1])))


def recurrent_form(q, k, v, log_g):
    """The recurrent form as written, a position at a time, float32: q
    [T, H, d], k, v [T, KVH, d], log_g [T, KVH] -> (o [T, H, d], the
    last state [KVH, d, rows d], the last norm [KVH, d, d]).  What the
    kernels and the chunked form are held against."""
    t, h, d = q.shape
    kvh = k.shape[1]
    f32 = jnp.float32

    def step(carry, x):
        st, m = carry
        q, k, v, log_g = x
        k, g = k.astype(f32), jnp.exp(log_g.astype(f32))[:, None, None]
        st = g * st + v.astype(f32)[:, :, None] * phi_k(k)[:, None, :]
        m = g * m + k[:, :, None] * k[:, None, :]
        q = q.astype(f32).reshape(kvh, h // kvh, d)
        o = jnp.einsum("jgD,jeD->jge", phi_q(q), st, precision=_HIGHEST) \
            / jnp.einsum("jga,jab,jgb->jg", q, m, q,
                         precision=_HIGHEST)[..., None]
        return (st, m), o.reshape(h, d)

    init = (jnp.zeros((kvh, d, phi_rows(d) * d), f32),
            jnp.zeros((kvh, d, d), f32))
    (st, m), o = jax.lax.scan(step, init, (q, k, v, log_g))
    return o, st, m


class RetentionTiling(NamedTuple):
    """How the two kernels walk a state of `rows` rows of phi."""
    tile_rows: int    # rows of a decode step's grid step
    scan_rows: int    # rows a prefill contracts in one product
    chunk: int        # positions of a prefill's grid step


def retention_tiling(head_dim, bucket=None, tile_rows=None, chunk=None):
    """The tiling for a head size and, for a prefill, a bucket.

    A decode step moves a K/V head's state `[d, rows d]` in tiles of
    `tile_rows` rows of phi, the largest divisor of `rows` whose float32
    tile is at most 1 MiB (13 of 65 rows at d = 128: 832 KiB, which in
    and out and double-buffered is 3.3 MiB of VMEM).

    A prefill walks its prompt in chunks: inside a chunk the attention
    form, across chunks the state.  Position t of a chunk costs a query
    head `4 t d` operations in the attention form and `2 rows d d`
    against a state, equal at t = rows d / 2 (4,160 at d = 128), so the
    chunk lies well under that: 256, where the square a chunk computes
    (half of it masked) is 6% of its work against the state and a chunk
    of 5 x 256 query rows fills the MXU's rows; the largest power of two
    no more than that which divides the bucket.  Against the state it
    contracts `scan_rows` rows of phi in one product (the smallest
    divisor of `rows` over 4: 5 of 65, a contraction 640 deep), so that
    a chunk's result is summed inside the MXU and not through VMEM."""
    rows = phi_rows(head_dim)
    divisors = [r for r in range(1, rows + 1) if rows % r == 0]
    if tile_rows is None:
        tile_rows = max(r for r in divisors
                        if r * head_dim * head_dim * 4 <= 1 << 20)
    if rows % tile_rows:
        raise ValueError(f"{tile_rows} rows a tile do not divide {rows}")
    if chunk is None and bucket is not None:
        chunk = 256
        while chunk > 1 and bucket % chunk:
            chunk //= 2
    if bucket is not None and bucket % chunk:
        raise ValueError(f"chunk {chunk} does not divide bucket {bucket}")
    scan_rows = min([r for r in divisors if r > 4] or [rows])
    return RetentionTiling(tile_rows, scan_rows, chunk)


def _takes_kernel(head_dim, group, use_kernel=None, chunk=None):
    """The one predicate that picks the Pallas kernels over the XLA
    mathematics: heads of 128 lanes, a group of query heads that fits
    a register's sublanes beside k, v and the gate, on the TPU a chunk
    of whole lanes; and, unless `use_kernel` or
    PADDLE_TPU_FORCE_RETENTION says otherwise, a TPU."""
    if head_dim != _LANES or group + 3 > _SUBLANES:
        return False
    if use_kernel is None:
        env = os.environ.get("PADDLE_TPU_FORCE_RETENTION", "")
        use_kernel = env.lower() in ("1", "true", "yes") if env \
            else backend.is_tpu_backend()
    if use_kernel and chunk is not None and backend.is_tpu_backend() \
            and chunk % _LANES:
        return False
    return bool(use_kernel)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _decode_xla(q, k, v, log_g, state, norm, layer, active):
    s, h, d = q.shape
    kvh = k.shape[1]
    f32 = jnp.float32
    q32, k32 = q.astype(f32).reshape(s, kvh, h // kvh, d), k.astype(f32)
    g = jnp.exp(log_g.astype(f32))[:, :, None, None]
    old_s, old_m = state[layer], norm[layer]
    new_s = g * old_s + v.astype(f32)[..., :, None] * phi_k(k32)[..., None, :]
    new_m = g * old_m + k32[..., :, None] * k32[..., None, :]
    num = jnp.einsum("sjgD,sjeD->sjge", phi_q(q32), new_s,
                     precision=_HIGHEST)
    den = jnp.einsum("sjga,sjab,sjgb->sjg", q32, new_m, q32,
                     precision=_HIGHEST)
    live = active[:, None, None, None]
    o = jnp.where(live, num / jnp.maximum(den, _TINY)[..., None], 0.0)
    state = state.at[layer].set(jnp.where(live, new_s, old_s))
    norm = norm.at[layer].set(jnp.where(live, new_m, old_m))
    return o.reshape(s, h, d).astype(q.dtype), state, norm


def _decode_kernel(mode_ref, src_ref, x_ref, s_ref, m_ref, o_ref, s_out,
                   m_out, phi_s, vb_s, num_s, den_s, *, group, rows,
                   tile_rows):
    del src_ref
    i, t = pl.program_id(0), pl.program_id(2)
    d = x_ref.shape[-1]
    f32 = jnp.float32
    k_row, v_row, g_row = group, group + 1, group + 2
    live = mode_ref[i] == 0
    first_step = jnp.logical_and(
        jnp.logical_and(i == 0, pl.program_id(1) == 0), t == 0)

    @pl.when(jnp.logical_and(first_step, jnp.logical_not(live)))
    def _pass_along():
        # the blocks this step names are written back whatever happens:
        # they go back as they came
        s_out[...] = s_ref[...]
        m_out[...] = m_ref[...]

    def down_sublanes(row):
        """[1, d] -> [d, d], entry [e, a] = row[e]."""
        return jnp.broadcast_to(row, (d, d)).T

    @pl.when(jnp.logical_and(live, t == 0))
    def _head():
        x = x_ref[...]          # rows: the query heads, k, v, g (all lanes)
        g, k = x[g_row:g_row + 1, :], x[k_row:k_row + 1, :]
        # the divisor: norm = g norm + k k^T, and q^T norm q of each head
        m = g * m_ref[...] + down_sublanes(k) * k
        m_out[...] = m
        den_s[...] = jnp.broadcast_to(
            (_mm(x, m, (1, 0), "float32") * x).sum(axis=1, keepdims=True),
            den_s.shape)
        vb_s[...] = down_sublanes(x[v_row:v_row + 1, :])
        num_s[...] = jnp.zeros_like(num_s)
        # phi of every row at once, each of its rows spread over a
        # register's sublanes: the query heads' and the key's
        for r, c in enumerate(_pair_weight(rows)):
            p = x * pltpu.roll(x, r, 1)
            for h in range(group):
                phi_s[r, h] = jnp.broadcast_to(p[h:h + 1, :], (_SUBLANES, d))
            phi_s[r, group] = jnp.broadcast_to(c * p[k_row:k_row + 1, :],
                                               (_SUBLANES, d))

    @pl.when(live)
    def _tile():
        g = x_ref[g_row:g_row + 1, :]
        for e0 in range(0, d, _SUBLANES):
            e = slice(e0, e0 + _SUBLANES)
            vb = vb_s[e, :]
            acc = [jnp.zeros((_SUBLANES, d), f32)] * group
            for j in range(tile_rows):
                a = slice(j * d, (j + 1) * d)
                r = t * tile_rows + j
                new = g * s_ref[e, a] + vb * phi_s[r, group]
                s_out[e, a] = new
                acc = [acc[h] + new * phi_s[r, h] for h in range(group)]
            for h in range(group):
                num_s[h, e, :] += acc[h]

    @pl.when(t == pl.num_programs(2) - 1)
    def _answer():
        o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(live)
        def _():
            for h in range(group):
                num = jnp.sum(num_s[h].T, axis=0, keepdims=True)   # [1, e]
                o_ref[h:h + 1, :] = num / jnp.maximum(
                    den_s[h:h + 1, :], _TINY)


def _decode_pallas(q, k, v, log_g, state, norm, layer, active, tile_rows):
    s, h, d = q.shape
    kvh = k.shape[1]
    group = h // kvh
    rows = phi_rows(d)
    tiles = rows // tile_rows
    f32 = jnp.float32
    # one block a (slot, K/V head): its query heads, k, v and the gate
    x = jnp.concatenate([
        q.reshape(s, kvh, group, d).astype(f32),
        k.astype(f32)[:, :, None], v.astype(f32)[:, :, None],
        jnp.broadcast_to(jnp.exp(log_g.astype(f32))[:, :, None, None],
                         (s, kvh, 1, d)),
        jnp.zeros((s, kvh, _SUBLANES - group - 3, d), f32)], axis=2)
    # a slot that is not active names the block visited last again (the
    # last of the active slot before it), or, before any active slot,
    # the first block of the first one (slot 0's where none is active)
    idx = jnp.arange(s, dtype=jnp.int32)
    act = active.astype(bool)
    before = jax.lax.cummax(jnp.where(act, idx, -1))
    first = jnp.argmax(act).astype(jnp.int32)
    mode = jnp.where(act, 0, jnp.where(before >= 0, 1, 2)).astype(jnp.int32)
    src = jnp.where(act, idx, jnp.where(before >= 0, before, first))

    def block(i, j, t, mode, src):
        m = mode[i]
        return (src[i], jnp.where(m == 0, j, jnp.where(m == 1, kvh - 1, 0)),
                jnp.where(m == 0, t, jnp.where(m == 1, tiles - 1, 0)))

    def s_map(i, j, t, mode, src):
        ss, jj, tt = block(i, j, t, mode, src)
        return layer, ss, jj, 0, tt

    def m_map(i, j, t, mode, src):
        ss, jj, _ = block(i, j, t, mode, src)
        return layer, ss, jj, 0, 0

    def x_map(i, j, t, *_):
        return i, j, 0, 0

    s_spec = _vmem_spec((None, None, None, d, tile_rows * d), s_map)
    m_spec = _vmem_spec((None, None, None, d, d), m_map)
    x_spec = _vmem_spec((None, None, _SUBLANES, d), x_map)
    call = pl.pallas_call(
        functools.partial(_decode_kernel, group=group, rows=rows,
                          tile_rows=tile_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, kvh, tiles),
            in_specs=[x_spec, s_spec, m_spec],
            out_specs=[x_spec, s_spec, m_spec],
            scratch_shapes=[
                _scratch((rows, group + 1, _SUBLANES, d)), _scratch((d, d)),
                _scratch((group, d, d)), _scratch((_SUBLANES, d))]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
        # operand numbers count the scalar-prefetch arguments
        input_output_aliases={3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=backend.interpret(),
        name="retention_decode",
    )
    with jax.named_scope("retention_decode"):
        o, state, norm = call(mode, src, x, state, norm)
    return o[:, :, :group].reshape(s, h, d).astype(q.dtype), state, norm


def retention_decode(q, k, v, log_g, state, norm, layer, active,
                     use_kernel=None, tile_rows=None):
    """One position of every active slot: decay the slot's state by its
    gates, add `phi(k) v^T`, and answer its query heads against the new
    state.

    q [S, H, d]; k, v [S, KVH, d]; log_g float32 [S, KVH]; state float32
    [L, S, KVH, d, rows d] and norm float32 [L, S, KVH, d, d], the
    resident arrays; layer: a Python int; active bool [S].  Returns (o
    [S, H, d] in q's type, state, norm): layer `layer` of the active
    slots advanced by one position, everything else as it was (a slot
    that is not active answers 0).  All of it float32 on the vector
    unit: 13 operations for the 8 bytes of a state's entry read and
    written.  On the kernel's path the arrays are aliased to the results
    and a slot that is not active is neither fetched nor written."""
    if not _takes_kernel(q.shape[-1], q.shape[1] // k.shape[1], use_kernel):
        return _decode_xla(q, k, v, log_g, state, norm, layer, active)
    tile_rows = retention_tiling(q.shape[-1], tile_rows=tile_rows).tile_rows
    return _decode_pallas(q, k, v, log_g, state, norm, layer, active,
                          tile_rows)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _masked(k, v, log_g, true_len):
    """Positions at or past `true_len` carry no key, no value and
    log g = 0: they leave every state as it is."""
    valid = jnp.arange(k.shape[0]) < true_len
    return (jnp.where(valid[:, None, None], k, 0),
            jnp.where(valid[:, None, None], v, 0),
            jnp.where(valid[:, None], log_g.astype(jnp.float32), 0.0))


def _prefill_xla(q, k, v, log_g, chunk):
    """The chunked form over one prompt (k, v, log_g already masked):
    (o [T, H, d] float32, the last state [KVH, d, rows d] and norm [KVH,
    d, d])."""
    t, h, d = q.shape
    kvh = k.shape[1]
    n = t // chunk
    f32 = jnp.float32
    qc = q.astype(f32).reshape(n, chunk, kvh, h // kvh, d)
    kc = k.astype(f32).reshape(n, chunk, kvh, d)
    vc = v.astype(f32).reshape(n, chunk, kvh, d)
    bc = jnp.cumsum(log_g.reshape(n, chunk, kvh), axis=1)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(carry, xs):
        st, m = carry
        q, k, v, b = xs
        sc = jnp.einsum("tjgd,sjd->jgts", q, k, precision=_HIGHEST)
        decay = jnp.exp(jnp.minimum(b.T[:, :, None] - b.T[:, None, :], 0.0))
        a = jnp.where(seen, sc * sc * decay[:, None], 0.0)    # [j, g, t, s]
        eb = jnp.exp(b).T[:, None, :]                          # [j, 1, t]
        num = jnp.einsum("jgts,sje->jgte", a, v, precision=_HIGHEST) \
            + eb[..., None] * jnp.einsum("tjgD,jeD->jgte", phi_q(q), st,
                                         precision=_HIGHEST)
        den = a.sum(axis=-1) + eb * jnp.einsum(
            "tjga,jab,tjgb->jgt", q, m, q, precision=_HIGHEST)
        o = num / jnp.maximum(den, _TINY)[..., None]
        last = jnp.exp(b[-1])[:, None, None]                   # [j, 1, 1]
        left = jnp.exp(b[-1][None] - b)[..., None]             # [s, j, 1]
        st = last * st + jnp.einsum(
            "sje,sjD->jeD", v * left, phi_k(k), precision=_HIGHEST)
        m = last * m + jnp.einsum(
            "sja,sjb->jab", k * left, k, precision=_HIGHEST)
        return (st, m), o.transpose(2, 0, 1, 3)                # [t, j, g, e]

    init = (jnp.zeros((kvh, d, phi_rows(d) * d), f32),
            jnp.zeros((kvh, d, d), f32))
    (st, m), o = jax.lax.scan(one, init, (qc, kc, vc, bc))
    return o.reshape(t, h, d), st, m


def _prefill_kernel(slot_ref, q_ref, k_ref, v_ref, bcol_ref, brow_ref,
                    last_ref, s_any, m_any, o_ref, s_out, m_out, nacc_s, *,
                    group, rows, scan_rows, chunk, operands):
    del slot_ref, s_any, m_any
    d = k_ref.shape[-1]
    f32 = jnp.float32
    wide = scan_rows * d

    @pl.when(pl.program_id(1) == 0)
    def _fresh():
        # the slot's state is this prompt's alone
        s_out[...] = jnp.zeros_like(s_out)
        m_out[...] = jnp.zeros_like(m_out)

    # the group's query heads one after the other down the rows
    q = jnp.concatenate([q_ref[:, i * d:(i + 1) * d] for i in range(group)],
                        axis=0)                                # [G C, d]
    k, v = k_ref[...], v_ref[...]
    bcol = bcol_ref[...]                                       # [C, 1]
    brow = brow_ref[...]                                       # [1, C]
    bq = jnp.concatenate([bcol] * group, axis=0)               # [G C, 1]
    last = last_ref[...]         # [1, wide]: the chunk's whole sum of log g

    # inside the chunk: the attention form
    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)       # [G C, C]
    row = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) % chunk
    col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    a = jnp.where(col <= row,
                  sc * sc * jnp.exp(jnp.minimum(bq - brow, 0.0)), 0.0)
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    num = _mm(a, vf, (1, 0), operands)
    den = a.sum(axis=1, keepdims=True)

    # the divisor's state: q^T norm q, then norm = g norm + k k^T
    left = jnp.exp(last[:, :d] - bcol)                         # [C, d]
    decay = jnp.exp(last)                                      # [1, wide]
    m = m_out[...]
    den_before = (_mm(qf, m, (1, 0), "float32") * qf).sum(axis=1,
                                                         keepdims=True)
    m_out[...] = decay[:, :d] * m + _mm(kf * left, kf, (0, 0), "float32")

    # against the state of the chunks before, `scan_rows` rows of phi at
    # a time; the same rows of phi_k carry this chunk into the state
    vt = (vf * left).T                                         # [e, C]
    nacc_s[...] = jnp.zeros_like(nacc_s)

    def some_rows(i, _):
        r0 = i * scan_rows
        pq = jnp.concatenate(
            [qf * pltpu.roll(qf, r0 + j, 1) for j in range(scan_rows)],
            axis=1)                                            # [G C, wide]
        pk = jnp.concatenate(
            [kf * pltpu.roll(kf, r0 + j, 1) * jnp.where(
                jnp.logical_or(r0 + j == 0, r0 + j == rows - 1), 1.0, 2.0)
             for j in range(scan_rows)], axis=1)               # [C, wide]
        lanes = pl.ds(pl.multiple_of(r0 * d, d), wide)
        st = s_out[:, lanes]                                   # [e, wide]
        nacc_s[...] += _mm(pq, st, (1, 1), operands)
        s_out[:, lanes] = decay * st + _mm(vt, pk, (1, 0), operands)

    jax.lax.fori_loop(0, rows // scan_rows, some_rows, None)
    eb = jnp.exp(bq)
    o = ((num + eb * nacc_s[...])
         / jnp.maximum(den + eb * den_before, _TINY)).astype(o_ref.dtype)
    for i in range(group):
        o_ref[:, i * d:(i + 1) * d] = o[i * chunk:(i + 1) * chunk]


def _prefill_pallas(q, k, v, log_g, state, norm, layer, slot, tiling,
                    operands):
    t, h, d = q.shape
    kvh = k.shape[1]
    chunk, scan_rows = tiling.chunk, tiling.scan_rows
    group, rows, n = h // kvh, phi_rows(d), t // chunk
    # the running sum of log g inside each chunk, down the rows and
    # across the lanes, and each chunk's whole sum
    b = jnp.cumsum(log_g.reshape(n, chunk, kvh), axis=1).transpose(2, 0, 1)
    bcol = b.reshape(kvh, t, 1)
    brow = b.reshape(kvh, n, 1, chunk)
    last = jnp.broadcast_to(brow[..., -1:], (kvh, n, 1, scan_rows * d))
    slot = jnp.asarray(slot, jnp.int32).reshape(1)
    call = pl.pallas_call(
        functools.partial(_prefill_kernel, group=group, rows=rows,
                          scan_rows=scan_rows, chunk=chunk,
                          operands=operands),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kvh, n),
            in_specs=[
                _vmem_spec((chunk, group * d), lambda j, c, *_: (c, j)),
                _vmem_spec((chunk, d), lambda j, c, *_: (c, j)),
                _vmem_spec((chunk, d), lambda j, c, *_: (c, j)),
                _vmem_spec((None, chunk, 1), lambda j, c, *_: (j, c, 0)),
                _vmem_spec((None, None, 1, chunk),
                           lambda j, c, *_: (j, c, 0, 0)),
                _vmem_spec((None, None, 1, scan_rows * d),
                           lambda j, c, *_: (j, c, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                _vmem_spec((chunk, group * d), lambda j, c, *_: (c, j)),
                _vmem_spec((None, None, None, d, rows * d),
                           lambda j, c, slot: (layer, slot[0], j, 0, 0)),
                _vmem_spec((None, None, None, d, d),
                           lambda j, c, slot: (layer, slot[0], j, 0, 0))],
            scratch_shapes=[_scratch((group * chunk, d))]),
        out_shape=[jax.ShapeDtypeStruct((t, h * d), q.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
        # operand numbers count the scalar-prefetch argument
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # a K/V head's state in and out of its buffers (2 x 4.3 MB
            # at d = 128) beside a chunk's scores and rows of phi
            vmem_limit_bytes=64 << 20),
        interpret=backend.interpret(),
        name="retention_prefill",
    )
    with jax.named_scope("retention_prefill"):
        o, state, norm = call(slot, q.reshape(t, h * d),
                              k.reshape(t, kvh * d), v.reshape(t, kvh * d),
                              bcol, brow, last, state, norm)
    return o.reshape(t, h, d), state, norm


def retention_prefill(q, k, v, log_g, true_len, state, norm, layer, slot,
                      use_kernel=None, chunk=None, operands="bfloat16"):
    """One prompt into one slot, chunk by chunk.

    q [T, H, d]; k, v [T, KVH, d]; log_g [T, KVH]: a bucket's shape, of
    which the first `true_len` positions are the prompt; state and norm
    the resident arrays (`retention_decode`); layer: a Python int; slot:
    int32 scalar, traced.  Returns (o [T, H, d] in q's type, rows at or
    past `true_len` of no meaning; state, norm): the slot's state of
    `layer` is that of position `true_len - 1` and replaces whatever the
    slot held; the bucket's padding does not touch it.  On the kernel's
    path a K/V head's state lives in VMEM from the first chunk to the
    last and goes to the slot's region of the aliased array once.  Its
    three products against the state (phi(q) with the state, phi(k) with
    the decayed values, a chunk's weights with v) round their `operands`
    to bfloat16 for one pass of the MXU and sum in float32, as the
    model's other products do: that is what is served (a prefill of
    8,192 positions costs 0.35 s, with "float32" operands, six passes,
    0.52: my chip run, PR 36); the tests pass "float32" where they hold
    the kernel to the XLA mathematics exactly.  The state as stored, the
    divisor's state and its products are float32 either way."""
    k, v, log_g = _masked(k, v, log_g, true_len)
    tiling = retention_tiling(q.shape[-1], q.shape[0], chunk=chunk)
    if _takes_kernel(q.shape[-1], q.shape[1] // k.shape[1], use_kernel,
                     tiling.chunk):
        return _prefill_pallas(q, k, v, log_g, state, norm, layer, slot,
                               tiling, operands)
    o, st, m = _prefill_xla(q, k, v, log_g, tiling.chunk)
    at = (jnp.int32(layer), jnp.asarray(slot, jnp.int32)) \
        + (jnp.zeros((), jnp.int32),) * 3
    state = jax.lax.dynamic_update_slice(state, st[None, None], at)
    norm = jax.lax.dynamic_update_slice(norm, m[None, None], at)
    return o.astype(q.dtype), state, norm
