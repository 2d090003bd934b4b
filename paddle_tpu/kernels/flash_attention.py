"""Pallas TPU flash attention (forward + custom-VJP backward).

The native-kernel tier of the attention stack: replaces the reference's
hand-fused CUDA attention (/root/reference/paddle/fluid/operators/fused/
multihead_matmul_op.cu, operators/math/bert_encoder_functor.cu) with
tiled kernels that never materialise the [S, S] score matrix in HBM.

Structure.  `flash_tiling` decides everything from (seq, head_dim,
causal).  A grid step of `flash_fwd` and `flash_dq` is one block of
queries (`block_q` rows) of one head against the keys a grid step holds
(`block_major` rows of K and V): the whole sequence where a block's
strip of scores against it fits VMEM, which at the sizes this package
trains and serves it does, else blocks of it streamed through the
innermost, "arbitrary" grid axis, the running max, sum and accumulator
passing through VMEM scratch once a grid step.  `flash_dkv` mirrors
it: a grid step is one block of keys (`block_k`) against the resident
q and dO.

Inside a step nothing loops and every shape is static.  With
causal=True the step branches once on where the diagonal lies
(`_strips`) and computes the live part only (`_key_strips`,
`_query_strips`): the keys before the diagonal tile in one product for
all the block's rows, and inside the diagonal tile, chunk of keys by
chunk of keys, the rows from that chunk's own on.  So a chunk of K is
loaded into the MXU once for every query that meets it (a product with
few rows a load is bound by the loads), the tiles above the diagonal
are never computed, and only the strips inside the diagonal tile pay
the mask's select (in the forward, only the chunk x chunk tiles the
diagonal crosses), against a comparison formed once a step.  The forward's softmax runs
between its two rounds of products, chunk of rows by chunk of rows over
whatever strips reach it: a row's maximum and sum are formed once, with
no rescaling of an accumulator (a column of per-row numbers costs the
vector unit as much as a 128-wide tile of scores, every time it is
touched).  `flash_dkv` computes the scores transposed ([keys, queries]),
so that its products need no transposed operand and the statistics
broadcast down the sublanes as they lie.  Matmuls run in the input
dtype (bf16 -> full-rate MXU) accumulating f32 via
preferred_element_type; exp, max and sum are f32; masked scores are
NEG_INF.

Row statistics (the saved logsumexp, and delta = rowsum(dO * O)) cross
the kernel boundary as [batch*heads, 1, seq] float32: the sequence is
the minor dimension, held in 128-lane tiles, a block of them is
block_q * 4 bytes.  (A trailing axis of 1 is padded to 128 lanes on the
chip: 512 KB a 1024-row block, and a copy on XLA's side for each.)
`flash_fwd` turns its columns of row sums into rows on the way out,
`flash_dq` turns the rows back once a step.

Backward recomputes scores from the saved logsumexp (no SxS residual):
one kernel for dq and one for dk/dv, the flash-attention-2
decomposition.

On non-TPU backends the same kernels run in interpret mode, which is how
tests/test_flash_attention.py checks numerics vs the XLA composition.
"""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret

_LANES = 128
NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _vmem_spec(*args):
    return pl.BlockSpec(*args, memory_space=pltpu.VMEM)


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# --------------------------------------------------------------------------
# tiling
# --------------------------------------------------------------------------

class FlashTiling(NamedTuple):
    """How the three training kernels walk one head's score matrix."""
    block_q: int        # q rows of a grid step of `flash_fwd`, `flash_dq`
    block_k: int        # k rows of a grid step of `flash_dkv`
    chunk_q: int        # edge of the tiles the diagonal is followed in,
    chunk_k: int        # by `flash_fwd`/`flash_dq` and by `flash_dkv`
    block_major: int    # rows of the other operand a grid step holds
    tiles_visited: int  # chunk_q x chunk_q tiles the forward pass computes
    tiles_total: int    # ... of the whole square


# What one operand of a grid step may take of VMEM: a block's strip of
# scores against the resident operand ([block, block_major] float32),
# and that operand itself ([block_major, head_dim], counted at 4 bytes
# an element so that the tiling does not depend on the dtype).  With K
# and V double-buffered that fits Mosaic's default 16 MiB of scoped
# VMEM (this file sets no vmem_limit_bytes).
_STRIP_BYTES = 4 << 20


def _fit(want, seq):
    """The largest power-of-two block <= `want` and >= 64 that divides
    `seq`; the whole of `seq` where none does (short or oddly sized
    sequences, which `dot_product_attention` keeps off the chip's
    kernel path)."""
    cand = want
    while cand >= 64:
        if cand <= seq and seq % cand == 0:
            return cand
        cand //= 2
    return seq


def flash_tiling(seq, head_dim, causal, block_q=None, block_k=None,
                 window=None):
    """The tiling of `flash_fwd`, `flash_dq` and `flash_dkv` for one
    (seq, head_dim, causal), and the count that says the causal pruning
    engages: `tiles_visited` of `tiles_total` tiles of chunk_q x
    chunk_q, those on or under the diagonal when causal and all of them
    when not.  `block_q` / `block_k` override the blocks (tests, the
    interpreter).  With `window` (forward only, causal: a query sees
    the `window` keys up to its own) the q block is one that divides
    the window too, so that the band's far edge runs along a tile's
    diagonal as its near edge does, and `tiles_visited` counts the
    band's tiles only (`_band_strips`).

    Measured on the v5e at the train cell's [128, 1024, 64], causal
    (PERF.md section 6, PR 30).  Rows of a grid step: 1024 beat 512 and
    256 in all three kernels (the more rows a loaded chunk of K meets,
    the better the MXU is used); 512 where the strip of 1024 rows
    against the whole sequence would not fit, so that K and V stay
    resident (seq 2048, head_dim 256: 0.88 ms against 1.23).  Chunks:
    256 for `flash_fwd` and `flash_dq` (0.284 and 0.401 ms a call
    against 0.362 and 0.414 at 128), 128 for `flash_dkv` (0.486 against
    0.564 at 256)."""
    rows = 1024 if seq * 1024 * 4 <= _STRIP_BYTES else 512
    if window is not None and not block_q:
        block_q = _fit(rows, math.gcd(seq, window))
    block_q = min(block_q, seq) if block_q else _fit(rows, seq)
    block_k = min(block_k, seq) if block_k else _fit(rows, seq)
    if seq % block_q or seq % block_k:
        raise ValueError(
            f"seq {seq} must be divisible by block sizes ({block_q},{block_k})")
    if window is not None and (not causal or window % block_q):
        raise ValueError(
            f"a window ({window}) needs causal=True and a q block "
            f"({block_q}) that divides it")
    chunk_q, chunk_k = _fit(256, block_q), _fit(128, block_k)
    # the other operand whole where it and the strip fit, else the
    # largest power-of-two share of it that both blocks divide
    step = block_q * block_k // math.gcd(block_q, block_k)
    widest = max(head_dim, block_q, block_k)
    block_major = seq
    while (block_major * widest * 4 > _STRIP_BYTES
           and block_major % (2 * step) == 0):
        block_major //= 2
    n = seq // chunk_q
    visited = n * (n + 1) // 2 if causal else n * n
    if window is not None:
        # a q block's diagonal tile and the band's edge tile, chunk by
        # chunk a triangle each, and the whole tiles between them
        per, reach = block_q // chunk_q, window // block_q
        tri = per * (per + 1) // 2
        visited = sum(tri + min(t, reach - 1) * per * per
                      + (tri if t >= reach else 0)
                      for t in range(seq // block_q))
    return FlashTiling(block_q, block_k, chunk_q, chunk_k, block_major,
                       visited, n * n)


def _strips(causal, offset, block, n_major, run, mirrored=False):
    """Call `run(diag)` for the live part of one grid step's strip of
    scores, every shape in it static: `diag` is the place, among the
    resident operand's `n_major` blocks, of the one block x block tile
    the diagonal crosses, or None where the whole operand is live and
    no mask is needed.

    `offset` (traced) is where the step's own block starts within the
    resident operand.  At block `c` of it, that tile is the diagonal
    one; the blocks before it are live too (`flash_fwd`, `flash_dq`:
    keys before the queries) or, `mirrored`, the blocks after it
    (`flash_dkv`: queries after the keys).  Past the operand's far end
    (before its start, `mirrored`) all of it is live; on the other side
    nothing is, and nothing runs.  One branch per place of the diagonal,
    of which one runs."""
    if not causal:
        run(None)
        return
    for c in range(n_major):
        pl.when(offset == c * block)(functools.partial(run, c))
    whole = offset < 0 if mirrored else offset >= n_major * block
    pl.when(whole)(functools.partial(run, None))


def _key_strips(diag, block, chunk, block_major):
    """Static strips (row0, col0, width, masked) of a q block's scores
    against the resident keys: all its rows against the keys before the
    diagonal tile; then inside that tile, key chunk by key chunk, the
    rows from the chunk's own on.  One product a strip: a chunk of keys
    is loaded into the MXU once for every query that meets it."""
    if diag is None:
        return ((0, 0, block_major, False),)
    plain = ((0, 0, diag * block, False),) if diag else ()
    return plain + tuple((j * chunk, diag * block + j * chunk, chunk, True)
                         for j in range(block // chunk))


def _query_strips(diag, block, chunk, block_major):
    """... (rows, col0, width, masked) of a k block's transposed scores
    against the resident queries: inside the diagonal tile, query chunk
    by query chunk, the first `rows` keys, up to the chunk's own; then
    all its keys against the queries after that tile."""
    if diag is None:
        return ((block, 0, block_major, False),)
    after = (diag + 1) * block
    tri = tuple(((i + 1) * chunk, diag * block + i * chunk, chunk, True)
                for i in range(block // chunk))
    return tri + (((block, after, block_major - after, False),)
                  if after < block_major else ())


def _band_strips(diag, block, chunk, n_major, reach):
    """Static strips (row0, row1, col0, width, kind) of a q block's
    scores against the resident keys when a query sees the `reach`
    tiles of keys up to its own only (`flash_fwd` with a window).
    `diag` is the place of the diagonal tile among the resident
    operand's `n_major` tiles, and may lie past them.  The band's far
    edge is the diagonal of tile `diag - reach`, of which the part
    above it is live: key chunk by key chunk, the rows up to the
    chunk's own (kind "edge": in the last of them a row sees the keys
    after its own place).  Then the whole tiles between, in one
    product; then the diagonal tile as `_key_strips` cuts it (kind
    "diag").  Tiles outside the resident operand are left out."""
    edge, per = diag - reach, block // chunk
    strips = []
    if 0 <= edge < n_major:
        strips += [(0, (j + 1) * chunk, edge * block + j * chunk, chunk,
                    "edge") for j in range(per)]
    lo, hi = max(edge + 1, 0), min(diag, n_major)
    if hi > lo:
        strips.append((0, block, lo * block, (hi - lo) * block, None))
    if diag < n_major:
        strips += [(j * chunk, block, diag * block + j * chunk, chunk,
                    "diag") for j in range(per)]
    return tuple(strips)


def _seen(rows, chunk, keys_on_rows=False):
    """[rows, chunk] bool, the causal mask of a strip inside the diagonal
    tile: queries on the rows, counted from the first that meets the
    chunk of keys on the columns; or, `keys_on_rows` (`flash_dkv`'s
    transposed scores), keys on the rows, counted so that the last
    `chunk` of them face the chunk of queries on the columns."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)
    return col + (rows - chunk) >= row if keys_on_rows else row >= col


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                sm_scale, causal, block_q, chunk, reach=None):
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    block_major = k_ref.shape[0]
    n_rows = block_q // chunk
    streamed = bool(scratch)        # K and V arrive in several blocks

    if streamed:
        acc_ref, m_ref, l_ref = scratch

        @pl.when(kb == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def _finalize(rows, m, l, acc):
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[rows, :] = (acc / l_safe).astype(o_ref.dtype)
        # the column of row statistics leaves as a row: sequence minor
        lse = jnp.broadcast_to(m + jnp.log(l_safe), (acc.shape[0], _LANES))
        lse_ref[:, rows] = lse.T[:1]

    def _run(diag):
        """Softmax over the live keys of this step.  The products run
        strip by strip (`_key_strips`), the softmax between them chunk
        of rows by chunk of rows over whatever strips reach it, so that
        a row's maximum and sum are formed once."""
        if reach is None:
            strips = tuple(
                (row0, block_q, col0, width, "diag" if masked else None)
                for row0, col0, width, masked in _key_strips(
                    diag, block_q, chunk, block_major))
        else:
            strips = _band_strips(diag, block_q, chunk,
                                  block_major // block_q, reach)
        scores = [jax.lax.dot_general(
            q_ref[row0:row1, :], k_ref[col0:col0 + width, :], _NT,
            preferred_element_type=jnp.float32)
            for row0, row1, col0, width, _ in strips]
        seen = {"diag": _seen(chunk, chunk)}
        if reach is not None:
            seen["edge"] = jnp.logical_not(seen["diag"])

        def _tiles(of, i):
            """Rows [i*chunk, (i+1)*chunk) of every strip that has them:
            (strip, tile, the edge of the band that crosses the tile)."""
            for si, (row0, row1, _, _, kind) in enumerate(strips):
                r, end = i * chunk - row0, (i + 1) * chunk
                if r >= 0 and end <= row1:
                    crossed = (kind == "diag" and r == 0) \
                        or (kind == "edge" and end == row1)
                    yield si, of[si][r:r + chunk], kind if crossed else None

        probs = [[] for _ in strips]    # per strip, p by chunk of rows
        stats = []
        for i in range(n_rows):
            rows = slice(i * chunk, (i + 1) * chunk)
            tiles = [(si, jnp.where(seen[crossed], t * sm_scale, NEG_INF)
                      if crossed else t * sm_scale)
                     for si, t, crossed in _tiles(scores, i)]
            m = functools.reduce(jnp.maximum, [
                t.max(axis=-1, keepdims=True) for _, t in tiles])
            if streamed:
                m = jnp.maximum(m_ref[rows, :], m)
            l = 0.0
            for si, t in tiles:
                p = jnp.exp(t - m)
                l = l + p.sum(axis=-1, keepdims=True)
                probs[si].append(p.astype(v_ref.dtype))
            stats.append((m, l))
        outs = [jax.lax.dot_general(
            ps[0] if len(ps) == 1 else jnp.concatenate(ps, axis=0),
            v_ref[col0:col0 + width, :], _NN,
            preferred_element_type=jnp.float32)
            for ps, (_, _, col0, width, _) in zip(probs, strips)]
        for i, (m, l) in enumerate(stats):
            rows = slice(i * chunk, (i + 1) * chunk)
            acc = functools.reduce(
                jnp.add, [t for _, t, _ in _tiles(outs, i)])
            if streamed:
                alpha = jnp.exp(m_ref[rows, :] - m)
                m_ref[rows, :] = m
                l_ref[rows, :] = l_ref[rows, :] * alpha + l
                acc_ref[rows, :] = acc_ref[rows, :] * alpha + acc
            else:
                _finalize(rows, m, l, acc)

    offset = qi * block_q - kb * block_major
    if reach is None:
        _strips(causal, offset, block_q, block_major // block_q, _run)
    else:
        # one branch per place of the diagonal from which the band still
        # reaches the resident keys, of which at most one runs
        n_major = block_major // block_q
        for c in range(n_major + reach if streamed else n_major):
            pl.when(offset == c * block_q)(functools.partial(_run, c))

    if streamed:
        @pl.when(kb == pl.num_programs(2) - 1)
        def _last():
            _finalize(slice(None), m_ref[...], l_ref[...], acc_ref[...])


def _major_index(causal, block, block_major, group=1, window=None):
    """Index map of the streamed operand of `flash_fwd` / `flash_dq`:
    K/V block `kb` for q block `qi`.  Causal, a block wholly past the
    diagonal names the last live one again, which Pallas does not fetch
    twice; with a window, so does a block wholly before the band, the
    first live one.  `group` query heads read one head of K and V."""
    def index(bh, qi, kb):
        last_live = (qi * block) // block_major
        kb = jnp.minimum(kb, last_live) if causal else kb
        if window is not None:
            kb = jnp.maximum(
                kb, jnp.maximum(qi * block - window, 0) // block_major)
        return (bh if group == 1 else bh // group), kb, 0
    return index


def _fwd(q, k, v, sm_scale, causal, tiling, window=None):
    """-> out [b, h, s, d], lse [b*h, 1, s] (the kernels' layout)."""
    return _fwd_call(q, k, v, sm_scale, causal, tiling, interpret(), window)


# jitted, so that a model's layers trace and lower each kernel once and
# not once a layer (the kernels' bodies are unrolled, static code);
# `interpreted` is an argument so that it is part of the cache's key
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7), inline=True)
def _fwd_call(q, k, v, sm_scale, causal, tiling, interpreted, window=None):
    """k and v may hold fewer heads than q (a whole number of query
    heads to each): the index map sends a query head to its own."""
    b, h, s, d = q.shape
    block_q, chunk, block_major = (tiling.block_q, tiling.chunk_q,
                                   tiling.block_major)
    q3, k3, v3 = (x.reshape(-1, s, d) for x in (q, k, v))
    q_spec = _vmem_spec((None, block_q, d), lambda bh, qi, kb: (bh, qi, 0))
    kv_spec = _vmem_spec((None, block_major, d),
                         _major_index(causal, block_q, block_major,
                                      h // k.shape[1], window))
    windowed = {} if window is None else {"reach": window // block_q}
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, chunk=chunk, **windowed),
        grid=(b * h, s // block_q, s // block_major),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            q_spec,
            _vmem_spec((None, 1, block_q), lambda bh, qi, kb: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q, d)),
            _scratch((block_q, 1)),
            _scratch((block_q, 1)),
        ] if block_major < s else [],
        compiler_params=_compiler_params(),
        interpret=interpreted,
        name="flash_fwd",
    )
    with jax.named_scope("flash_fwd"):
        out, lse = call(q3, k3, v3)
    return out.reshape(b, h, s, d), lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc_ref, *, sm_scale, causal, block_q, chunk):
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    block_major = k_ref.shape[0]

    @pl.when(kb == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def _run(diag):
        # the rows of statistics, turned into columns once a q block
        lse = lse_ref[0][:, None]                         # [bq, 1]
        delta = delta_ref[0][:, None]
        seen = _seen(block_q, chunk)
        for row0, col0, width, masked in _key_strips(
                diag, block_q, chunk, block_major):
            k_blk = k_ref[col0:col0 + width, :]
            s = sm_scale * jax.lax.dot_general(
                q_ref[row0:, :], k_blk, _NT,
                preferred_element_type=jnp.float32)
            if masked:
                s = jnp.where(seen[:block_q - row0], s, NEG_INF)
            p = jnp.exp(s - lse[row0:])                   # [rows, width]
            dp = jax.lax.dot_general(
                do_ref[row0:, :], v_ref[col0:col0 + width, :], _NT,
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta[row0:]) * sm_scale
            dq_acc_ref[row0:, :] += jax.lax.dot_general(
                ds.astype(k_blk.dtype), k_blk, _NN,
                preferred_element_type=jnp.float32)

    _strips(causal, qi * block_q - kb * block_major, block_q,
            block_major // block_q, _run)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, sm_scale, causal, block_k, chunk):
    ki = pl.program_id(1)
    qb = pl.program_id(2)
    block_major = q_ref.shape[0]

    @pl.when(qb == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def _run(diag):
        # scores transposed, keys on the sublanes: the statistics
        # broadcast down them as they lie, and p^T, ds^T are the left
        # operands of plain products
        seen = _seen(block_k, chunk, keys_on_rows=True)
        for rows, col0, width, masked in _query_strips(
                diag, block_k, chunk, block_major):
            q = q_ref[col0:col0 + width, :]               # [width, d]
            do = do_ref[col0:col0 + width, :]
            lse = lse_ref[:, col0:col0 + width]           # [1, width]
            delta = delta_ref[:, col0:col0 + width]
            s = sm_scale * jax.lax.dot_general(
                k_ref[:rows, :], q, _NT,
                preferred_element_type=jnp.float32)       # [rows, width]
            if masked:
                s = jnp.where(seen[block_k - rows:], s, NEG_INF)
            p = jnp.exp(s - lse)
            dv_acc_ref[:rows, :] += jax.lax.dot_general(
                p.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                v_ref[:rows, :], do, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            dk_acc_ref[:rows, :] += jax.lax.dot_general(
                ds.astype(q.dtype), q, _NN,
                preferred_element_type=jnp.float32)

    _strips(causal, ki * block_k - qb * block_major, block_k,
            block_major // block_k, _run, mirrored=True)

    @pl.when(qb == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _stats_rows(x):
    """Row statistics [batch, heads, seq] in the kernels' layout,
    [batch*heads, 1, seq]."""
    b, h, s = x.shape
    return x.reshape(b * h, 1, s)


def _delta(do, out):
    # rowsum(dO * O) — plain XLA, fuses into one pass
    return _stats_rows(jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1))


def _bwd(sm_scale, causal, tiling, res, g):
    q, k, v, out, lse = res
    return _bwd_core(sm_scale, causal, tiling, q, k, v, g, lse,
                     _delta(g, out))


def _bwd_core(sm_scale, causal, tiling, q, k, v, do, lse, delta):
    """Shared FA-2 backward given the row statistics `lse` and `delta`
    in the kernels' layout (`_stats_rows`).

    The (out, lse)-output variant folds its lse cotangent in here:
    ds = p*(dp - delta + dlse) = p*(dp - (delta - dlse)), so the caller
    just passes delta - dlse and the kernels stay byte-identical."""
    return _bwd_call(sm_scale, causal, tiling, interpret(), q, k, v, do,
                     lse, delta)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), inline=True)
def _bwd_call(sm_scale, causal, tiling, interpreted, q, k, v, do, lse,
              delta):
    b, h, s, d = q.shape
    block_q, block_k, block_major = (tiling.block_q, tiling.block_k,
                                     tiling.block_major)
    q3, k3, v3, do3 = (x.reshape(b * h, s, d) for x in (q, k, v, do))

    q_spec = _vmem_spec((None, block_q, d), lambda bh, qi, kb: (bh, qi, 0))
    kv_spec = _vmem_spec((None, block_major, d),
                         _major_index(causal, block_q, block_major))
    row_spec = _vmem_spec((None, 1, block_q), lambda bh, qi, kb: (bh, 0, qi))
    call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, chunk=tiling.chunk_q),
        grid=(b * h, s // block_q, s // block_major),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d))],
        compiler_params=_compiler_params(),
        interpret=interpreted,
        name="flash_dq",
    )
    with jax.named_scope("flash_dq"):
        dq = call(q3, k3, v3, do3, lse, delta)

    # q, dO and their statistics are the streamed operands here; causal,
    # a block wholly before the k block names the first live one again
    def major(ki, qb):
        first_live = (ki * block_k) // block_major
        return jnp.maximum(qb, first_live) if causal else qb
    k_spec = _vmem_spec((None, block_k, d), lambda bh, ki, qb: (bh, ki, 0))
    qm_spec = _vmem_spec((None, block_major, d),
                         lambda bh, ki, qb: (bh, major(ki, qb), 0))
    rows_spec = _vmem_spec((None, 1, block_major),
                           lambda bh, ki, qb: (bh, 0, major(ki, qb)))
    call = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_k=block_k, chunk=tiling.chunk_k),
        grid=(b * h, s // block_k, s // block_major),
        in_specs=[qm_spec, k_spec, k_spec, qm_spec, rows_spec, rows_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s, d), v.dtype),
        ],
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        compiler_params=_compiler_params(),
        interpret=interpreted,
        name="flash_dkv",
    )
    with jax.named_scope("flash_dkv"):
        dk, dv = call(q3, k3, v3, do3, lse, delta)

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
# `kv_append` is that reader's writer inside the decode step (grouped
# heads write through `gqa_decode`, further down): per slot, the
# 128-lane tile that holds column `pos[s]` comes in, the new column is
# merged under an iota mask, the same tile goes out, on the cache
# aliased to the call's output.  (An XLA scatter or
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
# grouped-query decode over the resident cache: a walk of the live tiles
# --------------------------------------------------------------------------
#
# `gqa_decode` writes this step's column of K and V of every slot and
# attends, in one call, on caches `[L, S, KVH, D, T]` whose head of K and
# V is read by `group = H / KVH` query heads (1 for plain multi-head
# attention).  It is `kernels/mla.py`'s design over two arrays.  **The
# walk has as many steps as the slots have live tiles.**  A visit is one
# (slot, tile) pair that holds a live column; `_visits` lists them in XLA
# from the lengths (each slot's first visit, the slot of each visit) and
# the tables are scalar-prefetched.  The grid is `(slots,)`: a grid step
# brings the slot's queries and takes its result through BlockSpecs, and
# its body loops over the slot's visits only.  The caches stay where they
# lie (`pl.ANY`); the kernel copies a visit's `[KVH, D, tile]` of K and of
# V into one of `GqaTiling.buffers` VMEM buffers itself, always `buffers
# - 1` visits ahead of the one it computes, whatever slot those belong
# to.  Visit v lives in buffer v mod `buffers`, so no turn is carried
# from one grid step to the next.  A tile is copied in parts, and of a
# slot's last tile only the parts that hold a live column: what lies past
# them in the buffer is an earlier visit's, finite and masked.
#
# The new column is written by the kernel that reads it: on the visit
# whose tile holds the write position the 128 lanes around it take this
# step's K and V in VMEM before the scores are computed, and the same 128
# lanes go back to the caches, which are aliased to the results, through
# BlockSpecs.  Nothing else of the caches is written.  The write
# position and the live length are two inputs: in a ring that has
# wrapped the column `pos mod T` lies in any tile, not the last.

class GqaTiling(NamedTuple):
    """How `gqa_decode` walks one slot's K and V."""
    tile: int       # columns of a visit: one step of the online softmax
    part: int       # columns of one copy; a tile is whole parts
    buffers: int    # tiles in VMEM: the one computed and those on their way


# elements of K and of V the buffers may hold: with both, 8 MiB in
# bfloat16, half of what a kernel is given on the v5e
_GQA_BUFFER_ELEMS = 2 << 20


def gqa_tiling(kv_heads, head_dim, depth, block_k=None):
    """The tiling of `gqa_decode` over K and V `[kv_heads, head_dim,
    depth]` a slot.  The tile is the largest power-of-two multiple of
    128, at most 512, that divides the depth and whose four buffers fit
    `_GQA_BUFFER_ELEMS` (`block_k` overrides it: tests and the
    interpreter, for small depths); it is copied in parts of 128
    columns into one of four buffers.

    Measured on the v5e at the Trinity cell's `[3, 64, 4, 128, 9728]`
    and `[9, 64, 4, 128, 2048]`, 32 query heads, lengths drawn as its
    (mean 3,619: 474 MB live a full layer, 255 MB a ring), the kernel
    alone with the caches donated, ms a call of a full layer / of a ring
    (PERF.md section 6, PR 37).  The rectangle before it, `kv_append`
    and all: 0.931 / 0.431.  Tiles of 512 in parts of 128, four buffers:
    0.717 / 0.383 (0.706 in a second run), which is what its copies alone
    take (0.718 / 0.383: 700 GB/s with the 17 MB of written lanes); the
    computation alone takes 0.629 / 0.337 and hides behind them.  Parts
    of 256: 0.744 / 0.397, of 512: 0.732 / 0.400 (fewer copies in
    flight); a copy a K/V head: 0.737 to 0.912 (more descriptors).  Three
    buffers 0.725 / 0.386, two 0.861, six 0.718 / 0.383.  Tiles of 256:
    0.745 to 0.753; of 1,024 and 2,048 in a ring: 0.398 to 0.402.  The
    whole last tile copied, not its live parts: 0.757 / 0.393."""
    buffers, tile = 4, block_k
    if tile is None:
        tile = 512
        while tile > _LANES and (
                depth % tile
                or buffers * kv_heads * head_dim * tile > _GQA_BUFFER_ELEMS):
            tile //= 2
    if depth % tile or tile % _LANES:
        raise ValueError(f"cache depth {depth} must be a multiple of the "
                         f"tile {tile}, and that of {_LANES}")
    return GqaTiling(tile, _LANES, buffers)


def tiles_walked(lengths, tile):
    """Visits of one walk over slots of these lengths."""
    return sum(-(-int(n) // tile) for n in lengths)


def _visits(lengths, depth, tile):
    """lengths int32 [S], each in [1, depth] -> (first visit of each slot
    and, last, the number of visits [S + 1]; slot of each visit
    [S * depth / tile], entries past the last visit never read)."""
    s = lengths.shape[0]
    ends = jnp.cumsum((lengths + tile - 1) // tile)
    v = jnp.arange(s * (depth // tile), dtype=jnp.int32)
    # the slots whose visits all lie before v (a search would be a loop)
    slot = jnp.minimum((ends[None, :] <= v[:, None]).sum(axis=1), s - 1)
    first = jnp.concatenate([jnp.zeros(1, ends.dtype), ends])
    return first.astype(jnp.int32), slot.astype(jnp.int32)


def gqa_walk(lengths, kv_heads, head_dim, depth, block_k=None):
    """The visit tables of `gqa_decode` for slots of these live lengths
    (int32 [S], each in [1, depth]): they depend on the lengths alone, so
    a decode step builds them once for all its layers of one depth."""
    lengths = jnp.asarray(lengths, jnp.int32)
    return _visits(lengths, depth,
                   gqa_tiling(kv_heads, head_dim, depth, block_k).tile)


def _gqa_decode_kernel(len_ref, at_ref, first_ref, slot_ref, layer_ref,
                       q_ref, kn_ref, vn_ref, k_hbm, v_hbm, o_ref, ko_ref,
                       vo_ref, kbuf_ref, vbuf_ref, sem_ref, acc_ref, m_ref,
                       l_ref, *, sm_scale, tile, part, buffers):
    i = pl.program_id(0)
    length, at = len_ref[i], at_ref[i]
    layer = layer_ref[0]
    begin, end = first_ref[i], first_ref[i + 1]
    visits = first_ref[pl.num_programs(0)]
    parts = tile // part
    kv_heads = q_ref.shape[0]
    sides = ((k_hbm, kbuf_ref), (v_hbm, vbuf_ref))

    def copies(v, j, slot=0, column=0):
        return [pltpu.make_async_copy(
            hbm.at[layer, slot, :, :, pl.ds(column + j * part, part)],
            buf.at[v % buffers, :, :, pl.ds(j * part, part)],
            sem_ref.at[n, v % buffers, j])
            for n, (hbm, buf) in enumerate(sides)]

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
                @pl.when(j < live)
                def _part():
                    for c in copies(v, j, slot, column):
                        c.start()

    @pl.when(i == 0)
    def _prime():
        # no part of a buffer is ever read before something was put there
        kbuf_ref[...] = jnp.zeros_like(kbuf_ref)
        vbuf_ref[...] = jnp.zeros_like(vbuf_ref)
        for v in range(buffers - 1):
            fetch(v)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def visit(v, last):
        fetch(v + buffers - 1)
        live = live_parts(v, i) if last else None
        for j in range(parts):
            for c in copies(v, j):
                if last:
                    pl.when(j < live)(c.wait)
                else:
                    c.wait()
        kbuf, vbuf = kbuf_ref.at[v % buffers], vbuf_ref.at[v % buffers]
        column = (v - begin) * tile

        @pl.when((at >= column) & (at < column + tile))
        def _write():
            # this step's column into the 128 lanes around it: the slot's
            # own lane of its 128 slots' columns, spread over the lanes
            around = pl.ds(pl.multiple_of(
                (at - column) // _LANES * _LANES, _LANES), _LANES)
            lane = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape[1:], 1)
            for new_ref, buf, out_ref in ((kn_ref, kbuf, ko_ref),
                                          (vn_ref, vbuf, vo_ref)):
                for h in range(kv_heads):
                    new = jnp.where(lane == i % _LANES,
                                    new_ref[h].astype(jnp.float32), 0.0)
                    lanes = jnp.where(
                        lane == at % _LANES,
                        new.sum(axis=1, keepdims=True).astype(out_ref.dtype),
                        buf[h, :, around])
                    buf[h, :, around] = lanes
                    out_ref[h] = lanes

        for h in range(kv_heads):
            k, v_blk = kbuf[h], vbuf[h]                    # [D, tile]
            s = jax.lax.dot_general(
                q_ref[h], k, _NN,
                preferred_element_type=jnp.float32) * sm_scale
            if last:
                k_pos = column + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(k_pos < length, s, NEG_INF)  # [group, tile]
            m_prev = m_ref[h, :, 0:1]
            m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_ref[h] = jnp.broadcast_to(
                l_ref[h, :, 0:1] * alpha + p.sum(axis=1, keepdims=True),
                l_ref.shape[1:])
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, _NT,
                preferred_element_type=jnp.float32)        # [group, D]
            m_ref[h] = jnp.broadcast_to(m_cur, m_ref.shape[1:])

    jax.lax.fori_loop(begin, end - 1,
                      lambda v, _: visit(v, last=False), None)
    visit(end - 1, last=True)
    o_ref[...] = (acc_ref[...] / l_ref[:, :, 0:1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(10, 11), inline=True)
def _gqa_decode_call(lengths, at, first, slot, layer, q, k_new, v_new,
                     k_cache, v_cache, sm_scale, tiling):
    # jitted so that the layers of one depth share one trace of the
    # kernel (the layer is data), inlined so that the caller's program
    # holds the call itself, aliases and all
    s, kvh, group, d = q.shape
    tile, part, buffers = tiling

    def per_slot(i, *_):
        return i, 0, 0, 0

    def columns(i, *_):
        return i // _LANES, 0, 0, 0

    def written(i, lens, at, first, slot, layer):
        return layer[0], i, 0, 0, at[i] // _LANES

    lanes = _vmem_spec((None, None, kvh, d, _LANES), written)
    call = pl.pallas_call(
        functools.partial(_gqa_decode_kernel, sm_scale=sm_scale, tile=tile,
                          part=part, buffers=buffers),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(s,),
            in_specs=[
                _vmem_spec((None, kvh, group, d), per_slot),
                _vmem_spec((None, kvh, d, _LANES), columns),
                _vmem_spec((None, kvh, d, _LANES), columns),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[_vmem_spec((None, kvh, group, d), per_slot),
                       lanes, lanes],
            scratch_shapes=[
                pltpu.VMEM((buffers, kvh, d, tile), k_cache.dtype),
                pltpu.VMEM((buffers, kvh, d, tile), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, buffers, tile // part)),
                _scratch((kvh, group, d)), _scratch((kvh, group, _LANES)),
                _scratch((kvh, group, _LANES))]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        # operand numbers count the scalar-prefetch arguments
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret(),
        name="gqa_decode",
    )
    with jax.named_scope("gqa_decode"):
        return call(lengths, at, first, slot, layer, q, k_new, v_new,
                    k_cache, v_cache)


def gqa_decode(q, k_new, v_new, k_cache, v_cache, layer, at, lengths,
               walk=None, sm_scale=None, block_k=None):
    """Write each slot's new column of K and V, then every query head of
    every slot against its head of the slot's K and V up to its live
    length, read where they lie.

    q: [S, H, 1, D]; k_new, v_new: [S, KVH, D], this step's columns;
    k_cache/v_cache: the resident caches [L, S, KVH, D, T], T a multiple
    of 128 and H of KVH (query head h reads K/V head h // (H / KVH), K
    and V never repeated; H == KVH is plain multi-head attention);
    layer: int32 scalar, traced or not; at: int32 [S], the column each
    slot writes, inside [0, T); lengths: int32 [S], the leading columns
    of the slot that are live once the column is written, each in
    (at, T] (a ring that has wrapped gives its whole depth, and writes
    anywhere in it); walk: `gqa_walk(lengths, KVH, D, T)` where the
    caller has it already (the same for every layer of one depth).
    Returns (o [S, H, 1, D] in q's type, float32 scores and
    accumulation; the caches with slot s's column at[s] of `layer`
    written and nothing else changed: they are aliased to the results,
    and only the 128 lanes around each column are written)."""
    s, h, q_len, d = q.shape
    if q_len != 1:
        raise ValueError(f"gqa_decode needs q_len == 1, got {q_len}")
    kvh, t = k_cache.shape[2], k_cache.shape[-1]
    if k_cache.shape[1:] != (s, kvh, d, t) or h % kvh \
            or v_cache.shape != k_cache.shape:
        raise ValueError(
            f"resident caches must be [L, {s}, KVH, {d}, T] with KVH "
            f"dividing {h}, got {k_cache.shape} and {v_cache.shape}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    tiling = gqa_tiling(kvh, d, t, block_k)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(s)
    first, slot = walk if walk is not None else _visits(
        lengths, t, tiling.tile)

    def side_by_side(new, dtype):
        # the slots' columns side by side, 128 slots a block: a slot's
        # column is one lane of its block
        blocks = -(-s // _LANES)
        new = jnp.pad(new.astype(dtype),
                      ((0, blocks * _LANES - s), (0, 0), (0, 0)))
        return new.reshape(blocks, _LANES, kvh, d).transpose(0, 2, 3, 1)

    o, k_cache, v_cache = _gqa_decode_call(
        lengths, jnp.asarray(at, jnp.int32).reshape(s), first, slot,
        jnp.asarray(layer, jnp.int32).reshape(1),
        q.reshape(s, kvh, h // kvh, d), side_by_side(k_new, k_cache.dtype),
        side_by_side(v_new, v_cache.dtype), k_cache, v_cache,
        float(sm_scale), tiling)
    return o.reshape(s, h, 1, d), k_cache, v_cache


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, sm_scale, causal, tiling):
    out, _ = _fwd(q, k, v, sm_scale, causal, tiling)
    return out


def _flash_fwd(q, k, v, sm_scale, causal, tiling):
    out, lse = _fwd(q, k, v, sm_scale, causal, tiling)
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_lse(q, k, v, sm_scale, causal, tiling):
    out, lse = _fwd(q, k, v, sm_scale, causal, tiling)
    return out, lse.reshape(q.shape[:3])


def _flash_lse_fwd(q, k, v, sm_scale, causal, tiling):
    out, lse = _fwd(q, k, v, sm_scale, causal, tiling)
    return (out, lse.reshape(q.shape[:3])), (q, k, v, out, lse)


def _flash_lse_bwd(sm_scale, causal, tiling, res, g):
    q, k, v, out, lse = res
    do, dlse = g
    # dlse rides the same kernels: ds gains +p*dlse, i.e. delta -> delta
    # - dlse (see _bwd_core)
    delta = _delta(do, out) - _stats_rows(dlse.astype(jnp.float32))
    return _bwd_core(sm_scale, causal, tiling, q, k, v, do, lse, delta)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _resolve(q, sm_scale, causal, block_q, block_k):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    return float(sm_scale), bool(causal), flash_tiling(
        q.shape[-2], q.shape[-1], bool(causal), block_q, block_k)


def flash_attention(q, k, v, causal=False, sm_scale=None,
                    block_q=None, block_k=None):
    """Tiled attention over [batch, heads, seq, head_dim] inputs
    (self-attention: q, k and v of one shape).

    The tiling comes from `flash_tiling(seq, head_dim, causal)`; with
    causal=True only the tiles on or under the diagonal are computed.
    `block_q` / `block_k` override the compute tile and must divide seq
    (tests, the interpreter).  head_dim should be an MXU-friendly
    64/128/256.  Returns the same shape/dtype as q.
    """
    return _flash(q, k, v, *_resolve(q, sm_scale, causal, block_q, block_k))


def flash_attention_fwd(q, k, v, sm_scale=None, window=None, block_q=None):
    """Causal forward pass only (a served model's prefill), for what the
    training entry points above do not take: k and v [batch, kv_heads,
    seq, head_dim] may hold fewer heads than q [batch, heads, seq,
    head_dim] (grouped-query attention: query head h reads head h //
    (heads / kv_heads), through the index map, K and V never repeated),
    and with `window` a query sees the `window` keys up to and with its
    own, the tiles wholly outside that band not visited
    (`flash_tiling(..., window=)`)."""
    if q.shape[1] % k.shape[1] or k.shape != v.shape:
        raise ValueError(f"{q.shape[1]} query heads over K {k.shape}, "
                         f"V {v.shape}")
    seq = q.shape[-2]
    if window is not None and window >= seq:
        window = None
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    tiling = flash_tiling(seq, q.shape[-1], True, block_q, None, window)
    return _fwd(q, k, v, float(sm_scale), True, tiling, window)[0]


def flash_attention_with_lse(q, k, v, causal=False, sm_scale=None,
                             block_q=None, block_k=None):
    """flash_attention that ALSO returns the per-row logsumexp
    [batch, heads, seq] (f32), fully differentiable through both
    outputs — the building block for ring attention's (out, lse) block
    combine (distributed/ring_attention.py): partial attentions over kv
    shards merge exactly via softmax-weighted averaging of normalized
    outputs.  Tiled as `flash_attention` is."""
    return _flash_lse(q, k, v,
                      *_resolve(q, sm_scale, causal, block_q, block_k))
