"""Fused scaled-dot-product attention.

Replaces the reference's fused transformer attention
(/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu and
math/bert_encoder_functor.cu) with a TPU-native path: a Pallas
flash-attention kernel (added in kernels/flash_attention.py) for large
sequence lengths, and an XLA-fused softmax(QK^T)V composition otherwise.
"""

import math
import os
import warnings

import jax
import jax.numpy as jnp

NEG_INF = -1e9
# the decode path masks with -1e30 (flash_attention.py's NEG_INF), NOT
# this module's -1e9: models/generate.py's inline decode math always
# used -1e30, and the serving engine's token-exactness contract is that
# decode_attention reproduces it bitwise
DECODE_NEG_INF = -1e30


def _xla_attention(q, k, v, mask, scale, is_causal, dropout_p, training,
                   rng_key, window=None):
    # q,k,v: [B, H, S, D]; or k, v [B, KVH, S, D], H / KVH query heads
    # reading one head of K and V: their rows then lie one head after
    # the other against that head, and K and V are not repeated
    b, h, s_q, d = q.shape
    grouped = k.shape[1] != h
    if grouped:
        q = q.reshape(b, k.shape[1], -1, d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if is_causal:
        s_k = logits.shape[-1]
        causal = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        if window is not None:
            # a query sees the `window` keys up to and with its own
            causal &= ~jnp.tril(causal, k=s_k - s_q - window)
        if grouped:
            causal = jnp.tile(causal, (h // k.shape[1], 1))
        logits = jnp.where(causal, logits, NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, NEG_INF)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        if rng_key is None:
            from ..nn.parameter import default_rng

            rng_key = default_rng.next_key()
        keep = jax.random.bernoulli(rng_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return out.reshape(b, h, s_q, d) if grouped else out


def _flash_per_shard(q, k, v, causal, scale):
    """The flash kernel, split by hand where GSPMD would have to.

    XLA refuses to partition a Mosaic call ("Mosaic kernels cannot be
    automatically partitioned"), so under a mesh with automatic axes (a
    jit over sharded arrays: distributed/sharded.py sets the mesh) the
    kernel runs inside a shard_map: each device takes its own
    [batch, heads] shard — batch over "dp", heads over "tp", this
    package's axis conventions (distributed/mesh.py) — with seq and
    head_dim whole.  No mesh, or one whose axes are all manual already
    (inside a shard_map), calls the kernel directly."""
    from jax.sharding import AxisType, PartitionSpec as P

    from .flash_attention import flash_attention

    mesh = jax.sharding.get_abstract_mesh()
    auto = [n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t == AxisType.Auto]
    if not auto:
        return flash_attention(q, k, v, causal=causal, sm_scale=scale)

    def axis_for(name, dim):
        return name if name in auto and dim % mesh.shape[name] == 0 \
            else None

    spec = P(axis_for("dp", q.shape[0]), axis_for("tp", q.shape[1]),
             None, None)
    # every automatic axis turns manual (Mosaic lowers only where no
    # axis is left to the partitioner); axes the spec does not name
    # hold replicas
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        sm_scale=scale),
        in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=frozenset(auto), check_vma=False)(q, k, v)


def _decode_takes_kernel(t, head_dim, use_flash=None):
    """The one predicate that picks the Pallas decode kernels over the
    XLA mathematics, for `decode_attention` and for the engine's
    `resident_decode_attention` alike: shapes the kernel tiles (cache
    depth a multiple of 128, head_dim 64/128/256) and, unless
    `use_flash` or PADDLE_TPU_FORCE_FLASH_DECODE says otherwise, a TPU
    and a cache at least 1024 deep."""
    if t % 128 or head_dim not in (64, 128, 256):
        return False
    if use_flash is not None:
        return bool(use_flash)
    env = os.environ.get("PADDLE_TPU_FORCE_FLASH_DECODE", "")
    if env:
        return env.lower() in ("1", "true", "yes")
    from .backend import is_tpu_backend

    return is_tpu_backend() and t >= 1024


def decode_attention(q, k, v, pos=None, mask=None, scale=None,
                     use_flash=None):
    """Single-query decode attention: q [B, H, 1, D] against a KV-cache
    prefix k/v [B, H, T, D] -> [B, H, 1, D].

    `pos` is the CURRENT token's cache position — scalar (whole batch at
    one position, models/generate.py's cohort decode) or [B] (per-slot
    ragged positions, the serving engine); cache columns > pos are
    masked.  Alternatively pass an explicit `mask` (bool keeps-where-
    true, else additive) when the live set is not a prefix (the fused-op
    path).  With neither, the full cache is attended (pos = T-1).

    Numerics contract: the XLA path is bitwise the inline decode math
    models/generate.py shipped with (f32 scores, -1e30 masked columns,
    f32 softmax, cast back to q.dtype) — masked columns underflow to
    exactly 0.0 in f32, so padded cache depth never perturbs the live
    sums and cached decode stays token-exact vs a full forward.  The
    flash path (TPU, deep caches) is the online-softmax Pallas kernel in
    flash_attention.py: same math re-associated, allclose not bitwise,
    so the serving engine pins one path per process."""
    head_dim = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(head_dim)
    t = k.shape[-2]
    if pos is not None and mask is not None:
        raise ValueError("pass pos or mask, not both")
    if pos is None and mask is None:
        pos = t - 1

    if mask is None and q.shape[-2] == 1 \
            and _decode_takes_kernel(t, head_dim, use_flash):
        from .flash_attention import flash_decode

        return flash_decode(q, k, v, jnp.asarray(pos, jnp.int32) + 1,
                            sm_scale=scale)

    if mask is None:
        pos_arr = jnp.asarray(pos, jnp.int32)
        idx = jnp.arange(t, dtype=jnp.int32)
        if pos_arr.ndim == 0:
            live = (idx <= pos_arr)[None, None, None, :]
        else:                                   # [B] per-row positions
            live = (idx[None, :] <= pos_arr[:, None])[:, None, None, :]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k.astype(q.dtype)) * scale
    if mask is None:
        s = jnp.where(live, s.astype(jnp.float32), DECODE_NEG_INF)
    elif mask.dtype == jnp.bool_:
        s = jnp.where(mask, s.astype(jnp.float32), DECODE_NEG_INF)
    else:
        s = s.astype(jnp.float32) + mask
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(q.dtype))


def resident_decode_walk(pos, k_cache):
    """What `resident_decode_attention` derives from the positions alone
    for grouped caches of this shape `[L, S, KVH, D, T]`, ring or not:
    `gqa_decode`'s visit tables over each slot's min(pos + 1, T) live
    columns, the same in every layer of one depth, so a decode step
    builds them once and hands them to each layer's call (`walk=`).
    None where that call does not take the kernel."""
    _, _, kv_heads, head_dim, t = k_cache.shape
    if not _decode_takes_kernel(t, head_dim):
        return None
    from .flash_attention import gqa_walk

    return gqa_walk(jnp.minimum(jnp.asarray(pos, jnp.int32) + 1, t),
                    kv_heads, head_dim, t)


def resident_decode_attention(q, k_new, v_new, k_cache, v_cache, layer,
                              pos, scale=None, ring=False, walk=None):
    """One layer of the decode engine's step on the cache it owns
    (serving/decode.py): write each slot's new column, then attend.

    q, k_new, v_new: [S, H, 1, D], this step's projections; k_cache,
    v_cache: the engine's stacked caches in their resident layout
    [L, S, H, D, T] (K and V transposed, T minor); layer: int32 scalar,
    traced (the loop's counter); pos: int32 [S], each slot's current
    position.  A stale pos >= T (an inactive slot) is clamped for the
    write into the slot's own last column.  Returns (o [S, H, 1, D],
    k_cache, v_cache).

    k_new, v_new and the caches may hold fewer heads than q (grouped
    queries: query head h reads head h // (H / KVH), K and V never
    repeated).  With `ring` the cache is a ring over the last T
    positions (a window layer's): position p lives in column p mod T,
    the step writes column pos mod T and attends min(pos + 1, T)
    columns, in whatever order they lie (the softmax does not mind, and
    a rotary phase is in the stored key).

    Reader and writer both take the stacked cache as it lies, so the
    caller can carry it through its layer loop and the compiled step
    holds one copy of it.  Where `_decode_takes_kernel` holds, grouped
    heads are the one Pallas call `gqa_decode`, which walks each slot's
    live tiles and writes the column into the tile it reads (`walk`:
    `resident_decode_walk(pos, k_cache)`, where the caller built
    it for all its layers), and equal heads the calls `kv_append` and
    `flash_decode`; elsewhere the same mathematics in XLA, through
    `decode_attention`'s own code (which is what keeps the engine
    token-exact against generate())."""
    t = k_cache.shape[-1]
    pos = jnp.asarray(pos, jnp.int32)
    posw = pos % t if ring else jnp.minimum(pos, t - 1)
    live = jnp.minimum(pos + 1, t) if ring else pos + 1
    grouped = k_cache.shape[2] != q.shape[1]
    k_col, v_col = k_new[:, :, 0, :], v_new[:, :, 0, :]     # [S, KVH, D]
    if _decode_takes_kernel(t, q.shape[-1]):
        from .flash_attention import (flash_decode_resident, gqa_decode,
                                      kv_append)

        if grouped:
            return gqa_decode(q, k_col, v_col, k_cache, v_cache, layer,
                              posw, jnp.minimum(pos + 1, t), walk=walk,
                              sm_scale=scale)
        k_cache, v_cache = kv_append(k_cache, v_cache, k_col, v_col,
                                     layer, posw)
        o = flash_decode_resident(q, k_cache, v_cache, layer, live,
                                  sm_scale=scale)
        return o, k_cache, v_cache
    slots = jnp.arange(q.shape[0])
    k_cache = k_cache.at[layer, slots, :, :, posw].set(
        k_col.astype(k_cache.dtype))
    v_cache = v_cache.at[layer, slots, :, :, posw].set(
        v_col.astype(v_cache.dtype))
    if grouped or ring:
        return _grouped_decode(q, k_cache[layer], v_cache[layer], live,
                               scale), k_cache, v_cache
    o = decode_attention(q, jnp.swapaxes(k_cache[layer], -1, -2),
                         jnp.swapaxes(v_cache[layer], -1, -2), pos=pos,
                         scale=scale, use_flash=False)
    return o, k_cache, v_cache


def _grouped_decode(q, k, v, live, scale):
    """The XLA mathematics of `gqa_decode`'s attention: q [S, H, 1, D]
    against one layer k, v [S, KVH, D, T] of which each slot's first
    `live` [S] columns count; float32 scores and softmax as
    `decode_attention`'s."""
    s, h, _, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(s, k.shape[1], -1, d)
    sc = jnp.einsum("skgd,skdt->skgt", qg, k.astype(q.dtype)) * scale
    seen = jnp.arange(k.shape[-1], dtype=jnp.int32)[None, :] < live[:, None]
    sc = jnp.where(seen[:, None, None, :], sc.astype(jnp.float32),
                   DECODE_NEG_INF)
    p = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    return jnp.einsum("skgt,skdt->skgd", p, v.astype(q.dtype)).reshape(
        s, h, 1, d)


def resident_mla_attention(q_latent, q_rope, new, latent, layer, pos,
                           scale, use_kernel=None):
    """One layer of the decode engine's step on a latent (MLA) cache it
    owns: write each slot's new latent column, then attend.

    q_latent [S, H, rank], q_rope [S, H, rope]: this step's queries, the
    first absorbed into the latent space; new [S, rank + rope]: this
    step's `[c_kv ; k_rope]`; latent: the resident cache
    [L, S, rank + rope, T]; layer: a Python int; pos: int32 [S].  A
    stale pos >= T (an inactive slot) is clamped for the write into the
    slot's own last column.  Returns (softmax(scores) . c_kv
    [S, H, rank], latent).

    Under the same predicate as `resident_decode_attention`
    (`_decode_takes_kernel`, on the rotary width, and a rank of whole
    lanes; `use_kernel` as its `use_flash`) both are the one Pallas call
    `mla_decode` (kernels/mla.py), which writes the column into the tile
    it reads; elsewhere the same mathematics in XLA."""
    rank, t = q_latent.shape[-1], latent.shape[-1]
    pos = jnp.asarray(pos, jnp.int32)
    posw = jnp.minimum(pos, t - 1)
    if rank % 128 == 0 and _decode_takes_kernel(t, q_rope.shape[-1],
                                                use_kernel):
        from .mla import mla_decode

        return mla_decode(q_latent, q_rope, new, latent, layer, posw, scale)
    latent = latent.at[layer, jnp.arange(pos.shape[0]), :, posw].set(
        new.astype(latent.dtype))
    c = latent[layer].astype(q_latent.dtype)              # [S, R+r, T]
    s = (jnp.einsum("shc,sct->sht", q_latent, c[:, :rank])
         + jnp.einsum("shr,srt->sht", q_rope.astype(q_latent.dtype),
                      c[:, rank:])) * scale
    live = jnp.arange(t, dtype=jnp.int32)[None, :] <= pos[:, None]
    s = jnp.where(live[:, None, :], s.astype(jnp.float32), DECODE_NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q_latent.dtype)
    return jnp.einsum("sht,sct->shc", p, c[:, :rank]), latent


def dot_product_attention(q, k, v, mask=None, dropout_p=0.0, is_causal=False,
                          scale=None, training=True, rng_key=None,
                          use_flash=None, window=None):
    """q/k/v: [batch, heads, seq, head_dim] -> [batch, heads, seq, head_dim].

    Forward only (`training=False`), causal: k and v may hold fewer
    heads than q (grouped queries, never repeated in memory), and with
    `window` a query sees the `window` keys up to and with its own."""
    head_dim = q.shape[-1]
    served = window is not None or k.shape[1] != q.shape[1]
    if served and (training or not is_causal or mask is not None):
        raise ValueError("grouped heads and a window are served forward "
                         "only: training=False, is_causal=True, no mask")
    scale = scale if scale is not None else 1.0 / math.sqrt(head_dim)

    # the flash kernel supports neither arbitrary masks nor in-kernel
    # dropout, and needs self-attention shapes with block-aligned seq;
    # anything else must take the XLA path even if the caller forced
    # use_flash=True (silent wrong numerics otherwise)
    seq = q.shape[-2]
    can_flash = (
        (dropout_p == 0.0 or not training)
        and mask is None
        and q.shape[-2] == k.shape[-2]
        and seq % 128 == 0
        and head_dim in (64, 128, 256)
        and (window is None or window % 128 == 0)
    )
    forced_flash = use_flash is True
    if use_flash is None:
        # Below ~1k tokens XLA's fused softmax(QK^T)V is faster on-chip
        # (the S^2 matrix still fits cache-friendly tiles); flash wins
        # once the S^2 materialisation starts thrashing HBM (measured
        # crossover on v5e: 512 -> XLA, 2048 -> flash by ~20%).
        # PADDLE_TPU_FORCE_FLASH=0/1 overrides the heuristic for
        # on-chip A/B runs.  (The kernel's tiling is not a switch: it
        # follows from the shapes, flash_attention.flash_tiling.)
        from .backend import is_tpu_backend

        env = os.environ.get("PADDLE_TPU_FORCE_FLASH", "")
        if env:
            use_flash = env.lower() in ("1", "true", "yes")
        else:
            use_flash = (is_tpu_backend() and seq >= 1024)
    if forced_flash and not can_flash:
        warnings.warn(
            "use_flash=True requested but the flash kernel cannot serve this "
            f"call (mask={mask is not None}, dropout={dropout_p}, seq={seq}, "
            f"head_dim={head_dim}; needs no mask, no train-dropout, "
            "self-attention, seq%128==0, head_dim in 64/128/256) — "
            "falling back to the XLA path", stacklevel=2)
    if use_flash and can_flash and served:
        from .flash_attention import flash_attention_fwd

        return flash_attention_fwd(q, k, v, sm_scale=scale, window=window)
    if use_flash and can_flash:
        return _flash_per_shard(q, k, v, is_causal, scale)
    return _xla_attention(q, k, v, mask, scale, is_causal, dropout_p,
                          training, rng_key, window)
