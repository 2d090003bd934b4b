"""Autoregressive decoding engine with KV cache for the GPT family.

Capability beyond the reference (its generative path is beam-search
seq2seq, layers/rnn.py + the machine-translation book model — see
models/seq2seq.py for that parity); this is the TPU-first incremental
decoder for causal LMs:

- STATIC shapes end to end: the cache is a fixed [L, B, H, max_len, D]
  buffer updated with dynamic_update_slice, and generation is ONE
  lax.scan over max_new_tokens — the whole generate() compiles to a
  single XLA program, no per-token retrace/dispatch.
- Prefill processes the whole prompt as one batched causal pass (MXU-
  sized matmuls) and fills the cache; decode steps then attend over the
  cache prefix with a position mask.
- Sampling: greedy, temperature, top-k, nucleus (top-p), all inside
  the scan via jax.random.categorical on masked logits.

Math mirrors models/gpt.py GPT.forward exactly (same param names from
nn.layers.param_dict, same SDPA scale 1/sqrt(head_dim), fp32 softmax)
— tested token-exact against the cache-free model, for dense-FFN and
MoE configs alike (decode steps use drop-free expert capacity; parity
with a full-forward recompute holds when the recompute's capacity does
not bind either — see _block_tail).
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn.layers import param_dict

__all__ = ["DecodeParams", "build_decode_params", "prefill",
           "decode_step", "generate", "beam_search", "init_cache"]


class DecCfg(NamedTuple):
    """Hashable static geometry (jit static arg; GPTConfig itself is an
    unhashable dataclass and must not ride the pytree)."""
    hidden_size: int
    num_heads: int
    num_layers: int
    max_seq_len: int
    dtype: str
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25

    @classmethod
    def from_model_cfg(cls, cfg):
        return cls(cfg.hidden_size, cfg.num_heads, cfg.num_layers,
                   cfg.max_seq_len, cfg.dtype, cfg.moe_top_k,
                   cfg.moe_capacity_factor)

    # -- the decode engine's seam (serving/decode.py, "The seam") ------
    cache_kind = "kv [layers, slots, heads, head_dim, max_len]"

    def cache_arrays(self, slots, max_len):
        """K and V in the resident layout: transposed, depth minor."""
        kv = (self.num_layers, slots, self.num_heads,
              self.hidden_size // self.num_heads, max_len)
        return {"k": jnp.zeros(kv, self.dtype),
                "v": jnp.zeros(kv, self.dtype)}

    def prefill(self, trees, cache, prompt, true_len, slot):
        return _slot_prefill(DecodeParams(*trees, self), cache, prompt,
                             true_len, slot)

    def decode(self, trees, cache, token, pos, active=None):
        return _slot_decode(DecodeParams(*trees, self), cache, token, pos)

    def head(self, trees, hidden):
        return jnp.einsum("bh,vh->bv", hidden, trees[0]["wte.weight"])


class DecodeParams(NamedTuple):
    """Stacked decode-ready parameters: emb/head plain dicts, blocks
    stacked [L, ...] for lax.scan over layers; cfg is a static DecCfg
    (kept out of jit traces via static args)."""
    emb: dict
    blocks: dict
    head: dict
    cfg: DecCfg

    @property
    def trees(self):
        return self.emb, self.blocks, self.head


def build_decode_params(model):
    """GPT -> DecodeParams (concrete arrays; reusable across calls).

    MoE configs decode too: top-k expert CHOICE is per-token, but the
    capacity-drop mask is cohort-dependent, so decode steps route with
    drop-free capacity (cap = cohort size; see _block_tail) — cached
    decode then matches a full-forward recompute exactly whenever that
    recompute's own capacity does not bind."""
    from ..distributed.pipeline import stack_block_params

    flat = param_dict(model)
    emb = {n: v for n, v in flat.items()
           if n.startswith(("wte.", "wpe."))}
    head = {n: v for n, v in flat.items() if n.startswith("norm_f.")}
    blocks = stack_block_params([param_dict(b) for b in model.blocks])
    return DecodeParams(emb, blocks, head,
                        DecCfg.from_model_cfg(model.cfg))


def init_cache(cfg, batch, max_len, dtype=None):
    """Fixed-size KV buffer [L, B, H, max_len, D] (+ f32-safe dtype)."""
    dtype = dtype or cfg.dtype
    head_dim = cfg.hidden_size // cfg.num_heads
    shape = (cfg.num_layers, batch, cfg.num_heads, max_len, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _split_heads(x, num_heads):
    b, s, e = x.shape
    return jnp.transpose(x.reshape(b, s, num_heads, e // num_heads),
                         (0, 2, 1, 3))


def _block_tail(x, attn_out, bp, cfg, decode=False):
    """Residual + MLP/MoE shared by prefill and decode (GPTBlock.forward
    with dropout off).

    MoE capacity: prefill keeps cfg.moe_capacity_factor so the prompt
    pass matches the training forward bit-for-bit; decode steps raise
    the factor to E/k (cap = cohort size) so NO token is ever
    capacity-dropped — small per-step cohorts have high load-fraction
    variance and would otherwise drop more often than training cohorts,
    silently degrading generation."""
    x = x + attn_out @ bp["attn.out_proj.weight"] \
        + bp["attn.out_proj.bias"]
    h = F.layer_norm(x, [x.shape[-1]], bp["norm2.weight"],
                     bp["norm2.bias"])
    if "moe.wg" in bp:
        from ..distributed.moe import moe_ffn

        factor = cfg.moe_capacity_factor
        if decode:
            n_experts = bp["moe.wg"].shape[-1]
            factor = max(factor, n_experts / cfg.moe_top_k)
        ff, _ = moe_ffn({"wg": bp["moe.wg"], "w1": bp["moe.w1"],
                         "w2": bp["moe.w2"]}, h, k=cfg.moe_top_k,
                        capacity_factor=factor)
    else:
        ff = F.gelu(h @ bp["fc1.weight"] + bp["fc1.bias"]) \
            @ bp["fc2.weight"] + bp["fc2.bias"]
    return x + ff


def _qkv(hn, bp, num_heads):
    q = _split_heads(hn @ bp["attn.q_proj.weight"]
                     + bp["attn.q_proj.bias"], num_heads)
    k = _split_heads(hn @ bp["attn.k_proj.weight"]
                     + bp["attn.k_proj.bias"], num_heads)
    v = _split_heads(hn @ bp["attn.v_proj.weight"]
                     + bp["attn.v_proj.bias"], num_heads)
    return q, k, v


def _merge_heads(o):
    b, h, s, d = o.shape
    return jnp.transpose(o, (0, 2, 1, 3)).reshape(b, s, h * d)


def _slot_prefill(params, cache, prompt, true_len, slot):
    """The decode engine's prefill of one request into one slot, at a
    static bucket shape (`DecCfg.prefill`).

    `prompt` is [1, bucket] zero-padded; causal masking makes the pad
    columns exactly inert for the real positions (masked scores
    underflow to f32 zero), and MoE routes DROP-FREE (cap = cohort
    size) so pad tokens cannot displace real ones — the first emitted
    token is bitwise what generate()'s unpadded prefill emits.  Returns
    (cache, the final hidden state at the true last position [1, H],
    no counters)."""
    cfg = params.cfg
    bucket = prompt.shape[1]
    pos = jnp.arange(bucket, dtype=jnp.int32)[None, :]
    x = jnp.take(params.emb["wte.weight"], prompt, axis=0) \
        + jnp.take(params.emb["wpe.weight"], pos, axis=0)

    def layer(x, bp):
        hn = F.layer_norm(x, [cfg.hidden_size], bp["norm1.weight"],
                          bp["norm1.bias"])
        q, k, v = _qkv(hn, bp, cfg.num_heads)
        o = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                           training=False)
        return _block_tail(x, _merge_heads(o), bp, cfg,
                           decode=True), (k, v)

    x, (ks, vs) = jax.lax.scan(layer, x, params.blocks)
    # ks: [L, 1, H, bucket, D], transposed into the resident layout
    # [L, 1, H, D, bucket] (on the device the scan's output is held
    # bucket minor already, so the transpose moves nothing) and dropped
    # into columns [0, bucket) of this slot's region [:, slot] of the
    # donated cache.  Columns from `bucket` on keep the last tenant's
    # values: none is attended before the decode step that writes it
    # (see serving/decode.py _decode_step_impl)
    def into_slot(cache, new):
        return jax.lax.dynamic_update_slice(
            cache, jnp.swapaxes(new, -1, -2).astype(cache.dtype),
            (0, slot, 0, 0, 0))

    cache = {"k": into_slot(cache["k"], ks),
             "v": into_slot(cache["v"], vs)}
    x = F.layer_norm(x, [cfg.hidden_size], params.head["norm_f.weight"],
                     params.head["norm_f.bias"])
    # the TRUE last prompt position (LN is per-position, so slicing
    # before the head matches generate()'s slice-after bitwise)
    h = jax.lax.dynamic_slice(
        x, (0, true_len - 1, 0), (1, 1, cfg.hidden_size))[:, 0]
    return cache, h, {}


def _slot_decode(params, cache, token, pos):
    """One full-width step of the decode engine over every slot
    (`DecCfg.decode`): token [S] at per-slot positions pos [S] ->
    (cache, final hidden states [S, H], no counters)."""
    from ..kernels.attention import resident_decode_attention

    cfg = params.cfg
    scale = 1.0 / (cfg.hidden_size // cfg.num_heads) ** 0.5
    x = jnp.take(params.emb["wte.weight"], token[:, None], axis=0) \
        + jnp.take(params.emb["wpe.weight"], pos, axis=0)[:, None, :]

    def layer(carry, xs):
        # the stacked caches [L, S, H, D, T] ride the carry whole: the
        # layer's reader and writer address layer `l` inside them
        x, k_cache, v_cache = carry
        bp, l = xs
        hn = F.layer_norm(x, [cfg.hidden_size], bp["norm1.weight"],
                          bp["norm1.bias"])
        q, k, v = _qkv(hn, bp, cfg.num_heads)      # [S, H, 1, D]
        # per-slot ragged positions; off the kernel path the SAME
        # single-query math generate() decodes with — the
        # token-exactness hinge
        o, k_cache, v_cache = resident_decode_attention(
            q, k, v, k_cache, v_cache, l, pos, scale=scale)
        x = _block_tail(x, _merge_heads(o), bp, cfg, decode=True)
        return (x, k_cache, v_cache), None

    (x, ks, vs), _ = jax.lax.scan(
        layer, (x, cache["k"], cache["v"]),
        (params.blocks, jnp.arange(cache["k"].shape[0], dtype=jnp.int32)))
    x = F.layer_norm(x, [cfg.hidden_size], params.head["norm_f.weight"],
                     params.head["norm_f.bias"])
    return {"k": ks, "v": vs}, x[:, -1], {}


def prefill(params: DecodeParams, input_ids, cache, cfg=None):
    """Full-prompt causal pass; returns (last-position logits [B, V],
    cache filled at [..., :S, :])."""
    cfg = cfg or params.cfg
    seq = input_ids.shape[1]
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :]
    x = jnp.take(params.emb["wte.weight"], input_ids, axis=0) \
        + jnp.take(params.emb["wpe.weight"], pos, axis=0)

    def layer(x, bp):
        hn = F.layer_norm(x, [cfg.hidden_size], bp["norm1.weight"],
                          bp["norm1.bias"])
        q, k, v = _qkv(hn, bp, cfg.num_heads)
        o = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                           training=False)
        return _block_tail(x, _merge_heads(o), bp, cfg), (k, v)

    x, (ks, vs) = jax.lax.scan(layer, x, params.blocks)
    cache = {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0)),
    }
    x = F.layer_norm(x, [cfg.hidden_size], params.head["norm_f.weight"],
                     params.head["norm_f.bias"])
    logits = jnp.einsum("bh,vh->bv", x[:, -1], params.emb["wte.weight"])
    return logits, cache


def decode_step(params: DecodeParams, token, cache, pos, cfg=None):
    """One incremental step: token [B] at position pos (scalar) ->
    (logits [B, V], updated cache)."""
    from ..kernels.attention import decode_attention

    cfg = cfg or params.cfg
    scale = 1.0 / (cfg.hidden_size // cfg.num_heads) ** 0.5
    x = jnp.take(params.emb["wte.weight"], token[:, None], axis=0) \
        + params.emb["wpe.weight"][pos][None, None, :]

    def layer(x, xs):
        bp, k_cache, v_cache = xs
        hn = F.layer_norm(x, [cfg.hidden_size], bp["norm1.weight"],
                          bp["norm1.bias"])
        q, k, v = _qkv(hn, bp, cfg.num_heads)      # [B, H, 1, D]
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, 0, pos, 0))
        # the shared single-query kernel (kernels/attention.py): same
        # inline math this function used to carry — serving/decode.py
        # calls the identical code path, which is what makes the
        # engine's token-exactness vs generate() structural
        o = decode_attention(q, k_cache, v_cache, pos=pos, scale=scale)
        return _block_tail(x, _merge_heads(o), bp, cfg,
                           decode=True), (k_cache, v_cache)

    x, (ks, vs) = jax.lax.scan(
        layer, x, (params.blocks, cache["k"], cache["v"]))
    cache = {"k": ks, "v": vs}
    x = F.layer_norm(x, [cfg.hidden_size], params.head["norm_f.weight"],
                     params.head["norm_f.bias"])
    logits = jnp.einsum("bh,vh->bv", x[:, -1], params.emb["wte.weight"])
    return logits, cache


def _sample(logits, key, temperature, top_k, top_p):
    """Masked categorical draw; temperature<=0 means greedy."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        # clamp to [1, vocab]: either end would crash lax.top_k /
        # broadcasting deep in the trace
        kth = jax.lax.top_k(
            logits,
            max(1, min(int(top_k), logits.shape[-1])))[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p is not None:
        sorted_l = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest prefix with mass >= top_p stays; find its cutoff logit
        keep = cum - probs < top_p
        cutoff = jnp.min(jnp.where(keep, sorted_l, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "max_new_tokens", "temperature", "top_k", "top_p"))
def _generate_jit(trees, cfg, prompt_ids, max_new_tokens, temperature,
                  top_k, top_p, key):
    params = DecodeParams(*trees, cfg)
    batch, prompt_len = prompt_ids.shape
    cache = init_cache(cfg, batch, prompt_len + max_new_tokens)
    logits, cache = prefill(params, prompt_ids, cache, cfg)
    first = _sample(logits, key, temperature, top_k, top_p)

    def step(carry, i):
        token, cache, key = carry
        key, sub = jax.random.split(key)
        logits, cache = decode_step(params, token, cache,
                                    prompt_len + i, cfg)
        nxt = _sample(logits, sub, temperature, top_k, top_p)
        return (nxt, cache, key), nxt

    (_, _, _), rest = jax.lax.scan(
        step, (first, cache, key), jnp.arange(max_new_tokens - 1))
    return jnp.concatenate([first[:, None], rest.T], axis=1)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "max_new_tokens", "temperature", "top_k", "top_p", "eos_id"))
def _generate_eos_jit(trees, cfg, prompt_ids, max_new_tokens, temperature,
                      top_k, top_p, key, eos_id):
    """Greedy/sampled decode with EOS early exit: a lax.while_loop that
    stops as soon as EVERY row has emitted eos_id, so a batch whose
    sequences finish early doesn't pay the full max_new_tokens of
    decode steps (serving latency; the fixed-length scan above stays
    the jit-friendliest shape for benchmarking/throughput).  Finished
    rows keep emitting eos_id (the reference decoder's
    end-of-sentence semantics)."""
    params = DecodeParams(*trees, cfg)
    batch, prompt_len = prompt_ids.shape
    cache = init_cache(cfg, batch, prompt_len + max_new_tokens)
    logits, cache = prefill(params, prompt_ids, cache, cfg)
    first = _sample(logits, key, temperature, top_k, top_p)
    out = jnp.full((batch, max_new_tokens), eos_id, jnp.int32)
    out = out.at[:, 0].set(first)
    done = first == eos_id

    def cond(carry):
        i, _, _, _, done, _ = carry
        return jnp.logical_and(i < max_new_tokens,
                               jnp.logical_not(done.all()))

    def body(carry):
        i, token, cache, key, done, out = carry
        key, sub = jax.random.split(key)
        logits, cache = decode_step(params, token, cache,
                                    prompt_len + i - 1, cfg)
        nxt = _sample(logits, sub, temperature, top_k, top_p)
        nxt = jnp.where(done, eos_id, nxt)
        out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, i))
        return (i + 1, nxt, cache, key,
                jnp.logical_or(done, nxt == eos_id), out)

    _, _, _, _, _, out = jax.lax.while_loop(
        cond, body, (jnp.int32(1), first, cache, key, done, out))
    return out


@functools.partial(jax.jit, static_argnames=(
    "cfg", "beam_size", "max_new_tokens", "eos_id"))
def _beam_search_jit(trees, cfg, prompt_ids, beam_size, max_new_tokens,
                     eos_id, length_penalty):
    params = DecodeParams(*trees, cfg)
    batch, prompt_len = prompt_ids.shape
    K, V = beam_size, params.emb["wte.weight"].shape[0]
    neg = jnp.float32(-1e30)

    cache = init_cache(cfg, batch, prompt_len + max_new_tokens)
    logits0, cache = prefill(params, prompt_ids, cache)
    # beams live flattened [B*K] row-major; tile the prompt cache
    cache = {k: jnp.repeat(v, K, axis=1) for k, v in cache.items()}

    def beam_update(logp, finished, logits_bkv):
        """One beam step: extend each live beam by every token, keep
        the global top-K per batch.  Finished beams may only extend
        with eos at zero added score (standard freeze)."""
        logp_tok = jax.nn.log_softmax(
            logits_bkv.astype(jnp.float32), axis=-1)
        if eos_id is not None:
            frozen = jnp.full((V,), neg).at[eos_id].set(0.0)
            logp_tok = jnp.where(finished[..., None], frozen, logp_tok)
        total = logp[..., None] + logp_tok           # [B, K, V]
        top, idx = jax.lax.top_k(total.reshape(batch, K * V), K)
        parent = idx // V                            # [B, K]
        token = (idx % V).astype(jnp.int32)
        fin_new = jnp.take_along_axis(finished, parent, axis=1)
        if eos_id is not None:
            fin_new = fin_new | (token == eos_id)
        return top, parent, token, fin_new

    # first expansion: only beam 0 is live so the top-K are K DISTINCT
    # first tokens of the single prompt continuation
    logp0 = jnp.full((batch, K), neg).at[:, 0].set(0.0)
    fin0 = jnp.zeros((batch, K), bool)
    logits_bkv = jnp.broadcast_to(logits0[:, None, :], (batch, K, V))
    logp, parent, token, finished = beam_update(logp0, fin0, logits_bkv)

    seqs = jnp.full((batch, K, max_new_tokens),
                    eos_id if eos_id is not None else 0, jnp.int32)
    seqs = seqs.at[:, :, 0].set(token)
    lens = jnp.ones((batch, K), jnp.float32)
    boffs = (jnp.arange(batch) * K)[:, None]

    def reorder(cache, parent):
        flat = (boffs + parent).reshape(-1)          # [B*K] global rows
        return {k: v[:, flat] for k, v in cache.items()}

    cache = reorder(cache, parent)

    def step(carry, i):
        token, cache, logp, finished, seqs, lens = carry
        logits, cache = decode_step(params, token.reshape(-1), cache,
                                    prompt_len + i)
        logp, parent, tok_new, fin_new = beam_update(
            logp, finished, logits.reshape(batch, K, V))
        cache = reorder(cache, parent)
        seqs = jnp.take_along_axis(seqs, parent[..., None], axis=1)
        seqs = seqs.at[:, :, i + 1].set(tok_new)
        was_fin = jnp.take_along_axis(finished, parent, axis=1)
        lens = jnp.take_along_axis(lens, parent, axis=1) \
            + (~was_fin).astype(jnp.float32)
        return (tok_new, cache, logp, fin_new, seqs, lens), None

    (token, cache, logp, finished, seqs, lens), _ = jax.lax.scan(
        step, (token, cache, logp, finished, seqs, lens),
        jnp.arange(max_new_tokens - 1))

    # GNMT-style normalization at final ranking; length_penalty is a
    # TRACED float (0.0 -> exponent 0 -> divisor 1), so sweeping it
    # reuses one compiled program
    scores = logp / (((5.0 + lens) / 6.0) ** length_penalty)
    order = jnp.argsort(-scores, axis=1)
    return (jnp.take_along_axis(seqs, order[..., None], axis=1),
            jnp.take_along_axis(scores, order, axis=1))


def beam_search(model_or_params, prompt_ids, beam_size, max_new_tokens,
                eos_id: Optional[int] = None,
                length_penalty: float = 0.0):
    """KV-cached beam search: (sequences [B, beam, T], scores [B, beam])
    sorted best-first.  The generative identity of the reference
    (layers.beam_search / dynamic_decode BeamSearchDecoder,
    layers/rnn.py) rebuilt on the static-shape cache decoder: beams ride
    flattened into the batch dim, the cache reorders by parent beam via
    one gather per step, and the whole search is a single lax.scan.

    Scores are summed token log-probs; `length_penalty` > 0 applies the
    GNMT normalization at final ranking.  With `eos_id`, finished beams
    freeze (eos-padded, score unchanged)."""
    params, prompt_ids = _resolve_and_check(model_or_params, prompt_ids,
                                            max_new_tokens)
    if beam_size < 1:
        raise ValueError("beam_size must be >= 1")
    vocab = params.emb["wte.weight"].shape[0]
    if beam_size > vocab:
        # the first expansion has only `vocab` live candidates; wider
        # beams would fill from dead -inf rows and return garbage
        raise ValueError(
            f"beam_size {beam_size} exceeds vocab_size {vocab}")
    return _beam_search_jit(
        (params.emb, params.blocks, params.head), params.cfg,
        prompt_ids, int(beam_size), int(max_new_tokens),
        None if eos_id is None else int(eos_id), float(length_penalty))


def _resolve_and_check(model_or_params, prompt_ids, max_new_tokens):
    """Shared generate/beam_search preamble: params resolution + the
    sequence-budget guards."""
    params = (model_or_params
              if isinstance(model_or_params, DecodeParams)
              else build_decode_params(model_or_params))
    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    total = prompt_ids.shape[1] + max_new_tokens
    if total > params.cfg.max_seq_len:
        raise ValueError(
            f"prompt+new = {total} exceeds max_seq_len "
            f"{params.cfg.max_seq_len}")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    return params, prompt_ids


def generate(model_or_params, prompt_ids, max_new_tokens,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None, rng_key=None, eos_id=None):
    """Generate [B, max_new_tokens] continuations of prompt_ids [B, S].

    One compiled program per (shape, sampling-config); defaults to
    greedy.  temperature > 0 enables sampling (pass rng_key for
    reproducibility).  eos_id engages early exit: decode stops the
    moment every row has emitted eos_id (a lax.while_loop instead of
    the fixed-length scan), and finished rows pad with eos_id."""
    params, prompt_ids = _resolve_and_check(model_or_params, prompt_ids,
                                            max_new_tokens)
    key = rng_key if rng_key is not None else jax.random.PRNGKey(0)
    trees = (params.emb, params.blocks, params.head)
    if eos_id is not None:
        return _generate_eos_jit(trees, params.cfg, prompt_ids,
                                 max_new_tokens, float(temperature),
                                 top_k, top_p, key, int(eos_id))
    return _generate_jit(trees, params.cfg, prompt_ids, max_new_tokens,
                         float(temperature), top_k, top_p, key)
