"""The Kimi-K2 / DeepSeek-V3 decoder as a served model: the decode
engine's seam (serving/decode.py, "The seam") over a latent cache.

The layer, as published (huggingface moonshotai/Kimi-K2.6 `config.json`,
`model_type: kimi_k2`, which is DeepSeek-V3's):

- RMSNorm everywhere; no bias anywhere; untied head.
- Attention is MLA: queries through a rank-`q_lora_rank` bottleneck with
  a norm; keys and values from one normalised latent `c_kv`
  (`kv_lora_rank`) per token, plus one rotary key `k_rope`
  (`qk_rope_head_dim`) per token that all heads share.  Rotary positions
  are YaRN's (`yarn_inv_freq`), on the rotary slice only, pairing lanes
  (2i, 2i+1) as DeepSeek-V3 does; the softmax scale carries YaRN's
  `mscale_all_dim` squared.
- The first `first_k_dense` layers have a dense SwiGLU; the others a
  router over `n_routed` experts (sigmoid scores, a selection bias, top
  `num_experts_per_tok`, weights normalised and scaled:
  `distributed/moe.py` `routed_experts`) and a shared expert.

Two forms of the same attention.  A prefill computes K and V of every
head from the latent (the published form) and writes the latent, not K
and V, into its slot.  A decode step never builds K or V: with
`W_kvb^h = [W_uk^h ; W_uv^h]`, `q~_h = q_nope_h W_uk^h^T` gives scores
`q~_h . c_kv + q_rope_h . k_rope`, and `o_h = (P_h c_kv) W_uv^h`
(`kernels/attention.py` `resident_mla_attention`: the Pallas call
`mla_decode` on the TPU, which writes the step's column and attends).

The cache is one resident array `[layers, slots, kv_lora_rank +
qk_rope_head_dim, max_len]`, depth minor (the 576 rows are whole
sublane tiles; as the minor dimension they would be padded to 640
lanes), holding `[c_kv ; k_rope]` after the norm and the rotation.  The
layers are unrolled (a leading dense layer and expert layers are not one
homogeneous scan, and each layer's weights are arrays of their own, so
no program slices a layer out of a stack); the cache is carried whole
from layer to layer and written in place.

A chip holds `experts_held` of the `n_routed` experts, from
`first_expert`: one chip's share of an expert-parallel deployment.  What
the absent experts would add is left out, and that partial result goes on
to the next layer; nothing stands in for the other chips.
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (expert_counters, expert_ffn, expert_layers_kept,
                     rms_norm, swiglu)

__all__ = ["K2Cfg", "K2Params", "param_shapes", "init_params",
           "yarn_inv_freq", "full_logits"]


class K2Cfg(NamedTuple):
    """Hashable static geometry, and the decode engine's seam."""
    vocab_size: int
    hidden_size: int
    num_layers: int
    first_k_dense: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed: int                 # the router's width: the deployment's
    experts_held: int             # held on this chip ...
    first_expert: int             # ... from this one on
    num_experts_per_tok: int
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    # (factor, original_max_position_embeddings, beta_fast, beta_slow,
    #  mscale, mscale_all_dim)
    yarn: tuple
    max_seq_len: int
    dtype: str

    @classmethod
    def from_hf(cls, c, max_seq_len=None):
        """From a dict under the source's `config.json` keys.
        `n_routed_experts` counts the experts held here; the router's
        width is `n_routed_experts_deployment` where that differs."""
        y = c["rope_scaling"]
        return cls(
            c["vocab_size"], c["hidden_size"], c["num_hidden_layers"],
            c["first_k_dense_replace"], c["num_attention_heads"],
            c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"],
            c.get("n_routed_experts_deployment", c["n_routed_experts"]),
            c["n_routed_experts"], c.get("first_expert", 0),
            c["num_experts_per_tok"], float(c["routed_scaling_factor"]),
            float(c["rms_norm_eps"]), float(c["rope_theta"]),
            (float(y["factor"]), int(y["original_max_position_embeddings"]),
             float(y["beta_fast"]), float(y["beta_slow"]),
             float(y["mscale"]), float(y["mscale_all_dim"])),
            int(max_seq_len or c["max_position_embeddings"]),
            c.get("dtype", "bfloat16"))

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        factor, _, _, _, _, all_dim = self.yarn
        m = yarn_mscale(factor, all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m

    # -- the decode engine's seam --------------------------------------
    cache_kind = "latent [layers, slots, kv_lora_rank + rope, max_len]"

    def cache_arrays(self, slots, max_len):
        return {"latent": jnp.zeros(
            (self.num_layers, slots, self.latent_width, max_len),
            self.dtype)}

    def cache_walk(self, lengths, slots, max_len):
        """What `mla_decode` walks in one layer of a decode step whose
        active slots hold `lengths` cached positions, the step's own
        among them: `latent_tiles`, beside the `latent_grid` of tiles
        that a rectangle over every slot's whole depth holds.  Nothing
        where the kernel does not tile the latent (`kernels/attention.py`
        `resident_mla_attention`)."""
        from ..kernels.flash_attention import tiles_walked
        from ..kernels.mla import mla_tiling

        if max_len % 128 or self.kv_lora_rank % 128:
            return {}
        tile = mla_tiling(self.latent_width, max_len).tile
        return {"latent_tiles": tiles_walked(lengths, tile),
                "latent_grid": slots * (max_len // tile)}

    def expert_layers(self, tokens):
        """The expert layers of a program over `tokens` tokens (a decode
        step's slots, a prefill's bucket) whose shape has
        `routed_experts`' kept case."""
        return expert_layers_kept(
            tokens, self.num_experts_per_tok, self.experts_held,
            self.n_routed, self.num_layers - self.first_k_dense)

    def prefill(self, trees, cache, prompt, true_len, slot):
        return _prefill(self, trees, cache, prompt, true_len, slot)

    def decode(self, trees, cache, token, pos, active=None):
        return _decode(self, trees, cache, token, pos)

    def head(self, trees, hidden):
        return hidden @ trees["lm_head"]


class K2Params(NamedTuple):
    """What `DecodeEngine` takes: the arrays and the static geometry."""
    trees: dict
    cfg: K2Cfg

    @classmethod
    def from_flat(cls, cfg, flat):
        """{name: array} under `param_shapes`' names -> the program's
        trees: the same arrays, a dict for each layer."""
        layers = []
        for i in range(cfg.num_layers):
            pre = f"layers.{i}."
            layers.append({n[len(pre):]: v for n, v in flat.items()
                           if n.startswith(pre)})
        return cls({"embed": flat["embed"], "layers": layers,
                    "final_norm": flat["final_norm"],
                    "lm_head": flat["lm_head"]}, cfg)


def param_shapes(cfg):
    """{name: (shape, kind)}; matrices are stored [in, out]."""
    h, heads = cfg.hidden_size, cfg.num_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    out = {"embed": ((cfg.vocab_size, h), "matrix")}
    for i in range(cfg.num_layers):
        p = f"layers.{i}."
        out.update({
            p + "attn_norm": ((h,), "gain"),
            p + "q_a": ((h, cfg.q_lora_rank), "matrix"),
            p + "q_a_norm": ((cfg.q_lora_rank,), "gain"),
            p + "q_b": ((cfg.q_lora_rank, heads * qk), "matrix"),
            p + "kv_a": ((h, cfg.latent_width), "matrix"),
            p + "kv_a_norm": ((cfg.kv_lora_rank,), "gain"),
            p + "kv_b": ((cfg.kv_lora_rank, heads * (
                cfg.qk_nope_head_dim + cfg.v_head_dim)), "matrix"),
            p + "o": ((heads * cfg.v_head_dim, h), "matrix"),
            p + "ffn_norm": ((h,), "gain"),
        })
        if i < cfg.first_k_dense:
            f = cfg.intermediate_size
            out.update({p + "gate_up": ((h, 2 * f), "matrix"),
                        p + "down": ((f, h), "matrix")})
        else:
            f, e = cfg.moe_intermediate_size, cfg.experts_held
            out.update({
                p + "router": ((h, cfg.n_routed), "matrix"),
                p + "router_bias": ((cfg.n_routed,), "bias"),
                p + "shared_gate_up": ((h, 2 * f), "matrix"),
                p + "shared_down": ((f, h), "matrix"),
                p + "experts_gate_up": ((e, h, 2 * f), "matrix"),
                p + "experts_down": ((e, f, h), "matrix"),
            })
    out["final_norm"] = ((h,), "gain")
    out["lm_head"] = ((h, cfg.vocab_size), "matrix")
    return out


def init_params(cfg, key, std=0.02, bias_std=0.02):
    """Seeded random weights under `param_shapes`' names: matrices
    N(0, std), gains 1 + N(0, std), the router's selection bias
    N(0, bias_std) in float32 (small against the scores' spread and not
    zero, so that choice and weight differ)."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, (shape, kind)) in zip(keys, shapes.items()):
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "bias":
            out[name] = z * bias_std
        else:
            out[name] = ((1.0 if kind == "gain" else 0.0)
                         + z * std).astype(cfg.dtype)
    return out


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def yarn_mscale(factor, mscale):
    """`yarn_get_mscale` of DeepSeek-V3's modelling file."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim, theta, yarn):
    """YaRN's rotary frequencies [dim / 2] (DeepSeek-V3's
    `DeepseekV3YarnRotaryEmbedding`): the published frequencies for the
    lanes that turn more than `beta_fast` times in the original context,
    those divided by `factor` for the lanes that turn less than
    `beta_slow` times, a linear blend between."""
    factor, original, beta_fast, beta_slow, _, _ = yarn
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra.astype(np.float32)

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _rope(cfg, x, pos):
    """Rotate x [..., rope] at positions pos (the leading dimensions of
    x, or broadcastable to them): lanes (2i, 2i+1) are a pair; the result
    holds the pairs' first halves, then their second halves."""
    factor, _, _, _, mscale, all_dim = cfg.yarn
    # the published scale of cos and sin: 1 where the two are equal
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, all_dim)
    angle = pos.astype(jnp.float32)[..., None] * yarn_inv_freq(
        cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn)
    cos, sin = jnp.cos(angle) * m, jnp.sin(angle) * m
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _ffn(cfg, lp, h, counters, valid=None):
    """The layer's FFN of tokens h [N, H]: dense, or routed + shared."""
    if "router" not in lp:
        return swiglu(h, lp["gate_up"], lp["down"]), counters
    return expert_ffn(h, lp, lp["router_bias"], (
        cfg.first_expert, cfg.n_routed, cfg.num_experts_per_tok,
        cfg.routed_scaling_factor), counters, valid)


def _queries_and_latent(cfg, lp, h, pos):
    """h [N, H] at positions pos [N] -> (q_nope [N, heads, nope], q_rope
    [N, heads, rope], latent [N, rank + rope]), rotated and normalised."""
    n = h.shape[0]
    cq = rms_norm(cfg, h @ lp["q_a"], lp["q_a_norm"])
    q = (cq @ lp["q_b"]).reshape(n, cfg.num_heads, -1)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = _rope(cfg, q[..., cfg.qk_nope_head_dim:], pos[:, None])
    kv = h @ lp["kv_a"]
    c_kv = rms_norm(cfg, kv[:, :cfg.kv_lora_rank], lp["kv_a_norm"])
    k_rope = _rope(cfg, kv[:, cfg.kv_lora_rank:], pos)
    return q_nope, q_rope.astype(h.dtype), jnp.concatenate(
        [c_kv, k_rope.astype(h.dtype)], axis=-1)


def _kv_b(cfg, lp):
    """W_kvb as (W_uk [rank, heads, nope], W_uv [rank, heads, v])."""
    w = lp["kv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _decode(cfg, trees, cache, token, pos):
    """One step of every slot: token [S] at pos [S] -> (cache, final
    hidden [S, H], counters: `expert_counts` int32 [held] and
    `expert_layers_kept` int32 [])."""
    from ..kernels.attention import resident_mla_attention

    latent = cache["latent"]
    x = jnp.take(trees["embed"], token, axis=0)
    counters = expert_counters(cfg.experts_held)
    for l, lp in enumerate(trees["layers"]):
        h = rms_norm(cfg, x, lp["attn_norm"])
        q_nope, q_rope, new = _queries_and_latent(cfg, lp, h, pos)
        w_uk, w_uv = _kv_b(cfg, lp)
        q_latent = jnp.einsum("shd,chd->shc", q_nope, w_uk)
        o, latent = resident_mla_attention(
            q_latent, q_rope, new, latent, l, pos, cfg.softmax_scale)
        o = jnp.einsum("shc,chd->shd", o, w_uv)
        x = x + o.reshape(o.shape[0], -1) @ lp["o"]
        y, counters = _ffn(cfg, lp, rms_norm(cfg, x, lp["ffn_norm"]),
                           counters)
        x = x + y
    return {"latent": latent}, rms_norm(cfg, x, trees["final_norm"]), \
        counters


def _pad_heads(x, width):
    return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, width - x.shape[-1])))


def _causal_attention(q, k, v, scale):
    """q, k [1, heads, N, nope + rope], v [1, heads, N, v] -> [1, heads,
    N, v] through the package's attention dispatch.  Its flash kernel
    takes one head size of 64, 128 or 256 for q, k and v alike, so the
    three are zero-padded to the next of those (zeros add nothing to a
    score, and the output's padding is cut off)."""
    from ..kernels.attention import dot_product_attention

    widest = max(q.shape[-1], v.shape[-1])
    width = next((w for w in (64, 128, 256) if w >= widest), widest)
    o = dot_product_attention(
        _pad_heads(q, width), _pad_heads(k, width), _pad_heads(v, width),
        is_causal=True, scale=scale, training=False)
    return o[..., :v.shape[-1]]


def _forward(cfg, trees, ids, valid):
    """The published form over one sequence ids [N] (positions 0..N-1,
    causal): (hidden [N, H] before the final norm, each layer's latent
    [N, rank + rope], counters).  Tokens that are not `valid` make no
    expert assignment."""
    n = ids.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    x = jnp.take(trees["embed"], ids, axis=0)
    counters = expert_counters(cfg.experts_held)
    latents = []
    for lp in trees["layers"]:
        h = rms_norm(cfg, x, lp["attn_norm"])
        q_nope, q_rope, new = _queries_and_latent(cfg, lp, h, pos)
        latents.append(new)
        w_uk, w_uv = _kv_b(cfg, lp)
        c_kv = new[:, :cfg.kv_lora_rank]
        k_nope = jnp.einsum("nc,chd->hnd", c_kv, w_uk)
        v = jnp.einsum("nc,chd->hnd", c_kv, w_uv)
        k_rope = jnp.broadcast_to(new[None, :, cfg.kv_lora_rank:],
                                  (cfg.num_heads, n, cfg.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1).swapaxes(0, 1)
        k = jnp.concatenate([k_nope, k_rope], axis=-1)
        o = _causal_attention(q[None], k[None], v[None],
                              cfg.softmax_scale)[0]            # [H, N, v]
        x = x + o.swapaxes(0, 1).reshape(n, -1) @ lp["o"]
        y, counters = _ffn(cfg, lp, rms_norm(cfg, x, lp["ffn_norm"]),
                           counters, valid=valid)
        x = x + y
    return x, latents, counters


def full_logits(cfg, trees, ids):
    """Logits [N, vocab] of every position of one sequence ids [N]: the
    published form, no cache (what the engine's tokens are held against
    where no float32 reference fits: chip_smoke.py)."""
    x, _, _ = _forward(cfg, trees, ids, None)
    return cfg.head(trees, rms_norm(cfg, x, trees["final_norm"]))


def _prefill(cfg, trees, cache, prompt, true_len, slot):
    """One request into one slot at a static bucket shape: prompt
    [1, bucket], zero-padded (causal masking keeps the padding out of
    the real positions, and padding makes no expert assignment) ->
    (cache with the slot's columns [0, bucket) written in every layer,
    the final hidden state at the true last position [1, H], counters).
    Columns from `bucket` on keep the last tenant's values: none is
    attended before the decode step that writes it."""
    bucket = prompt.shape[1]
    x, latents, counters = _forward(
        cfg, trees, prompt[0], jnp.arange(bucket, dtype=jnp.int32) < true_len)
    # [L, 1, rank + rope, bucket]: the bucket's latents, depth minor,
    # dropped into the slot's region of the donated cache in one write
    block = jnp.stack(latents).swapaxes(-1, -2)[:, None]
    latent = jax.lax.dynamic_update_slice(
        cache["latent"], block.astype(cache["latent"].dtype),
        (0, slot, 0, 0))
    h = jax.lax.dynamic_slice(x, (true_len - 1, 0), (1, cfg.hidden_size))
    return {"latent": latent}, rms_norm(cfg, h, trees["final_norm"]), counters
