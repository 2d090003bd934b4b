"""The AFMoE decoder (Arcee Trinity) as a served model: the decode
engine's seam (serving/decode.py, "The seam") over two caches of
different depth, a growing one for the full-attention layers and a ring
for the window layers.

The layer, as published (huggingface arcee-ai/Trinity-Mini `config.json`,
`model_type: afmoe`, and that type's public modelling code for what the
config has no key for):

    x = Embed[ids] * sqrt(hidden_size)                    # mup_enabled
    a = RMSNorm_input(x)
    q = a Wq -> [heads, d]; k = a Wk, v = a Wv -> [kv_heads, d];
    g = a Wg -> [heads * d]                               # no bias
    q = RMSNorm_q(q), k = RMSNorm_k(k)                    # per head, over d
    sliding_attention: q, k = RoPE(q, k, pos) (theta, all d lanes, the
        halves paired as HF's rotate_half); keys j with i - window < j <= i
    full_attention: no positional encoding; keys j <= i
    o_h = softmax(q_h k_{h // group}^T / sqrt(d)) v_{h // group}
    x = x + RMSNorm_post_attn((concat_h(o_h) * sigmoid(g)) Wo)
    m = RMSNorm_pre_mlp(x)
    f = SwiGLU(m)                        in the first num_dense_layers
      = SwiGLU_shared(m) + routed(m)     after them: sigmoid scores over
        all routed experts, top k by score + expert_bias, weights the
        chosen scores over their sum times route_scale
        (`distributed/moe.py` `routed_experts`)
    x = x + RMSNorm_post_mlp(f)
    logits = RMSNorm_final(x) W_head                      # untied

The caches, both in the resident layout (depth minor, K and V stored
transposed): `k_full` / `v_full` `[full layers, slots, kv_heads, d,
max_len]`, column p holding position p; `k_window` / `v_window` `[window
layers, slots, kv_heads, d, window]`, a ring: position p lives in column
p mod window.  A prefill of true length n leaves in the ring the
positions max(0, n - window) .. n - 1 (`_ring_columns`; the bucket's
padding is written nowhere a later step reads), a decode step at `pos`
writes column pos mod window and attends min(pos + 1, window) columns.
Rotary phases are in the stored keys, so the order of a ring's columns
does not matter to the softmax.  The `heads / kv_heads` query heads of a
K/V head read its columns where they lie (`kernels/attention.py`
`resident_decode_attention`: on the TPU the one Pallas call `gqa_decode`
a layer, which walks each slot's live tiles and writes the step's column
into the tile it reads, by visit tables built once a step for the full
caches and once for the rings; `flash_fwd` with a window and grouped
heads in the prefill): K and V are never repeated over the query heads.

Types: weights, activations (the residual stream too), K, V and the
caches are `cfg.dtype` (bfloat16 as served); norms, router scores,
`expert_bias` and softmax are computed in float32.

The layers are unrolled (window and full layers, dense and expert layers
are not one homogeneous scan, and each layer's weights are arrays of
their own); both caches are carried whole from layer to layer and
written in place.

A chip holds `experts_held` of the `n_routed` experts, from
`first_expert`: one chip's share of an expert-parallel deployment.  What
the absent experts would add is left out, and that partial result goes on
to the next layer; nothing stands in for the other chips.
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .blocks import (expert_counters, expert_ffn, expert_layers_kept,
                     normed_heads, rms_norm, rope, swiglu)

__all__ = ["AfmoeCfg", "AfmoeParams", "param_shapes", "init_params",
           "full_logits"]

WINDOW, FULL = "sliding_attention", "full_attention"


class AfmoeCfg(NamedTuple):
    """Hashable static geometry, and the decode engine's seam."""
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_dense_layers: int
    layer_types: tuple            # WINDOW or FULL, one a layer
    num_heads: int
    num_kv_heads: int
    head_dim: int
    sliding_window: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed: int                 # the router's width: the deployment's
    experts_held: int             # held on this chip ...
    first_expert: int             # ... from this one on
    num_experts_per_tok: int
    route_scale: float
    rms_norm_eps: float
    rope_theta: float
    mup_enabled: bool
    max_seq_len: int
    dtype: str

    @classmethod
    def from_hf(cls, c, max_seq_len=None):
        """From a dict under the source's `config.json` keys.
        `num_experts` counts the experts held here; the router's width
        is `num_experts_deployment` where that differs."""
        types = tuple(c["layer_types"])
        if len(types) != c["num_hidden_layers"] \
                or set(types) - {WINDOW, FULL}:
            raise ValueError(f"layer_types {types} for "
                             f"{c['num_hidden_layers']} layers")
        if c.get("score_func", "sigmoid") != "sigmoid" \
                or not c.get("route_norm", True) \
                or c.get("num_shared_experts", 1) != 1:
            raise ValueError("the expert layer served is sigmoid scores, "
                             "normalised, with one shared expert")
        return cls(
            c["vocab_size"], c["hidden_size"], c["num_hidden_layers"],
            c["num_dense_layers"], types, c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["sliding_window"],
            c["intermediate_size"], c["moe_intermediate_size"],
            c.get("num_experts_deployment", c["num_experts"]),
            c["num_experts"], c.get("first_expert", 0),
            c["num_experts_per_tok"], float(c["route_scale"]),
            float(c["rms_norm_eps"]), float(c["rope_theta"]),
            bool(c.get("mup_enabled", False)),
            int(max_seq_len or c["max_position_embeddings"]),
            c.get("dtype", "bfloat16"))

    def layers_of(self, kind):
        return sum(t == kind for t in self.layer_types)

    # -- the decode engine's seam --------------------------------------
    cache_kind = ("kv [full layers, slots, kv_heads, head_dim, max_len] + "
                  "ring [window layers, slots, kv_heads, head_dim, window]")

    def _caches(self, max_len):
        """(name, kind of layer, depth) of the two caches."""
        return (("full", FULL, max_len),
                ("window", WINDOW, min(self.sliding_window, max_len)))

    def cache_arrays(self, slots, max_len):
        """K and V of the full layers, as deep as a request may grow, and
        of the window layers, a ring as deep as the window (no deeper
        than a request may grow); a kind of layer the model lacks has no
        array."""
        out = {}
        for name, kind, depth in self._caches(max_len):
            shape = (self.layers_of(kind), slots, self.num_kv_heads,
                     self.head_dim, depth)
            if shape[0]:
                out["k_" + name] = jnp.zeros(shape, self.dtype)
                out["v_" + name] = jnp.zeros(shape, self.dtype)
        return out

    def cache_walk(self, lengths, slots, max_len):
        """What `gqa_decode` walks in one full layer and in one ring of
        a decode step whose active slots hold `lengths` cached
        positions, the step's own among them: `full_tiles` and
        `window_tiles`, beside the `full_grid` and `window_grid` of
        tiles that a rectangle over every slot's whole depth holds.
        Nothing for a cache whose shape the kernel does not tile
        (`kernels/attention.py` `_decode_takes_kernel`)."""
        from ..kernels.attention import _decode_takes_kernel
        from ..kernels.flash_attention import gqa_tiling, tiles_walked

        out = {}
        for name, kind, depth in self._caches(max_len):
            if not self.layers_of(kind) or not _decode_takes_kernel(
                    depth, self.head_dim, use_flash=True):
                continue
            tile = gqa_tiling(self.num_kv_heads, self.head_dim, depth).tile
            out[name + "_tiles"] = tiles_walked(
                [min(n, depth) for n in lengths], tile)
            out[name + "_grid"] = slots * (depth // tile)
        return out

    def cache_reads(self, lengths):
        """The cached positions that requests of `lengths` positions
        read in one full layer (`live_full`) and in one window layer
        (`live_window`), summed; on the host, from lengths the engine
        holds."""
        return {"live_full": sum(lengths),
                "live_window": sum(min(n, self.sliding_window)
                                   for n in lengths)}

    def expert_layers(self, tokens):
        """The expert layers of a program over `tokens` tokens (a decode
        step's slots, a prefill's bucket) whose shape has
        `routed_experts`' kept case."""
        return expert_layers_kept(
            tokens, self.num_experts_per_tok, self.experts_held,
            self.n_routed, self.num_layers - self.num_dense_layers)

    def prefill(self, trees, cache, prompt, true_len, slot):
        return _prefill(self, trees, cache, prompt, true_len, slot)

    def decode(self, trees, cache, token, pos, active=None):
        return _decode(self, trees, cache, token, pos)

    def head(self, trees, hidden):
        return hidden @ trees["lm_head"]


class AfmoeParams(NamedTuple):
    """What `DecodeEngine` takes: the arrays and the static geometry."""
    trees: dict
    cfg: AfmoeCfg

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
    h, d = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_heads * d, cfg.num_kv_heads * d
    out = {"embed": ((cfg.vocab_size, h), "matrix")}
    for i in range(cfg.num_layers):
        p = f"layers.{i}."
        out.update({
            p + "input_norm": ((h,), "gain"),
            p + "q": ((h, q), "matrix"),
            p + "k": ((h, kv), "matrix"),
            p + "v": ((h, kv), "matrix"),
            p + "attn_gate": ((h, q), "matrix"),
            p + "q_norm": ((d,), "gain"),
            p + "k_norm": ((d,), "gain"),
            p + "o": ((q, h), "matrix"),
            p + "post_attn_norm": ((h,), "gain"),
            p + "pre_mlp_norm": ((h,), "gain"),
            p + "post_mlp_norm": ((h,), "gain"),
        })
        if i < cfg.num_dense_layers:
            f = cfg.intermediate_size
            out.update({p + "gate_up": ((h, 2 * f), "matrix"),
                        p + "down": ((f, h), "matrix")})
        else:
            f, e = cfg.moe_intermediate_size, cfg.experts_held
            out.update({
                p + "router": ((h, cfg.n_routed), "matrix"),
                p + "expert_bias": ((cfg.n_routed,), "bias"),
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
    N(0, bias_std) in float32."""
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

def _ffn(cfg, lp, h, counters, valid=None):
    """The layer's FFN of tokens h [N, H]: dense, or routed + shared."""
    if "router" not in lp:
        return swiglu(h, lp["gate_up"], lp["down"]), counters
    return expert_ffn(h, lp, lp["expert_bias"], (
        cfg.first_expert, cfg.n_routed, cfg.num_experts_per_tok,
        cfg.route_scale), counters, valid)


def _projections(cfg, lp, a, pos, window):
    """a [N, H] at positions pos [N] -> (q [N, heads, d], k, v
    [N, kv_heads, d], gate [N, heads * d]): q and k normalised per head
    and, in a window layer, rotated."""
    q = normed_heads(cfg, a, lp["q"], cfg.num_heads, lp["q_norm"])
    k = normed_heads(cfg, a, lp["k"], cfg.num_kv_heads, lp["k_norm"])
    v = (a @ lp["v"]).reshape(a.shape[0], cfg.num_kv_heads, cfg.head_dim)
    if window:
        q, k = rope(cfg, q, pos), rope(cfg, k, pos)
    return q, k, v, a @ lp["attn_gate"]


def _after_attention(cfg, lp, x, o, gate, counters, valid=None):
    """The rest of a layer from the heads' outputs o [N, heads * d]."""
    o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
    x = x + rms_norm(cfg, o @ lp["o"], lp["post_attn_norm"])
    f, counters = _ffn(cfg, lp, rms_norm(cfg, x, lp["pre_mlp_norm"]),
                       counters, valid=valid)
    return x + rms_norm(cfg, f, lp["post_mlp_norm"]), counters


def _embed(cfg, trees, ids):
    x = jnp.take(trees["embed"], ids, axis=0)
    return x * math.sqrt(cfg.hidden_size) if cfg.mup_enabled else x


def _decode(cfg, trees, cache, token, pos):
    """One step of every slot: token [S] at pos [S] -> (cache, final
    hidden [S, H], counters: `expert_counts` int32 [held] and
    `expert_layers_kept` int32 [])."""
    from ..kernels.attention import (resident_decode_attention,
                                     resident_decode_walk)

    cache = dict(cache)
    x = _embed(cfg, trees, token)
    counters = expert_counters(cfg.experts_held)
    seen = {WINDOW: 0, FULL: 0}
    # the kernel's visit tables follow from the positions alone: once a
    # step for each depth, not once a layer
    walks = {name: resident_decode_walk(pos, cache["k_" + name])
             for name in ("full", "window") if "k_" + name in cache}
    for lp, kind in zip(trees["layers"], cfg.layer_types):
        name = "window" if kind == WINDOW else "full"
        q, k, v, gate = _projections(
            cfg, lp, rms_norm(cfg, x, lp["input_norm"]), pos, kind == WINDOW)
        o, cache["k_" + name], cache["v_" + name] = \
            resident_decode_attention(
                q[:, :, None], k[:, :, None], v[:, :, None],
                cache["k_" + name], cache["v_" + name], seen[kind], pos,
                ring=kind == WINDOW, walk=walks[name])
        seen[kind] += 1
        x, counters = _after_attention(
            cfg, lp, x, o.reshape(o.shape[0], -1), gate, counters)
    return cache, rms_norm(cfg, x, trees["final_norm"]), counters


def _forward(cfg, trees, ids, valid):
    """The published form over one sequence ids [N] (positions 0..N-1):
    (hidden [N, H] before the final norm, each layer's (k, v)
    [kv_heads, d, N] as its cache holds them, counters).  Tokens
    that are not `valid` make no expert assignment."""
    from ..kernels.attention import dot_product_attention

    n = ids.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    x = _embed(cfg, trees, ids)
    counters = expert_counters(cfg.experts_held)
    kvs = []
    for lp, kind in zip(trees["layers"], cfg.layer_types):
        q, k, v, gate = _projections(
            cfg, lp, rms_norm(cfg, x, lp["input_norm"]), pos, kind == WINDOW)
        k, v = k.swapaxes(0, 1), v.swapaxes(0, 1)       # [kv_heads, N, d]
        kvs.append((k.swapaxes(1, 2), v.swapaxes(1, 2)))
        o = dot_product_attention(
            q.swapaxes(0, 1)[None], k[None], v[None], is_causal=True,
            training=False,
            window=cfg.sliding_window if kind == WINDOW else None)[0]
        x, counters = _after_attention(
            cfg, lp, x, o.swapaxes(0, 1).reshape(n, -1), gate, counters,
            valid=valid)
    return x, kvs, counters


def full_logits(cfg, trees, ids):
    """Logits [N, vocab] of every position of one sequence ids [N]: the
    published form, no cache (what the engine's tokens are held against
    where no float32 reference fits: chip_smoke.py)."""
    x, _, _ = _forward(cfg, trees, ids, None)
    return cfg.head(trees, rms_norm(cfg, x, trees["final_norm"]))


def _ring_columns(x, true_len, ring):
    """x [..., bucket] with position p in column p -> [..., min(ring,
    bucket)] with position p in column p mod ring: column c takes the
    last position under `true_len` that is c mod ring, so that after a
    prompt of `true_len` the ring holds positions max(0, true_len -
    ring) .. true_len - 1 and none of the bucket's padding where a later
    step reads (a column no position has reached yet is written by the
    decode step that first attends it)."""
    bucket = x.shape[-1]
    if bucket <= ring:
        return x
    turns = -(-bucket // ring)
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, turns * ring - bucket)])
    x = x.reshape(x.shape[:-1] + (turns, ring))
    turn = jnp.maximum(true_len - 1 - jnp.arange(ring), 0) // ring
    out = x[..., 0, :]
    for j in range(1, turns):
        out = jnp.where(turn == j, x[..., j, :], out)
    return out


def _prefill(cfg, trees, cache, prompt, true_len, slot):
    """One request into one slot at a static bucket shape: prompt
    [1, bucket], zero-padded (causal masking keeps the padding out of
    the real positions, and padding makes no expert assignment) ->
    (cache with the slot's columns written in every layer: [0, bucket)
    of a full layer, the ring's as `_ring_columns` lays them; the final
    hidden state at the true last position [1, H]; counters)."""
    bucket = prompt.shape[1]
    x, kvs, counters = _forward(
        cfg, trees, prompt[0], jnp.arange(bucket, dtype=jnp.int32) < true_len)
    cache = dict(cache)
    for name, kind in (("full", FULL), ("window", WINDOW)):
        mine = [kv for kv, t in zip(kvs, cfg.layer_types) if t == kind]
        if not mine:
            continue
        for which, kv in (("k_", 0), ("v_", 1)):
            # [layers of the kind, 1, kv_heads, d, columns], depth minor,
            # dropped into the slot's region of the donated cache in one
            # write
            block = jnp.stack([m[kv] for m in mine])[:, None]
            if kind == WINDOW:
                block = _ring_columns(block, true_len,
                                      cache[which + name].shape[-1])
            cache[which + name] = jax.lax.dynamic_update_slice(
                cache[which + name],
                block.astype(cache[which + name].dtype), (0, slot, 0, 0, 0))
    h = jax.lax.dynamic_slice(x, (true_len - 1, 0), (1, cfg.hidden_size))
    return cache, rms_norm(cfg, h, trees["final_norm"]), counters
