"""The Brumby decoder (Manifest AI, `model_type: brumby`) as a served
model: the decode engine's seam (serving/decode.py, "The seam") over a
recurrent state, a fixed-size array per slot that no context makes
deeper.

The layer is the Qwen3 block with the softmax taken out (huggingface
manifestai/Brumby-14B-Base `config.json`; Buckman, Gelada, Zhang,
"Scaling Context Requires Rethinking Attention", arXiv:2507.04239, for
what the config has no key for; benchmarks/configs/brumby-14b.json
`assumed` lists each item):

    a = RMSNorm_input(x)
    q = a Wq -> [heads, d]; k = a Wk, v = a Wv -> [kv_heads, d]   # no bias
    q = RMSNorm_q(q), k = RMSNorm_k(k)            # per head, over d
    q, k = RoPE(q, k, pos)        # theta, all d lanes, HF's rotate_half
    log g = log sigmoid(a Wg + bg) -> [kv_heads], float32
    o = gated power retention of degree 2 (kernels/retention.py): query
        head i reads K/V head i // group,
        o_t = sum_s (q_t . k_s)^2 exp(G_t - G_s) v_s / (the same sum
        without v_s), G the running sum of log g
    x = x + concat_h(o_h) Wo
    x = x + SwiGLU(RMSNorm_post_attn(x))
    logits = RMSNorm_final(x) W_head                              # untied

The cache (`cache_arrays`) is the recurrent form's state and nothing
else: `state` float32 `[layers, slots, kv_heads, d, (d / 2 + 1) d]` and
`norm` float32 `[layers, slots, kv_heads, d, d]`
(kernels/retention.py gives the layout and why), 34.6 MB a layer and
slot at the published widths whatever the context, where K and V in
bfloat16 would cost that at 8,448 positions.  `max_len` bounds the
positions (the rotation's), not the bytes.  A prefill walks its prompt
chunk by chunk and leaves in the slot the state of the true last
position, replacing whatever the slot held; a decode step advances the
active slots' states in place and leaves the others as they are.

Types: weights, activations, q, k and v are `cfg.dtype` (bfloat16 as
served); norms, gates, `phi`, the state and the divisor are float32; the
prefill kernel's three products against the state round their operands
to bfloat16 and sum in float32 (kernels/retention.py
`retention_prefill`).

The layers are unrolled, both arrays carried whole from layer to layer.
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels.retention import (phi_rows, retention_decode,
                                 retention_prefill, retention_tiling)
from .blocks import normed_heads, rms_norm, rope, swiglu

__all__ = ["BrumbyCfg", "BrumbyParams", "param_shapes", "init_params",
           "gate_bias", "full_logits"]


class BrumbyCfg(NamedTuple):
    """Hashable static geometry, and the decode engine's seam."""
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    rms_norm_eps: float
    rope_theta: float
    max_seq_len: int
    dtype: str

    @classmethod
    def from_hf(cls, c, max_seq_len=None):
        """From a dict under the source's `config.json` keys; refuses
        what is not served."""
        if c.get("sliding_window") is not None \
                or c.get("use_sliding_window", False):
            raise ValueError("a sliding window is not served")
        if c.get("rope_scaling") is not None:
            raise ValueError("a rope scaling is not served")
        if c.get("attention_bias", False):
            raise ValueError("a bias on q, k, v or o is not served")
        if c.get("tie_word_embeddings", False):
            raise ValueError("the head served is untied")
        if c.get("hidden_act", "silu") != "silu":
            raise ValueError("the feed-forward served is SwiGLU (silu)")
        if c["num_attention_heads"] % c["num_key_value_heads"]:
            raise ValueError("query heads must divide over the K/V heads")
        return cls(
            c["vocab_size"], c["hidden_size"], c["num_hidden_layers"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], float(c["rms_norm_eps"]),
            float(c["rope_theta"]),
            int(max_seq_len or c["max_position_embeddings"]),
            c.get("dtype", "bfloat16"))

    @property
    def slot_state_bytes(self):
        """Bytes of `state` and `norm` one slot holds, all layers."""
        d = self.head_dim
        return self.num_layers * self.num_kv_heads * (phi_rows(d) + 1) \
            * d * d * 4

    # -- the decode engine's seam --------------------------------------
    cache_kind = ("recurrent state [layers, slots, kv_heads, head_dim, "
                  "(head_dim / 2 + 1) head_dim] + its divisor's [layers, "
                  "slots, kv_heads, head_dim, head_dim]")
    # the arrays of `cache_arrays` that are states: of a fixed size a
    # slot, with no depth
    cache_states = ("state", "norm")

    def cache_arrays(self, slots, max_len):
        """The recurrent state of every slot; `max_len` does not enter."""
        del max_len
        d = self.head_dim
        lead = (self.num_layers, slots, self.num_kv_heads, d)
        return {"state": jnp.zeros(lead + (phi_rows(d) * d,), jnp.float32),
                "norm": jnp.zeros(lead + (d,), jnp.float32)}

    def prefill(self, trees, cache, prompt, true_len, slot):
        return _prefill(self, trees, cache, prompt, true_len, slot)

    def decode(self, trees, cache, token, pos, active=None):
        return _decode(self, trees, cache, token, pos, active)

    def head(self, trees, hidden):
        return hidden @ trees["lm_head"]


class BrumbyParams(NamedTuple):
    """What `DecodeEngine` takes: the arrays and the static geometry."""
    trees: dict
    cfg: BrumbyCfg

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
    q, kv, f = cfg.num_heads * d, cfg.num_kv_heads * d, cfg.intermediate_size
    out = {"embed": ((cfg.vocab_size, h), "matrix")}
    for i in range(cfg.num_layers):
        p = f"layers.{i}."
        out.update({
            p + "input_norm": ((h,), "gain"),
            p + "q": ((h, q), "matrix"),
            p + "k": ((h, kv), "matrix"),
            p + "v": ((h, kv), "matrix"),
            p + "gate": ((h, cfg.num_kv_heads), "matrix"),
            p + "gate_bias": ((cfg.num_kv_heads,), "gate_bias"),
            p + "q_norm": ((d,), "gain"),
            p + "k_norm": ((d,), "gain"),
            p + "o": ((q, h), "matrix"),
            p + "post_attn_norm": ((h,), "gain"),
            p + "gate_up": ((h, 2 * f), "matrix"),
            p + "down": ((f, h), "matrix"),
        })
    out["final_norm"] = ((h,), "gain")
    out["lm_head"] = ((h, cfg.vocab_size), "matrix")
    return out


def gate_bias(uniform, half_life):
    """The gate's offset for draws `uniform` in [0, 1): half-lives
    (positions until a state has decayed to a half: ln 2 / -log g)
    log-uniform over `half_life` = (shortest, longest), as the logit u of
    the gate g = sigmoid(u) = 2 ** (-1 / half-life)."""
    lo, hi = half_life
    life = lo * (hi / lo) ** uniform
    g = 2.0 ** (-1.0 / life)
    return jnp.log(g) - jnp.log1p(-g)


def init_params(cfg, key, std=0.02, half_life=(64.0, 8192.0)):
    """Seeded random weights under `param_shapes`' names: matrices
    N(0, std), gains 1 + N(0, std), the gates' offsets `gate_bias` of
    uniform draws, float32."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, (shape, kind)) in zip(keys, shapes.items()):
        if kind == "gate_bias":
            out[name] = gate_bias(jax.random.uniform(k, shape, jnp.float32),
                                  half_life)
        else:
            z = jax.random.normal(k, shape, jnp.float32)
            out[name] = ((1.0 if kind == "gain" else 0.0)
                         + z * std).astype(cfg.dtype)
    return out


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def _projections(cfg, lp, a, pos):
    """a [N, H] at positions pos [N] -> (q [N, heads, d], k, v
    [N, kv_heads, d], log g float32 [N, kv_heads]): q and k normalised
    per head and rotated."""
    q = normed_heads(cfg, a, lp["q"], cfg.num_heads, lp["q_norm"])
    k = normed_heads(cfg, a, lp["k"], cfg.num_kv_heads, lp["k_norm"])
    v = (a @ lp["v"]).reshape(a.shape[0], cfg.num_kv_heads, cfg.head_dim)
    u = jnp.dot(a, lp["gate"], preferred_element_type=jnp.float32) \
        + lp["gate_bias"]
    return rope(cfg, q, pos), rope(cfg, k, pos), v, jax.nn.log_sigmoid(u)


def _after_retention(cfg, lp, x, o):
    """The rest of a layer from the heads' outputs o [N, heads * d]."""
    x = x + o @ lp["o"]
    return x + swiglu(rms_norm(cfg, x, lp["post_attn_norm"]),
                      lp["gate_up"], lp["down"])


def _embed(trees, ids):
    return jnp.take(trees["embed"], ids, axis=0)


def _decode(cfg, trees, cache, token, pos, active):
    """One step of every slot: token [S] at pos [S] -> (cache, final
    hidden [S, H], no counters: the bytes of state a step moves are its
    active slots' states once each way, which the engine knows)."""
    s = token.shape[0]
    if active is None:
        active = jnp.ones(s, bool)
    state, norm = cache["state"], cache["norm"]
    x = _embed(trees, token)
    for layer, lp in enumerate(trees["layers"]):
        q, k, v, log_g = _projections(
            cfg, lp, rms_norm(cfg, x, lp["input_norm"]), pos)
        o, state, norm = retention_decode(q, k, v, log_g, state, norm,
                                          layer, active)
        x = _after_retention(cfg, lp, x, o.reshape(s, -1))
    return {"state": state, "norm": norm}, \
        rms_norm(cfg, x, trees["final_norm"]), {}


def _prefill(cfg, trees, cache, prompt, true_len, slot):
    """One request into one slot at a static bucket shape: prompt
    [1, bucket], zero-padded (positions at or past `true_len` carry no
    key, no value and log g = 0) -> (cache with the slot's state, in
    every layer, that of position true_len - 1; the final hidden state at
    that position [1, H]; counters: `chunks`, the chunks each layer
    walked)."""
    bucket = prompt.shape[1]
    state, norm = cache["state"], cache["norm"]
    pos = jnp.arange(bucket, dtype=jnp.int32)
    x = _embed(trees, prompt[0])
    for layer, lp in enumerate(trees["layers"]):
        q, k, v, log_g = _projections(
            cfg, lp, rms_norm(cfg, x, lp["input_norm"]), pos)
        o, state, norm = retention_prefill(
            q, k, v, log_g, true_len, state, norm, layer, slot)
        x = _after_retention(cfg, lp, x, o.reshape(bucket, -1))
    h = jax.lax.dynamic_slice(x, (true_len - 1, 0), (1, cfg.hidden_size))
    chunks = bucket // retention_tiling(cfg.head_dim, bucket).chunk
    return {"state": state, "norm": norm}, \
        rms_norm(cfg, h, trees["final_norm"]), \
        {"chunks": jnp.int32(chunks)}


def full_logits(cfg, trees, ids):
    """Logits [N, vocab] of every position of one sequence ids [N]: the
    attention form over the whole sequence, no state, no chunk (what the
    engine's tokens are held against where no float32 reference fits:
    chip_smoke.py), a block of queries at a time."""
    n = ids.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    group = cfg.num_heads // cfg.num_kv_heads
    block = math.gcd(n, 512)
    x = _embed(trees, ids)
    for lp in trees["layers"]:
        q, k, v, log_g = _projections(
            cfg, lp, rms_norm(cfg, x, lp["input_norm"]), pos)
        big_g = jnp.cumsum(jnp.repeat(log_g, group, axis=1), axis=0).T
        kf = jnp.repeat(k, group, axis=1).astype(jnp.float32)
        vf = jnp.repeat(v, group, axis=1).astype(jnp.float32)

        def some_queries(row0, q=q, kf=kf, vf=vf, big_g=big_g):
            rows = row0 + jnp.arange(block)
            qb = jax.lax.dynamic_slice_in_dim(q, row0, block, 0)
            gb = jax.lax.dynamic_slice_in_dim(big_g, row0, block, 1)
            sc = jnp.einsum("thd,shd->hts", qb.astype(jnp.float32), kf)
            a = jnp.where(pos[None, None, :] <= rows[None, :, None],
                          sc * sc * jnp.exp(jnp.minimum(
                              gb[:, :, None] - big_g[:, None, :], 0.0)), 0.0)
            return jnp.einsum("hts,shd->thd", a, vf) \
                / a.sum(axis=-1).T[..., None]

        o = jax.lax.map(some_queries, jnp.arange(0, n, block))
        x = _after_retention(cfg, lp, x,
                             o.reshape(n, -1).astype(x.dtype))
    return cfg.head(trees, rms_norm(cfg, x, trees["final_norm"]))
