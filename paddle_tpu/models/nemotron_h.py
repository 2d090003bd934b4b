"""The Nemotron-H decoder (NVIDIA, `model_type: nemotron_h`) as a served
model: the decode engine's seam (serving/decode.py, "The seam") over two
states a slot and a cache with a depth, side by side.

The model is a stack of blocks of three kinds, by the config's
`hybrid_override_pattern` (M, E, *), each `x = x + mixer(RMSNorm(x))`
(huggingface nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 `config.json`
and the `nemotron_h` modelling code; benchmarks/configs/
nemotron-3-nano-30b-a3b.json `assumed` lists what the config has no key
for):

    x = Embed[ids]                                   # not scaled
    M: [z | xBC | dt] = a W_in                       # no bias
       xBC = silu(causal depthwise conv_4(xBC) + b_conv)
           -> x [heads, head_dim], B, C [groups, state]
       dt = softplus(dt + dt_bias), A = -exp(A_log)
       S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t
           (head h reads the B and C of group h // (heads / groups);
           kernels/ssd.py)
       out = RMSNorm_grouped(y * silu(z)) W_out      # groups of
                                                     # d_inner / n_groups
    E: sigmoid scores over all routed experts, top k by score + bias,
       the chosen scores over their sum times the scale; each chosen
       expert held here relu(a W_up)^2 W_down, plus the shared expert's
       (`distributed/moe.py` `routed_experts` with `relu2_act`)
    *: grouped-query attention, softmax(q k^T / sqrt(d)) v, causal, no
       rotary, no bias
    logits = RMSNorm_f(x) W_head                     # untied

The cache (`cache_arrays`): `ssd` float32 `[mamba layers, slots, heads /
pack x state, pack x head_dim]` (kernels/ssd.py's layout, 12.6 MB a slot
at the published widths) and `conv` `[mamba layers, conv_kernel - 1,
slots, conv_dim]`, the last conv_kernel - 1 pre-convolution inputs,
oldest first, in the activations' type (the slots and channels minor, so
that no layout pads the 3 inputs and a step shifts them in place); both
states (`cache_states`), of a fixed size a slot.  Beside them `k` / `v` `[attention layers, slots, kv_heads,
head_dim, max_len]`, the resident layout of models/afmoe.py's full
caches.  A prefill walks its prompt in chunks and leaves in the slot the
SSD state of the true last position, the conv window of the prompt's
last inputs (zeros before its first) and the prompt's K/V columns; a
decode step advances the active slots' states in place, leaves the
others as they are, and writes every slot's K/V column.

Types: weights, activations, K, V and the conv window are `cfg.dtype`
(bfloat16 as served); norms, router scores, the selection bias, dt, A,
D, the SSD state and the gated norm are float32.

Each kind of layer is traced once a shape (`_mamba`, `_experts`,
`_attention`: jitted, the layer's index data), and the arrays are
carried whole from layer to layer and written in place.

A chip holds `experts_held` of the `n_routed` experts from
`first_expert`, one chip's share of an expert-parallel deployment; what
the absent experts would add is left out, and nothing stands in for the
other chips.  The held experts' width is stored padded to whole lanes
(`from_flat`): relu(0)^2 = 0, so the padding adds nothing.
"""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..distributed.moe import relu2_act, routed_experts
from ..kernels.ssd import _prefill_xla, ssd_decode, ssd_prefill, ssd_tiling
from .blocks import expert_counters, expert_layers_kept, rms_norm

__all__ = ["NemotronHCfg", "NemotronHParams", "param_shapes", "init_params",
           "full_logits"]

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
_LANES = 128


class NemotronHCfg(NamedTuple):
    """Hashable static geometry, and the decode engine's seam."""
    vocab_size: int
    hidden_size: int
    pattern: str                  # one of M, E, * a layer
    mamba_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_intermediate_size: int
    shared_intermediate_size: int
    n_routed: int                 # the router's width: the deployment's
    experts_held: int             # held on this chip ...
    first_expert: int             # ... from this one on
    num_experts_per_tok: int
    route_scale: float
    rms_norm_eps: float
    max_seq_len: int
    dtype: str

    @classmethod
    def from_hf(cls, c, max_seq_len=None):
        """From a dict under the source's `config.json` keys; refuses
        what is not served.  `n_routed_experts` counts the experts held
        here; the router's width is `n_routed_experts_deployment` where
        that differs."""
        pattern = c["hybrid_override_pattern"]
        if len(pattern) != c["num_hidden_layers"] \
                or set(pattern) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(f"hybrid_override_pattern {pattern!r} for "
                             f"{c['num_hidden_layers']} layers")
        if c.get("mlp_hidden_act", "relu2") != "relu2" \
                or c.get("mamba_hidden_act", "silu") != "silu":
            raise ValueError("the experts served are relu2, the mixer's "
                             "activation silu")
        if c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1 \
                or not c.get("norm_topk_prob", True) \
                or c.get("n_shared_experts", 1) != 1:
            raise ValueError("the router served is one group, normalised, "
                             "with one shared expert")
        if c.get("attention_bias", False) or c.get("mlp_bias", False) \
                or c.get("mamba_proj_bias", False) or c.get("use_bias", False) \
                or not c.get("use_conv_bias", True):
            raise ValueError("no bias but the convolution's is served")
        if c.get("tie_word_embeddings", False):
            raise ValueError("the head served is untied")
        if c.get("sliding_window") is not None:
            raise ValueError("a sliding window is not served")
        if c["num_attention_heads"] % c["num_key_value_heads"] \
                or c["mamba_num_heads"] % c["n_groups"]:
            raise ValueError("heads must divide over K/V heads and groups")
        return cls(
            c["vocab_size"], c["hidden_size"], pattern,
            c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
            c["ssm_state_size"], c["conv_kernel"], c["chunk_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"],
            c.get("n_routed_experts_deployment", c["n_routed_experts"]),
            c["n_routed_experts"], c.get("first_expert", 0),
            c["num_experts_per_tok"], float(c["routed_scaling_factor"]),
            float(c["layer_norm_epsilon"]),
            int(max_seq_len or c["max_position_embeddings"]),
            c.get("dtype", "bfloat16"))

    def layers_of(self, kind):
        return self.pattern.count(kind)

    @property
    def d_inner(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def tiling(self):
        return ssd_tiling(self.mamba_heads, self.mamba_head_dim,
                          self.ssm_state_size, self.n_groups,
                          chunk=self.chunk_size)

    @property
    def expert_width(self):
        """The held experts' width as stored: whole lanes."""
        return -(-self.moe_intermediate_size // _LANES) * _LANES

    # -- the decode engine's seam --------------------------------------
    cache_kind = ("ssd state [mamba layers, slots, heads / pack x state, "
                  "pack x head_dim] + conv window [mamba layers, "
                  "conv_kernel - 1, slots, conv_dim] + kv [attention "
                  "layers, slots, kv_heads, head_dim, max_len]")
    # the arrays of `cache_arrays` that are states: of a fixed size a
    # slot, with no depth
    cache_states = ("ssd", "conv")

    def cache_arrays(self, slots, max_len):
        """Both states of every slot, and K and V as deep as a request
        may grow; a kind of layer the model lacks has no array."""
        out = {}
        m, a = self.layers_of(MAMBA), self.layers_of(ATTENTION)
        if m:
            pack = self.tiling.pack
            out["ssd"] = jnp.zeros(
                (m, slots, self.mamba_heads // pack * self.ssm_state_size,
                 pack * self.mamba_head_dim), jnp.float32)
            out["conv"] = jnp.zeros(
                (m, self.conv_kernel - 1, slots, self.conv_dim), self.dtype)
        if a:
            shape = (a, slots, self.num_kv_heads, self.head_dim, max_len)
            out["k"] = jnp.zeros(shape, self.dtype)
            out["v"] = jnp.zeros(shape, self.dtype)
        return out

    def cache_reads(self, lengths):
        """The cached positions that requests of `lengths` positions
        read in one attention layer (`live_full`), summed; on the host,
        from lengths the engine holds."""
        return {"live_full": sum(lengths)} if self.layers_of(ATTENTION) \
            else {}

    def expert_layers(self, tokens):
        """The expert layers of a program over `tokens` tokens whose
        shape has `routed_experts`' kept case."""
        return expert_layers_kept(
            tokens, self.num_experts_per_tok, self.experts_held,
            self.n_routed, self.layers_of(EXPERTS))

    def prefill(self, trees, cache, prompt, true_len, slot):
        return _prefill(self, trees, cache, prompt, true_len, slot)

    def decode(self, trees, cache, token, pos, active=None):
        return _decode(self, trees, cache, token, pos, active)

    def head(self, trees, hidden):
        return hidden @ trees["lm_head"]


class NemotronHParams(NamedTuple):
    """What `DecodeEngine` takes: the arrays and the static geometry."""
    trees: dict
    cfg: NemotronHCfg

    @classmethod
    def from_flat(cls, cfg, flat):
        """{name: array} under `param_shapes`' names -> the program's
        trees: a dict for each layer, the held experts' width padded
        with zeros to whole lanes (`cfg.expert_width`), so that both of
        their products are `moe_grouped_mm`'s on the TPU."""
        pad = cfg.expert_width - cfg.moe_intermediate_size
        layers = []
        for i in range(len(cfg.pattern)):
            pre = f"layers.{i}."
            lp = {n[len(pre):]: v for n, v in flat.items()
                  if n.startswith(pre)}
            if pad and "experts_up" in lp:
                lp["experts_up"] = jnp.pad(lp["experts_up"],
                                           ((0, 0), (0, 0), (0, pad)))
                lp["experts_down"] = jnp.pad(lp["experts_down"],
                                             ((0, 0), (0, pad), (0, 0)))
            layers.append(lp)
        return cls({"embed": flat["embed"], "layers": layers,
                    "final_norm": flat["final_norm"],
                    "lm_head": flat["lm_head"]}, cfg)


def param_shapes(cfg):
    """{name: (shape, kind)}; matrices are stored [in, out], the
    convolution's weight [conv_kernel, conv_dim] (tap k multiplies the
    input k - conv_kernel + 1 positions back)."""
    h = cfg.hidden_size
    out = {"embed": ((cfg.vocab_size, h), "matrix")}
    for i, kind in enumerate(cfg.pattern):
        p = f"layers.{i}."
        out[p + "norm"] = ((h,), "gain")
        if kind == MAMBA:
            width = cfg.d_inner + cfg.conv_dim + cfg.mamba_heads
            out.update({
                p + "in_proj": ((h, width), "matrix"),
                p + "conv_weight": ((cfg.conv_kernel, cfg.conv_dim), "conv"),
                p + "conv_bias": ((cfg.conv_dim,), "conv"),
                p + "dt_bias": ((cfg.mamba_heads,), "dt_bias"),
                p + "A_log": ((cfg.mamba_heads,), "A_log"),
                p + "D": ((cfg.mamba_heads,), "D"),
                p + "gate_norm": ((cfg.d_inner,), "gain"),
                p + "out_proj": ((cfg.d_inner, h), "matrix")})
        elif kind == ATTENTION:
            q, kv = cfg.num_heads * cfg.head_dim, \
                cfg.num_kv_heads * cfg.head_dim
            out.update({p + "q": ((h, q), "matrix"),
                        p + "k": ((h, kv), "matrix"),
                        p + "v": ((h, kv), "matrix"),
                        p + "o": ((q, h), "matrix")})
        else:
            f, e = cfg.moe_intermediate_size, cfg.experts_held
            fs = cfg.shared_intermediate_size
            out.update({
                p + "router": ((h, cfg.n_routed), "matrix"),
                p + "router_bias": ((cfg.n_routed,), "bias"),
                p + "experts_up": ((e, h, f), "matrix"),
                p + "experts_down": ((e, f, h), "matrix"),
                p + "shared_up": ((h, fs), "matrix"),
                p + "shared_down": ((fs, h), "matrix")})
    out["final_norm"] = ((h,), "gain")
    out["lm_head"] = ((h, cfg.vocab_size), "matrix")
    return out


def init_params(cfg, key, std=0.02, bias_std=0.002,
                time_step=(0.001, 0.1, 1e-4)):
    """Seeded random weights under `param_shapes`' names, as the
    configuration's own init keys draw them: matrices N(0, std), gains
    1 + N(0, std); the convolution U(-1/sqrt(k), 1/sqrt(k)) (the
    convolution's default); `dt_bias` the softplus inverse of dt drawn
    log-uniform over (time_step_min, time_step_max), floored at
    time_step_floor; `A_log` log U[1, 16]; `D` 1 + N(0, std); the
    router's bias N(0, bias_std).  Matrices and the convolution in
    `cfg.dtype`, the rest float32."""
    lo, hi, floor = time_step
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    out = {}
    f32 = jnp.float32
    for k, (name, (shape, kind)) in zip(keys, shapes.items()):
        if kind == "dt_bias":
            dt = jnp.exp(math.log(lo) + jax.random.uniform(k, shape, f32)
                         * (math.log(hi) - math.log(lo)))
            dt = jnp.maximum(dt, floor)
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif kind == "A_log":
            out[name] = jnp.log(jax.random.uniform(k, shape, f32, 1.0, 16.0))
        elif kind == "D":
            out[name] = 1.0 + std * jax.random.normal(k, shape, f32)
        elif kind == "bias":
            out[name] = bias_std * jax.random.normal(k, shape, f32)
        elif kind == "conv":
            bound = 1.0 / math.sqrt(cfg.conv_kernel)
            out[name] = jax.random.uniform(k, shape, f32, -bound,
                                           bound).astype(cfg.dtype)
        else:
            z = jax.random.normal(k, shape, f32)
            out[name] = ((1.0 if kind == "gain" else 0.0)
                         + z * std).astype(cfg.dtype)
    return out


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def _split_in(cfg, zxbcdt):
    """W_in's product [N, width] -> (z, xBC, dt) in that order."""
    di, cd = cfg.d_inner, cfg.conv_dim
    return zxbcdt[:, :di], zxbcdt[:, di:di + cd], zxbcdt[:, di + cd:]


def _ssd_inputs(cfg, lp, xbc, dt):
    """The convolved xBC [N, conv_dim] and raw dt [N, heads] -> (x [N,
    heads, head_dim], dt float32 after the softplus, A float32 [heads],
    B, C [N, groups, state])."""
    n, di, gn = xbc.shape[0], cfg.d_inner, cfg.n_groups * cfg.ssm_state_size
    x = xbc[:, :di].reshape(n, cfg.mamba_heads, cfg.mamba_head_dim)
    b = xbc[:, di:di + gn].reshape(n, cfg.n_groups, cfg.ssm_state_size)
    c = xbc[:, di + gn:].reshape(n, cfg.n_groups, cfg.ssm_state_size)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    return x, dt, -jnp.exp(lp["A_log"].astype(jnp.float32)), b, c


def _conv_out(cfg, lp, window):
    """window [conv_kernel, N, conv_dim] of pre-convolution inputs,
    oldest first -> silu(sum of taps + bias) [N, conv_dim] in
    `cfg.dtype`."""
    w = lp["conv_weight"].astype(jnp.float32)
    y = jnp.einsum("knc,kc->nc", window.astype(jnp.float32), w) \
        + lp["conv_bias"].astype(jnp.float32)
    return jax.nn.silu(y).astype(cfg.dtype)


def _mixer_out(cfg, lp, x, y, z):
    """y float32 [N, heads, head_dim] of the recurrence -> the mixer's
    output [N, H]: + D x, times silu(z), the grouped norm, W_out."""
    n = y.shape[0]
    y = y + lp["D"][None, :, None] * x.astype(jnp.float32)
    y = y.reshape(n, cfg.d_inner) * jax.nn.silu(z.astype(jnp.float32))
    y = y.reshape(n, cfg.n_groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    y = y.reshape(n, cfg.d_inner) * lp["gate_norm"].astype(jnp.float32)
    return y.astype(cfg.dtype) @ lp["out_proj"]


def _conv_windows(cfg, xbc):
    """xBC [N, conv_dim] of consecutive positions -> each position's
    window [conv_kernel, N, conv_dim], zeros before the first."""
    k = cfg.conv_kernel
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    return jnp.stack([padded[i:i + xbc.shape[0]] for i in range(k)])


@functools.partial(jax.jit, static_argnums=(0,))
def _mamba_decode(cfg, lp, x, ssd, conv, layer, active):
    """A Mamba block of one step of every slot: x [S, H] -> (x, ssd,
    conv); the active slots' conv window shifted and SSD state advanced
    in place in layer `layer` (traced) of both, the others' left."""
    z, xbc, dt = _split_in(cfg, rms_norm(cfg, x, lp["norm"]) @ lp["in_proj"])
    old = conv[layer]                                  # [k - 1, S, C]
    window = jnp.concatenate([old, xbc[None].astype(old.dtype)])
    conv = conv.at[layer].set(jnp.where(active[None, :, None], window[1:],
                                        old))
    xs, dt, a, b, c = _ssd_inputs(cfg, lp, _conv_out(cfg, lp, window), dt)
    y, ssd = ssd_decode(xs, dt, a, b, c, ssd, layer, active)
    return x + _mixer_out(cfg, lp, xs, y, z), ssd, conv


@functools.partial(jax.jit, static_argnums=(0,))
def _mamba_prefill(cfg, lp, x, ssd, conv, layer, true_len, slot):
    """A Mamba block over one prompt x [T, H] at a bucket's shape ->
    (x, ssd, conv): the slot's SSD state of position true_len - 1 and
    its conv window of the prompt's last conv_kernel - 1 inputs (zeros
    before position 0), never the bucket's padding."""
    k = cfg.conv_kernel
    z, xbc, dt = _split_in(cfg, rms_norm(cfg, x, lp["norm"]) @ lp["in_proj"])
    xs, dt, a, b, c = _ssd_inputs(
        cfg, lp, _conv_out(cfg, lp, _conv_windows(cfg, xbc)), dt)
    y, ssd = ssd_prefill(xs, dt, a, b, c, true_len, ssd, layer, slot,
                         chunk=cfg.chunk_size)
    last = jax.lax.dynamic_slice_in_dim(
        jnp.pad(xbc, ((k - 1, 0), (0, 0))), true_len, k - 1, 0)
    zero = jnp.zeros((), jnp.int32)
    conv = jax.lax.dynamic_update_slice(
        conv, last[None, :, None].astype(conv.dtype), (layer, zero, slot, zero))
    return x + _mixer_out(cfg, lp, xs, y, z), ssd, conv


@functools.partial(jax.jit, static_argnums=(0,))
def _experts(cfg, lp, x, valid):
    """An expert block: x [N, H] -> (x, assignments on each held expert,
    1 where the routed part ran over the kept rows)."""
    h = rms_norm(cfg, x, lp["norm"])
    routed, counts, kept = routed_experts(
        h, lp["router"], lp["router_bias"],
        (lp["experts_up"], lp["experts_down"]), cfg.first_expert,
        cfg.n_routed, cfg.num_experts_per_tok, cfg.route_scale,
        valid=valid, act=relu2_act)
    shared = relu2_act(jnp.dot(h, lp["shared_up"],
                               preferred_element_type=jnp.float32))
    return x + routed + shared.astype(h.dtype) @ lp["shared_down"], \
        counts, kept


def _qkv(cfg, lp, a):
    n = a.shape[0]
    return ((a @ lp["q"]).reshape(n, cfg.num_heads, cfg.head_dim),
            (a @ lp["k"]).reshape(n, cfg.num_kv_heads, cfg.head_dim),
            (a @ lp["v"]).reshape(n, cfg.num_kv_heads, cfg.head_dim))


@functools.partial(jax.jit, static_argnums=(0,))
def _attention_decode(cfg, lp, x, kc, vc, layer, pos, walk):
    """An attention block of one step of every slot: each slot's column
    of layer `layer` written at `pos`, then attended up to it."""
    from ..kernels.attention import resident_decode_attention

    q, k, v = _qkv(cfg, lp, rms_norm(cfg, x, lp["norm"]))
    o, kc, vc = resident_decode_attention(
        q[:, :, None], k[:, :, None], v[:, :, None], kc, vc, layer, pos,
        walk=walk)
    return x + o.reshape(x.shape[0], -1) @ lp["o"], kc, vc


def _causal(q, k, v):
    """q [T, heads, d], k, v [T, kv_heads, d] -> causal grouped-query
    attention [T, heads x d]."""
    from ..kernels.attention import dot_product_attention

    o = dot_product_attention(
        q.swapaxes(0, 1)[None], k.swapaxes(0, 1)[None],
        v.swapaxes(0, 1)[None], is_causal=True, training=False)[0]
    return o.swapaxes(0, 1).reshape(q.shape[0], -1)


@functools.partial(jax.jit, static_argnums=(0,))
def _attention_prefill(cfg, lp, x, kc, vc, layer, slot):
    """An attention block over one prompt: the slot's columns [0,
    bucket) of layer `layer` written (the padding's are overwritten by
    the steps that first attend them)."""
    q, k, v = _qkv(cfg, lp, rms_norm(cfg, x, lp["norm"]))
    zero = jnp.zeros((), jnp.int32)
    at = (layer, slot, zero, zero, zero)
    kc = jax.lax.dynamic_update_slice(
        kc, k.transpose(1, 2, 0)[None, None].astype(kc.dtype), at)
    vc = jax.lax.dynamic_update_slice(
        vc, v.transpose(1, 2, 0)[None, None].astype(vc.dtype), at)
    return x + _causal(q, k, v) @ lp["o"], kc, vc


def _decode(cfg, trees, cache, token, pos, active):
    """One step of every slot: token [S] at pos [S] -> (cache, final
    hidden [S, H], counters: `expert_counts` int32 [held] and
    `expert_layers_kept` int32 [])."""
    from ..kernels.attention import resident_decode_walk

    if active is None:
        active = jnp.ones(token.shape[0], bool)
    cache = dict(cache)
    x = jnp.take(trees["embed"], token, axis=0)
    counters = expert_counters(cfg.experts_held)
    # the attention kernel's visit tables follow from the positions
    # alone: once a step, not once a layer
    walk = resident_decode_walk(pos, cache["k"]) if "k" in cache else None
    seen = {MAMBA: 0, ATTENTION: 0}
    for lp, kind in zip(trees["layers"], cfg.pattern):
        if kind == EXPERTS:
            x, counts, kept = _experts(cfg, lp, x, None)
            counters = {
                "expert_counts": counters["expert_counts"] + counts,
                "expert_layers_kept": counters["expert_layers_kept"] + kept}
            continue
        layer = jnp.int32(seen[kind])
        seen[kind] += 1
        if kind == MAMBA:
            x, cache["ssd"], cache["conv"] = _mamba_decode(
                cfg, lp, x, cache["ssd"], cache["conv"], layer, active)
        else:
            x, cache["k"], cache["v"] = _attention_decode(
                cfg, lp, x, cache["k"], cache["v"], layer, pos, walk)
    return cache, rms_norm(cfg, x, trees["final_norm"]), counters


def _prefill(cfg, trees, cache, prompt, true_len, slot):
    """One request into one slot at a static bucket shape: prompt
    [1, bucket], zero-padded (the padding gets dt = 0, causal masking
    keeps it out of the real positions, and it makes no expert
    assignment) -> (cache with the slot's states and columns written in
    every layer; the final hidden state at the true last position
    [1, H]; counters, `chunks` among them: the chunks each Mamba layer
    walked)."""
    bucket = prompt.shape[1]
    cache = dict(cache)
    x = jnp.take(trees["embed"], prompt[0], axis=0)
    valid = jnp.arange(bucket, dtype=jnp.int32) < true_len
    counters = expert_counters(cfg.experts_held)
    seen = {MAMBA: 0, ATTENTION: 0}
    for lp, kind in zip(trees["layers"], cfg.pattern):
        if kind == EXPERTS:
            x, counts, kept = _experts(cfg, lp, x, valid)
            counters = {
                "expert_counts": counters["expert_counts"] + counts,
                "expert_layers_kept": counters["expert_layers_kept"] + kept}
            continue
        layer = jnp.int32(seen[kind])
        seen[kind] += 1
        if kind == MAMBA:
            x, cache["ssd"], cache["conv"] = _mamba_prefill(
                cfg, lp, x, cache["ssd"], cache["conv"], layer, true_len,
                slot)
        else:
            x, cache["k"], cache["v"] = _attention_prefill(
                cfg, lp, x, cache["k"], cache["v"], layer, slot)
    h = jax.lax.dynamic_slice(x, (true_len - 1, 0), (1, cfg.hidden_size))
    if cfg.layers_of(MAMBA):
        counters["chunks"] = jnp.int32(bucket // cfg.chunk_size)
    return cache, rms_norm(cfg, h, trees["final_norm"]), counters


def full_logits(cfg, trees, ids):
    """Logits [N, vocab] of every position of one sequence ids [N]: the
    chunked form in XLA, attention over the whole sequence, no state and
    no cache (what the engine's tokens are held against where no float32
    reference fits: chip_smoke.py).  N a multiple of the chunk."""
    x = jnp.take(trees["embed"], ids, axis=0)
    for lp, kind in zip(trees["layers"], cfg.pattern):
        a = rms_norm(cfg, x, lp["norm"])
        if kind == MAMBA:
            z, xbc, dt = _split_in(cfg, a @ lp["in_proj"])
            xs, dt, am, b, c = _ssd_inputs(
                cfg, lp, _conv_out(cfg, lp, _conv_windows(cfg, xbc)), dt)
            y, _ = _prefill_xla(xs, dt, am, b, c, cfg.chunk_size)
            x = x + _mixer_out(cfg, lp, xs, y, z)
        elif kind == ATTENTION:
            x = x + _causal(*_qkv(cfg, lp, a)) @ lp["o"]
        else:
            x = _experts(cfg, lp, x, None)[0]
    return cfg.head(trees, rms_norm(cfg, x, trees["final_norm"]))
