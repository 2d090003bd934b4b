"""kimi-k2.6: one chip's share of Kimi-K2.6's text model, served: the
model through the program's public entry points, its plain reference,
and the operation and byte counts of its shapes.

The harness loads this file by the configuration's name.  Three parts:

1. `init_params`: every weight from the seed, on the device, a leaf at
   a time, in the type it is served in (bfloat16; the router's selection
   bias float32).  The program's side and the reference both start from
   these arrays; the reference takes nothing else.
2. `build_engine`: `paddle_tpu.models.kimi_k2` behind
   `serving.DecodeEngine`.  Nothing here re-implements the program.
3. `ReferenceLM`: the layer as published (DeepSeek-V3's, which
   `model_type: kimi_k2` is) in plain `jax.numpy`, float32 at "highest"
   matmul precision: attention in its first form (K and V of every head
   built from the latent), no cache, no kernels, one full forward pass
   over prompt and served tokens, every held expert over every token
   under a mask.  It imports nothing of `paddle_tpu`.  The weights stay
   in their stored bfloat16 and one layer at a time is raised to
   float32, so that 3.5 G parameters fit beside it.  `control=True`
   judges the token that the same pass puts first with every matrix
   product's operands rounded to fp8 (e4m3, per-tensor scale); `fault=`
   the token of a pass with a planted fault.  The harness compares the
   widest gap only; the reference also writes, to standard error, how
   the gaps of all compared tokens are distributed, how many routings a
   bfloat16 rounding of the router's input changes, and under
   `--control 1` the same for the fp8 pass and the planted faults
   (`ReferenceLM.report`), so that a limit is set from what a run reads.

The share (configs/kimi-k2.6.json, `deployment`): the chip holds
`n_routed_experts` of the `n_routed_experts_deployment` routed experts
and `vocab_size` rows of the vocabulary.  The router scores all 384 and
normalises over all 8 chosen; what the absent experts would have added
is left out, in the program and here alike.
"""

import functools
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# shapes and counts
# ---------------------------------------------------------------------------


def _dims(cfg):
    return dict(
        h=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vd=cfg["v_head_dim"], rq=cfg["q_lora_rank"],
        rkv=cfg["kv_lora_rank"], fd=cfg["intermediate_size"],
        fe=cfg["moe_intermediate_size"], held=cfg["n_routed_experts"],
        routed=cfg["n_routed_experts_deployment"],
        layers=cfg["num_hidden_layers"],
        dense=cfg["first_k_dense_replace"], vocab=cfg["vocab_size"])


def param_specs(cfg):
    """[(name, shape, kind)] under the names of
    `paddle_tpu.models.kimi_k2.param_shapes`; matrices are [in, out]."""
    d = _dims(cfg)
    h, heads = d["h"], d["heads"]
    out = [("embed", (d["vocab"], h), "matrix")]
    for i in range(d["layers"]):
        p = f"layers.{i}."
        out += [
            (p + "attn_norm", (h,), "gain"),
            (p + "q_a", (h, d["rq"]), "matrix"),
            (p + "q_a_norm", (d["rq"],), "gain"),
            (p + "q_b", (d["rq"], heads * (d["nope"] + d["rope"])), "matrix"),
            (p + "kv_a", (h, d["rkv"] + d["rope"]), "matrix"),
            (p + "kv_a_norm", (d["rkv"],), "gain"),
            (p + "kv_b", (d["rkv"], heads * (d["nope"] + d["vd"])), "matrix"),
            (p + "o", (heads * d["vd"], h), "matrix"),
            (p + "ffn_norm", (h,), "gain")]
        if i < d["dense"]:
            out += [(p + "gate_up", (h, 2 * d["fd"]), "matrix"),
                    (p + "down", (d["fd"], h), "matrix")]
        else:
            out += [
                (p + "router", (h, d["routed"]), "matrix"),
                (p + "router_bias", (d["routed"],), "bias"),
                (p + "shared_gate_up", (h, 2 * d["fe"]), "matrix"),
                (p + "shared_down", (d["fe"], h), "matrix"),
                (p + "experts_gate_up", (d["held"], h, 2 * d["fe"]),
                 "matrix"),
                (p + "experts_down", (d["held"], d["fe"], h), "matrix")]
    return out + [("final_norm", (h,), "gain"),
                  ("lm_head", (h, d["vocab"]), "matrix")]


def param_count(cfg):
    return sum(int(np.prod(shape)) for _, shape, _ in param_specs(cfg))


def _attention_weights(d):
    return (d["h"] * d["rq"] + d["rq"] * d["heads"] * (d["nope"] + d["rope"])
            + d["h"] * (d["rkv"] + d["rope"])
            + d["rkv"] * d["heads"] * (d["nope"] + d["vd"])
            + d["heads"] * d["vd"] * d["h"])


def serve_flops_per_token(cfg):
    """The published mathematics a token meets on this chip, whatever
    implements it: 2 for each weight it meets in a matrix product (the
    router, the shared expert, and of the routed experts the
    `num_experts_per_tok` x held / routed it is expected to find here;
    the head; the embedding look-up not), plus attention in its first
    form (scores over nope + rope, values over v, every head) over the
    cell's mean live context (`assumed.mean_live_context`)."""
    d = _dims(cfg)
    expert = 3 * d["h"] * d["fe"]
    moe = d["h"] * d["routed"] + expert * (
        1 + cfg["num_experts_per_tok"] * d["held"] / d["routed"])
    weights = (d["layers"] * _attention_weights(d)
               + d["dense"] * 3 * d["h"] * d["fd"]
               + (d["layers"] - d["dense"]) * moe + d["h"] * d["vocab"])
    attention = d["layers"] * 2 * d["heads"] * (
        d["nope"] + d["rope"] + d["vd"]) * cfg["assumed"][
            "mean_live_context"]
    return 2 * weights + attention


def latent_bytes_per_token(cfg):
    """Bytes of latent cache one cached position holds over all layers."""
    d = _dims(cfg)
    return d["layers"] * (d["rkv"] + d["rope"]) * 2


def mla_decode_flops_per_cached_token(cfg):
    """Operations `mla_decode` needs for one cached position of one slot
    over all layers, in the absorbed form: every head's score over
    rank + rope and its weighted sum over rank."""
    d = _dims(cfg)
    return d["layers"] * d["heads"] * 2 * (2 * d["rkv"] + d["rope"])


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def expert_bytes(cfg):
    """Bytes of the held routed experts' weights over all expert layers:
    what one program run reads of them once every held expert has an
    assignment."""
    d = _dims(cfg)
    return expert_layers(cfg) * d["held"] * 3 * d["h"] * d["fe"] * 2


def expert_flops_per_assignment(cfg):
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, shape, kind, std, dtype):
    z = jax.random.normal(key, shape, jnp.bfloat16).astype(jnp.float32) * std
    if kind == "bias":
        return z                       # the selection bias stays float32
    return ((1.0 + z) if kind == "gain" else z).astype(dtype)


def init_params(cfg, seed):
    """{name: array}: matrices N(0, initializer_range); gains 1 + N(0,
    initializer_range) rather than 1, so that no leaf is inert in the
    comparison; `e_score_correction_bias` N(0, assumed
    `e_score_correction_bias_std`), float32: against sigmoid scores
    around a half with a spread of a third it changes which experts are
    chosen for a good share of the tokens, and weighs nothing."""
    key = jax.random.PRNGKey(int(seed) % (2 ** 32))
    a = cfg["assumed"]
    out = {}
    for i, (name, shape, kind) in enumerate(param_specs(cfg)):
        std = a["e_score_correction_bias_std"] if kind == "bias" \
            else a["initializer_range"]
        out[name] = _draw(jax.random.fold_in(key, i), shape, kind, std,
                          cfg["dtype"])
    return out


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def build_engine(cfg, job, seed, clock):
    """A `DecodeEngine` with its loop thread, holding the seed's
    weights, with the cell's slots, depth and prefill buckets."""
    from paddle_tpu.models import kimi_k2
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    eng = job["engine"]
    kcfg = kimi_k2.K2Cfg.from_hf(cfg, max_seq_len=eng["max_len"])
    want = {n: tuple(s) for n, s, _ in param_specs(cfg)}
    have = {n: tuple(s) for n, (s, _) in kimi_k2.param_shapes(kcfg).items()}
    if want != have:
        odd = sorted(set(want.items()) ^ set(have.items()))[:4]
        raise RuntimeError(f"models/kimi_k2.py's leaves differ from "
                           f"param_specs: {odd}")
    params = kimi_k2.K2Params.from_flat(kcfg, init_params(cfg, seed))
    # every caller's first request is in the queue at once
    return DecodeEngine(params, config=DecodeConfig(
        slots=eng["slots"], max_len=eng["max_len"],
        buckets=tuple(eng["buckets"]), max_queue_depth=job["clients"],
        clock=clock))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _round_fp8(x):
    """x rounded to e4m3 under a per-tensor scale that puts its largest
    magnitude at 240.  `reduce_precision` and not a cast there and back:
    the compiler may drop such a pair of casts."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _product(precision):
    def mm(a, b, spec=None):
        if precision == "fp8":
            a, b = _round_fp8(a), _round_fp8(b)
        if spec is None:
            return jnp.matmul(a, b, precision="highest")
        return jnp.einsum(spec, a, b, precision="highest")
    return mm


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def yarn_frequencies(cfg):
    """inv_freq [rope / 2] of DeepseekV3YarnRotaryEmbedding."""
    y, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        float(cfg["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / y["factor"]

    def correction_dim(rotations):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _rotate(cfg, x):
    """apply_rotary_pos_emb of DeepSeek-V3 on x [T, ..., rope], position
    t in row t: de-interleave the lanes ((2i, 2i+1) -> (i, i + rope/2)),
    then x * cos + rotate_half(x) * sin."""
    y = cfg["rope_scaling"]
    t, dim = x.shape[0], x.shape[-1]
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * yarn_frequencies(cfg)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    m = _yarn_mscale(y["factor"], y["mscale"]) \
        / _yarn_mscale(y["factor"], y["mscale_all_dim"])
    cos, sin = jnp.cos(emb) * m, jnp.sin(emb) * m
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x = x.reshape(x.shape[:-1] + (dim // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (dim,))
    half = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * cos + half * sin


def _attention(cfg, w, x, mm, head_block):
    d = _dims(cfg)
    t, heads, eps = x.shape[0], d["heads"], cfg["rms_norm_eps"]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = mm(_rms_norm(mm(h, w["q_a"]), w["q_a_norm"], eps), w["q_b"])
    q = q.reshape(t, heads, d["nope"] + d["rope"])
    q = jnp.concatenate([q[..., :d["nope"]],
                         _rotate(cfg, q[..., d["nope"]:])], axis=-1)
    kv = mm(h, w["kv_a"])
    c_kv = _rms_norm(kv[:, :d["rkv"]], w["kv_a_norm"], eps)
    k_rope = _rotate(cfg, kv[:, d["rkv"]:])
    kv = mm(c_kv, w["kv_b"]).reshape(t, heads, d["nope"] + d["vd"])
    k = jnp.concatenate(
        [kv[..., :d["nope"]],
         jnp.broadcast_to(k_rope[:, None, :], (t, heads, d["rope"]))],
        axis=-1)
    v = kv[..., d["nope"]:]
    y = cfg["rope_scaling"]
    m = _yarn_mscale(y["factor"], y["mscale_all_dim"])
    scale = (d["nope"] + d["rope"]) ** -0.5 * m * m
    mask = jnp.tril(jnp.ones((t, t), bool))

    def some_heads(qkv):
        q, k, v = qkv                             # [head_block, T, .]
        s = mm(q, k, "hqd,hkd->hqk") * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return mm(p, v, "hqk,hkd->hqd")

    def blocks(a):                                # [T, H, .] -> [n, hb, T, .]
        a = jnp.swapaxes(a, 0, 1)
        return a.reshape((heads // head_block, head_block) + a.shape[1:])

    o = jax.lax.map(some_heads, (blocks(q), blocks(k), blocks(v)))
    o = jnp.swapaxes(o.reshape(heads, t, d["vd"]), 0, 1)
    return x + mm(o.reshape(t, heads * d["vd"]), w["o"])


def _swiglu(h, gate_up, down, mm):
    gu = mm(h, gate_up)
    f = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], down)


def _routing_flips(cfg, w, h, mm, chosen):
    """[T, 2] bool: the token's chosen set differs, its chosen experts
    held here differ, when the router reads h rounded to bfloat16, which
    is what the served model's router reads: routing is a step function,
    and this is how often rounding alone crosses a step."""
    k, first = cfg["num_experts_per_tok"], cfg.get("first_expert", 0)
    held, routed = w["experts_gate_up"].shape[0], w["router"].shape[1]
    rounded = h.astype(jnp.bfloat16).astype(jnp.float32)
    _, other = jax.lax.top_k(
        jax.nn.sigmoid(mm(rounded, w["router"])) + w["router_bias"], k)

    def members(c):                               # [T, routed] of 0 / 1
        return jnp.zeros((c.shape[0], routed), jnp.int32).at[
            jnp.arange(c.shape[0])[:, None], c].set(1)

    differ = members(chosen) != members(other)
    return jnp.stack([jnp.any(differ, axis=1),
                      jnp.any(differ[:, first:first + held], axis=1)], axis=1)


def _moe(cfg, w, h, mm, fault):
    """Routed experts held here + the shared expert, of tokens h [T, H]:
    every held expert over every token, under the mask of who chose it.
    Returns (y, `_routing_flips`)."""
    k = cfg["num_experts_per_tok"]
    first = cfg.get("first_expert", 0)
    scores = jax.nn.sigmoid(mm(h, w["router"]))
    biased = scores + w["router_bias"]
    _, chosen = jax.lax.top_k(biased, k)                       # [T, k]
    picked = jnp.take_along_axis(
        biased if fault == "bias_in_weights" else scores, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20)
    weights = picked * cfg["routed_scaling_factor"]

    def expert(y, e_w):
        e, gate_up, down = e_w
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=1)
        return y + mine[:, None] * _swiglu(h, gate_up, down, mm), None

    held = w["experts_gate_up"].shape[0]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (jnp.arange(held), w["experts_gate_up"],
                         w["experts_down"]))
    if fault != "no_shared_expert":
        y = y + _swiglu(h, w["shared_gate_up"], w["shared_down"], mm)
    return y, _routing_flips(cfg, w, h, mm, chosen)


def _layer(cfg, w, x, precision, fault, head_block):
    """One decoder layer over x [T, H]; w: its weights as stored, raised
    to float32 here.  Returns (x, the expert layer's `_routing_flips`)."""
    mm = _product(precision)
    w = {n: v.astype(jnp.float32) for n, v in w.items()}
    x = _attention(cfg, w, x, mm, head_block)
    h = _rms_norm(x, w["ffn_norm"], cfg["rms_norm_eps"])
    if "router" in w:
        y, flips = _moe(cfg, w, h, mm, fault)
        return x + y, flips
    return (x + _swiglu(h, w["gate_up"], w["down"], mm),
            jnp.zeros((x.shape[0], 2), bool))


def _head(cfg, norm, head, x, precision):
    x = _rms_norm(x, norm.astype(jnp.float32), cfg["rms_norm_eps"])
    return _product(precision)(x, head.astype(jnp.float32))


FAULTS = ("no_shared_expert", "bias_in_weights")


class ReferenceLM:
    """The reference over one request at a time: logits of every
    position of prompt + served tokens, a layer at a time."""

    PAD_TO = 1024     # requests are padded to a multiple of this

    def __init__(self, cfg, seed, max_len, params=None):
        self.cfg, self.max_len = cfg, max_len
        self.p = params if params is not None else init_params(cfg, seed)
        heads = cfg["num_attention_heads"]
        self._layer = jax.jit(functools.partial(
            _layer, cfg, head_block=math.gcd(heads, 8)),
            static_argnames=("precision", "fault"))
        self._head = jax.jit(functools.partial(_head, cfg),
                             static_argnames=("precision",))
        self._gaps = {}                  # judge -> gaps of every token
        self._routings = np.zeros(3, np.int64)
        self._float32 = (None, None)     # the last request's ids, logits

    def logits(self, ids, precision="float32", fault=None, flips=None):
        """float32 logits [T, vocab] of token ids [T]; `flips`, a list,
        gains each expert layer's `_routing_flips`."""
        x = self.p["embed"][jnp.asarray(ids)].astype(jnp.float32)
        for i in range(self.cfg["num_hidden_layers"]):
            pre = f"layers.{i}."
            w = {n[len(pre):]: v for n, v in self.p.items()
                 if n.startswith(pre)}
            x, f = self._layer(w, x, precision=precision, fault=fault)
            if flips is not None and "router" in w:
                flips.append(f)
        return self._head(self.p["final_norm"], self.p["lm_head"], x,
                          precision=precision)

    def token_gaps(self, prompt, served, control=False, fault=None):
        """For served token i, at sequence position len(prompt) + i: how
        far its float32 logit lies under the float32 best.  With
        `control` (or a `fault`), the token judged is not the served one
        but the one the fp8 (or the faulty) forward pass puts first; with
        `control` the planted faults are read too, into `report()`."""
        n, start = len(served), len(prompt)
        total = min(self.max_len, -(-(start + n) // self.PAD_TO)
                    * self.PAD_TO)
        ids = np.zeros(total, np.int32)   # padding: the mask keeps it inert
        ids[:start] = prompt
        ids[start:start + n] = served
        if self._float32[0] is None or not np.array_equal(
                self._float32[0], ids):
            flips = []
            rows = self.logits(ids, flips=flips)[start - 1:start - 1 + n]
            self._float32 = (ids, rows)
            self._routings += [len(flips) * (start + n), *sum(
                np.asarray(f)[:start + n].sum(axis=0) for f in flips)]
        rows = self._float32[1]

        def judged(tok, judge):
            gap = np.asarray(jnp.max(rows, axis=-1) - jnp.take_along_axis(
                rows, tok[:, None], axis=-1)[:, 0])
            self._gaps.setdefault(judge, []).append(gap)
            return gap

        def first_of(precision, fault):
            other = self.logits(ids, precision, fault)
            return jnp.argmax(other[start - 1:start - 1 + n], axis=-1)

        if control:
            for f in FAULTS:
                judged(first_of("float32", f), f)
            gap = judged(first_of("fp8", None), "fp8")
        elif fault:
            gap = judged(first_of("float32", fault), fault)
        else:
            gap = judged(jnp.asarray(np.asarray(served, np.int32)), "served")
        print("kimi-k2.6 reference, so far: " + json.dumps(self.report()),
              file=sys.stderr, flush=True)
        return gap

    def report(self):
        """What has been compared so far: for each judge (the served
        tokens; under `--control 1` the fp8 pass's and each planted
        fault's first choices) how the gaps of all its tokens are
        distributed, and how many (token, expert layer) routings a
        bfloat16 rounding of the router's input changes, anywhere and in
        an expert held here."""
        out = {}
        for judge, gaps in self._gaps.items():
            g = np.sort(np.concatenate(gaps))
            out[judge] = {
                "requests": len(gaps), "tokens": int(g.size),
                "share_not_first": float(np.mean(g > 0)),
                "share_over_0.1": float(np.mean(g > 0.1)),
                "mean": float(g.mean()),
                **{f"p{q}": float(g[min(g.size - 1, int(q / 100 * g.size))])
                   for q in (90, 99)},
                "max": float(g[-1])}
        routings, differ, differ_held = (int(v) for v in self._routings)
        out["routings_under_bfloat16"] = {
            "compared": routings, "chose_differently": differ,
            "in_an_expert_held_here": differ_held}
        return out
