"""trinity-mini: one chip's share of Arcee's Trinity-Mini (`model_type:
afmoe`), served: the model through the program's public entry points,
its plain reference, and the operation and byte counts of its shapes.

The harness loads this file by the configuration's name.  Three parts:

1. `init_params`: every weight from the seed, on the device, a leaf at
   a time, in the type it is served in (bfloat16; the router's selection
   bias float32).  The program's side and the reference both start from
   these arrays; the reference takes nothing else.
2. `build_engine`: `paddle_tpu.models.afmoe` behind
   `serving.DecodeEngine`.  Nothing here re-implements the program.
3. `ReferenceLM`: the layer as published (configs/trinity-mini.json
   `assumed` names what the config has no key for) in plain `jax.numpy`,
   float32 at "highest" matmul precision: K and V of a head repeated
   for the query heads that read it, a mask for causality and for the
   window, no cache, no ring, no kernels, one full forward pass over
   prompt and served tokens, attention a block of queries at a time so
   that 9,728 positions fit, every held expert over every token under a
   mask.  It imports nothing of `paddle_tpu`.  The weights stay in
   their stored bfloat16 and one layer at a time is raised to float32.
   `control=True` judges the token that the same pass puts first with
   every matrix product's operands rounded to fp8 (e4m3, per-tensor
   scale); `fault=` the token of a pass with a planted fault.
   `token_gaps` answers with one number a request, the mean of its
   tokens' gaps, so what the harness holds to `token_logit_gap` (the
   largest number over the sampled requests) is the worst request's
   mean gap: routing is a step function, the widest single gap is that
   of the one token worst hit by a changed routing and reads much the
   same under bfloat16 and under fp8 (limits/trinity-mini.*.json), and
   how often tokens are hit is what tells the two apart.  The reference
   also writes, to standard error, how the gaps of all compared tokens
   are distributed (their widest too), how many routings a bfloat16
   rounding of the router's input changes, and under `--control 1` the
   same for the fp8 pass and the planted faults (`ReferenceLM.report`),
   so that a limit is set from what a run reads.

The share (configs/trinity-mini.json, `deployment`): the chip holds
`num_experts` of the `num_experts_deployment` routed experts and
`vocab_size` rows of the vocabulary.  The router scores all 128 and
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

WINDOW, FULL = "sliding_attention", "full_attention"

# ---------------------------------------------------------------------------
# shapes and counts
# ---------------------------------------------------------------------------


def _dims(cfg):
    return dict(
        h=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kvh=cfg["num_key_value_heads"], d=cfg["head_dim"],
        fd=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        held=cfg["num_experts"], routed=cfg["num_experts_deployment"],
        layers=cfg["num_hidden_layers"], dense=cfg["num_dense_layers"],
        vocab=cfg["vocab_size"], window=cfg["sliding_window"])


def param_specs(cfg):
    """[(name, shape, kind)] under the names of
    `paddle_tpu.models.afmoe.param_shapes`; matrices are [in, out]."""
    d = _dims(cfg)
    h, q, kv = d["h"], d["heads"] * d["d"], d["kvh"] * d["d"]
    out = [("embed", (d["vocab"], h), "matrix")]
    for i in range(d["layers"]):
        p = f"layers.{i}."
        out += [
            (p + "input_norm", (h,), "gain"),
            (p + "q", (h, q), "matrix"),
            (p + "k", (h, kv), "matrix"),
            (p + "v", (h, kv), "matrix"),
            (p + "attn_gate", (h, q), "matrix"),
            (p + "q_norm", (d["d"],), "gain"),
            (p + "k_norm", (d["d"],), "gain"),
            (p + "o", (q, h), "matrix"),
            (p + "post_attn_norm", (h,), "gain"),
            (p + "pre_mlp_norm", (h,), "gain"),
            (p + "post_mlp_norm", (h,), "gain")]
        if i < d["dense"]:
            out += [(p + "gate_up", (h, 2 * d["fd"]), "matrix"),
                    (p + "down", (d["fd"], h), "matrix")]
        else:
            out += [
                (p + "router", (h, d["routed"]), "matrix"),
                (p + "expert_bias", (d["routed"],), "bias"),
                (p + "shared_gate_up", (h, 2 * d["fe"]), "matrix"),
                (p + "shared_down", (d["fe"], h), "matrix"),
                (p + "experts_gate_up", (d["held"], h, 2 * d["fe"]),
                 "matrix"),
                (p + "experts_down", (d["held"], d["fe"], h), "matrix")]
    return out + [("final_norm", (h,), "gain"),
                  ("lm_head", (h, d["vocab"]), "matrix")]


def param_count(cfg):
    return sum(int(np.prod(shape)) for _, shape, _ in param_specs(cfg))


def layers_of(cfg, kind):
    return sum(t == kind for t in cfg["layer_types"])


def _attention_weights(d):
    # q, the output gate and o; k and v
    return 3 * d["h"] * d["heads"] * d["d"] + 2 * d["h"] * d["kvh"] * d["d"]


def attention_flops_per_position(cfg):
    """Operations of one query token against one cached position in one
    layer: every query head's score over head_dim and its weighted sum
    over head_dim."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def kv_bytes_per_position(cfg):
    """Bytes of K and V one cached position holds in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2


def kv_bytes_per_token(cfg):
    """Bytes of K and V a token writes over all layers, full and ring."""
    return cfg["num_hidden_layers"] * kv_bytes_per_position(cfg)


def serve_flops_per_token(cfg):
    """The published mathematics a token meets on this chip, whatever
    implements it: 2 for each weight it meets in a matrix product (the
    router, the shared expert, and of the routed experts the
    `num_experts_per_tok` x held / routed it is expected to find here;
    the head; the embedding look-up not), plus attention over the cell's
    mean live context (`assumed.mean_live_context`): that many cached
    positions in a full layer, no more than the window in a window
    layer."""
    d = _dims(cfg)
    expert = 3 * d["h"] * d["fe"]
    moe = d["h"] * d["routed"] + expert * (
        1 + cfg["num_experts_per_tok"] * d["held"] / d["routed"])
    weights = (d["layers"] * _attention_weights(d)
               + d["dense"] * 3 * d["h"] * d["fd"]
               + (d["layers"] - d["dense"]) * moe + d["h"] * d["vocab"])
    live = cfg["assumed"]["mean_live_context"]
    positions = layers_of(cfg, FULL) * live \
        + layers_of(cfg, WINDOW) * min(live, d["window"])
    return 2 * weights + attention_flops_per_position(cfg) * positions


def swa_decode_bytes(cfg, live_full, live_window):
    """Bytes of K and V the decode steps had to read: `live_full` cached
    positions in each full layer, `live_window` in each ring."""
    return kv_bytes_per_position(cfg) * (
        layers_of(cfg, FULL) * live_full
        + layers_of(cfg, WINDOW) * live_window)


def swa_decode_flops(cfg, live_full, live_window):
    return attention_flops_per_position(cfg) * (
        layers_of(cfg, FULL) * live_full
        + layers_of(cfg, WINDOW) * live_window)


def swa_prefill_flops(cfg, bucket):
    """Operations of one prefill's attention at a bucket's shape, all
    layers: the (query, key) pairs on or under the diagonal in a full
    layer, those of them inside the window's band in a window layer."""
    w = min(cfg["sliding_window"], bucket)
    causal = bucket * (bucket + 1) // 2
    band = w * (w + 1) // 2 + (bucket - w) * w
    return attention_flops_per_position(cfg) * (
        layers_of(cfg, FULL) * causal + layers_of(cfg, WINDOW) * band)


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


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
    comparison; `expert_bias` N(0, assumed `expert_bias_std`), float32."""
    key = jax.random.PRNGKey(int(seed) % (2 ** 32))
    a = cfg["assumed"]
    out = {}
    for i, (name, shape, kind) in enumerate(param_specs(cfg)):
        std = a["expert_bias_std"] if kind == "bias" \
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
    from paddle_tpu.models import afmoe
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    eng = job["engine"]
    acfg = afmoe.AfmoeCfg.from_hf(cfg, max_seq_len=eng["max_len"])
    want = {n: tuple(s) for n, s, _ in param_specs(cfg)}
    have = {n: tuple(s) for n, (s, _) in afmoe.param_shapes(acfg).items()}
    if want != have:
        odd = sorted(set(want.items()) ^ set(have.items()))[:4]
        raise RuntimeError(f"models/afmoe.py's leaves differ from "
                           f"param_specs: {odd}")
    params = afmoe.AfmoeParams.from_flat(acfg, init_params(cfg, seed))
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


def _rotate(cfg, x):
    """HF's apply_rotary_pos_emb on x [T, heads, d], position t in row
    t: x * cos + rotate_half(x) * sin, the angles of lane i and of lane
    i + d / 2 alike t * theta ** (-2 i / d)."""
    t, dim = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * inv_freq.astype(np.float32)
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


def _attention(cfg, w, x, mm, window, fault):
    """x + RMSNorm_post_attn((softmax(q k^T) v * sigmoid(gate)) Wo) over
    x [T, H]; `window`: the layer is a sliding_attention one."""
    d = _dims(cfg)
    t, heads, kvh, hd, eps = (x.shape[0], d["heads"], d["kvh"], d["d"],
                              cfg["rms_norm_eps"])
    a = _rms_norm(x, w["input_norm"], eps)
    q = _rms_norm(mm(a, w["q"]).reshape(t, heads, hd), w["q_norm"], eps)
    k = _rms_norm(mm(a, w["k"]).reshape(t, kvh, hd), w["k_norm"], eps)
    v = mm(a, w["v"]).reshape(t, kvh, hd)
    if window or fault == "rope_in_full_layers":
        q, k = _rotate(cfg, q), _rotate(cfg, k)
    # K and V of a head, once for each query head that reads it
    k, v = (jnp.repeat(z, heads // kvh, axis=1).swapaxes(0, 1)
            for z in (k, v))                            # [heads, T, d]
    banded = window and fault != "no_window"
    block = math.gcd(t, 512)
    col = jnp.arange(t)[None, :]

    def some_queries(args):
        q, row0 = args                                  # [heads, block, d]
        row = row0 + jnp.arange(block)[:, None]
        seen = col <= row
        if banded:
            seen &= col > row - d["window"]
        s = mm(q, k, "hqd,hkd->hqk") * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return mm(p, v, "hqk,hkd->hqd")

    qb = q.swapaxes(0, 1).reshape(heads, t // block, block, hd).swapaxes(0, 1)
    o = jax.lax.map(some_queries, (qb, jnp.arange(0, t, block)))
    o = o.transpose(0, 2, 1, 3).reshape(t, heads * hd)
    if fault != "no_gate":
        o = o * jax.nn.sigmoid(mm(a, w["attn_gate"]))
    return x + _rms_norm(mm(o, w["o"]), w["post_attn_norm"], eps)


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
        jax.nn.sigmoid(mm(rounded, w["router"])) + w["expert_bias"], k)

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
    _, chosen = jax.lax.top_k(scores + w["expert_bias"], k)    # [T, k]
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    if cfg["route_norm"]:
        picked = picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20)
    weights = picked * cfg["route_scale"]

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


def _layer(cfg, w, x, window, precision, fault):
    """One decoder layer over x [T, H]; w: its weights as stored, raised
    to float32 here.  Returns (x, the expert layer's `_routing_flips`)."""
    mm = _product(precision)
    w = {n: v.astype(jnp.float32) for n, v in w.items()}
    eps = cfg["rms_norm_eps"]
    x = _attention(cfg, w, x, mm, window, fault)
    m = _rms_norm(x, w["pre_mlp_norm"], eps)
    if "router" in w:
        f, flips = _moe(cfg, w, m, mm, fault)
    else:
        f = _swiglu(m, w["gate_up"], w["down"], mm)
        flips = jnp.zeros((x.shape[0], 2), bool)
    return x + _rms_norm(f, w["post_mlp_norm"], eps), flips


def _head(cfg, norm, head, x, precision):
    x = _rms_norm(x, norm.astype(jnp.float32), cfg["rms_norm_eps"])
    return _product(precision)(x, head.astype(jnp.float32))


FAULTS = ("no_window", "rope_in_full_layers", "no_gate", "no_shared_expert")


class ReferenceLM:
    """The reference over one request at a time: logits of every
    position of prompt + served tokens, a layer at a time.  Every
    request is padded to `max_len`, one shape: a pass over 9,728
    positions costs the chip two seconds, compiling the four kinds of
    layer for another shape a quarter of a minute."""

    def __init__(self, cfg, seed, max_len, params=None):
        self.cfg, self.max_len = cfg, max_len
        self.p = params if params is not None else init_params(cfg, seed)
        self._layer = jax.jit(functools.partial(_layer, cfg),
                              static_argnames=("window", "precision",
                                               "fault"))
        self._head = jax.jit(functools.partial(_head, cfg),
                             static_argnames=("precision",))
        self._gaps = {}                  # judge -> gaps of every token
        self._routings = np.zeros(3, np.int64)
        self._float32 = (None, None)     # the last request's ids, logits

    def logits(self, ids, precision="float32", fault=None, flips=None):
        """float32 logits [T, vocab] of token ids [T]; `flips`, a list,
        gains each expert layer's `_routing_flips`."""
        x = self.p["embed"][jnp.asarray(ids)].astype(jnp.float32)
        if self.cfg["mup_enabled"]:
            x = x * math.sqrt(self.cfg["hidden_size"])
        for i, kind in enumerate(self.cfg["layer_types"]):
            pre = f"layers.{i}."
            w = {n[len(pre):]: v for n, v in self.p.items()
                 if n.startswith(pre)}
            x, f = self._layer(w, x, window=kind == WINDOW,
                               precision=precision, fault=fault)
            if flips is not None and "router" in w:
                flips.append(f)
        return self._head(self.p["final_norm"], self.p["lm_head"], x,
                          precision=precision)

    def token_gaps(self, prompt, served, control=False, fault=None):
        """What the harness compares: the mean of the request's `gaps`,
        as an array of one."""
        return np.array([self.gaps(prompt, served, control, fault).mean()])

    def gaps(self, prompt, served, control=False, fault=None):
        """For served token i, at sequence position len(prompt) + i: how
        far its float32 logit lies under the float32 best.  With
        `control` (or a `fault`), the token judged is not the served one
        but the one the fp8 (or the faulty) forward pass puts first; with
        `control` the planted faults are read too, into `report()`."""
        n, start = len(served), len(prompt)
        # padding: the mask keeps it inert
        ids = np.zeros(self.max_len, np.int32)
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
        print("trinity-mini reference, so far: " + json.dumps(self.report()),
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
                "request_mean_max": float(max(r.mean() for r in gaps)),
                **{f"p{q}": float(g[min(g.size - 1, int(q / 100 * g.size))])
                   for q in (90, 99)},
                "max": float(g[-1])}
        routings, differ, differ_held = (int(v) for v in self._routings)
        out["routings_under_bfloat16"] = {
            "compared": routings, "chose_differently": differ,
            "in_an_expert_held_here": differ_held}
        return out
