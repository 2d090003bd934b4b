"""brumby-14b: one pipeline stage of Manifest AI's Brumby-14B-Base
(`model_type: brumby`), served: the model through the program's public
entry points, its plain reference, and the operation and byte counts of
its shapes.

The harness loads this file by the configuration's name.  Three parts:

1. `init_params`: every weight from the seed, on the device, a leaf at
   a time, in the type it is served in (bfloat16; the gates' offsets
   float32).  The program's side and the reference both start from
   these arrays; the reference takes nothing else.
2. `build_engine`: `paddle_tpu.models.brumby` behind
   `serving.DecodeEngine`.  Nothing here re-implements the program.
3. `ReferenceLM`: the layer as published (configs/brumby-14b.json
   `assumed` names what the config has no key for) in its **attention
   form**, plain `jax.numpy`, float32 at "highest" matmul precision: K,
   V and the gates of a head repeated for the 5 query heads that read
   it, a causal mask, `(q k^T)^2 exp(G_t - G_s)`, the row's sum as the
   divisor, a block of queries at a time so that 10,240 positions fit;
   no `phi`, no state, no chunk, no cache, one forward pass over prompt
   and served tokens together.  It imports nothing of `paddle_tpu`.
   The program computes the same function in the recurrent form (a
   state built by a chunked prefill, then decayed and updated by every
   decode step), so the comparison tests the state's whole life.  The
   weights stay in their stored bfloat16 and one layer at a time is
   raised to float32; every request is padded to `max_len`, one shape.
   `control=True` judges the token that the same pass puts first with
   every matrix product's operands rounded to fp8 (e4m3, per-tensor
   scale); `fault=` the token of a pass with a planted fault (`FAULTS`).
   The reference also writes, to standard error, how the gaps of all
   compared tokens are distributed (`ReferenceLM.report`), so that a
   limit is set from what a run reads.

The stage (configs/brumby-14b.json, `deployment`): 8 of the 40 layers,
all heads, every width, and the whole vocabulary.
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
        kvh=cfg["num_key_value_heads"], d=cfg["head_dim"],
        f=cfg["intermediate_size"], layers=cfg["num_hidden_layers"],
        vocab=cfg["vocab_size"])


def param_specs(cfg):
    """[(name, shape, kind)] under the names of
    `paddle_tpu.models.brumby.param_shapes`; matrices are [in, out]."""
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
            (p + "gate", (h, d["kvh"]), "matrix"),
            (p + "gate_bias", (d["kvh"],), "gate_bias"),
            (p + "q_norm", (d["d"],), "gain"),
            (p + "k_norm", (d["d"],), "gain"),
            (p + "o", (q, h), "matrix"),
            (p + "post_attn_norm", (h,), "gain"),
            (p + "gate_up", (h, 2 * d["f"]), "matrix"),
            (p + "down", (d["f"], h), "matrix")]
    return out + [("final_norm", (h,), "gain"),
                  ("lm_head", (h, d["vocab"]), "matrix")]


def param_count(cfg):
    return sum(int(np.prod(shape)) for _, shape, _ in param_specs(cfg))


def monomials(cfg):
    """D: the monomials of degree 2 of a head, the published state's
    width (8,256 at 128; the program's layout stores 8,320)."""
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def retention_flops_per_position(cfg):
    """Operations of the recurrent form for one position in one layer:
    every query head against its K/V head's state (2 D d) and every K/V
    head's update of its state (2 D d)."""
    return 2 * monomials(cfg) * cfg["head_dim"] * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def serve_flops_per_token(cfg):
    """The published mathematics a token meets on this chip, whatever
    implements it: 2 for each weight it meets in a matrix product (the
    layers' and the head; the embedding look-up not), plus the recurrent
    form's work against the state in every layer."""
    weights = sum(int(np.prod(shape)) for name, shape, kind
                  in param_specs(cfg) if kind == "matrix" and name != "embed")
    return 2 * weights \
        + cfg["num_hidden_layers"] * retention_flops_per_position(cfg)


def slot_state_bytes(cfg):
    """Bytes of the published state one slot holds over all layers:
    float32 [D, d] and its divisor's [D], a K/V head."""
    return cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * monomials(cfg) * (cfg["head_dim"] + 1) * 4


def retention_decode_bytes(cfg, active_slots):
    """Bytes of state a decode step must read and write: the state of
    every active slot, once each way."""
    return 2 * active_slots * slot_state_bytes(cfg)


def retention_decode_flops(cfg, active_slots):
    return active_slots * cfg["num_hidden_layers"] \
        * retention_flops_per_position(cfg)


def retention_prefill_flops(cfg, bucket):
    """Operations of the published recurrence over one prefill's bucket,
    all layers, whatever the kernel's chunk: position t (t + 1 keys) costs
    a query head the cheaper of its two exact forms, `4 (t + 1) d` in the
    attention form and `2 D d` against a state, and every K/V head its
    state's update `2 D d`.  A bucket's padding counts: the kernel is
    given the bucket."""
    d, big_d = cfg["head_dim"], monomials(cfg)
    keys = np.arange(1, bucket + 1, dtype=np.float64)
    query = np.minimum(4 * keys * d, 2 * big_d * d).sum()
    return int(cfg["num_hidden_layers"] * (
        cfg["num_attention_heads"] * query
        + cfg["num_key_value_heads"] * bucket * 2 * big_d * d))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _draw(key, shape, kind, std, half_life, dtype):
    if kind == "gate_bias":
        # half-lives log-uniform over `half_life`, as the logit of the
        # gate 2 ** (-1 / half-life)
        lo, hi = half_life
        life = lo * (hi / lo) ** jax.random.uniform(key, shape, jnp.float32)
        g = 2.0 ** (-1.0 / life)
        return jnp.log(g) - jnp.log1p(-g)      # stays float32
    z = jax.random.normal(key, shape, jnp.bfloat16).astype(jnp.float32) * std
    return ((1.0 + z) if kind == "gain" else z).astype(dtype)


def init_params(cfg, seed):
    """{name: array}: matrices N(0, initializer_range); gains 1 + N(0,
    initializer_range) rather than 1, so that no leaf is inert in the
    comparison; `gate_bias` as assumed `gate_half_life` says, float32."""
    key = jax.random.PRNGKey(int(seed) % (2 ** 32))
    a = cfg["assumed"]
    out = {}
    for i, (name, shape, kind) in enumerate(param_specs(cfg)):
        out[name] = _draw(jax.random.fold_in(key, i), shape, kind,
                          a["initializer_range"],
                          tuple(a["gate_half_life"]), cfg["dtype"])
    return out


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def build_engine(cfg, job, seed, clock):
    """A `DecodeEngine` with its loop thread, holding the seed's
    weights, with the cell's slots, positions and prefill buckets."""
    from paddle_tpu.models import brumby
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    eng = job["engine"]
    bcfg = brumby.BrumbyCfg.from_hf(cfg, max_seq_len=eng["max_len"])
    want = {n: tuple(s) for n, s, _ in param_specs(cfg)}
    have = {n: tuple(s) for n, (s, _) in brumby.param_shapes(bcfg).items()}
    if want != have:
        odd = sorted(set(want.items()) ^ set(have.items()))[:4]
        raise RuntimeError(f"models/brumby.py's leaves differ from "
                           f"param_specs: {odd}")
    params = brumby.BrumbyParams.from_flat(bcfg, init_params(cfg, seed))
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


def _rotate(cfg, x, pos):
    """HF's apply_rotary_pos_emb on x [T, heads, d] at positions pos [T]:
    x * cos + rotate_half(x) * sin, the angles of lane i and of lane
    i + d / 2 alike pos * theta ** (-2 i / d)."""
    dim = x.shape[-1]
    inv_freq = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = pos.astype(jnp.float32)[:, None] * inv_freq.astype(np.float32)
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


# what the faults stand for: the program's chunk (kernels/retention.py
# `retention_tiling`), and the padding a prefill that did not mask its
# bucket would feed into the state
FAULT_CHUNK = 256
FAULT_PADDING = 64
FAULTS = ("no_carry", "no_gate", "no_divisor", "padding_in_state",
          "no_rotary")


def _retention(cfg, w, x, pos, mm, fault):
    """x + (gated power retention of degree 2, attention form) Wo over x
    [T, H] at positions pos [T]; a later row sees the rows before it."""
    d = _dims(cfg)
    t, heads, kvh, hd, eps = (x.shape[0], d["heads"], d["kvh"], d["d"],
                              cfg["rms_norm_eps"])
    a = _rms_norm(x, w["input_norm"], eps)
    q = _rms_norm(mm(a, w["q"]).reshape(t, heads, hd), w["q_norm"], eps)
    k = _rms_norm(mm(a, w["k"]).reshape(t, kvh, hd), w["k_norm"], eps)
    v = mm(a, w["v"]).reshape(t, kvh, hd)
    if fault != "no_rotary":
        q, k = _rotate(cfg, q, pos), _rotate(cfg, k, pos)
    log_g = jax.nn.log_sigmoid(mm(a, w["gate"]) + w["gate_bias"])
    if fault == "no_gate":
        log_g = jnp.zeros_like(log_g)
    # K, V and the gates of a head, once for each query head that reads it
    group = heads // kvh
    k, v = (jnp.repeat(z, group, axis=1).swapaxes(0, 1) for z in (k, v))
    big_g = jnp.cumsum(jnp.repeat(log_g, group, axis=1), axis=0).T  # [h, T]
    # a block's scores [40, 256, 10,240] in float32 are 0.42 GB, and a
    # few arrays of that size live at once beside 8.4 GB of weights
    block = math.gcd(t, 256)
    col = jnp.arange(t)[None, :]

    def some_queries(args):
        q, g_q, row0 = args                             # [heads, block, d]
        row = row0 + jnp.arange(block)[:, None]
        seen = col <= row
        if fault == "no_carry":
            # the state of the chunks before is dropped at each boundary
            seen &= col // FAULT_CHUNK == row // FAULT_CHUNK
        s = mm(q, k, "hqd,hkd->hqk")
        weight = jnp.where(
            seen, s * s * jnp.exp(g_q[:, :, None] - big_g[:, None, :]), 0.0)
        o = mm(weight, v, "hqk,hkd->hqd")
        if fault != "no_divisor":
            o = o / jnp.sum(weight, axis=-1, keepdims=True)
        return o

    qb = q.swapaxes(0, 1).reshape(heads, t // block, block, hd).swapaxes(0, 1)
    gb = big_g.reshape(heads, t // block, block).swapaxes(0, 1)
    o = jax.lax.map(some_queries, (qb, gb, jnp.arange(0, t, block)))
    o = o.transpose(0, 2, 1, 3).reshape(t, heads * hd)
    return x + mm(o, w["o"])


def _swiglu(h, gate_up, down, mm):
    gu = mm(h, gate_up)
    f = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], down)


def _layer(cfg, w, x, pos, precision, fault):
    """One decoder layer over x [T, H]; w: its weights as stored, raised
    to float32 here."""
    mm = _product(precision)
    w = {n: v.astype(jnp.float32) for n, v in w.items()}
    x = _retention(cfg, w, x, pos, mm, fault)
    m = _rms_norm(x, w["post_attn_norm"], cfg["rms_norm_eps"])
    return x + _swiglu(m, w["gate_up"], w["down"], mm)


HEAD_ROWS = 512       # rows of logits computed at a time
HEAD_PARTS = 8        # the head's columns raised to float32 a part at a time


def _judge(cfg, norm, head, x, tok, precision):
    """Of rows x [rows, H] and a token each: (the best logit, the
    token's logit, the token the row puts first), the head's columns a
    part at a time so that 151,936 of them in float32 fit."""
    mm = _product(precision)
    x = _rms_norm(x, norm.astype(jnp.float32), cfg["rms_norm_eps"])
    vocab = head.shape[1]
    edges = [vocab * i // HEAD_PARTS for i in range(HEAD_PARTS + 1)]
    logits = jnp.concatenate(
        [mm(x, head[:, a:b].astype(jnp.float32))
         for a, b in zip(edges, edges[1:]) if b > a], axis=1)
    return (jnp.max(logits, axis=-1),
            jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0],
            jnp.argmax(logits, axis=-1).astype(jnp.int32))


class ReferenceLM:
    """The reference over one request at a time: the final hidden state
    of every position of prompt + served tokens, a layer at a time, and
    logits of the served rows only (10,240 rows of 151,936 logits would
    be 6.2 GB).  Every request is padded to `max_len`, one shape."""

    def __init__(self, cfg, seed, max_len, params=None):
        self.cfg, self.max_len = cfg, max_len
        self.p = params if params is not None else init_params(cfg, seed)
        self._layer = jax.jit(functools.partial(_layer, cfg),
                              static_argnames=("precision", "fault"))
        self._judge = jax.jit(functools.partial(_judge, cfg),
                              static_argnames=("precision",))
        self._gaps = {}                  # judge -> gaps of every token
        self._float32 = (None, None)     # the last request's ids, hidden

    def hidden(self, ids, pos, precision="float32", fault=None):
        """float32 hidden states [T, H] before the final norm, of token
        ids [T] at positions pos [T]."""
        x = self.p["embed"][jnp.asarray(ids)].astype(jnp.float32)
        pos = jnp.asarray(pos, jnp.int32)
        for i in range(self.cfg["num_hidden_layers"]):
            pre = f"layers.{i}."
            w = {n[len(pre):]: v for n, v in self.p.items()
                 if n.startswith(pre)}
            x = self._layer(w, x, pos, precision=precision, fault=fault)
        return x

    def judged(self, x, first, tok, precision="float32"):
        """Of rows first .. first + len(tok) - 1 of hidden states x:
        (best logit, the logit of `tok`, the row's first token), numpy."""
        n, rows = len(tok), min(HEAD_ROWS, x.shape[0])
        out = [np.zeros(n, np.float32), np.zeros(n, np.float32),
               np.zeros(n, np.int32)]
        for at in range(0, n, rows):
            # a block that would run past the end starts earlier
            row0 = min(first + at, x.shape[0] - rows)
            skip = first + at - row0
            take = min(rows - skip, n - at)
            toks = np.zeros(rows, np.int32)
            toks[skip:skip + take] = tok[at:at + take]
            got = self._judge(
                self.p["final_norm"], self.p["lm_head"],
                jax.lax.dynamic_slice_in_dim(x, row0, rows, 0),
                jnp.asarray(toks), precision=precision)
            for o, g in zip(out, got):
                o[at:at + take] = np.asarray(g)[skip:skip + take]
        return out

    def _sequence(self, prompt, served, fault):
        """(ids, positions, row of the first served token's logits,
        served tokens judged), padded to `max_len` (the mask keeps what
        follows a request out of it).  `padding_in_state`: FAULT_PADDING
        pad tokens between prompt and answer, at the positions that
        follow the prompt, the answer's positions as they were."""
        start = len(prompt)
        pad = FAULT_PADDING if fault == "padding_in_state" else 0
        n = min(len(served), self.max_len - start - pad)
        ids = np.zeros(self.max_len, np.int32)
        pos = np.arange(self.max_len, dtype=np.int32)
        ids[:start] = prompt
        ids[start + pad:start + pad + n] = served[:n]
        pos[start + pad:] -= pad
        return ids, pos, start + pad - 1, n

    def token_gaps(self, prompt, served, control=False, fault=None):
        """What the harness compares: for served token i, at sequence
        position len(prompt) + i, how far its float32 logit lies under
        the float32 best.  With `control` (or a `fault`), the token
        judged is not the served one but the one the fp8 (or the faulty)
        forward pass puts first; with `control` the planted faults are
        read too, into `report()`."""
        served = np.asarray(served, np.int32)
        ids, pos, first, n = self._sequence(prompt, served, None)
        if self._float32[0] is None or not np.array_equal(
                self._float32[0], ids):
            self._float32 = (ids, self.hidden(ids, pos))
        x = self._float32[1]

        def gap_of(tok, judge, rows=first):
            best, at_tok, _ = self.judged(x, rows, tok)
            gap = best - at_tok
            self._gaps.setdefault(judge, []).append(gap)
            return gap

        def first_of(precision, fault):
            ids_f, pos_f, first_f, n_f = self._sequence(prompt, served,
                                                        fault)
            other = self.hidden(ids_f, pos_f, precision, fault)
            return self.judged(other, first_f, np.zeros(n_f, np.int32),
                               precision)[2]

        if control:
            for f in FAULTS:
                gap_of(first_of("float32", f), f)
            gap = gap_of(first_of("fp8", None), "fp8")
        elif fault:
            gap = gap_of(first_of("float32", fault), fault)
        else:
            gap = gap_of(served, "served")
        print("brumby-14b reference, so far: " + json.dumps(self.report()),
              file=sys.stderr, flush=True)
        return gap

    def report(self):
        """What has been compared so far: for each judge (the served
        tokens; under `--control 1` the fp8 pass's and each planted
        fault's first choices) how the gaps of all its tokens are
        distributed."""
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
        return out
