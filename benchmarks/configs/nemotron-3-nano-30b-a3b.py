"""nemotron-3-nano-30b-a3b: one chip's share of the first pipeline stage
of NVIDIA's Nemotron-3-Nano-30B-A3B (`model_type: nemotron_h`), served:
the model through the program's public entry points, its plain
reference, and the operation and byte counts of its shapes.

The harness loads this file by the configuration's name.  Three parts:

1. `init_params`: every weight from the seed, on the device, a block's
   leaves in one program, in the type it is served in (bfloat16; dt_bias, A_log, D and
   the router's selection bias float32), drawn as the configuration's
   own init keys say (configs/nemotron-3-nano-30b-a3b.json
   `assumed.mixer_init`).  The program's side and the reference both
   start from these arrays; the reference takes nothing else.
2. `build_engine`: `paddle_tpu.models.nemotron_h` behind
   `serving.DecodeEngine`.  Nothing here re-implements the program.
   Before the callers start, one seeded request is served alone and
   what it left in its slot is read back (`_probe`): the first Mamba
   layer's SSD state and conv window, the first attention layer's K
   and V.
3. `ReferenceLM`: the layers as published (the json's `assumed` names
   what the config has no key for) in plain `jax.numpy`, float32 at
   "highest" matmul precision, one forward pass over prompt and served
   tokens: the Mamba-2 mixer's recurrence in the published minimal
   chunked form (Dao, Gu, arXiv:2405.21060, "ssd_minimal": the
   chunks' states passed by one segment-sum matrix, no scan), its
   convolution over the whole sequence, attention with K and V repeated
   for the query heads that read them, a block of queries at a time,
   every held expert over every token under a mask; no kernel, no
   state, no cache.  It imports nothing of `paddle_tpu`.  The program
   computes the same function with a state: a chunked prefill kernel
   leaves it, every decode step advances it, so every compared token has
   passed through the states' whole life.  The weights stay in their
   stored bfloat16 and one layer at a time is raised to float32; every
   request is padded to `max_len`, one shape.  `control=True` judges the
   token that the same pass puts first with every matrix product's
   operands rounded to fp8 (e4m3, per-tensor scale), and besides it a
   pass whose SSD state is bfloat16 (`bf16_state`: the prompt's state
   rounded once, as a prefill would store it, then a step at a time,
   rounded after each) and each planted fault (`FAULTS`); `fault=` one
   of them alone.  `token_gaps` answers with a request's mean gap, so
   what the harness holds to `token_logit_gap` is the worst sampled
   request's mean gap, as trinity-mini's is, and with infinity where
   the probe's states lie outside the json's `state_check.tolerance`
   of the reference's for the same positions (`state_errors`: the
   mixer's inputs rounded to the served type there, so that the SSD
   state is held to the float32 recurrence and a bfloat16 state,
   which moves fewer tokens than the program's own bfloat16
   arithmetic, fails).  The controls and the faults leave states of
   their own, held to the same tolerance.  The reference writes, to
   standard error, how the gaps of all compared tokens are distributed
   and every judge's state errors (`ReferenceLM.report`), so that a
   limit is set from what a run reads.

The share (the json's `deployment`): the chip holds `n_routed_experts`
of the `n_routed_experts_deployment` routed experts and `vocab_size`
rows of the vocabulary.  The router scores all 128 and normalises over
all 6 chosen; what the absent experts would have added is left out, in
the program and here alike.
"""

import functools
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"

# ---------------------------------------------------------------------------
# shapes and counts
# ---------------------------------------------------------------------------


def _dims(cfg):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return dict(
        h=cfg["hidden_size"], mh=heads, mp=p, g=g, n=n,
        di=heads * p, cd=heads * p + 2 * g * n, k=cfg["conv_kernel"],
        heads=cfg["num_attention_heads"], kvh=cfg["num_key_value_heads"],
        d=cfg["head_dim"], fe=cfg["moe_intermediate_size"],
        fs=cfg["moe_shared_expert_intermediate_size"],
        held=cfg["n_routed_experts"],
        routed=cfg["n_routed_experts_deployment"],
        vocab=cfg["vocab_size"], pattern=cfg["hybrid_override_pattern"])


def param_specs(cfg):
    """[(name, shape, kind)] under the names of
    `paddle_tpu.models.nemotron_h.param_shapes`; matrices are [in,
    out], the convolution's weight [taps, channels]."""
    d = _dims(cfg)
    h = d["h"]
    out = [("embed", (d["vocab"], h), "matrix")]
    for i, kind in enumerate(d["pattern"]):
        p = f"layers.{i}."
        out.append((p + "norm", (h,), "gain"))
        if kind == MAMBA:
            out += [
                (p + "in_proj", (h, d["di"] + d["cd"] + d["mh"]), "matrix"),
                (p + "conv_weight", (d["k"], d["cd"]), "conv"),
                (p + "conv_bias", (d["cd"],), "conv"),
                (p + "dt_bias", (d["mh"],), "dt_bias"),
                (p + "A_log", (d["mh"],), "A_log"),
                (p + "D", (d["mh"],), "D"),
                (p + "gate_norm", (d["di"],), "gain"),
                (p + "out_proj", (d["di"], h), "matrix")]
        elif kind == ATTENTION:
            q, kv = d["heads"] * d["d"], d["kvh"] * d["d"]
            out += [(p + "q", (h, q), "matrix"), (p + "k", (h, kv), "matrix"),
                    (p + "v", (h, kv), "matrix"), (p + "o", (q, h), "matrix")]
        else:
            out += [
                (p + "router", (h, d["routed"]), "matrix"),
                (p + "router_bias", (d["routed"],), "bias"),
                (p + "experts_up", (d["held"], h, d["fe"]), "matrix"),
                (p + "experts_down", (d["held"], d["fe"], h), "matrix"),
                (p + "shared_up", (h, d["fs"]), "matrix"),
                (p + "shared_down", (d["fs"], h), "matrix")]
    return out + [("final_norm", (h,), "gain"),
                  ("lm_head", (h, d["vocab"]), "matrix")]


def param_count(cfg):
    return sum(int(np.prod(shape)) for _, shape, _ in param_specs(cfg))


def layers_of(cfg, kind):
    return cfg["hybrid_override_pattern"].count(kind)


def attention_flops_per_position(cfg):
    """Operations of one query token against one cached position in one
    attention layer: every query head's score and weighted sum."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def ssd_flops_per_position(cfg):
    """Operations of the recurrence for one position in one Mamba layer:
    every head's decay and rank-one update of its [head_dim, state]
    state (3 an entry) and its answer S C (2 an entry)."""
    d = _dims(cfg)
    return 5 * d["mh"] * d["mp"] * d["n"]


def serve_flops_per_token(cfg):
    """The published mathematics a token meets on this chip, whatever
    implements it: 2 for each weight it meets in a matrix product (the
    Mamba mixers' projections and convolution, the attention layers',
    the router, the shared expert, and of the routed experts the
    `num_experts_per_tok` x held / routed it is expected to find here;
    the head; the embedding look-up not), plus the recurrence in every
    Mamba layer and attention over the cell's mean live context
    (`assumed.mean_live_context`) in every attention layer."""
    d = _dims(cfg)
    h = d["h"]
    mamba = h * (d["di"] + d["cd"] + d["mh"]) + d["k"] * d["cd"] \
        + d["di"] * h
    attention = 2 * h * d["heads"] * d["d"] + 2 * h * d["kvh"] * d["d"]
    moe = h * d["routed"] + 2 * h * d["fs"] + 2 * h * d["fe"] \
        * cfg["num_experts_per_tok"] * d["held"] / d["routed"]
    weights = layers_of(cfg, MAMBA) * mamba \
        + layers_of(cfg, ATTENTION) * attention \
        + layers_of(cfg, EXPERTS) * moe + h * d["vocab"]
    return 2 * weights \
        + layers_of(cfg, MAMBA) * ssd_flops_per_position(cfg) \
        + layers_of(cfg, ATTENTION) * attention_flops_per_position(cfg) \
        * cfg["assumed"]["mean_live_context"]


def expert_layers(cfg):
    return layers_of(cfg, EXPERTS)


def expert_bytes(cfg):
    """Bytes of the held routed experts' weights over all expert layers,
    at the published width (1,856; the program stores 1,920): what one
    program run reads of them once every held expert has an
    assignment."""
    d = _dims(cfg)
    return expert_layers(cfg) * d["held"] * 2 * d["h"] * d["fe"] * 2


def expert_flops_per_assignment(cfg):
    """Up and down, no gate."""
    return 2 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def ssd_state_bytes(cfg):
    """Bytes of the published SSD state one slot holds over all Mamba
    layers: float32 [heads, head_dim, state] a layer."""
    d = _dims(cfg)
    return layers_of(cfg, MAMBA) * d["mh"] * d["mp"] * d["n"] * 4


def slot_state_bytes(cfg):
    """Bytes of both states one slot holds: the SSD state, and the conv
    window of conv_kernel - 1 inputs of every channel in bfloat16."""
    d = _dims(cfg)
    return ssd_state_bytes(cfg) \
        + layers_of(cfg, MAMBA) * (d["k"] - 1) * d["cd"] * 2


def ssd_decode_bytes(cfg, active_slots):
    """Bytes of SSD state a decode step must read and write: the state
    of every active slot, once each way."""
    return 2 * active_slots * ssd_state_bytes(cfg)


def ssd_decode_flops(cfg, active_slots):
    return active_slots * layers_of(cfg, MAMBA) * ssd_flops_per_position(cfg)


def ssd_prefill_flops(cfg, bucket):
    """Operations of the published chunked form over one prefill's
    bucket, all Mamba layers, in chunks of `chunk_size` Q (a bucket's
    padding counts: the kernel is given the bucket): a chunk's C B^T
    once a group (2 Q^2 N), and for every head the decays applied to it
    (Q^2), its product with dt x (2 Q^2 P), the chunk's contribution to
    the state (2 Q P N), the state before it read by C (2 Q N P), and
    the state carried on (P N)."""
    d = _dims(cfg)
    q, p, n = cfg["chunk_size"], d["mp"], d["n"]
    chunk = d["g"] * 2 * q * q * n \
        + d["mh"] * (q * q + 2 * q * q * p + 4 * q * p * n + p * n)
    return layers_of(cfg, MAMBA) * (bucket // q) * chunk


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _draw(key, shape, kind, std, bias_std, time_step, dtype):
    f32 = jnp.float32
    if kind == "dt_bias":
        lo, hi, floor = time_step
        dt = jnp.exp(math.log(lo) + jax.random.uniform(key, shape, f32)
                     * (math.log(hi) - math.log(lo)))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))     # softplus's inverse
    if kind == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if kind == "conv":
        # the convolution's default, fan-in one channel x shape[0] taps
        bound = 1.0 / math.sqrt(shape[0])
        return jax.random.uniform(key, shape, f32, -bound, bound).astype(dtype)
    z = jax.random.normal(key, shape, jnp.bfloat16).astype(f32)
    if kind == "bias":
        return z * bias_std                      # stays float32
    if kind == "D":
        return 1.0 + z * std                     # stays float32
    return ((1.0 + z * std) if kind == "gain" else z * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _draw_block(key, index, leaves, taps, std, bias_std, time_step, dtype):
    """The leaves [(shape, kind)] of one block, leaf j from
    fold_in(key, index[j]): one program for all the blocks of a kind, so
    that a cold start compiles four and not one a leaf's shape."""
    out = []
    for j, (shape, kind) in enumerate(leaves):
        k = jax.random.fold_in(key, index[j])
        if kind == "conv" and len(shape) == 1:
            # the bias's bound is the weight's: 1 / sqrt(taps)
            out.append(_draw(k, (taps,) + shape, kind, std, bias_std,
                             time_step, dtype)[0])
        else:
            out.append(_draw(k, shape, kind, std, bias_std, time_step,
                             dtype))
    return out


def init_params(cfg, seed):
    """{name: array}: matrices N(0, initializer_range); gains and D
    1 + N(0, initializer_range), so that no leaf is inert in the
    comparison; the convolution's weight and bias U(-1/2, 1/2); dt_bias
    and A_log from the config's time_step_* keys and [1, 16];
    `router_bias` N(0, assumed `expert_bias_std`), float32.  Leaf i of
    `param_specs` comes from fold_in(key, i), a block at a time."""
    key = jax.random.PRNGKey(int(seed) % (2 ** 32))
    a = cfg["assumed"]
    steps = (cfg["time_step_min"], cfg["time_step_max"],
             cfg["time_step_floor"])
    blocks = {}
    for i, (name, shape, kind) in enumerate(param_specs(cfg)):
        block = name.rsplit(".", 1)[0] if name.startswith("layers.") else ""
        blocks.setdefault(block, []).append((i, name, tuple(shape), kind))
    out = {}
    for leaves in blocks.values():
        drawn = _draw_block(
            key, jnp.asarray([i for i, *_ in leaves], jnp.uint32),
            tuple((shape, kind) for _, _, shape, kind in leaves),
            cfg["conv_kernel"], a["initializer_range"], a["expert_bias_std"],
            steps, cfg["dtype"])
        out.update((name, v) for (_, name, _, _), v in zip(leaves, drawn))
    return out


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def build_engine(cfg, job, seed, clock):
    """A `DecodeEngine` with its loop thread, holding the seed's
    weights, with the cell's slots, depth and prefill buckets."""
    from paddle_tpu.models import nemotron_h
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    eng = job["engine"]
    ncfg = nemotron_h.NemotronHCfg.from_hf(cfg, max_seq_len=eng["max_len"])
    want = {n: tuple(s) for n, s, _ in param_specs(cfg)}
    have = {n: tuple(s) for n, (s, _) in
            nemotron_h.param_shapes(ncfg).items()}
    if want != have:
        odd = sorted(set(want.items()) ^ set(have.items()))[:4]
        raise RuntimeError(f"models/nemotron_h.py's leaves differ from "
                           f"param_specs: {odd}")
    params = nemotron_h.NemotronHParams.from_flat(ncfg,
                                                  init_params(cfg, seed))
    # every caller's first request is in the queue at once
    engine = DecodeEngine(params, config=DecodeConfig(
        slots=eng["slots"], max_len=eng["max_len"],
        buckets=tuple(eng["buckets"]), max_queue_depth=job["clients"],
        clock=clock))
    _PROBES[int(seed)] = _probe(engine, cfg, seed)
    return engine


# seed -> what `_probe` read back from the engine built for that seed,
# for the `ReferenceLM` of the same seed (the harness builds the engine
# first and the reference after the window)
_PROBES = {}


def _probe(engine, cfg, seed):
    """Serve one seeded request alone (`state_check`'s prompt_len and
    new_tokens) before the callers start, and read back, once the
    engine is idle, what it left in its slot: the first Mamba layer's
    SSD state [heads, head_dim, N] and conv window, the first attention
    layer's K and V [positions, kv_heads, d] over the positions fed (the
    prompt and every served token but the last).  A slot whose request
    has ended is inactive in every later step, so these are the states
    of its last step."""
    from paddle_tpu.kernels.ssd import unpack_state

    check = cfg["state_check"]
    rng = np.random.default_rng([int(seed), 1])
    prompt = rng.integers(0, cfg["vocab_size"], check["prompt_len"],
                          dtype=np.int32)
    with engine._lock:
        slot = engine._free_slots_locked()[0]
    tokens = np.asarray(engine.submit(
        prompt, max_new_tokens=check["new_tokens"]).result(timeout=600))
    while True:
        with engine._lock:
            if not engine._has_work_locked():
                state = engine._state
                break
        time.sleep(0.01)
    ids = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    mcfg = engine.params.cfg

    def host(a):
        return np.asarray(a).astype(np.float32)

    return {"ids": ids, "start": len(prompt),
            "ssd": host(unpack_state(state["ssd"][0, slot], mcfg.mamba_heads,
                                     mcfg.tiling.pack)),
            "conv": host(state["conv"][0, :, slot]),
            **{n: host(state[n][0, slot, :, :, :len(ids)]).transpose(2, 0, 1)
               for n in ("k", "v")}}


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


def _round_bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _served(cfg, x):
    """x rounded to the type the configuration serves activations in
    (`dtype`): bfloat16 as served, nothing in a float32 rehearsal."""
    return _round_bf16(x) if cfg["dtype"] == "bfloat16" else x


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
    """HF's apply_rotary_pos_emb on x [T, heads, d], position t in row t,
    theta rope_theta over all lanes (partial_rotary_factor 1): the
    planted fault 'rotary' only."""
    t, dim = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * inv_freq.astype(np.float32)
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


def _segsum(x):
    """x [..., T] -> [..., T, T], entry [i, j] = sum of x[j + 1 .. i]
    for j <= i, -inf above the diagonal (ssd_minimal's `segsum`)."""
    t = x.shape[-1]
    xx = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1),
                   jnp.broadcast_to(x[..., :, None], x.shape + (t,)), 0.0)
    seg = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def ssd_minimal(x, a, b, c, block_len, mm, carry=True):
    """The published minimal chunked SSD (arXiv:2405.21060, listing 1)
    over one sequence: x [T, H, P] (already times dt), a [T, H] (dt A),
    b, c [T, H, N] (each head's group's).  Returns (y [T, H, P], the
    last state [H, P, N]).  `carry=False`: the chunks' states are not
    passed on (the planted fault `no_carry`)."""
    t, h, p = x.shape
    m = t // block_len
    x, a, b, c = (z.reshape((m, block_len) + z.shape[1:])
                  for z in (x, a, b, c))
    a = a.transpose(2, 0, 1)                                  # [h, c, l]
    a_cum = jnp.cumsum(a, axis=-1)
    # 1. inside each chunk (diagonal blocks)
    decay = jnp.exp(_segsum(a))                               # [h, c, l, s]
    scores = mm(c, b, "clhn,cshn->chls")
    y_diag = mm(scores * decay.transpose(1, 0, 2, 3), x, "chls,cshp->clhp")
    # 2. each chunk's own state
    decay_states = jnp.exp(a_cum[:, :, -1:] - a_cum)          # [h, c, l]
    states = mm(b * decay_states.transpose(1, 2, 0)[..., None], x,
                "clhn,clhp->chpn")
    # 3. the states passed from chunk to chunk
    states = jnp.concatenate([jnp.zeros_like(states[:1]), states], axis=0)
    decay_chunk = jnp.exp(_segsum(jnp.pad(a_cum[:, :, -1], ((0, 0), (1, 0)))))
    if not carry:
        decay_chunk = jnp.where(jnp.eye(m + 1, dtype=bool), decay_chunk, 0.0)
    new = mm(decay_chunk, states, "hzc,chpn->zhpn")
    states, last = new[:-1], new[-1]
    # 4. the state before each chunk read out by C
    y_off = mm(c, states, "clhn,chpn->clhp") \
        * jnp.exp(a_cum).transpose(1, 2, 0)[..., None]
    if not carry:
        y_off = jnp.zeros_like(y_off)
    return (y_diag + y_off).reshape(t, h, p), last


def _ssd_bf16_state(x, a, b, c, y_chunked, last_prompt, start, stop=None):
    """A bfloat16 SSD state: the prompt's state as a prefill stores it
    (rounded once), then a position at a time, the state rounded after
    each; rows before `start` from the chunked form.  x [T, H, P] (times
    dt), a [T, H] (dt A), b, c [T, H, N].  Returns (y, the state after
    the positions before `stop`, all of them by default)."""
    def step(s, xs):
        x, a, b, c, live = xs
        new = _round_bf16(jnp.exp(a)[:, None, None] * s
                          + x[:, :, None] * b[:, None, :])
        s = jnp.where(live, new, s)
        return s, jnp.einsum("hpn,hn->hp", s, c, precision="highest")

    at = jnp.arange(x.shape[0])
    live = at >= start
    if stop is not None:
        live = jnp.logical_and(live, at < stop)
    last, y = jax.lax.scan(step, _round_bf16(last_prompt), (x, a, b, c, live))
    return jnp.where(live[:, None, None], y, y_chunked), last


FAULTS = ("no_carry", "conv_window_zeroed", "no_D", "no_z_gate",
          "bc_by_mod", "rotary", "no_shared_expert")
# the kind of block each fault (and the bfloat16 state) lies in: the
# other kinds are traced without it
FAULT_KIND = {"rotary": ATTENTION, "no_shared_expert": EXPERTS}


def _mamba_parts(cfg, w, x, mm, start, fault, served=False):
    """The Mamba-2 mixer's inputs over x [T, H]: (z, xBC before the
    convolution, x [T, heads, head_dim], dt x, dt A [T, heads], B, C
    [T, heads, N]); a later row sees the rows before it.  `served`: the
    normed input, W_in's product and the convolution's output rounded
    to the type the configuration serves activations in."""
    d = _dims(cfg)
    t, eps = x.shape[0], cfg["layer_norm_epsilon"]
    rnd = functools.partial(_served, cfg) if served else (lambda v: v)
    a_in = rnd(_rms_norm(x, w["norm"], eps))
    zxbcdt = rnd(mm(a_in, w["in_proj"]))
    z, xbc, dt = (zxbcdt[:, :d["di"]], zxbcdt[:, d["di"]:d["di"] + d["cd"]],
                  zxbcdt[:, d["di"] + d["cd"]:])
    # the causal depthwise convolution, zeros before position 0
    k = d["k"]

    def conv(inp):
        padded = jnp.pad(inp, ((k - 1, 0), (0, 0)))
        return sum(padded[i:i + t] * w["conv_weight"][i] for i in range(k))

    out = conv(xbc)
    if fault == "conv_window_zeroed":
        before = (jnp.arange(t) < start)[:, None]
        out = jnp.where(before, out, conv(jnp.where(before, 0.0, xbc)))
    conv_out = rnd(jax.nn.silu(out + w["conv_bias"]))
    xs = conv_out[:, :d["di"]].reshape(t, d["mh"], d["mp"])
    gn = d["g"] * d["n"]
    b = conv_out[:, d["di"]:d["di"] + gn].reshape(t, d["g"], d["n"])
    c = conv_out[:, d["di"] + gn:].reshape(t, d["g"], d["n"])
    group = d["mh"] // d["g"]
    heads = jnp.arange(d["mh"])
    of = heads % d["g"] if fault == "bc_by_mod" else heads // group
    b, c = b[:, of], c[:, of]                                 # [T, H, N]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    da = dt * -jnp.exp(w["A_log"])
    return z, xbc, xs, xs * dt[..., None], da, b, c


def _mamba(cfg, w, x, mm, start, fault, judge):
    """x + the Mamba-2 mixer over x [T, H]; a later row sees the rows
    before it.  `start`: the first served position (where a prefill's
    state and conv window hand over to the decode steps)."""
    d = _dims(cfg)
    t, eps = x.shape[0], cfg["layer_norm_epsilon"]
    z, _, xs, ux, da, b, c = _mamba_parts(cfg, w, x, mm, start, fault)
    chunk = cfg["chunk_size"]
    if judge == "bf16_state":
        # the prompt alone through the chunked form, its last state kept
        prompt = (jnp.arange(t) < start)[:, None]
        y, last = ssd_minimal(ux * prompt[..., None], da * prompt, b, c,
                              chunk, mm)
        y, _ = _ssd_bf16_state(ux, da, b, c, y, last, start)
    else:
        # `no_carry`: each chunk of the program's chunk_size starts from
        # a zero state
        y, _ = ssd_minimal(ux, da, b, c, chunk, mm,
                           carry=fault != "no_carry")
    if fault != "no_D":
        y = y + w["D"][:, None] * xs
    y = y.reshape(t, d["di"])
    if fault != "no_z_gate":
        y = y * jax.nn.silu(z)
    y = _rms_norm(y.reshape(t, d["g"], -1), 1.0, eps).reshape(t, d["di"]) \
        * w["gate_norm"]
    return x + mm(y, w["out_proj"])


def _mamba_states(cfg, w, x, mm, start, length, fault, judge):
    """Both states a Mamba layer leaves in a slot after the first
    `length` positions of x [T, H] (`start` of them the prompt): the SSD
    state [heads, head_dim, N] and the conv window [conv_kernel - 1,
    conv_dim] (the last pre-convolution inputs, oldest first), the
    mixer's inputs in the served type.  Positions from `length` on add
    nothing (dt = 0)."""
    k, chunk = cfg["conv_kernel"], cfg["chunk_size"]
    _, xbc, _, ux, da, b, c = _mamba_parts(cfg, w, x, mm, start, fault,
                                           served=True)
    at = jnp.arange(x.shape[0])
    if judge == "bf16_state":
        prompt = at < start
        _, last = ssd_minimal(ux * prompt[:, None, None], da * prompt[:, None],
                              b, c, chunk, mm)
        _, state = _ssd_bf16_state(ux, da, b, c, jnp.zeros_like(ux), last,
                                   start, length)
    else:
        live = at < length
        if fault == "no_carry":
            # only the positions of the last chunk reach its state
            live = jnp.logical_and(live, at >= (length - 1) // chunk * chunk)
        _, state = ssd_minimal(ux * live[:, None, None], da * live[:, None],
                               b, c, chunk, mm)
    window = jax.lax.dynamic_slice_in_dim(
        jnp.pad(xbc, ((k - 1, 0), (0, 0))), length, k - 1, 0)
    return state, window


def _qkv(cfg, w, x, mm, fault):
    """Queries [T, heads, d] and the K and V [T, kv_heads, d] a cache
    holds, of x [T, H]."""
    d = _dims(cfg)
    t, heads, kvh, hd = x.shape[0], d["heads"], d["kvh"], d["d"]
    a = _rms_norm(x, w["norm"], cfg["layer_norm_epsilon"])
    q = mm(a, w["q"]).reshape(t, heads, hd)
    k = mm(a, w["k"]).reshape(t, kvh, hd)
    v = mm(a, w["v"]).reshape(t, kvh, hd)
    if fault == "rotary":
        q, k = _rotate(cfg, q), _rotate(cfg, k)
    return q, k, v


def _attention(cfg, w, x, mm, fault):
    """x + causal grouped-query attention over x [T, H]."""
    d = _dims(cfg)
    t, heads, kvh, hd = x.shape[0], d["heads"], d["kvh"], d["d"]
    q, k, v = _qkv(cfg, w, x, mm, fault)
    # K and V of a head, once for each query head that reads it
    k, v = (jnp.repeat(z, heads // kvh, axis=1).swapaxes(0, 1)
            for z in (k, v))                                  # [heads, T, d]
    block = math.gcd(t, 512)
    col = jnp.arange(t)[None, :]

    def some_queries(args):
        q, row0 = args                                        # [heads, b, d]
        row = row0 + jnp.arange(block)[:, None]
        s = mm(q, k, "hqd,hkd->hqk") * hd ** -0.5
        p = jax.nn.softmax(jnp.where(col <= row, s, -jnp.inf), axis=-1)
        return mm(p, v, "hqk,hkd->hqd")

    qb = q.swapaxes(0, 1).reshape(heads, t // block, block, hd).swapaxes(0, 1)
    o = jax.lax.map(some_queries, (qb, jnp.arange(0, t, block)))
    return x + mm(o.transpose(0, 2, 1, 3).reshape(t, heads * hd), w["o"])


def _relu2(h, up, down, mm):
    return mm(jnp.square(jax.nn.relu(mm(h, up))), down)


def _experts(cfg, w, x, mm, fault):
    """x + routed experts held here + the shared expert, of tokens
    x [T, H]: every held expert over every token, under the mask of who
    chose it."""
    k = cfg["num_experts_per_tok"]
    first = cfg.get("first_expert", 0)
    h = _rms_norm(x, w["norm"], cfg["layer_norm_epsilon"])
    scores = jax.nn.sigmoid(mm(h, w["router"]))
    _, chosen = jax.lax.top_k(scores + w["router_bias"], k)    # [T, k]
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    weights = picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]

    def expert(y, e_w):
        e, up, down = e_w
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=1)
        return y + mine[:, None] * _relu2(h, up, down, mm), None

    held = w["experts_up"].shape[0]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (jnp.arange(held), w["experts_up"],
                         w["experts_down"]))
    if fault != "no_shared_expert":
        y = y + _relu2(h, w["shared_up"], w["shared_down"], mm)
    return x + y


def _layer(cfg, kind, w, x, start, precision, fault, judge):
    """One block over x [T, H]; w: its weights as stored, raised to
    float32 here."""
    mm = _product(precision)
    w = {n: v.astype(jnp.float32) for n, v in w.items()}
    if kind == MAMBA:
        return _mamba(cfg, w, x, mm, start, fault, judge)
    if kind == ATTENTION:
        return _attention(cfg, w, x, mm, fault)
    return _experts(cfg, w, x, mm, fault)


def _head(cfg, norm, head, x, precision):
    x = _rms_norm(x, norm.astype(jnp.float32), cfg["layer_norm_epsilon"])
    return _product(precision)(x, head.astype(jnp.float32))


def _states(cfg, kind, w, x, start, length, precision, fault, judge):
    """What a layer of `kind` leaves in a slot after the first `length`
    positions of x [T, H]: a Mamba layer's SSD state and conv window
    (`_mamba_states`), an attention layer's K and V [T, kv_heads, d]."""
    mm = _product(precision)
    w = {n: v.astype(jnp.float32) for n, v in w.items()}
    if kind == MAMBA:
        return _mamba_states(cfg, w, x, mm, start, length, fault, judge)
    _, k, v = _qkv(cfg, w, x, mm, fault)
    return k, v


# how each judge's forward pass differs from the float32 reference's
JUDGES = {"fp8": {"precision": "fp8"},
          "bf16_state": {"judge": "bf16_state"},
          **{f: {"fault": f} for f in FAULTS}}


def _relative(got, want, axis=None):
    """The largest of |got - want| / |want| (Frobenius) over the entries
    `axis` leaves."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    num = np.sqrt(np.sum(np.square(got - want), axis=axis))
    den = np.sqrt(np.sum(np.square(want), axis=axis))
    return float(np.max(num / np.maximum(den, 1e-30)))


class ReferenceLM:
    """The reference over one request at a time: logits of every
    position of prompt + served tokens, a layer at a time.  Every
    request is padded to `max_len`, one shape.

    Beside the tokens, the slot's states: `probe` (by default what
    `build_engine` of the same seed read back from its engine) holds
    what one request served alone left in its slot; `slot_states` is
    what this reference leaves for the same positions, and
    `state_errors` how far the two lie apart."""

    def __init__(self, cfg, seed, max_len, params=None, probe=None):
        self.cfg, self.max_len = cfg, max_len
        self.p = params if params is not None else init_params(cfg, seed)
        self.probe = probe if probe is not None else _PROBES.get(int(seed))
        self._layer = jax.jit(functools.partial(_layer, cfg),
                              static_argnames=("kind", "precision", "fault",
                                               "judge"))
        self._head = jax.jit(functools.partial(_head, cfg),
                             static_argnames=("precision",))
        self._states = jax.jit(functools.partial(_states, cfg),
                               static_argnames=("kind", "precision", "fault",
                                                "judge"))
        self._gaps = {}                  # judge -> gaps of every token
        self._float32 = (None, None)     # the last request's ids, logits
        self._state_errors = {}          # judge -> {array: error}
        self._reference_states = None

    def _weights(self, i):
        pre = f"layers.{i}."
        return {n[len(pre):]: v for n, v in self.p.items()
                if n.startswith(pre)}

    def logits(self, ids, start, precision="float32", fault=None,
               judge=None):
        """float32 logits [T, vocab] of token ids [T]; `start`, the
        first served position."""
        x = self.p["embed"][jnp.asarray(ids)].astype(jnp.float32)
        start = jnp.int32(start)
        for i, kind in enumerate(self.cfg["hybrid_override_pattern"]):
            mine = fault is not None and FAULT_KIND.get(fault, MAMBA) == kind
            x = self._layer(kind, self._weights(i), x, start,
                            precision=precision,
                            fault=fault if mine else None,
                            judge=judge if kind == MAMBA else None)
        return self._head(self.p["final_norm"], self.p["lm_head"], x,
                          precision=precision)

    def slot_states(self, precision="float32", fault=None, judge=None):
        """What the probe's positions leave in a slot by this reference:
        the first Mamba layer's SSD state [heads, head_dim, N] (the
        mixer's inputs in the served type, the recurrence in float32)
        and conv window [conv_kernel - 1, conv_dim], the first attention
        layer's K and V [positions, kv_heads, d]."""
        ids, start = self.probe["ids"], self.probe["start"]
        padded = np.zeros(self.max_len, np.int32)
        padded[:len(ids)] = ids
        x = self.p["embed"][jnp.asarray(padded)].astype(jnp.float32)
        n = len(ids)
        start, length = jnp.int32(start), jnp.int32(n)
        out = {}
        for i, kind in enumerate(self.cfg["hybrid_override_pattern"]):
            w = self._weights(i)
            mine = fault is not None and FAULT_KIND.get(fault, MAMBA) == kind
            how = dict(precision=precision, fault=fault if mine else None,
                       judge=judge if kind == MAMBA else None)
            if kind == MAMBA and "ssd" not in out:
                out["ssd"], out["conv"] = self._states(kind, w, x, start,
                                                       length, **how)
            elif kind == ATTENTION:
                k, v = self._states(kind, w, x, start, length, **how)
                out["k"], out["v"] = k[:n], v[:n]
                break
            x = self._layer(kind, w, x, start, **how)
        return {name: np.asarray(a, np.float32) for name, a in out.items()}

    def state_errors(self, judge="served"):
        """How far the slot's states lie from this reference's, each as
        the largest relative (Frobenius) error: `ssd` over the heads,
        `conv` the window, `kv` the worse of K and V.  `judge` "served":
        the program's, as the probe read them back; else the states of
        the reference's own pass as that judge (`JUDGES`) alters it.
        None without a probe."""
        if self.probe is None:
            return None
        if judge not in self._state_errors:
            if self._reference_states is None:
                self._reference_states = self.slot_states()
            want = self._reference_states
            got = self.probe if judge == "served" \
                else self.slot_states(**JUDGES[judge])
            self._state_errors[judge] = {
                "ssd": _relative(got["ssd"], want["ssd"], axis=(1, 2)),
                "conv": _relative(got["conv"], want["conv"]),
                "kv": max(_relative(got["k"], want["k"]),
                          _relative(got["v"], want["v"]))}
        return self._state_errors[judge]

    def state_holds(self, judge="served"):
        """Whether every state error is within the configuration's
        `state_check.tolerance`; never without a probe."""
        errors = self.state_errors(judge)
        tol = self.cfg["state_check"]["tolerance"]
        return errors is not None and all(errors[n] <= tol[n] for n in tol)

    def token_gaps(self, prompt, served, control=False, fault=None):
        """What the harness compares: the mean of the request's `gaps`,
        and infinity where the slot's states are not within their
        tolerance (limits/nemotron-3-nano-30b-a3b.*.json says why: the
        widest single gap of the program's bfloat16 arithmetic lies
        within 1.3 times of the fp8 pass's, its mean 2.4 times under; a
        bfloat16 SSD state moves fewer tokens than that arithmetic and
        only its state shows it)."""
        judge = "fp8" if control else (fault or "served")
        mean = self.gaps(prompt, served, control, fault).mean()
        return np.array([mean, 0.0 if self.state_holds(judge) else np.inf])

    def gaps(self, prompt, served, control=False, fault=None):
        """For served token i, at sequence position len(prompt) + i: how
        far its float32 logit lies under the float32 best.  With
        `control` (or a `fault`), the token judged is not the served one
        but the one the fp8 (or the faulty) forward pass puts first; with
        `control` the bfloat16 state and the planted faults are read
        too, into `report()`."""
        n, start = len(served), len(prompt)
        # padding: the mask keeps it inert
        ids = np.zeros(self.max_len, np.int32)
        ids[:start] = prompt
        ids[start:start + n] = served
        if self._float32[0] is None or not np.array_equal(
                self._float32[0], ids):
            rows = self.logits(ids, start)[start - 1:start - 1 + n]
            self._float32 = (ids, rows)
        rows = self._float32[1]

        def judged(tok, judge):
            gap = np.asarray(jnp.max(rows, axis=-1) - jnp.take_along_axis(
                rows, tok[:, None], axis=-1)[:, 0])
            self._gaps.setdefault(judge, []).append(gap)
            return gap

        def first_of(precision="float32", fault=None, judge=None):
            other = self.logits(ids, start, precision, fault, judge)
            return jnp.argmax(other[start - 1:start - 1 + n], axis=-1)

        if control:
            for f in FAULTS:
                judged(first_of(fault=f), f)
            judged(first_of(judge="bf16_state"), "bf16_state")
            gap = judged(first_of("fp8"), "fp8")
            for judge in JUDGES:
                self.state_errors(judge)
        elif fault == "bf16_state":
            gap = judged(first_of(judge="bf16_state"), fault)
        elif fault:
            gap = judged(first_of(fault=fault), fault)
        else:
            gap = judged(jnp.asarray(np.asarray(served, np.int32)), "served")
            self.state_errors()
        print("nemotron-3-nano-30b-a3b reference, so far: "
              + json.dumps(self.report()), file=sys.stderr, flush=True)
        return gap

    def report(self):
        """What has been compared so far: for each judge (the served
        tokens; under `--control 1` the fp8 pass's, the bfloat16 state's
        and each planted fault's first choices) how the gaps of all its
        tokens are distributed; under `slot_states`, each judge's
        `state_errors` beside the tolerance."""
        out = {"slot_states": dict(
            self._state_errors,
            tolerance=self.cfg["state_check"]["tolerance"])}
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
