"""gpt2-medium: the model built through the program's public entry
points, its plain reference, and the operation counts of its shapes.

The harness loads this file by the configuration's name.  Three parts:

1. `init_params`: every weight from the seed, on the device, in one
   jitted call, in the type it is trained and served in (bfloat16).  The
   program's side and the reference both start from these arrays; the
   reference takes nothing else.
2. `build_trainer` / `build_engine`: `models/gpt.py` + `models/train.py`
   + `optimizer/functional.py` for training, `serving.DecodeEngine` for
   serving.  Nothing here re-implements the program.
3. `reference_*`: GPT-2 as published (pre-LayerNorm decoder, learned
   positions, tied output embedding) in plain `jax.numpy`, float32 at
   "highest" matmul precision, no kernels, no cache, no batching across
   requests.  It imports nothing of `paddle_tpu`.  Departures from the
   published model, both the program's: exact (erf) GELU instead of
   `gelu_new`, dropout off.  `precision="fp8"` turns it into the
   control: every matrix product's operands rounded to fp8 under a
   per-tensor scale (e4m3 forward, e5m2 for the gradient coming back),
   the step below bfloat16 that would tempt a later PR.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# shapes and counts
# ---------------------------------------------------------------------------

_BLOCK = (  # suffix, shape as a function of (H), kind
    ("norm1.weight", lambda h: (h,), "gain"),
    ("norm1.bias", lambda h: (h,), "bias"),
    ("attn.q_proj.weight", lambda h: (h, h), "matrix"),
    ("attn.q_proj.bias", lambda h: (h,), "bias"),
    ("attn.k_proj.weight", lambda h: (h, h), "matrix"),
    ("attn.k_proj.bias", lambda h: (h,), "bias"),
    ("attn.v_proj.weight", lambda h: (h, h), "matrix"),
    ("attn.v_proj.bias", lambda h: (h,), "bias"),
    ("attn.out_proj.weight", lambda h: (h, h), "matrix_out"),
    ("attn.out_proj.bias", lambda h: (h,), "bias"),
    ("norm2.weight", lambda h: (h,), "gain"),
    ("norm2.bias", lambda h: (h,), "bias"),
    ("fc1.weight", lambda h: (h, 4 * h), "matrix"),
    ("fc1.bias", lambda h: (4 * h,), "bias"),
    ("fc2.weight", lambda h: (4 * h, h), "matrix_out"),
    ("fc2.bias", lambda h: (h,), "bias"),
)


def param_specs(cfg):
    """[(name, shape, kind)] under the names `nn.layers.param_dict`
    gives `models/gpt.py`'s GPT, in a fixed order."""
    h, v, p = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    out = [("wte.weight", (v, h), "matrix"), ("wpe.weight", (p, h), "matrix")]
    for i in range(cfg["n_layer"]):
        out += [(f"blocks.{i}.{s}", shape(h), kind)
                for s, shape, kind in _BLOCK]
    return out + [("norm_f.weight", (h,), "gain"), ("norm_f.bias", (h,), "bias")]


def param_count(cfg):
    return sum(int(np.prod(shape)) for _, shape, _ in param_specs(cfg))


def train_flops_per_token(cfg, seq):
    """Required forward + backward operations for one token of a
    sequence of `seq`: 6 for each weight a token meets in a matrix
    product (the tied output head counted, the embedding look-ups not),
    and causal attention's two products, half of the square."""
    h, layers = cfg["n_embd"], cfg["n_layer"]
    weights = layers * 12 * h * h + cfg["vocab_size"] * h
    return 6 * weights + 6 * layers * seq * h


def serve_flops_per_token(cfg):
    """2 for each weight a token meets; attention over the cache left
    out (at these lengths under a tenth of it)."""
    h = cfg["n_embd"]
    return 2 * (cfg["n_layer"] * 12 * h * h + cfg["vocab_size"] * h)


def flash_train_floor_s(cfg, batch, seq, peaks):
    """The least seconds the chip needs for one step's flash calls
    (forward, dq and dk/dv kernels of every layer, causal): the larger
    of operations over peak FLOP/s and bytes over peak bytes/s.
    Forward 2 products, backward 5 (scores again, dv, dp, dq, dk), each
    2*S*S*D a head, halved by the mask."""
    heads, d = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    bh = batch * heads
    flops = cfg["n_layer"] * 7 * 2 * seq * seq * d * bh / 2
    tensor = bh * seq * d * 2  # one bf16 [B*H, S, D]
    bytes_ = cfg["n_layer"] * 15 * tensor  # fwd 3 in 1 out, dq 4+1, dkv 4+2
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


def kv_bytes_per_token(cfg):
    """Bytes of K and V one cached position holds over all layers."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * 2


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def init_params(cfg, seed):
    """{name: array of the configuration's dtype}: one normal draw for
    the whole model, cut into leaves.  Matrices N(0, 0.02), the two that
    write into the residual stream scaled by 1/sqrt(2 L) as GPT-2 does; gains 1 +
    N(0, 0.02) and biases N(0, 0.02) rather than 1 and 0, so that no
    leaf is inert in the comparison."""
    specs = param_specs(cfg)
    sizes = [int(np.prod(s)) for _, s, _ in specs]
    std = cfg["initializer_range"]
    out_scale = 1.0 / np.sqrt(2.0 * cfg["n_layer"])
    dtype = jnp.dtype(cfg["dtype"])

    @jax.jit
    def draw(key):
        flat = jax.random.normal(key, (sum(sizes),), jnp.bfloat16)
        params, off = {}, 0
        for (name, shape, kind), n in zip(specs, sizes):
            z = flat[off:off + n].reshape(shape).astype(jnp.float32) * std
            off += n
            if kind == "matrix_out":
                z = z * out_scale
            elif kind == "gain":
                z = 1.0 + z
            params[name] = z.astype(dtype)
        return params

    return draw(jax.random.PRNGKey(int(seed) % (2 ** 32)))


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def build_model(cfg, params):
    """`models/gpt.py`'s GPT holding `params`.  Built under
    `eval_shape`, so that the layer runtime's own leaf-by-leaf random
    initialisation (about 6 s for 388 leaves) is traced, not run."""
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.nn.layers import param_dict

    gcfg = GPTConfig(vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
                     num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                     max_seq_len=cfg["n_positions"], dropout=0.0,
                     dtype=cfg["dtype"])
    box = {}

    def make():
        box["model"] = GPT(gcfg)
        return param_dict(box["model"])

    shapes = jax.eval_shape(make)
    model = box["model"]
    want = {n: (tuple(s), jnp.dtype(cfg["dtype"]))
            for n, s, _ in param_specs(cfg)}
    have = {n: (tuple(v.shape), v.dtype) for n, v in shapes.items()}
    if want != have:
        odd = sorted(set(want.items()) ^ set(have.items()))[:4]
        raise RuntimeError(f"models/gpt.py's leaves differ from "
                           f"param_specs: {odd}")
    for name, p in model.named_parameters():
        p.value = params[name]
    return model


class Trainer:
    """The compiled step with its state: the one object that set-up
    drives through its first steps and then hands to the window."""

    def __init__(self, cfg, job, seed):
        from paddle_tpu.models.train import init_train_state, make_train_step
        from paddle_tpu.optimizer.functional import AdamW

        self.cfg, self.job, self.seed = cfg, job, seed
        opt = job["optimizer"]
        self.beta1 = opt["beta1"]
        model = build_model(cfg, init_params(cfg, seed))
        optimizer = AdamW(opt["learning_rate"], beta1=opt["beta1"],
                          beta2=opt["beta2"], epsilon=opt["epsilon"],
                          coeff=opt["weight_decay"])
        self.state = init_train_state(model, optimizer)
        self._step = make_train_step(model, optimizer)
        self.tokens_per_step = job["batch"] * job["seq"]

    def step(self, x, y):
        """One optimizer step; returns the loss, still on the device."""
        self.state, loss = self._step(self.state, x, y)
        return loss

    def first_gradient_norms(self):
        """Per-leaf norm of the first gradient as AdamW got it, from
        Moment1 after one step: m1 = (1 - beta1) * g."""
        m1 = {n: s["Moment1"] for n, s in self.state.opt_state.items()
              if n != "__step__"}
        return jax.device_get(_leaf_norms(m1, 1.0 / (1.0 - self.beta1)))

    def change_norms(self):
        """Per-leaf norm of parameters now minus parameters at the
        start, which the seed gives again."""
        start = init_params(self.cfg, self.seed)
        return jax.device_get(_leaf_diff_norms(self.state.params, start))

    def free(self):
        self.state = None
        self._step = None


@functools.partial(jax.jit, static_argnums=1)
def _leaf_norms(tree, scale):
    return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) * scale
            for n, v in tree.items()}


@jax.jit
def _leaf_diff_norms(a, b):
    return {n: jnp.sqrt(jnp.sum(jnp.square(
        a[n].astype(jnp.float32) - b[n].astype(jnp.float32)))) for n in a}


def build_trainer(cfg, job, seed):
    return Trainer(cfg, job, seed)


def build_engine(cfg, job, seed, clock):
    """A `DecodeEngine` with its loop thread, holding the seed's
    weights, with the cell's slots, depth and prefill buckets."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    model = build_model(cfg, init_params(cfg, seed))
    eng = job["engine"]
    return DecodeEngine(model, config=DecodeConfig(
        slots=eng["slots"], max_len=eng["max_len"],
        buckets=tuple(eng["buckets"]), clock=clock))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

_STACKED = tuple(s for s, _, _ in _BLOCK)


def _stack(cfg, flat):
    """{name: leaf} -> (embeddings and final norm, blocks stacked [L, ...]),
    float32."""
    f32 = {n: v.astype(jnp.float32) for n, v in flat.items()}
    blocks = {s: jnp.stack([f32[f"blocks.{i}.{s}"]
                            for i in range(cfg["n_layer"])])
              for s in _STACKED}
    rest = {n: v for n, v in f32.items() if not n.startswith("blocks.")}
    return {"rest": rest, "blocks": blocks}


def _unstack_norms(tree_norms):
    """Norms of the stacked tree ({rest: scalar, blocks: [L]}) under the
    flat leaf names."""
    out = {n: float(v) for n, v in tree_norms["rest"].items()}
    for s, per_layer in tree_norms["blocks"].items():
        for i, v in enumerate(np.asarray(per_layer)):
            out[f"blocks.{i}.{s}"] = float(v)
    return out


def _round(x, exponent_bits, mantissa_bits, top):
    """x rounded to a narrow float under a per-tensor scale that puts
    its largest magnitude at `top`.  `reduce_precision` and not a cast
    there and back: the compiler may drop such a pair of casts."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return jax.lax.reduce_precision(
        x / scale, exponent_bits=exponent_bits,
        mantissa_bits=mantissa_bits) * scale


def _as_stored(x, dtype):
    """float32 values rounded to what storage in `dtype` keeps."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _einsum(a, b, spec):
    if spec is None:
        return jnp.matmul(a, b, precision="highest")
    return jnp.einsum(spec, a, b, precision="highest")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fp8_product(a, b, spec):
    """A matrix product as fp8 training computes it (Micikevicius et al.
    2022): operands in e4m3 going forward, the incoming gradient in e5m2
    going backward, accumulation in float32."""
    return _fp8_fwd(a, b, spec)[0]


def _fp8_fwd(a, b, spec):
    qa, qb = _round(a, 4, 3, 240.0), _round(b, 4, 3, 240.0)
    return _einsum(qa, qb, spec), (qa, qb)


def _fp8_bwd(spec, operands, dy):
    _, vjp = jax.vjp(lambda x, y: _einsum(x, y, spec), *operands)
    return vjp(_round(dy, 5, 2, 57344.0))


_fp8_product.defvjp(_fp8_fwd, _fp8_bwd)


def _matmul(precision):
    if precision == "fp8":
        return lambda a, b, spec=None: _fp8_product(a, b, spec)
    return lambda a, b, spec=None: _einsum(a, b, spec)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _hidden(cfg, p, ids, precision):
    """Final hidden states [B, S, H] of token ids [B, S]."""
    mm = _matmul(precision)
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    b, s = ids.shape
    d = cfg["n_embd"] // heads
    x = p["rest"]["wte.weight"][ids] + p["rest"]["wpe.weight"][:s][None]
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def block(x, w):
        h = _layer_norm(x, w["norm1.weight"], w["norm1.bias"], eps)

        def split(t):
            return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

        q = split(mm(h, w["attn.q_proj.weight"]) + w["attn.q_proj.bias"])
        k = split(mm(h, w["attn.k_proj.weight"]) + w["attn.k_proj.bias"])
        v = split(mm(h, w["attn.v_proj.weight"]) + w["attn.v_proj.bias"])
        scores = mm(q, k, "bhqd,bhkd->bhqk") / np.sqrt(d)
        scores = jnp.where(mask, scores, -jnp.inf)
        att = mm(jax.nn.softmax(scores, axis=-1), v, "bhqk,bhkd->bhqd")
        att = att.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
        x = x + mm(att, w["attn.out_proj.weight"]) + w["attn.out_proj.bias"]
        h = _layer_norm(x, w["norm2.weight"], w["norm2.bias"], eps)
        h = jax.nn.gelu(mm(h, w["fc1.weight"]) + w["fc1.bias"],
                        approximate=False)
        return x + mm(h, w["fc2.weight"]) + w["fc2.bias"], None

    x, _ = jax.lax.scan(block, x, p["blocks"])
    return _layer_norm(x, p["rest"]["norm_f.weight"],
                       p["rest"]["norm_f.bias"], eps)


def _logits(cfg, p, ids, precision):
    return _matmul(precision)(_hidden(cfg, p, ids, precision),
                              p["rest"]["wte.weight"], "bsh,vh->bsv")


def _loss_sum(cfg, p, x, y, precision):
    logits = _logits(cfg, p, x, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


def _tree_norms(tree):
    return {"rest": {n: jnp.sqrt(jnp.sum(jnp.square(v)))
                     for n, v in tree["rest"].items()},
            "blocks": {n: jnp.sqrt(jnp.sum(jnp.square(v),
                                           axis=tuple(range(1, v.ndim))))
                       for n, v in tree["blocks"].items()}}


def reference_train(cfg, job, seed, batches, precision="float32",
                    fault=None, rows_per_block=2):
    """Follow the first `len(batches)` steps of the job from the seed's
    weights.  Returns the loss of each step, per-leaf norms of the first
    gradient, and per-leaf norms of the parameters' change over all the
    steps.  Parameters are stored in the configuration's dtype (bfloat16)
    between steps; everything else is float32.  The batch goes
    through in blocks of rows so that it fits beside the optimizer state.

    fault="half_batch": the second half of every batch left out and the
    mean taken over the rest (one of the faults the limits are held
    against)."""
    opt = job["optimizer"]
    lr, b1, b2 = opt["learning_rate"], opt["beta1"], opt["beta2"]
    eps, decay = opt["epsilon"], opt["weight_decay"]

    @jax.jit
    def grads(p, x, y):
        n_tok = x.shape[0] * x.shape[1]
        xb = x.reshape(-1, rows_per_block, x.shape[1])
        yb = y.reshape(-1, rows_per_block, y.shape[1])

        def body(acc, xy):
            l, g = jax.value_and_grad(
                lambda q: _loss_sum(cfg, q, xy[0], xy[1], precision))(p)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        (l, g), _ = jax.lax.scan(body, zero, (xb, yb))
        return l / n_tok, jax.tree.map(lambda t: t / n_tok, g)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, g, m, v, t):
        lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)

        def leaf(p, g, m, v):
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * jnp.square(g)
            new = p - lr_t * m / (jnp.sqrt(v) + eps) - lr * decay * p
            return _as_stored(new, cfg["dtype"]), m, v

        out = jax.tree.map(leaf, p, g, m, v)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    norms = jax.jit(_tree_norms)
    diff_norms = jax.jit(lambda a, b: _tree_norms(
        jax.tree.map(jnp.subtract, a, b)))

    p = _stack(cfg, init_params(cfg, seed))
    start = jax.tree.map(jnp.copy, p)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    for t, (x, y) in enumerate(batches, start=1):
        if fault == "half_batch":
            x, y = x[:len(x) // 2], y[:len(y) // 2]
        loss, g = grads(p, jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
        if t == 1:
            grad_norms = _unstack_norms(jax.device_get(norms(g)))
        p, m, v = update(p, g, m, v, jnp.float32(t))
    change = _unstack_norms(jax.device_get(diff_norms(p, start)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


class ReferenceLM:
    """The reference over one request at a time: logits of every
    position of prompt + served tokens, in float32 and, for the control,
    in fp8."""

    def __init__(self, cfg, seed, max_len):
        self.cfg, self.max_len = cfg, max_len
        self.p = _stack(cfg, init_params(cfg, seed))
        self._gaps = jax.jit(self._gaps_impl, static_argnums=4)

    def _gaps_impl(self, p, ids, start, n, control):
        """ids [1, max_len] (prompt, served tokens, padding; the causal
        mask keeps padding inert).  For served token i, at sequence
        position start + i: how far its float32 logit lies under the
        float32 best.  With `control`, the token judged is not the
        served one but the one the fp8 forward pass puts first."""
        ref = _logits(self.cfg, p, ids, "float32")[0]
        pos = start - 1 + jnp.arange(self.max_len)
        pos = jnp.clip(pos, 0, self.max_len - 1)
        rows = ref[pos]
        if control:
            low = _logits(self.cfg, p, ids, "fp8")[0]
            tok = jnp.argmax(low[pos], axis=-1)
        else:
            tok = jnp.roll(ids[0], -start)
        gap = jnp.max(rows, axis=-1) - jnp.take_along_axis(
            rows, tok[:, None], axis=-1)[:, 0]
        return jnp.where(jnp.arange(self.max_len) < n, gap, 0.0)

    def token_gaps(self, prompt, served, control=False):
        n = len(served)
        ids = np.zeros((1, self.max_len), np.int32)
        ids[0, :len(prompt)] = prompt
        ids[0, len(prompt):len(prompt) + n] = served
        gaps = self._gaps(self.p, jnp.asarray(ids), np.int32(len(prompt)),
                          np.int32(n), bool(control))
        return np.asarray(gaps)[:n]
