"""Tensor-parallel / fully-sharded training via partition rules.

This is the capability the reference lacks (SURVEY.md §2.3: "Tensor
parallel ... NO") built the TPU way: instead of rewriting the graph with
collective ops (transpiler/collective.py in the reference does this for
DP), we attach `jax.sharding.NamedSharding`s to the *arrays* of the train
state according to regex partition rules, and `jax.jit` propagates the
shardings through the whole train step — XLA inserts all-gathers /
reduce-scatters / psums on ICI where the math demands them.

Megatron-style rules for a transformer block (weights are [in, out]):
  qkv / fc1 weights  -> shard OUT dim over "tp"  (column parallel)
  out_proj / fc2     -> shard IN  dim over "tp"  (row parallel)
  embeddings         -> shard vocab dim over "tp"
  layernorm, biases of row-parallel layers -> replicated

Optimizer moments inherit param shardings for free: FunctionalOptimizer
.init builds them with zeros_like(param), which preserves sharding — so
Adam/LAMB state is automatically sharded like the weights (ZeRO-style for
the tp-sharded slices).

ZeRO staging under XLA (make_sharded_train_step(zero1=True) is stage 1):
stage 2 (sharded GRADIENTS) has no separate array to annotate here —
within the one compiled step XLA materializes each grad only between
its producer and the update that consumes it and frees it immediately,
so grad residency is already transient; the partitioner turns the
dp-psum feeding a dp-sharded update into reduce-scatter where
profitable.  Stage 3 (sharded PARAMS) is spelled differently in this
framework: shard the params themselves via PartitionRules (fsdp-style
specs) and XLA inserts the all-gathers per layer — no separate "zero3"
flag is needed, the rules ARE the mechanism.
"""

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "PartitionRules", "gpt_rules", "bert_rules", "mlp_rules",
    "fsdp_rules", "shard_params", "shard_train_state", "shard_batch",
    "make_sharded_train_step",
]


class PartitionRules:
    """Ordered (regex, PartitionSpec) table; first match wins.

    The analogue of the reference's per-op placement decisions in
    multi_devices_graph_pass.cc — but declarative and per-parameter.
    """

    def __init__(self, rules, default=P()):
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]
        self.default = default

    def spec(self, name, value=None):
        for pat, spec in self.rules:
            if pat.search(name):
                return spec
        return self.default

    def __add__(self, other):
        out = PartitionRules([], default=self.default)
        out.rules = self.rules + other.rules
        return out


def gpt_rules():
    """Megatron TP sharding for models/gpt.py / models/bert.py naming.

    No trailing `.*` catch-all: unmatched names already fall through to
    PartitionRules' replicated default, and keeping the table specific
    is what lets `gpt_rules() + fsdp_rules()` compose (a catch-all here
    would shadow fsdp's `.*` -> P("dp") under first-match-wins)."""
    col = P(None, "tp")   # [in, out] -> out sharded
    row = P("tp", None)   # [in, out] -> in sharded
    return PartitionRules([
        (r"(q_proj|k_proj|v_proj|fc1|linear1)\.weight$", col),
        (r"(q_proj|k_proj|v_proj|fc1|linear1)\.bias$", P("tp")),
        (r"(out_proj|fc2|linear2)\.weight$", row),
        (r"(wte|wpe|word_emb|pos_emb|embedding)\.weight$", P("tp", None)),
        # MoE expert-major weights shard over the expert-parallel axis;
        # the router stays replicated (it must match BEFORE any
        # composed catch-all, hence an explicit rule despite equalling
        # the default)
        (r"moe\.(w1|w2)$", P("ep", None, None)),
        (r"moe\.wg$", P()),
    ])


def bert_rules():
    return gpt_rules()


def mlp_rules():
    # no `.*` catch-all for the same composability reason as gpt_rules
    return PartitionRules([
        (r"\.weight$", P(None, "tp")),
    ])


def fsdp_rules():
    """ZeRO-3/FSDP-style rules: every parameter's dim 0 shards over dp
    (params, grads, AND moments all divide by the dp degree; XLA
    all-gathers each layer's weights where the forward/backward needs
    them and reduce-scatters grads into the sharded update).  Biases
    and other small dims that don't divide are clamped to replicated by
    _named.  Compose with gpt_rules as `gpt_rules() + fsdp_rules()` —
    specific rules FIRST, this catch-all LAST, since
    PartitionRules.spec returns the FIRST matching rule (the reverse
    order would have the `.*` -> P("dp") rule shadow every gpt rule).
    That composition gives tp+fsdp on DIFFERENT params; for tp+fsdp on
    the SAME param use explicit per-name rules."""
    return PartitionRules([
        (r".*", P("dp")),
    ])


def _named(mesh, spec, value):
    # drop axes that exceed rank; clamp spec to array rank
    rank = np.ndim(value)
    parts = list(spec) + [None] * max(0, rank - len(spec))
    parts = parts[:rank]
    # un-shard dims not divisible by the axis size (e.g. tiny test models)
    def axsize(a):
        if a is None:
            return 1
        names = (a,) if isinstance(a, str) else a
        return int(np.prod([mesh.shape[n] for n in names]))
    shape = np.shape(value)
    parts = [a if shape[i] % axsize(a) == 0 else None
             for i, a in enumerate(parts)]
    while parts and parts[-1] is None:
        parts.pop()
    return NamedSharding(mesh, P(*parts))


def shard_params(params, mesh, rules):
    """device_put a {name: array} dict per the partition rules."""
    return {
        n: jax.device_put(v, _named(mesh, rules.spec(n, v), v))
        for n, v in params.items()
    }


def shard_batch(mesh, *arrays, spec=None):
    """Shard batch arrays: leading dim over dp, second (seq) over sp."""
    out = []
    for a in arrays:
        s = spec
        if s is None:
            s = P("dp", "sp") if np.ndim(a) >= 2 else P("dp")
        out.append(jax.device_put(a, _named(mesh, s, a)))
    return tuple(out) if len(out) > 1 else out[0]


def _zero1_spec(spec, shape, mesh):
    """Add dp-sharding of dim 0 to an optimizer-moment spec (ZeRO-1).

    The param itself stays replicated over dp (plain data parallelism);
    only the OPTIMIZER STATE shards, cutting its memory by the dp
    degree — the ZeRO-1 trade (arXiv:1910.02054 §5.1) expressed the
    pjit way: annotate the moment arrays and let XLA partition the
    update computation over dp and all-gather the new params.  A dim-0
    axis of SIZE 1 (e.g. "tp" on a pure-DP mesh — which gpt_rules puts
    on the vocab embedding, the largest param) counts as free, or the
    headline memory saving would silently not materialize exactly
    where it matters; indivisible dims are left for _named to clamp."""
    dp = mesh.shape.get("dp", 1)
    if dp <= 1 or not shape:
        return spec

    def axsize(a):
        names = (a,) if isinstance(a, str) else (a or ())
        return int(np.prod([mesh.shape[n] for n in names]))

    parts = list(spec) + [None] * (len(shape) - len(spec))
    if parts and axsize(parts[0]) == 1 and shape[0] % dp == 0:
        parts[0] = "dp"
        return P(*parts)
    return spec


def shard_train_state(state, mesh, rules, zero1=False):
    """Shard a models.train.TrainState: params + matching opt moments per
    rules, buffers/step/rng replicated.  zero1=True additionally shards
    the optimizer moments' dim 0 over dp (see _zero1_spec)."""
    from ..models.train import TrainState

    params = shard_params(state.params, mesh, rules)

    def shard_opt(leaf_path, leaf):
        # opt_state is a pytree whose dict keys mirror param names
        for n, p in params.items():
            if ("/" + n + "/" in leaf_path or leaf_path.endswith("/" + n)) \
                    and np.shape(leaf) == np.shape(p):
                spec = rules.spec(n)
                if zero1:
                    spec = _zero1_spec(spec, np.shape(leaf), mesh)
                return jax.device_put(leaf, _named(mesh, spec, leaf))
        return jax.device_put(leaf, NamedSharding(mesh, P()))

    opt_state = _tree_map_with_path(shard_opt, state.opt_state)
    rep = NamedSharding(mesh, P())
    return TrainState(
        params=params,
        opt_state=opt_state,
        buffers=jax.device_put(state.buffers, rep),
        step=jax.device_put(state.step, rep),
        rng=jax.device_put(state.rng, rep),
    )


def _tree_map_with_path(fn, tree, path=""):
    if isinstance(tree, dict):
        return {k: _tree_map_with_path(fn, v, path + "/" + str(k))
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        t = [_tree_map_with_path(fn, v, path + f"/{i}")
             for i, v in enumerate(tree)]
        return type(tree)(t)
    return fn(path, tree)


def make_sharded_train_step(model, optimizer, mesh, rules=None,
                            loss_fn=None, rng_seed=0, zero1=False,
                            accum_steps=1):
    """Build (step, sharded_state). step(state, *batch) -> (state, loss).

    The step function is models.train.make_train_step's jitted step —
    sharding is carried entirely by the arrays; XLA compiles the TP/DP/SP
    collectives from the NamedShardings. Batch arrays should be placed
    with shard_batch (dp×sp).

    accum_steps=k > 1 scans grad accumulation over k microbatches
    inside the step (see models.train.make_train_step — batch leading
    dims must divide by k); composes with zero1 and the rules.
    zero1=True shards the optimizer moments over dp (ZeRO-1): params
    stay replicated, state memory divides by the dp degree, and XLA
    partitions the update + all-gathers the fresh params — the
    stage-1 memory optimisation the reference's DP never had.  The
    output state's shardings are pinned to the input's: without the
    constraint XLA's sharding inference returns dp-SHARDED params
    after step 1, breaking the replicated-params contract and forcing
    a recompile of the donated-state step on call 2.
    """
    from ..models.train import init_train_state, make_train_step

    rules = rules or gpt_rules()
    state = init_train_state(model, optimizer, rng_seed=rng_seed)
    state = shard_train_state(state, mesh, rules, zero1=zero1)
    if not zero1:
        jitted = make_train_step(model, optimizer, loss_fn=loss_fn,
                                 jit=True, accum_steps=accum_steps)
    else:
        inner = make_train_step(model, optimizer, loss_fn=loss_fn,
                                jit=False, accum_steps=accum_steps)
        state_sh = jax.tree.map(lambda a: a.sharding, state)

        def pinned(st, *batch):
            st2, loss = inner(st, *batch)
            st2 = jax.tree.map(jax.lax.with_sharding_constraint, st2,
                               state_sh)
            return st2, loss

        jitted = jax.jit(pinned, donate_argnums=(0,))

    def step(st, *batch):
        # traced under the mesh, so code that GSPMD cannot partition by
        # itself (the Pallas kernels, kernels/attention.py) can find
        # the axes to split over by hand
        with jax.set_mesh(mesh):
            return jitted(st, *batch)

    return step, state
