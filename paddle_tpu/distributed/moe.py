"""Mixture-of-Experts with expert parallelism over a mesh axis.

The reference has no MoE (SURVEY §2.3: expert parallel — NO); this module
is capability the TPU rebuild adds, designed mesh-first the way the
scaling-book prescribes: experts are a sharded leading dimension, tokens
are dispatched to expert shards with one-hot einsums (GShard/Switch
style, all static shapes for the MXU), and the `ep` mesh axis turns the
dispatch/combine einsums into XLA all_to_all collectives over ICI —
no hand-written communication.

Forms:
- `top_k_gating`: softmax router with top-k expert choice, capacity
  clipping, and the Switch load-balance auxiliary loss.
- `moe_ffn`: dense (single-device or auto-sharded under jit) MoE FFN.
- `sharded_moe_ffn`: the same computation with explicit sharding
  constraints so pjit lowers dispatch/combine to all_to_all over "ep".
- `sigmoid_top_k` / `routed_experts`: the DeepSeek-V3 family's layer as
  one chip of an expert-parallel deployment runs it: sigmoid scores and
  a selection bias over ALL routed experts, no capacity and no drop, and
  a grouped product over the experts this chip is told it holds.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# `routed_experts`' kept case runs this many times the rows that its held
# experts expect
KEPT_OVER_EXPECTED = 2


def init_moe_params(key, num_experts, d_model, d_hidden, dtype=jnp.float32):
    """Router + per-expert FFN weights: wg [D,E], w1 [E,D,H], w2 [E,H,D]."""
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = 1.0 / math.sqrt(d_model)
    s2 = 1.0 / math.sqrt(d_hidden)
    return {
        "wg": (jax.random.normal(k1, (d_model, num_experts)) * s1
               ).astype(dtype),
        "w1": (jax.random.normal(k2, (num_experts, d_model, d_hidden))
               * s1).astype(dtype),
        "w2": (jax.random.normal(k3, (num_experts, d_hidden, d_model))
               * s2).astype(dtype),
    }


def top_k_gating(x, wg, k=2, capacity_factor=1.25, min_capacity=4):
    """Route tokens to top-k experts.

    x: [N, D] tokens. Returns (dispatch [N, E, C] bool-ish float,
    combine [N, E, C], aux_loss) with C = ceil(k*N/E * capacity_factor).
    """
    n, _ = x.shape
    e = wg.shape[1]
    cap = max(int(min_capacity),
              int(math.ceil(k * n / e * capacity_factor)))
    logits = x.astype(jnp.float32) @ wg.astype(jnp.float32)    # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)

    dispatch = jnp.zeros((n, e, cap), jnp.float32)
    combine = jnp.zeros((n, e, cap), jnp.float32)
    masked = probs
    # Switch load-balance loss on the FULL router distribution
    me = probs.mean(axis=0)                                    # [E]
    total_mask = jnp.zeros((n, e), jnp.float32)
    counts = jnp.zeros((e,), jnp.float32)  # slots taken by earlier passes

    for _ in range(k):
        idx = jnp.argmax(masked, axis=1)                       # [N]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)     # [N, E]
        # position inside the expert's capacity, offset past the slots
        # already taken by previous choice passes (GShard position
        # bookkeeping; without the offset 2nd-choice tokens double-book)
        pos = ((jnp.cumsum(onehot, axis=0) - 1.0)
               + counts[None, :]) * onehot                     # [N, E]
        keep = (pos < cap) & (onehot > 0)
        pos_c = jax.nn.one_hot(pos.sum(axis=1).astype(jnp.int32), cap,
                               dtype=jnp.float32)              # [N, C]
        slot = keep.astype(jnp.float32)[:, :, None] * pos_c[:, None, :]
        gate = (probs * onehot).sum(axis=1, keepdims=True)     # [N, 1]
        dispatch = dispatch + slot
        combine = combine + slot * gate[:, :, None]
        total_mask = total_mask + onehot
        counts = counts + keep.astype(jnp.float32).sum(axis=0)
        masked = masked * (1.0 - onehot)                       # next choice

    ce = total_mask.mean(axis=0) / k                           # frac routed
    aux_loss = e * jnp.sum(me * ce)
    return dispatch, combine, aux_loss


def moe_ffn(params, x, k=2, capacity_factor=1.25, activation=jax.nn.gelu):
    """MoE feed-forward over tokens x: [..., D] -> [..., D], plus the
    load-balance aux loss. Static-shape einsum dispatch (MXU-friendly)."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    toks = x.reshape(-1, d)
    dispatch, combine, aux = top_k_gating(
        toks, params["wg"], k=k, capacity_factor=capacity_factor)
    xin = jnp.einsum("nd,nec->ecd", toks.astype(jnp.float32), dispatch)
    h = activation(jnp.einsum("ecd,edh->ech", xin,
                              params["w1"].astype(jnp.float32)))
    out = jnp.einsum("ech,ehd->ecd", h, params["w2"].astype(jnp.float32))
    y = jnp.einsum("ecd,nec->nd", out, combine)
    return y.reshape(*lead, d).astype(x.dtype), aux


def shard_moe_params(params, mesh, axis="ep"):
    """Place expert-major weights over the mesh's expert axis; the router
    is replicated."""
    put = lambda v, spec: jax.device_put(v, NamedSharding(mesh, spec))
    return {
        "wg": put(params["wg"], P()),
        "w1": put(params["w1"], P(axis, None, None)),
        "w2": put(params["w2"], P(axis, None, None)),
    }


def sharded_moe_ffn(params, x, mesh, axis="ep", k=2, capacity_factor=1.25,
                    activation=jax.nn.gelu):
    """Expert-parallel MoE forward: expert weights sharded over `axis`,
    dispatch/combine einsums constrained so XLA lowers them to
    all_to_all over that axis (tokens replicated or batch-sharded by the
    caller's outer pjit)."""
    cst = jax.lax.with_sharding_constraint
    lead = x.shape[:-1]
    d = x.shape[-1]
    toks = x.reshape(-1, d)
    dispatch, combine, aux = top_k_gating(
        toks, params["wg"], k=k, capacity_factor=capacity_factor)
    xin = jnp.einsum("nd,nec->ecd", toks.astype(jnp.float32), dispatch)
    xin = cst(xin, NamedSharding(mesh, P(axis, None, None)))
    h = activation(jnp.einsum("ecd,edh->ech", xin,
                              params["w1"].astype(jnp.float32)))
    out = jnp.einsum("ech,ehd->ecd", h, params["w2"].astype(jnp.float32))
    out = cst(out, NamedSharding(mesh, P(axis, None, None)))
    y = jnp.einsum("ecd,nec->nd", out, combine)
    return y.reshape(*lead, d).astype(x.dtype), aux


def moe_ffn_shardmap(params, x, axis="ep", k=2, capacity_factor=1.25,
                     activation=jax.nn.gelu):
    """Expert-parallel MoE for use INSIDE a `jax.shard_map` body.

    `sharded_moe_ffn` above is the pjit-style path (sharding
    constraints, XLA inserts the all_to_alls); this is its shard_map
    twin for composition with the pipeline schedules in
    distributed/pipeline.py, whose gpipe/interleaved_gpipe bodies are
    per-device code where sharding constraints don't exist — the GShard
    dispatch/combine all_to_alls over `axis` are written explicitly
    (the role NCCL all-to-all plays in MoE ports of the reference's
    collective ops, operators/collective/).

    params' expert-major leaves are the LOCAL slices ([E_loc, ...]
    with E_loc = E / axis_size); the router `wg` is replicated [D, E].
    x is this device's token shard.  Tokens are gated locally, slots
    exchange expert-major over `axis`, local experts run, and the
    reverse exchange returns each token's expert outputs for the
    combine.  With enough capacity (no drops) the result is
    numerically the dense moe_ffn of the same tokens.
    """
    ep = jax.lax.axis_size(axis)
    lead = x.shape[:-1]
    d = x.shape[-1]
    toks = x.reshape(-1, d)
    e_loc = params["w1"].shape[0]
    assert params["wg"].shape[-1] == ep * e_loc, (
        f"moe_ffn_shardmap: router wg routes over "
        f"{params['wg'].shape[-1]} experts but w1 holds {e_loc} local "
        f"experts x {ep} '{axis}' shards = {ep * e_loc}.  Expert-major "
        f"leaves (w1/w2) must be the LOCAL [E/ep, ...] slices of the "
        f"global expert dim — pass params already sharded over '{axis}' "
        f"(e.g. via moe_rules), not the replicated full-expert arrays.")
    dispatch, combine, aux = top_k_gating(
        toks, params["wg"], k=k, capacity_factor=capacity_factor)
    cap = dispatch.shape[-1]
    xin = jnp.einsum("nd,nec->ecd", toks.astype(jnp.float32), dispatch)
    # [E, C, D] -> [ep, E_loc, C, D] -> exchange: leading dim becomes
    # the SOURCE peer whose tokens fill those slots
    xin = xin.reshape(ep, e_loc, cap, d)
    xin = jax.lax.all_to_all(xin, axis, split_axis=0, concat_axis=0)
    h = activation(jnp.einsum("secd,edh->sech", xin,
                              params["w1"].astype(jnp.float32)))
    out = jnp.einsum("sech,ehd->secd", h,
                     params["w2"].astype(jnp.float32))
    # reverse exchange: slots travel back to their token owners
    out = jax.lax.all_to_all(out, axis, split_axis=0, concat_axis=0)
    y = jnp.einsum("ecd,nec->nd", out.reshape(ep * e_loc, cap, d),
                   combine)
    return y.reshape(*lead, d).astype(x.dtype), aux


def sigmoid_top_k(h, router, bias, top_k, scale):
    """DeepSeek-V3's `noaux_tc` router with one group: scores
    sigmoid(h @ router) in float32 over all routed experts; the `top_k`
    experts of a token are the largest of score + bias (the bias chooses
    and does not weigh); weights are the chosen scores over their sum,
    times `scale`.  h [N, D], router [D, E], bias [E] ->
    (experts int32 [N, k], weights float32 [N, k])."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=1)
    return experts.astype(jnp.int32), \
        scale * chosen / chosen.sum(axis=1, keepdims=True)


def grouped_product(rows, weights, counts, use_kernel=None):
    """rows [M, K] sorted by group, group g owning the next counts[g];
    weights [G, K, N] -> float32 [M, N], row r of group g being
    `rows[r] @ weights[g]`; rows past the groups' hold anything: either
    product of an expert, whatever its function.  On the TPU, at shapes
    it tiles, the Pallas call `moe_grouped_mm` (kernels/grouped_mm.py;
    `takes_kernel` logs, once a shape, why it refuses one); elsewhere
    `jax.lax.ragged_dot`.  `use_kernel` forces the choice (the tests'
    interpreter runs)."""
    from ..kernels import grouped_mm
    from ..kernels.backend import is_tpu_backend

    tiles = grouped_mm.takes_kernel(rows.shape[0], rows.shape[1],
                                    weights.shape[2])
    if tiles and (is_tpu_backend() if use_kernel is None else use_kernel):
        return grouped_mm.moe_grouped_mm(rows, weights, counts)
    return jax.lax.ragged_dot(rows, weights, counts,
                              preferred_element_type=jnp.float32)


def swiglu_act(gu):
    """SwiGLU's activation of the first product, gate and up side by
    side: float32 [M, 2F] -> [M, F]."""
    f = gu.shape[-1] // 2
    return jax.nn.silu(gu[..., :f]) * gu[..., f:]


def relu2_act(u):
    """The squared ReLU (Primer; `relu2`) of the first product: float32
    [M, F] -> [M, F]."""
    return jnp.square(jax.nn.relu(u))


def routed_experts(h, router, bias, experts_held, first_expert, n_routed,
                   top_k, scale, valid=None, use_kernel=None, act=swiglu_act):
    """The routed part of an expert layer on a chip that holds experts
    [first_expert, first_expert + held) of `n_routed`.

    h [N, D]; router [D, n_routed]; bias [n_routed]; experts_held = (the
    first matrix [held, D, F'], down [held, F, D]) and `act` the model's
    expert function between them, a module-level function of the first
    product's float32 [M, F'] -> [M, F]: `swiglu_act` (gate and up side
    by side, F' = 2F; K2 and afmoe) or `relu2_act` (up alone, F' = F;
    nemotron_h); valid: bool [N] or None, tokens that are padding make
    no assignment.  Every token is routed over all
    `n_routed` (`sigmoid_top_k`, the weights normalised over all its
    chosen experts, held here or not); the assignments that fall on the
    experts held here are sorted by expert and go through one grouped
    product (`grouped_product`: the Pallas call `moe_grouped_mm` on the
    TPU), so nothing is dropped at any imbalance; what the absent
    experts would have added is left out.

    Where the shape allows (`_rows_kept`: the held experts expect a
    small enough share of the N * top_k assignments), the usual case
    runs over the kept rows only: the gathers, the products and the sum
    back into the tokens, where the held assignments fit them; where
    they do not, every one of the N * top_k rows runs (`jax.lax.cond`:
    the device runs one of the two), so nothing is dropped either way.
    The layer is traced once a shape (`_routed`, jitted).

    Returns (y [N, D] in h's type, assignments on each held expert
    int32 [held], int32 [] 1 where the layer ran over the kept rows and
    0 where it ran over every row); the caller adds the shared
    expert."""
    first, down = experts_held
    held = first.shape[0]
    if router.shape[1] != n_routed or not \
            0 <= first_expert <= n_routed - held:
        raise ValueError(
            f"experts [{first_expert}, {first_expert + held}) do not lie "
            f"in a router over {router.shape[1]} (n_routed {n_routed})")
    return _routed(h, router, bias, first, down, valid,
                   first_expert=first_expert, n_routed=n_routed,
                   top_k=top_k, scale=float(scale), use_kernel=use_kernel,
                   act=act)


@functools.partial(jax.jit, static_argnames=(
    "first_expert", "n_routed", "top_k", "scale", "use_kernel", "act"))
def _routed(h, router, bias, w_in, down, valid, *, first_expert,
            n_routed, top_k, scale, use_kernel, act):
    """`routed_experts`' body: jitted and not inlined, so that a program
    with several expert layers of one shape traces and lowers it once."""
    held = w_in.shape[0]
    n, d = h.shape
    experts, weights = sigmoid_top_k(h, router, bias, top_k, scale)
    local = experts - first_expert
    here = (local >= 0) & (local < held)
    if valid is not None:
        here = here & valid[:, None]
    # assignments sorted by held expert, the others (group `held`) last
    group = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(group, stable=True)
    counts = jnp.sum(group[:, None] == jnp.arange(held)[None, :],
                     axis=0, dtype=jnp.int32)

    def experts_of(order):
        """The held experts' function of the assignments `order` names,
        float32 [len(order), D]; rows past the held assignments hold
        whatever the grouped product left there."""
        rows = jnp.take(h, order // top_k, axis=0)
        gu = grouped_product(rows, w_in, counts, use_kernel)
        return grouped_product(act(gu).astype(h.dtype), down, counts,
                               use_kernel)

    def every_row():
        # back into the tokens' order
        back = jnp.take(experts_of(order), jnp.argsort(order),
                        axis=0).reshape(n, top_k, d)
        return jnp.sum(jnp.where(here[..., None],
                                 back * weights[..., None], 0.0), axis=1)

    kept = _rows_kept(n * top_k, held / n_routed)
    if kept is None:
        return every_row().astype(h.dtype), counts, jnp.int32(0)

    def rows_kept():
        # the held assignments are the first of `order`: each row times
        # its weight, added to its token
        first = order[:kept]
        held_row = (jnp.arange(kept) < counts.sum())[:, None]
        out = jnp.where(
            held_row,
            experts_of(first) * weights.reshape(-1)[first][:, None], 0.0)
        return jnp.zeros((n, d), jnp.float32).at[first // top_k].add(out)

    fits = counts.sum() <= kept
    y = jax.lax.cond(fits, rows_kept, every_row)
    return y.astype(h.dtype), counts, fits.astype(jnp.int32)


def _rows_kept(rows, share):
    """Static rows of `routed_experts`' kept case for `rows` assignments
    of which the held experts expect `share`: `KEPT_OVER_EXPECTED` times
    that many, in whole row tiles of the grouped product; None (no such
    case: every row runs) where that is more than half of `rows`."""
    from ..kernels.grouped_mm import ROW_TILE

    kept = -(-int(math.ceil(KEPT_OVER_EXPECTED * share * rows))
             // ROW_TILE) * ROW_TILE
    return kept if 2 * kept <= rows else None
