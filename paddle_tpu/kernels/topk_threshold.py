"""Pallas TPU top-k threshold kernel for DGC gradient sparsification.

Parity target: the reference's DGC sparse-allreduce path
(/root/reference/paddle/fluid/framework/details/sparse_all_reduce_op_handle.cc
+ the external dgc library's CUDA top-k). A full sort (lax.top_k) is
O(N log N) and HBM-heavy at gradient sizes; DGC itself only needs a
THRESHOLD approximating the kth largest |g| (the paper samples gradients
to estimate it). This kernel computes a cumulative histogram of |x|
against 256 linear edges in one streaming pass — each grid step loads a
[rows, 128] tile into VMEM and adds its per-lane counts of |x| >= edge
to a resident [256, 128] accumulator on the VPU; XLA sums the lanes and
the threshold is the largest edge keeping >= k elements. Guarantees
kept_count >= k (conservative: the bin containing the true kth value is
kept whole), with one data pass instead of a sort.

On non-TPU backends the kernel runs in interpret mode (numerics tests).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret

NUM_EDGES = 256
DEFAULT_BLOCK = 64 * 1024
_LANES = 128
_TILE = 8 * _LANES                     # one f32 vreg tile of elements


def _count_ge_kernel(edges_ref, x_ref, out_ref):
    # input is already |x|; padding is -1 so it never crosses an edge.
    # One pass per edge over the [rows, 128] tile held in VMEM: a
    # broadcast compare against all edges at once would be a
    # [block, NUM_EDGES] intermediate (64 MB at the default block),
    # four times the scoped-VMEM limit.  Counts stay per-lane vectors
    # (the lane sum happens outside in XLA) and accumulate in the one
    # output block every grid step revisits.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def one_edge(e, carry):
        ge = (x_ref[...] >= edges_ref[e]).astype(jnp.float32)
        out_ref[pl.ds(e, 1), :] += jnp.sum(ge, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, NUM_EDGES, one_edge, 0)


@functools.partial(jax.jit, static_argnames=("block",))
def count_ge_histogram(flat_abs, edges, block=DEFAULT_BLOCK):
    """[N] |values| + [NUM_EDGES] edges -> int32 [NUM_EDGES] counts of
    |x| >= edge, via a tiled one-pass Pallas reduction.  `block` is the
    number of elements per grid step, a multiple of 1024."""
    if block % _TILE:
        raise ValueError(f"block {block} must be a multiple of {_TILE}")
    n = flat_abs.shape[0]
    block = min(block, -(-n // _TILE) * _TILE)
    pad = (-n) % block
    x = jnp.pad(flat_abs.astype(jnp.float32), (0, pad),
                constant_values=-1.0)                    # pads count 0
    rows = block // _LANES
    call = pl.pallas_call(
        _count_ge_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(x.shape[0] // block,),
            in_specs=[pl.BlockSpec((rows, _LANES),
                                   lambda i, edges: (i, 0))],
            out_specs=pl.BlockSpec((NUM_EDGES, _LANES),
                                   lambda i, edges: (0, 0))),
        out_shape=jax.ShapeDtypeStruct((NUM_EDGES, _LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret(),
        name="topk_threshold",
    )
    with jax.named_scope("topk_threshold"):
        per_lane = call(edges.astype(jnp.float32), x.reshape(-1, _LANES))
    # a lane holds at most N/128 hits, exact in f32 far past any
    # gradient size; the cross-lane total is summed as integers
    return per_lane.astype(jnp.int32).sum(axis=1)


def topk_threshold(v, k, block=DEFAULT_BLOCK):
    """Approximate kth-largest |v|: the largest histogram edge that keeps
    at least k elements. mask = |v| >= threshold keeps >= k elements
    (within one 1/256 bin of exactly k)."""
    flat = jnp.abs(v.reshape(-1)).astype(jnp.float32)
    vmax = jnp.max(flat)
    edges = jnp.linspace(0.0, 1.0, NUM_EDGES, dtype=jnp.float32) \
        * jnp.maximum(vmax, 1e-30)
    counts = count_ge_histogram(flat, edges, block=block)
    keep_ok = counts >= k                                 # monotone in -edge
    # the largest edge index still keeping >= k elements
    idx = jnp.max(jnp.where(keep_ok, jnp.arange(NUM_EDGES), 0))
    return edges[idx]


def dgc_topk_mask_pallas(v, sparsity, block=DEFAULT_BLOCK):
    """DGC keep-mask via the streaming threshold kernel: keeps the
    largest ~(1-sparsity) fraction of |v| (always >= the exact k)."""
    n = v.size
    k = max(1, int(round(n * (1.0 - sparsity))))
    t = topk_threshold(v, k, block=block)
    return (jnp.abs(v) >= t).astype(v.dtype)
