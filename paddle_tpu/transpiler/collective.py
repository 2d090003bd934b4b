"""`fluid.transpiler.collective` — dp gradient-sync emission.

Parity: python/paddle/fluid/transpiler/collective.py — the reference's
GradAllReduce/LocalSGD are program-rewriting transpilers inserting
c_allreduce/broadcast ops, and ``fuse_all_reduce_op_pass`` coalesces
the per-gradient allreduces into fused groups.  Under SPMD the psum is
emitted at trace time; this module owns THAT emission
(:func:`sync_gradients`, called from the executor's ``dp_grad_sync``
scope) and implements the coalescing half as **bucketed gradient
synchronization** (the PyTorch-DDP design, Li et al. VLDB 2020):

- gradients are flattened and packed, per dtype, into fixed-capacity
  buckets of ``FLAGS_dp_bucket_bytes`` — ONE psum per bucket instead of
  one per gradient;
- packing runs in reverse production order (the backward pass produces
  the LAST layer's gradients first), so a bucket's psum becomes
  schedulable as soon as its last gradient exists and XLA's
  latency-hiding scheduler overlaps it with the remaining backward
  compute;
- psum is elementwise, so the bucketed sync is BITWISE identical to
  the per-gradient sync (tests/test_passes.py
  ::test_dp_bucketed_training_bitwise pins it);
- gradients that are not plain dense arrays (SelectedRows-style
  lookup-table grads, custom pytree nodes) fall back to the unbucketed
  per-leaf sync, counted on ``passes.bucket_fallbacks`` — never a
  crash.

The legacy transpile() classes below keep the reference's 1.x entry
points importable.
"""

import numpy as np

from .. import flags
from ..distributed.strategies import LocalSGDTrainStep  # noqa: F401

# trace-time stats of the most recent sync_gradients emission: what
# the tests read to assert the collective count without parsing HLO
_LAST_SYNC = {}


def last_sync_stats():
    """Stats dict of the most recent gradient-sync trace: mode,
    grads/psums/buckets/fallbacks counts, total_bytes, per-bucket
    layout.  Empty dict before any dp trace."""
    return dict(_LAST_SYNC)


def plan_buckets(entries, bucket_bytes):
    """Pure planning: pack ``entries`` — ``(name, numel, itemsize,
    dtype_str)`` in firing order — into dtype-segregated fixed-capacity
    flat buckets.  A gradient may span bucket boundaries (the flattened
    design), so per dtype the bucket count is exactly
    ``ceil(total_bytes / bucket_bytes)``.

    Returns ``[{"dtype", "elems", "bytes", "names"}, ...]`` where
    ``names`` lists every gradient with elements in that bucket."""
    groups = {}
    order = []
    for name, numel, itemsize, dtype in entries:
        if dtype not in groups:
            groups[dtype] = []
            order.append(dtype)
        groups[dtype].append((name, int(numel), int(itemsize)))
    buckets = []
    for dtype in order:
        items = groups[dtype]
        itemsize = items[0][2]
        cap_elems = max(1, int(bucket_bytes) // itemsize)
        cur = None
        for name, numel, _ in items:
            remaining = numel
            while remaining > 0 or numel == 0:
                if cur is None or cur["elems"] >= cap_elems:
                    cur = {"dtype": dtype, "elems": 0, "bytes": 0,
                           "names": []}
                    buckets.append(cur)
                take = min(remaining, cap_elems - cur["elems"])
                if name not in cur["names"]:
                    cur["names"].append(name)
                cur["elems"] += take
                cur["bytes"] += take * itemsize
                remaining -= take
                if numel == 0:
                    break
    return buckets


def implied_collective_plan(entries, axes=("dp",), bucket_bytes=None):
    """STATIC twin of :func:`sync_gradients`'s emission, shared with
    the sharding analyzer (``analysis.sharding``): the same
    ``plan_buckets`` math over ``(name, numel, itemsize, dtype)``
    entries in firing order, returned as implied-collective records
    instead of traced psums.  Because the plan and the emission run
    the SAME planner with the SAME flag default, the analyzer's
    predicted collective count/bytes and the executed
    ``last_sync_stats`` agree exactly — the conformance property
    ``tests/test_sharding.py``'s ``dp_conformance`` pins.

    ``bucket_bytes=None`` reads ``FLAGS_dp_bucket_bytes``; 0 plans the
    legacy one-all-reduce-per-gradient sync."""
    if bucket_bytes is None:
        bucket_bytes = int(flags.flag("dp_bucket_bytes"))
    axes = list(axes)
    out = []
    entries = list(entries)
    if bucket_bytes > 0 and entries:
        for b in plan_buckets(entries, bucket_bytes):
            out.append({"kind": "all_reduce", "axes": axes,
                        "var": "+".join(b["names"]),
                        "bytes": int(b["bytes"]),
                        "dtype": b["dtype"]})
    else:
        for name, numel, itemsize, dtype in entries:
            out.append({"kind": "all_reduce", "axes": axes,
                        "var": name,
                        "bytes": int(numel) * int(itemsize),
                        "dtype": dtype})
    return out


def _is_dense(g):
    """A plain dense array jnp can flatten/concatenate: has shape and
    dtype, and is not a SelectedRows-style wrapper."""
    from ..selected_rows import SelectedRows

    if isinstance(g, SelectedRows):
        return False
    return hasattr(g, "dtype") and hasattr(g, "shape") \
        and not isinstance(g, (list, tuple, dict))


def sync_gradients(grads, axis_name, bucket_bytes=None, order=None,
                   key=None):
    """Emit the dp gradient allreduce for ``grads`` ({name: value}) at
    trace time, returning {name: synced}.

    ``axis_name=None`` (no dp mesh) returns the gradients unchanged.
    ``bucket_bytes`` defaults to ``FLAGS_dp_bucket_bytes``; 0 emits the
    legacy one-psum-per-gradient sync.  ``order`` is the firing order
    for packing (default: reversed insertion order — backward produces
    grads back-to-front).  ``key`` names the emission in the
    ``kind="pass_pipeline"`` telemetry record."""
    global _LAST_SYNC
    if axis_name is None:
        return dict(grads)
    import jax
    import jax.numpy as jnp

    if bucket_bytes is None:
        bucket_bytes = int(flags.flag("dp_bucket_bytes"))
    names = list(order) if order is not None else list(reversed(grads))
    dense = [n for n in names if _is_dense(grads[n])]
    dense_set = set(dense)
    fallback = [n for n in names if n not in dense_set]
    out = {}
    psums = 0
    bucketed = 0
    plan = []
    if bucket_bytes > 0 and dense:
        groups = {}
        g_order = []
        for n in dense:
            dt = str(grads[n].dtype)
            if dt not in groups:
                groups[dt] = []
                g_order.append(dt)
            groups[dt].append(n)
        plan = plan_buckets(
            [(n, int(np.prod(grads[n].shape, dtype=np.int64)),
              jnp.dtype(grads[n].dtype).itemsize, str(grads[n].dtype))
             for n in dense], bucket_bytes)
        for dt in g_order:
            ns = groups[dt]
            sizes = [int(np.prod(grads[n].shape, dtype=np.int64))
                     for n in ns]
            flats = [jnp.reshape(grads[n], (-1,)) for n in ns]
            flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
            # the EMISSION is driven by the plan: the per-bucket elem
            # counts below are the same numbers the telemetry reports,
            # by construction — the psum count can't drift from the
            # recorded plan
            chunk_elems = [b["elems"] for b in plan
                           if b["dtype"] == dt] or [int(flat.size)]
            chunks = []
            off = 0
            for e in chunk_elems:
                chunks.append(flat[off:off + e])
                off += e
            synced_chunks = [jax.lax.pmean(c, axis_name) for c in chunks]
            psums += len(synced_chunks)
            bucketed += len(synced_chunks)
            flat_s = (synced_chunks[0] if len(synced_chunks) == 1
                      else jnp.concatenate(synced_chunks))
            off = 0
            for n, sz in zip(ns, sizes):
                out[n] = jnp.reshape(flat_s[off:off + sz],
                                     grads[n].shape)
                off += sz
    else:
        for n in dense:
            out[n] = jax.lax.pmean(grads[n], axis_name)
            psums += 1
    from ..selected_rows import SelectedRows

    for n in fallback:
        # unbucketed path for non-dense gradients.  SelectedRows-style
        # lookup-table grads pass through UNSYNCED: their row sets are
        # per-shard (each device looked up its own batch's ids), so a
        # psum would add unrelated rows — aggregation belongs to the
        # sparse push / parameter-server path, exactly like the
        # reference's DistMultiTrainer split.  Other pytree grads sync
        # per leaf, one psum each.
        g = grads[n]
        if isinstance(g, SelectedRows):
            out[n] = g
        else:
            out[n] = jax.tree.map(
                lambda x: jax.lax.pmean(x, axis_name), g)
            # one collective PER LEAF: the stats are the ledger's
            # collective count, so a 3-leaf pytree grad is 3 psums
            psums += len(jax.tree.leaves(g))
    stats = {
        "mode": "bucketed" if bucketed else "per_grad",
        "grads": len(names),
        "psums": psums,
        "buckets": bucketed,
        "fallbacks": len(fallback),
        "bucket_bytes": int(bucket_bytes),
        "total_bytes": int(sum(
            np.prod(grads[n].shape, dtype=np.int64)
            * jnp.dtype(grads[n].dtype).itemsize for n in dense)),
        "plan": plan,
    }
    _LAST_SYNC = stats
    _note_sync(stats, key)
    return out


def _note_sync(stats, key):
    """Trace-time telemetry for one grad-sync emission: counters always
    (gate-free like the flight recorder's), plus a
    kind="pass_pipeline" record while the monitor is enabled — the
    bucketing is a pass in the ledger's eyes, it just runs at trace
    time instead of rewrite time."""
    try:
        from .. import monitor

        if stats["fallbacks"]:
            monitor.counter("passes.bucket_fallbacks").add(
                stats["fallbacks"])
        if stats["buckets"]:
            monitor.counter("passes.buckets_formed").add(
                stats["buckets"])
        if monitor.is_enabled():
            monitor.record_pass_pipeline({
                "kind": "pass_pipeline",
                "key": key or "dp_grad_sync",
                "passes": [{"name": "dp_grad_bucket", **{
                    k: v for k, v in stats.items() if k != "plan"}}],
                "before_ops": stats["grads"],
                "after_ops": stats["psums"],
                "ops_removed": stats["grads"] - stats["psums"],
            })
    except Exception:
        pass


def note_model_sync(records, key=None):
    """Record the model-parallel (GSPMD auto-axis) collectives of the
    most recent spmd step into ``last_sync_stats()["model"]``.

    Under the hybrid runtime the dp gradient psums are emitted manually
    (:func:`sync_gradients` above, stats set at trace time) while the
    mp collectives are inserted by XLA from the sharding constraints —
    there is no trace-time hook to count them.  The executor therefore
    notes the ``ShardingPlan``'s own implied-collective records here
    after dispatch: the records ARE the analyzer's, so the predicted
    table and the executed stats agree exactly by construction (pinned by
    ``tests/test_spmd_runtime.py``)."""
    records = [dict(r) for r in records]
    axes = sorted({a for r in records for a in r.get("axes", ())})
    _LAST_SYNC["model"] = {
        "psums": len(records),
        "total_bytes": int(sum(int(r.get("bytes", 0))
                               for r in records)),
        "axes": axes,
        "records": records,
    }
    try:
        from .. import monitor

        if monitor.is_enabled() and records:
            monitor.record_pass_pipeline({
                "kind": "pass_pipeline",
                "key": key or "mp_model_sync",
                "passes": [{"name": "mp_auto_collectives",
                            "psums": len(records),
                            "total_bytes":
                                _LAST_SYNC["model"]["total_bytes"],
                            "axes": axes}],
                "before_ops": len(records),
                "after_ops": len(records),
                "ops_removed": 0,
            })
    except Exception:
        pass
    return dict(_LAST_SYNC["model"])


def emit_skew_probe(ts_sec, ts_usec, axis_name="dp", gather=True):
    """Trace-time straggler probe (ISSUE 10), emitted inside the same
    ``dp_grad_sync`` scope the bucketed gradient collectives live in:
    one extra scalar pair per step instead of per gradient.

    ``ts_sec``/``ts_usec`` are per-device int32 rows carrying each
    rank's HOST pre-sync timestamp (epoch seconds mod 2**20 +
    microseconds — the int32-safe split encoding from
    ``monitor.fleet.host_timestamp``).  On device: a lexicographic
    pmax finds the latest arrival, each rank's barrier wait is
    ``t_latest - t_self`` at exact μs resolution, and one all_gather
    replicates the per-shard wait vector so EVERY rank knows the whole
    fleet's split without a host round trip.  Returns the replicated
    float32 ``[ndev]`` wait vector (μs).

    ``gather=False`` (the GSPMD runtime tier) returns the LOCAL wait as
    a ``[1]`` row instead — inside a partial-manual shard_map (mp as a
    GSPMD auto axis) an HLO AllGather carries no sharding through XLA's
    propagation pass and the partitioner aborts on the manual-subgroup
    mismatch, so the gather happens at the shard_map out-spec boundary
    (``P("dp")``) rather than in the body."""
    import jax
    import jax.numpy as jnp

    sec = ts_sec[0]
    usec = ts_usec[0]
    max_sec = jax.lax.pmax(sec, axis_name)
    # lexicographic max: only ranks holding the max second compete on
    # the microsecond component (others masked to -1, below any real
    # usec), so the combined difference below is exact
    tie_usec = jnp.where(sec == max_sec, usec, jnp.int32(-1))
    max_usec = jax.lax.pmax(tie_usec, axis_name)
    wait_us = ((max_sec - sec).astype(jnp.float32) * 1e6
               + (max_usec - usec).astype(jnp.float32))
    if not gather:
        return wait_us[None]
    return jax.lax.all_gather(wait_us, axis_name)


class Collective:
    def __init__(self, nrings=1):
        self.nrings = nrings
        self.nranks = 1
        self.rank = 0

    def transpile(self, startup_program=None, main_program=None, rank=0,
                  endpoints="127.0.0.1:6174", current_endpoint=None,
                  wait_port=True):
        eps = (endpoints.split(",") if isinstance(endpoints, str)
               else list(endpoints))
        self.nranks = len(eps)
        self.rank = rank
        self.startup_program = startup_program
        self.main_program = main_program
        return self


class GradAllReduce(Collective):
    """DP gradient allreduce: under pjit/shard_map the psum is inserted
    by XLA from the sharding annotations — nothing to rewrite."""


class LocalSGD(Collective):
    """Periodic parameter averaging; the executing implementation is
    LocalSGDTrainStep."""

    def __init__(self, nrings=1, k_steps=1):
        super().__init__(nrings)
        self.k_steps = k_steps


__all__ = ["GradAllReduce", "LocalSGD", "Collective",
           "sync_gradients", "plan_buckets", "last_sync_stats",
           "implied_collective_plan", "emit_skew_probe",
           "note_model_sync"]
