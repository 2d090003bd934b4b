"""Bucketed micro-batching over a Predictor / CompiledPredictor.

The serving problem with naive batching: every distinct total row
count is a distinct XLA shape, so organic traffic (1, 3, 7, 2, ...
rows) compiles an unbounded set of executables — a recompile storm
exactly when the service is busiest.  The classic fix (the reference's
serving stack pads to fixed batch sizes too) is a SMALL set of bucket
shapes, padded up to:

- buckets default to powers of two up to `max_batch` (1, 2, 4, 8...),
  so padding waste is < 2x and the executable set is O(log max_batch);
- every bucket is AOT-compiled at STARTUP (`prewarm`) through the
  monitor's compile ledger, so traffic never pays a trace+compile and
  the compile events are attributed like the executor's;
- the compiled-fn cache is keyed like the executor's compiled-step
  cache — (program identity, program version, bucket, per-feed
  feature signature, fetch names) — so a mutated program or a changed
  feature shape can never serve a stale executable.

Padding rows are zeros and are sliced off before results leave the
runtime; because XLA computes rows of these inference programs
independently, the non-padding rows are BITWISE identical to an
unbatched `Predictor.run` (asserted by tests/test_serving.py).
"""

import os

import numpy as np

import jax
import jax.numpy as jnp

from jax import export as _jax_export

__all__ = ["default_buckets", "pick_bucket", "BucketDispatcher"]


def default_buckets(max_batch):
    """Powers of two up to max_batch, plus max_batch itself: the
    smallest executable set with bounded (<2x) padding waste."""
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1")
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(int(max_batch))
    return out


def pick_bucket(buckets, rows):
    """Smallest bucket that fits `rows` (buckets sorted ascending)."""
    for b in buckets:
        if rows <= b:
            return b
    raise ValueError(f"{rows} rows exceed the largest bucket "
                     f"{buckets[-1]}")


def _mon():
    from .. import monitor

    return monitor


class BucketDispatcher:
    """Shape the batching + compiled-fn cache around one predictor.

    Works over either engine:
      - `Predictor`: per-bucket AOT executables compiled from its pure
        fn; the eager (uncompiled interpret) path exists for degraded
        mode.
      - `CompiledPredictor`: the serialized artifact IS the single
        bucket (its exported batch dim); no eager path.
    """

    def __init__(self, predictor, buckets=None, max_batch=8,
                 label="serving"):
        self.predictor = predictor
        self.label = label
        self._cache = {}          # full key -> compiled executable
        self._exported_bucket = None
        if hasattr(predictor, "_exported"):       # CompiledPredictor
            bucket = self._exported_batch_dim()
            self.buckets = [bucket]
            self.feed_names = list(self._exported_feed_names())
            self._exported_dtypes = {
                n: a.dtype for n, a in self._exported_tree().items()}
            self._specs = None
        else:                                     # Predictor
            self.buckets = sorted(set(
                buckets if buckets else default_buckets(max_batch)))
            self.feed_names = list(predictor.get_input_names())
            self._specs = predictor.feed_specs()
        self.max_rows = self.buckets[-1]

    # -- CompiledPredictor introspection --------------------------------
    def _exported_tree(self):
        exported = self.predictor._exported
        args, _kwargs = jax.tree_util.tree_unflatten(
            exported.in_tree,
            list(exported.in_avals))
        return args[0]            # the feeds dict the fn was traced with

    def _exported_feed_names(self):
        return sorted(self._exported_tree())

    def _exported_batch_dim(self):
        tree = self._exported_tree()
        dims = {int(a.shape[0]) for a in tree.values() if a.shape}
        if len(dims) != 1:
            raise ValueError(
                f"CompiledPredictor artifact has no single batch dim "
                f"(leading dims {sorted(dims)}); serve it through "
                f"Predictor instead")
        return dims.pop()

    # -- feeds ----------------------------------------------------------
    def prepare(self, feed):
        """(prepared jnp feed dict, row count) for one request; raises
        on missing feeds, mismatched per-feed row counts, or a request
        larger than the biggest bucket (callers split those — admission
        control rejects them loudly instead)."""
        if hasattr(self.predictor, "prepare_feed"):
            prepared = self.predictor.prepare_feed(feed)
        else:
            prepared = {}
            for n in self.feed_names:
                if n not in feed:
                    raise KeyError(f"missing feed '{n}'")
                prepared[n] = jnp.asarray(
                    np.asarray(feed[n]),
                    dtype=self._exported_dtypes.get(n))
        rows = {n: (int(a.shape[0]) if a.ndim else 1)
                for n, a in prepared.items()}
        distinct = set(rows.values())
        if len(distinct) != 1:
            raise ValueError(f"feeds disagree on batch rows: {rows}")
        n_rows = distinct.pop()
        if n_rows < 1:
            raise ValueError("empty request (0 rows)")
        if n_rows > self.max_rows:
            raise ValueError(
                f"request of {n_rows} rows exceeds the largest serving "
                f"bucket {self.max_rows}; split it client-side or raise "
                f"max_batch")
        return prepared, n_rows

    def merge(self, prepared_list, bucket):
        """Concatenate prepared request feeds along the batch axis and
        zero-pad to `bucket` rows.  Returns (batched feed dict,
        [(start, stop) row slice per request])."""
        slices = []
        off = 0
        for p in prepared_list:
            rows = int(next(iter(p.values())).shape[0])
            slices.append((off, off + rows))
            off += rows
        if off > bucket:
            raise ValueError(f"{off} rows exceed bucket {bucket}")
        batched = {}
        for n in self.feed_names:
            parts = [p[n] for p in prepared_list]
            if off < bucket:
                pad_shape = (bucket - off,) + tuple(parts[0].shape[1:])
                parts.append(jnp.zeros(pad_shape, parts[0].dtype))
            batched[n] = parts[0] if len(parts) == 1 \
                else jnp.concatenate(parts, axis=0)
        return batched, slices

    @staticmethod
    def split(outs, slices):
        """Per-request output lists from one batched result: request i
        gets [fetch[start_i:stop_i] for each fetch] — padding rows
        never leave the runtime."""
        return [[o[start:stop] for o in outs] for start, stop in slices]

    # -- compiled-fn cache (keyed like the executor's) ------------------
    def _feat_sig(self, batched):
        return tuple(
            (n, tuple(batched[n].shape[1:]), str(batched[n].dtype))
            for n in sorted(batched))

    def _key(self, bucket, feat_sig):
        p = self.predictor
        if hasattr(p, "_exported"):
            return (id(p._exported), 0, bucket, feat_sig, None)
        return (id(p._program), getattr(p._program, "_version", 0),
                bucket, feat_sig,
                tuple(p.get_output_names()))

    def _compile(self, bucket, example, feat_sig):
        """Lower+compile the predictor's jitted fn at the bucket shape.
        With telemetry on it goes through the monitor's AOT
        instrumentation, so the compile is wall-clocked and
        cost/memory-analyzed like an executor compile.  A compile error
        propagates: prewarm fails at start-up, not at first traffic."""
        mon = _mon()
        key = self._key(bucket, feat_sig)
        if mon.is_enabled():
            compiled = mon.aot_compile(
                self.predictor._fn, example,
                key=f"serving/{self.label}/b{bucket}")
        else:
            compiled = self.predictor._fn.lower(example).compile()
        self._cache[key] = compiled
        if mon.is_enabled():
            mon.counter("serving.bucket_compile").add(1)
        return compiled

    def _zero_example(self, bucket):
        """A zeros feed dict at the bucket shape, or None when any
        trailing dim is dynamic (prewarm then waits for real traffic
        to reveal the feature shapes)."""
        if self._specs is None:
            return None
        example = {}
        for n in self.feed_names:
            feat, dtype = self._specs[n]
            if feat is None or any(d is None for d in feat):
                return None
            example[n] = jnp.zeros((bucket,) + tuple(feat), dtype)
        return example

    def prewarm(self):
        """AOT-compile every bucket at startup so traffic never pays a
        trace+compile (the recompile-storm guard).  Returns the number
        of executables compiled; 0 when shapes are dynamic or the
        engine is a CompiledPredictor (already an executable)."""
        if hasattr(self.predictor, "_exported"):
            return 0
        n = 0
        for bucket in self.buckets:
            example = self._zero_example(bucket)
            if example is None:
                return n
            if self._key(bucket, self._feat_sig(example)) in self._cache:
                continue           # already imported from the AOT cache
            self._compile(bucket, example, self._feat_sig(example))
            n += 1
        return n

    # -- AOT artifact cache (ISSUE 19) ----------------------------------
    def export_aot(self, dirname, platforms=None):
        """Serialize one ``jax.export`` artifact per bucket
        (``b<bucket>.jaxexport``) into `dirname` — the cold-start cache
        payload a later replica imports instead of recompiling.  Rides
        the same serialization path as Predictor.export_compiled.
        Returns the number of artifacts written (0 for a
        CompiledPredictor — it already IS the artifact — or when shapes
        are dynamic)."""
        if hasattr(self.predictor, "_exported"):
            return 0
        os.makedirs(dirname, exist_ok=True)
        n = 0
        for bucket in self.buckets:
            example = self._zero_example(bucket)
            if example is None:
                return n
            exported = _jax_export.export(
                self.predictor._fn, platforms=platforms)(example)
            path = os.path.join(dirname, f"b{bucket}.jaxexport")
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(exported.serialize())
            os.replace(tmp, path)
            n += 1
        return n

    def import_aot(self, dirname):
        """Load per-bucket serialized executables into the compiled-fn
        cache WITHOUT tracing or compiling — zero compile-ledger
        events, which is the whole point: a cold replica reaches first
        byte on cache hits alone.  Each artifact lands under the same
        cache key `_compile` would have used, so a version/shape
        mismatch simply misses and falls through to a (ledgered)
        compile instead of serving a stale executable.  Returns the
        number of buckets imported."""
        if hasattr(self.predictor, "_exported"):
            return 0
        n = 0
        for bucket in self.buckets:
            path = os.path.join(dirname, f"b{bucket}.jaxexport")
            if not os.path.isfile(path):
                continue
            example = self._zero_example(bucket)
            if example is None:
                return n
            with open(path, "rb") as f:
                exported = _jax_export.deserialize(f.read())
            key = self._key(bucket, self._feat_sig(example))
            self._cache[key] = exported.call
            n += 1
        mon = _mon()
        if n and mon.is_enabled():
            mon.counter("serving.aot_import").add(n)
        return n

    def dispatch(self, batched, bucket):
        """Run one padded bucket batch through the compiled executable
        for (bucket, feature signature) — compiling on miss (a shape
        prewarm could not predict) — and return the fetch list with
        results materialized (block_until_ready: a dispatch error must
        surface HERE, inside the breaker/retry/watchdog envelope, not
        at some caller's later sync point)."""
        if hasattr(self.predictor, "_exported"):
            outs = self.predictor._exported.call(batched)
        else:
            key = self._key(bucket, self._feat_sig(batched))
            fn = self._cache.get(key)
            if fn is None:
                fn = self._compile(bucket, batched,
                                   self._feat_sig(batched))
            outs = fn(batched)
        outs = list(outs)
        jax.block_until_ready(outs)
        return outs

    # -- degraded paths -------------------------------------------------
    @property
    def eager_available(self):
        return hasattr(self.predictor, "run_eager")

    def dispatch_eager(self, prepared):
        """One UNBATCHED request through the op-by-op interpreter — the
        breaker-open fallback that shares nothing with the compiled
        path it is standing in for."""
        outs = self.predictor.run_eager(prepared)
        return [jnp.asarray(o) for o in outs]
