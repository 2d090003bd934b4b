"""Goodput ledger — exhaustive wall-clock attribution for training runs.

MegaScale (Jiang et al., NSDI 2024) and Google's TPUv4 fleet experience
(Zu et al., "Resiliency at Scale", NSDI 2024) both argue the operative
fleet metric is **goodput** — the fraction of wall time spent in
productive steps — and that badput must be *attributed* per cause to be
fixable.  This module partitions the entire duration of a
`train_from_dataset` run (or a long `Executor.run` session) into an
exhaustive, non-overlapping set of integer-ns categories:

    productive_step     device compute the run exists for (sync waits)
    compile             trace + XLA compile of a fresh program key
    data_wait           reader / prefetch starvation (main thread
                        blocked on the next batch)
    host_dispatch       executor host work: plan lookup, feed prep,
                        dispatch into the compiled step
    checkpoint_save     synchronous checkpoint writes
    recovery            retry backoff sleeps + rollback restores +
                        anomaly-guard skipped steps
    elastic_transition  elastic coordinator: membership barriers,
                        re-tracing decisions, forced saves
    dp_sync_wait        data-parallel straggler wait, folded in from
                        the PR-10 skew probe at run end
    unattributed        explicit residual — everything no hook saw

The repo's signature invariant holds here as everywhere: the category
buckets **sum exactly (==, not allclose) to the measured wall time**.
The ledger achieves that by construction, not reconciliation: it is a
stack of open spans plus one high-water mark; every transition
(push/pop/finish) reads the clock once and charges `now - mark` to the
innermost open category (`unattributed` when the stack is empty).
Integer nanoseconds never lose a remainder, so the partition is exact.

Gate-free when off: `active()` is a single module-global read and no
ledger object ever exists unless FLAGS_goodput is on.  One clock read
per transition when on.

`goodput_fraction` and `effective_mfu` (the compile-ledger cost-analysis
MFU scaled by goodput) recompute from the retained ledger — the emitted
kind="goodput" record carries the raw buckets so any consumer can
re-derive them with `==`.
"""

import threading
import time

from .. import flags

# Ordered: report tables and chrome tracks render in this order.
CATEGORIES = (
    "productive_step",
    "compile",
    "data_wait",
    "host_dispatch",
    "checkpoint_save",
    "recovery",
    "elastic_transition",
    "dp_sync_wait",
    "unattributed",
)

# Everything that is not a productive step is badput (host_dispatch and
# unattributed included: time the chip was not stepping is time to win
# back, whoever owns it).
BADPUT_CATEGORIES = tuple(c for c in CATEGORIES if c != "productive_step")

class GoodputLedger:
    """Exact wall-clock partition of one run.

    Single-owner: the thread that creates the ledger is the only one
    whose push/pop mutate it — hooks firing on other threads (prefetch
    producers, pollers) are no-ops, and their effect surfaces where the
    owner thread blocks on them (e.g. producer starvation is charged as
    `data_wait` at the consumer's queue get).
    """

    def __init__(self, key=None, clock=time.perf_counter_ns):
        self.key = key
        self._clock = clock
        self._tid = threading.get_ident()
        self._t0 = clock()
        self._mark = self._t0
        self._buckets = {c: 0 for c in CATEGORIES}
        # open spans: [category, ns charged while innermost]
        self._stack = []
        self._finished = None
        self.steps = 0
        self.transitions = 0

    # -- core accounting -------------------------------------------------

    def _charge(self, now):
        """Charge `now - mark` to the innermost open category (the
        explicit `unattributed` residual when no span is open) and
        advance the mark.  The only place time is ever booked."""
        delta = now - self._mark
        if delta > 0:
            if self._stack:
                top = self._stack[-1]
                self._buckets[top[0]] += delta
                top[1] += delta
            else:
                self._buckets["unattributed"] += delta
        self._mark = now

    def _owned(self):
        return self._finished is None and \
            threading.get_ident() == self._tid

    def push(self, category):
        """Open a span of `category`.  Returns True iff the span was
        opened (owner thread, not finished) — callers must pop only on
        True.  Nested spans win: time is charged to the innermost."""
        if not self._owned():
            return False
        self._charge(self._clock())
        self._stack.append([category, 0])
        self.transitions += 1
        return True

    def pop(self):
        """Close the innermost span; returns the integer ns charged to
        it while it was innermost (0 when not owner / nothing open)."""
        if not self._owned() or not self._stack:
            return 0
        self._charge(self._clock())
        cat, accum = self._stack.pop()
        if cat != "productive_step" and accum > 0:
            self._track(cat)
        return accum

    def span(self, category):
        return _Span(self, category)

    def retag(self, category):
        """Re-label the innermost open span from now on (time already
        charged to it keeps its old category).  Used when a span's true
        nature is only learned mid-flight — e.g. host_dispatch turning
        out to be a fresh compile."""
        if not self._owned() or not self._stack:
            return False
        self._charge(self._clock())
        self._stack[-1][0] = category
        return True

    def reclassify(self, src, dst, ns):
        """Move up to `ns` already-booked nanoseconds from bucket `src`
        to bucket `dst` (sum-preserving; clamped to what `src` holds).
        Returns the amount actually moved.  Used for after-the-fact
        attribution: dp_sync_wait folded from the skew table, guard-
        skipped steps converted productive_step -> recovery."""
        if ns <= 0 or src not in self._buckets or dst not in self._buckets:
            return 0
        moved = min(int(ns), self._buckets[src])
        if moved > 0:
            self._buckets[src] -= moved
            self._buckets[dst] += moved
        return moved

    def note_step(self, n=1):
        self.steps += n

    # -- dp skew fold ----------------------------------------------------

    def fold_dp_sync(self, table):
        """Fold the PR-10 skew probe into the ledger: the mean per-step
        barrier wait across this process's shards, times the probed
        step count, moves from productive_step (where the sync point
        charged it) into dp_sync_wait.  Sum-preserving by construction
        (reclassify clamps)."""
        if not table:
            return 0
        ranks = table.get("ranks") or []
        steps = int(table.get("steps") or 0)
        waits = [float(r.get("wait_us_mean") or 0.0) for r in ranks]
        if not waits or steps <= 0:
            return 0
        mean_wait_us = sum(waits) / len(waits)
        return self.reclassify("productive_step", "dp_sync_wait",
                               int(mean_wait_us * 1000.0) * steps)

    # -- output ----------------------------------------------------------

    def _track(self, category):
        """Badput chrome counter track: one gauge point per closed
        badput span (cumulative ms), riding the registry's bounded
        gauge history into merged_trace_events."""
        from . import gauge
        gauge("badput.%s_ms" % category).set(
            self._buckets[category] / 1e6)

    def wall_ns(self, now=None):
        if self._finished is not None:
            return self._finished["wall_ns"]
        return (now if now is not None else self._clock()) - self._t0

    def finish(self, extra=None):
        """Close every open span, stamp the wall clock, and build the
        kind="goodput" record.  Idempotent (returns the same record on
        repeat).  The exact-sum invariant is checked here with `==` —
        a failure is a bug in this file, so it raises."""
        if self._finished is not None:
            return self._finished
        if threading.get_ident() != self._tid:
            raise RuntimeError("GoodputLedger.finish() from non-owner "
                               "thread")
        now = self._clock()
        self._charge(now)
        del self._stack[:]
        wall = now - self._t0
        buckets = {c: int(self._buckets[c]) for c in CATEGORIES}
        total = sum(buckets.values())
        if total != wall:                           # pragma: no cover
            raise AssertionError(
                "goodput ledger lost time: categories sum to %d ns but "
                "wall is %d ns" % (total, wall))
        record = {
            "kind": "goodput",
            "key": self.key,
            "wall_ns": wall,
            "steps": self.steps,
            "transitions": self.transitions,
            "categories": buckets,
        }
        record.update(compute_fractions(record))
        m = _mfu()
        if m:
            record["mfu"] = m
            record["effective_mfu"] = m * record["goodput_fraction"]
        if extra:
            record.update(extra)
        self._finished = record
        self._flush_metrics(record)
        return record

    def _flush_metrics(self, record):
        """Land the finished ledger on /metrics: goodput gauges plus
        per-category badput ns counters (counters, so repeated runs in
        one process accumulate like every other resilience counter)."""
        from . import counter, gauge
        gauge("goodput.fraction").set(record["goodput_fraction"])
        gauge("goodput.wall_s").set(record["wall_ns"] / 1e9)
        if record.get("effective_mfu") is not None:
            gauge("goodput.effective_mfu").set(record["effective_mfu"])
        counter("goodput.productive_ns").add(
            record["categories"]["productive_step"])
        for cat in BADPUT_CATEGORIES:
            ns = record["categories"][cat]
            if ns:
                counter("badput.%s_ns" % cat).add(ns)

    def flight_record(self, now=None):
        """A non-mutating snapshot for the flight recorder: the run's
        time breakdown *so far*, with the currently-open interval
        charged to the innermost open category.  Safe to call from the
        crash-hook thread (tolerates racing the owner; the dump is a
        post-mortem estimate, finish() is the exact one)."""
        if self._finished is not None:
            return dict(self._finished)
        if now is None:
            now = self._clock()
        buckets = {c: int(self._buckets[c]) for c in CATEGORIES}
        pending = now - self._mark
        try:
            top = self._stack[-1][0] if self._stack else "unattributed"
        except IndexError:                          # racing a pop
            top = "unattributed"
        if pending > 0:
            buckets[top] += pending
        record = {
            "kind": "goodput",
            "key": self.key,
            "wall_ns": now - self._t0,
            "steps": self.steps,
            "transitions": self.transitions,
            "categories": buckets,
            "in_flight": True,
        }
        record.update(compute_fractions(record))
        return record


class _Span:
    __slots__ = ("_ledger", "_category", "_pushed", "ns")

    def __init__(self, ledger, category):
        self._ledger = ledger
        self._category = category
        self._pushed = False
        self.ns = 0

    def __enter__(self):
        self._pushed = self._ledger.push(self._category)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._pushed:
            self.ns = self._ledger.pop()
        return False


def compute_fractions(record):
    """Recompute goodput/badput fractions from a record's raw buckets —
    the same arithmetic finish() used, exposed so consumers (report,
    bench assertions) can verify `==` against the stored values."""
    wall = int(record.get("wall_ns") or 0)
    cats = record.get("categories") or {}
    productive = int(cats.get("productive_step") or 0)
    if wall <= 0:
        return {"goodput_fraction": 0.0, "badput_fraction": 0.0}
    good = productive / wall
    return {"goodput_fraction": good, "badput_fraction": 1.0 - good}


def _mfu():
    from paddle_tpu import monitor

    return monitor.mfu()


# -- module-global active ledger (the gate) -----------------------------
#
# The hot path's entire cost with the flag off is reading this global
# and seeing None.  At most one ledger is active per process — a nested
# Executor.run inside train_from_dataset joins the outer run's ledger
# instead of fighting it for the wall clock.

_active = None


def active():
    """The currently-active ledger, or None.  THE gate: one global
    read."""
    return _active


def start_run(key=None, force=False):
    """Open a run ledger if FLAGS_goodput is on (or `force`) and none
    is already active.  Returns the new ledger, or None when gated off
    / already owned by an enclosing run (callers must only finish what
    they started)."""
    global _active
    if _active is not None:
        return None
    if not force and not flags.flag("goodput"):
        return None
    _active = GoodputLedger(key=key)
    return _active


def finish_run(ledger, extra=None):
    """Finish `ledger`, clear the active slot, emit the kind="goodput"
    record onto the telemetry stream, and return the record.  None-safe
    so call sites can pass the (possibly None) result of start_run."""
    global _active
    if ledger is None:
        return None
    if _active is ledger:
        _active = None
    record = ledger.finish(extra=extra)
    from paddle_tpu import monitor
    monitor.record_goodput(record)
    return record


def abandon(ledger):
    """Drop an active ledger without emitting (error-path cleanup)."""
    global _active
    if ledger is not None and _active is ledger:
        _active = None


def flight_records():
    """What the flight recorder dumps: the active ledger's in-flight
    breakdown (so an OOM/crash dump answers "was it slow before it
    died"), else nothing — finished runs already live in
    monitor.goodput_records()."""
    led = _active
    if led is None:
        return []
    try:
        return [led.flight_record()]
    except Exception:                               # pragma: no cover
        return []
