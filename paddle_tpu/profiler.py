"""Profiler.

Parity: /root/reference/python/paddle/fluid/profiler.py (:253 profiler
context, :129 start_profiler, :196 stop_profiler) + the C++ RecordEvent
span profiler (platform/profiler.h:124) and chrome-trace export
(tools/timeline.py:137).

TPU mapping: device-side tracing delegates to jax.profiler (XPlane →
TensorBoard/Perfetto); host-side spans keep the reference's RAII-span +
aggregate-table + chrome-trace-export shape.
"""

import contextlib
import gc
import json
import os
import statistics
import sys
import threading
import time

import jax

from . import flags

__all__ = ["profiler", "start_profiler", "stop_profiler", "RecordEvent",
           "cuda_profiler", "reset_profiler", "is_profiling",
           "export_chrome_tracing", "add_span", "spans",
           "trace_clock_offset_ns", "TRACE_PREFIX"]

# A span recorded while a jax.profiler session runs is also written
# into that session's trace under this prefix, on the device events'
# clock, with its own perf_counter_ns start as the stat `pc_ns`.
TRACE_PREFIX = "paddle_tpu:"
_trace_enabled = jax.profiler.TraceAnnotation.is_enabled

# Span storage: the nesting STACK is per-thread (spans nest within one
# thread), but the recorded events are aggregated across threads —
# train_from_dataset's producer thread records spans too, and events
# landing in an unreachable threading.local would silently vanish from
# stop_profiler's table and export_chrome_tracing (the thread-local
# event-loss bug).  Every per-thread event list is registered in
# _thread_events at first use; readers merge them, tagged with the tid.
_state = threading.local()
_registry_lock = threading.Lock()
# append-only list of every thread's event list.  NOT keyed by tid:
# thread idents are recycled after a thread exits, and a tid-keyed dict
# would overwrite (and lose) a dead producer thread's events when a new
# thread draws the same ident.  Each registered list stays reachable
# from its thread's threading.local, so entries are cleared in place,
# never removed (a retired thread costs one empty list).
_event_lists = []


def _events():
    ev = getattr(_state, "events", None)
    if ev is None:
        ev = _state.events = []
        _state.stack = []
        with _registry_lock:
            _event_lists.append(ev)
    return ev


def _all_events():
    """Every recorded event, across ALL threads, in timestamp order."""
    with _registry_lock:
        lists = list(_event_lists)
    out = [e for evs in lists for e in evs]
    out.sort(key=lambda e: e["ts"])
    return out


def _clear_events():
    with _registry_lock:
        lists = list(_event_lists)
    for evs in lists:
        del evs[:]    # in place: each thread keeps its registered list


def _event(name, start_ns, end_ns, depth, attrs=None):
    event = {
        "name": name,
        "ts": start_ns / 1000.0,
        "dur": (end_ns - start_ns) / 1000.0,
        "depth": depth,
        "tid": threading.get_ident(),
        "start_ns": start_ns,
        "end_ns": end_ns,
    }
    if attrs:
        event["attrs"] = attrs
    return event


class RecordEvent:
    """RAII host-side span (platform/profiler.h:124 parity), recorded
    on two clocks.

    On while a `start_profiler` session is active or a `jax.profiler`
    session is (`TraceAnnotation.is_enabled()`: an operator's own
    `start_trace`, the benchmark's traced stretch); off otherwise, at
    the cost of that one check, so a RecordEvent sprinkled through the
    hot path records nothing in steady state and needs no flag.  On, the
    same two `perf_counter_ns` readings go to the event store
    (`_all_events()`, `spans()`) and, while jax traces, a
    `TraceAnnotation(TRACE_PREFIX + name, pc_ns=start, **attrs)` stays
    open for the span's length: the span lies in the xplane trace beside
    the device events, and `pc_ns` ties the two clocks
    (`trace_clock_offset_ns`).

    A span that straddles `reset_profiler` or the start of a session
    (entered before, exited after) is dropped rather than resurrected:
    its start predates the clear, so appending it would re-populate the
    just-cleared table with a stale event — the `epoch` stamp catches
    exactly that."""

    __slots__ = ("name", "attrs", "start", "_epoch", "_annotation")

    def __init__(self, name, **attrs):
        self.name = name
        self.attrs = attrs
        self.start = None
        self._epoch = None
        self._annotation = None

    def __enter__(self):
        tracing = _note_trace_session(_trace_enabled())
        if not (tracing or _active["on"]):
            self.start = None      # armed-off: __exit__ is a no-op
            return self
        _events()
        self._epoch = _active["epoch"]
        start = time.perf_counter_ns()
        if tracing:
            self._annotation = jax.profiler.TraceAnnotation(
                TRACE_PREFIX + self.name, pc_ns=start, **self.attrs)
            self._annotation.__enter__()
        # armed only now: an annotation that failed to open leaves no
        # entry on the stack for later spans to count as their depth
        self.start = start
        _state.stack.append(self.name)
        return self

    def __exit__(self, *exc):
        if self.start is None:
            return False
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        end = time.perf_counter_ns()
        _state.stack.pop()
        if self._epoch != _active["epoch"]:
            # reset_profiler (or a new session) cleared the event store
            # while this span was open: discard, don't resurrect
            return False
        _events().append(_event(self.name, self.start, end,
                                len(_state.stack), self.attrs))
        return False


# `epoch` counts event-store clears (reset_profiler / start_profiler);
# an in-flight RecordEvent compares its entry epoch before appending.
# `tracing` is what the span sites last saw of the jax.profiler session.
# `owner` is where the open `start_profiler` session was started: a
# jax.profiler session is process-wide, so a second start names it.
_active = {"on": False, "jax_trace": False, "epoch": 0,
           "tracing": False, "owner": None}


def _note_trace_session(enabled):
    """Keep `_active["tracing"]` in step with the jax.profiler session
    and return `enabled`.  A session begins when a span site (or a
    reader) first sees the profiler on: the store is cleared for it,
    unless a `start_profiler` session, which cleared it itself, is on.
    Its events outlive `stop_trace`, until the next session or
    `reset_profiler()`."""
    if enabled != _active["tracing"]:
        with _registry_lock:
            began = enabled and not _active["tracing"]
            _active["tracing"] = enabled
        if began and not _active["on"]:
            reset_profiler()
        _hook_gc()
    return enabled


# What pauses the process itself: each garbage collection that runs
# while a session is on becomes a `host.gc` span (`generation`,
# `collected`) in the store and, under a jax trace, in the trace.  The
# hook is in `gc.callbacks` only while a session is on (`_hook_gc`: a
# `start_profiler` session, or a jax session a span site has noted), so
# a process that never traces runs no code of it.  It runs inside the
# interpreter's collector, on whatever thread allocated, so it takes no
# lock: its events go to a list of their own, registered here once, and
# a collection under way is one slot (the collector does not nest).  A
# jax session whose start no span has noted yet (`_note_trace_session`)
# records no collection, since the noting clears the store.
GC_SPAN = "host.gc"
_gc_events = []
_event_lists.append(_gc_events)
_gc_open = None      # (start_ns, epoch, annotation) of the collection


def _on_gc(phase, info):
    global _gc_open
    if phase == "start":
        tracing = _trace_enabled()
        if not ((tracing and _active["tracing"]) or _active["on"]):
            return
        start = time.perf_counter_ns()
        annotation = None
        if tracing:
            annotation = jax.profiler.TraceAnnotation(
                TRACE_PREFIX + GC_SPAN, pc_ns=start,
                generation=info["generation"])
            annotation.__enter__()
        _gc_open = (start, _active["epoch"], annotation)
    elif _gc_open is not None:
        start, epoch, annotation = _gc_open
        _gc_open = None
        attrs = {"generation": info["generation"],
                 "collected": info["collected"]}
        if annotation is not None:
            annotation.set_metadata(collected=info["collected"])
            annotation.__exit__(None, None, None)
        if epoch == _active["epoch"]:
            _gc_events.append(_event(
                GC_SPAN, start, time.perf_counter_ns(),
                len(getattr(_state, "stack", ())), attrs))


def _hook_gc():
    """Hook `_on_gc` into the collector while a session is on, and
    take it out when none is."""
    hooked = _on_gc in gc.callbacks
    if _active["on"] or _active["tracing"]:
        if not hooked:
            gc.callbacks.append(_on_gc)
    elif hooked:
        gc.callbacks.remove(_on_gc)


def add_span(name, start_ns, end_ns, depth=0):
    """Record one already-measured span (perf_counter_ns endpoints) —
    the entry point the op-profile sampling mode uses so its per-op
    timings appear in stop_profiler's table and the chrome trace.
    No-op outside a profiling session, same contract as RecordEvent."""
    if not _active["on"]:
        return
    _events().append(_event(name, start_ns, end_ns, depth))


def spans(prefix=None):
    """The newest session's spans as `(name, start_ns, end_ns, attrs)`
    on `perf_counter_ns`, in order of their starts; those whose name
    starts with `prefix`, if one is given.  They outlive the session
    (`stop_trace`, `stop_profiler`) until the next one begins or
    `reset_profiler()`."""
    _note_trace_session(_trace_enabled())
    return [(e["name"], e["start_ns"], e["end_ns"], e.get("attrs", {}))
            for e in _all_events()
            if prefix is None or e["name"].startswith(prefix)]


def trace_clock_offset_ns(xplane_events):
    """What to add to a `perf_counter_ns` reading to lay it on the
    clock of a jax.profiler trace: the median of `start_ns - pc_ns`
    over the `TRACE_PREFIX` events among `xplane_events` (events of a
    `jax.profiler.ProfileData` line: `.name`, `.start_ns`, `.stats`).
    The two clocks run at one rate and the offset is the session's, so
    every span stamped with `perf_counter_ns` (request traces, goodput,
    the merged chrome trace) can be laid against the device's events.
    None where the trace holds no such event."""
    offsets = []
    for e in xplane_events:
        if e.name.startswith(TRACE_PREFIX):
            pc_ns = dict(e.stats).get("pc_ns")
            if pc_ns is not None:
                offsets.append(e.start_ns - int(pc_ns))
    return statistics.median(offsets) if offsets else None


def is_profiling():
    """True while a start_profiler/profiler() session is active — the
    executor's dispatch path checks this before opening RecordEvent
    spans so steady-state training never accumulates events."""
    return _active["on"]


def _caller():
    """`file:line in function` of the nearest frame outside this module
    and contextlib (the `profiler()` context's own frames)."""
    frame = sys._getframe(1)
    while frame.f_back is not None and frame.f_code.co_filename in (
            __file__, contextlib.__file__):
        frame = frame.f_back
    code = frame.f_code
    return f"{code.co_filename}:{frame.f_lineno} in {code.co_name}"


def start_profiler(state="All", tracer_option="Default"):
    """Open the process's one profiling session; `stop_profiler` ends
    it.  A session that is already open is an error, not a no-op: with
    "All" (or "GPU"/"TPU") the session holds the process-wide
    `jax.profiler` trace, and a second one would run without it.  The
    error of `jax.profiler.start_trace` itself (someone else's
    `start_trace` is open) passes through, and leaves no session."""
    if _active["on"]:
        raise RuntimeError(
            "a profiler session is already open (started at "
            f"{_active['owner']}); call stop_profiler() before starting "
            "another")
    if state in ("All", "GPU", "TPU"):
        trace_dir = flags.flag("profiler_dir")
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        _active["jax_trace"] = True
    _events()            # register this thread before clearing
    _clear_events()
    _active["epoch"] += 1
    _active["owner"] = _caller()
    _active["on"] = True
    _hook_gc()


# Fluid-parity sort keys (profiler.py:196): each maps to the table
# column it ranks by, descending — the reference prints the costliest
# first whatever the key
_SORT_FIELDS = {"total": "total_us", "max": "max_us", "min": "min_us",
                "ave": "ave_us", "calls": "calls"}


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    """End the profiling session and print the aggregate span table
    (calls / total / max / min / ave μs, sorted by `sorted_key` —
    "total" | "max" | "min" | "ave" | "calls", reference parity), plus
    — when the monitor has per-op attribution data (a compiled step's
    static split and/or a sampling run) — the Fluid per-op table with
    device-time, FLOPs, bytes, and %-of-step columns.  Safe to call
    with no session open; called outside the calling thread's spans,
    whose nesting depth starts again at 0."""
    _active["on"] = False
    _active["owner"] = None
    _hook_gc()
    _events()            # this thread has a stack
    del _state.stack[:]  # its next session's spans start at depth 0
    if _active["jax_trace"]:
        try:
            jax.profiler.stop_trace()
        finally:
            _active["jax_trace"] = False
    events = _all_events()
    table = {}
    for e in events:
        row = table.setdefault(e["name"], {"calls": 0, "total_us": 0.0,
                                           "max_us": 0.0,
                                           "min_us": float("inf")})
        row["calls"] += 1
        row["total_us"] += e["dur"]
        row["max_us"] = max(row["max_us"], e["dur"])
        row["min_us"] = min(row["min_us"], e["dur"])
    for row in table.values():
        row["ave_us"] = row["total_us"] / row["calls"]
        if row["min_us"] == float("inf"):
            row["min_us"] = 0.0
    if sorted_key is not None and sorted_key not in _SORT_FIELDS:
        raise ValueError(
            f"sorted_key must be one of {sorted(_SORT_FIELDS)} or None, "
            f"got {sorted_key!r}")
    field = _SORT_FIELDS[sorted_key or "total"]
    items = sorted(table.items(), key=lambda kv: -kv[1][field])
    if table:
        lines = [f"{'Event':<40}{'Calls':>8}{'Total(us)':>14}"
                 f"{'Max(us)':>12}{'Min(us)':>12}{'Ave(us)':>12}"]
        for name, row in items:
            lines.append(
                f"{name:<40}{row['calls']:>8}{row['total_us']:>14.1f}"
                f"{row['max_us']:>12.1f}{row['min_us']:>12.1f}"
                f"{row['ave_us']:>12.1f}")
        print("\n".join(lines))
    _print_op_table()
    _print_mem_table()
    if not events:
        return {}
    if profile_path:
        # default (merged) export: the session's trace should carry the
        # monitor's step/counter tracks alongside the host spans
        export_chrome_tracing(profile_path + ".json")
    return table


def _print_op_table():
    """The per-op attribution section (ISSUE 5 tentpole surface):
    scope, calls, measured μs, XLA-cost FLOPs/bytes, %-of-step.  Quiet
    when no attribution data exists — a plain host-span session prints
    exactly what it used to."""
    try:
        from . import monitor

        rows = monitor.op_table()
    except Exception:
        return
    if not rows:
        return
    lines = ["", "Per-op attribution (device cost by ProgramDesc op):",
             f"{'Op (section/type_idx)':<36}{'Calls':>7}{'Time(us)':>12}"
             f"{'GFLOPs':>10}{'MBytes':>10}{'%':>8}"]
    for r in rows:
        t = r.get("total_us", r.get("est_us"))
        pct = r.get("time_pct", r.get("flops_pct"))
        lines.append(
            f"{r['scope']:<36}"
            f"{r.get('calls', '-'):>7}"
            + (f"{t:>12.1f}" if t is not None else f"{'-':>12}")
            + (f"{r['flops'] / 1e9:>10.4f}" if r.get("flops") is not None
               else f"{'-':>10}")
            + (f"{r['bytes_accessed'] / 1e6:>10.3f}"
               if r.get("bytes_accessed") is not None else f"{'-':>10}")
            + (f"{pct:>8.2f}" if pct is not None else f"{'-':>8}"))
    print("\n".join(lines))


def _fmt_bytes(b):
    if b is None:
        return "-"
    if b >= 2 ** 30:
        return f"{b / 2 ** 30:.2f} GiB"
    if b >= 2 ** 20:
        return f"{b / 2 ** 20:.2f} MiB"
    return f"{b / 2 ** 10:.1f} KiB"


def _print_mem_table():
    """The "Peak HBM" section (ISSUE 6 surface): headline peak bytes,
    the variable-class split (parameter / optimizer state / activation
    / gradient / temp / donated-reuse), and the top peak scopes.
    Quiet when no compile has been memory-attributed."""
    try:
        from . import monitor

        prof = monitor.mem_profile_split()
        rows = monitor.mem_table()
    except Exception:
        return
    if not prof:
        return
    peak = prof.get("peak") or {}
    hbm = peak.get("hbm_bytes")
    lines = ["", "Peak HBM (live-buffer attribution at the program "
                 "peak):",
             f"  peak {_fmt_bytes(hbm if hbm is not None else peak.get('model_bytes'))}"
             f" at program position {peak.get('pos')}"
             + (f" (model {_fmt_bytes(peak.get('model_bytes'))})"
                if hbm is not None else "")]
    classes = prof.get("classes") or {}
    if classes:
        parts = [f"{c}={_fmt_bytes(d['peak_bytes'])}"
                 for c, d in sorted(classes.items(),
                                    key=lambda kv: -kv[1]["peak_bytes"])]
        lines.append("  classes: " + "  ".join(parts))
    if rows:
        lines.append(f"{'Scope':<36}{'Peak':>12}{'%':>8}{'Buffers':>9}")
        for r in rows[:12]:
            lines.append(f"{r['scope']:<36}"
                         f"{_fmt_bytes(r['peak_bytes']):>12}"
                         f"{r['peak_pct']:>8.2f}{r['buffers']:>9}")
    print("\n".join(lines))


def export_chrome_tracing(path, events=None):
    """Unified chrome://tracing JSON (tools/timeline.py:137 parity,
    extended per ISSUE 3): host RecordEvent spans — every recording
    thread, tagged with its real tid — MERGED with the monitor's
    step-boundary spans, xla-compile spans, and counter tracks
    (examples/s, cache hit/miss, live bytes), all on the shared
    perf_counter timeline.  One Perfetto load shows host dispatch,
    steps, and counters together; tools/parse_xplane.py accepts the
    same file.

    Passing an explicit `events` list exports EXACTLY those host spans
    (the parameter is a filter — a per-phase subset must not be
    contaminated by the process-global monitor state); the default
    exports everything recorded plus the monitor's merged tracks."""
    from . import monitor
    from .monitor.trace import host_span_events

    if events is None:
        trace_events = monitor.merged_trace_events(_all_events())
    else:
        trace_events = host_span_events(events)
    trace = {"traceEvents": trace_events, "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path="/tmp/profile",
             tracer_option="Default"):
    """Parity: fluid.profiler.profiler context (profiler.py:253)."""
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


def reset_profiler():
    """Clear all recorded events — on every thread — (reference
    profiler.py reset_profiler parity) without stopping an active
    profiling session.

    Safe with respect to in-flight spans: a `RecordEvent` that is OPEN
    when reset runs will, on exit, see the epoch has advanced and drop
    itself instead of appending a stale event whose start predates the
    clear (or crashing on missing state).  Spans ENTERED after the
    reset record normally."""
    _active["epoch"] += 1
    _clear_events()


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """Reference-parity shim: nvprof integration has no TPU meaning.
    The context still brackets a RecordEvent span so scripts keep a
    timeline, and the arguments are accepted unchanged."""
    with RecordEvent("cuda_profiler(shim)"):
        yield
