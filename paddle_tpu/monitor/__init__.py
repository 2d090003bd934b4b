"""paddle_tpu.monitor — runtime telemetry subsystem.

Three pillars (ISSUE 3 tentpole):

1. **Step metrics** — `Executor.run` / `train_from_dataset` /
   `CompiledProgram` (and the bench harnesses) feed a `MetricsSession`
   automatically while telemetry is enabled: wall step time,
   host-dispatch μs, run-plan/compiled-step cache hits and misses,
   feed/fetch bytes, examples/s — all landing in a counters/gauges
   registry with optional JSONL emission and the in-process
   `snapshot()` API.
2. **Compile & memory accounting** — every jit compile is a ledger
   event (count, wall time, program key) carrying XLA's OWN
   `cost_analysis()` FLOPs and `memory_analysis()` bytes, so
   `monitor.mfu(step_time)` needs no hand-coded per-model FLOP formula.
3. **Unified trace** — `profiler.export_chrome_tracing` merges host
   RecordEvent spans with step-boundary spans and chrome-trace counter
   tracks (examples/s, cache, live bytes) built here (`trace.py`).

Usage::

    from paddle_tpu import monitor
    monitor.enable(jsonl_path="/tmp/telemetry.jsonl")
    ... train ...
    snap = monitor.snapshot()        # machine-readable, json.dump-safe
    print(snap["mfu"], snap["compile"]["count"])
    monitor.disable()

Telemetry off (the default) costs the dispatch path one boolean check.
"""

from .compile_ledger import (CompileLedger, PEAK_FLOPS, peak_flops,
                             parse_cost_analysis, parse_memory_analysis)
from .jsonl_writer import JsonlWriter, read_jsonl
from .registry import Counter, Gauge, MetricsRegistry
from .session import MetricsSession
from . import op_profile                                  # noqa: F401
from . import mem_profile                                 # noqa: F401
from . import flight_recorder  # noqa: F401  — installs crash hooks
from . import fleet                                       # noqa: F401
from . import exporter                                    # noqa: F401
from . import tracing                                     # noqa: F401
from . import goodput                                     # noqa: F401
from .fleet import fleet_skew, rank_info, rank_tag        # noqa: F401

__all__ = [
    "enable", "disable", "is_enabled", "snapshot", "reset",
    "counter", "gauge", "record_step", "observe_steps", "record_compile",
    "record_lint", "lint_records",
    "record_pass_pipeline", "pass_pipeline_records",
    "aot_compile", "instrument_jit", "mfu", "step_records",
    "compile_events", "jsonl_path", "merged_trace_events",
    "op_table", "op_profile_split", "op_profile", "flight_recorder",
    "flight_dump",
    "mem_profile", "mem_profile_split", "mem_table", "peak_breakdown",
    "serving_table", "record_serving", "serving_records",
    "tracing", "record_trace", "trace_records",
    "fleet", "exporter", "fleet_skew", "rank_info", "rank_tag",
    "record_fleet_skew", "fleet_skew_records",
    "record_elastic", "elastic_records",
    "record_fleet_serving", "fleet_serving_records",
    "goodput", "record_goodput", "goodput_records",
    "MetricsRegistry", "MetricsSession", "CompileLedger", "JsonlWriter",
    "read_jsonl", "Counter", "Gauge", "PEAK_FLOPS", "peak_flops",
    "parse_cost_analysis", "parse_memory_analysis",
]

# process-global instances: one registry, one compile ledger, one step
# session — every layer reports into the same place, which is the point
_registry = MetricsRegistry()
_ledger = CompileLedger(_registry)
_session = MetricsSession(_registry, _ledger)
# op-profile splits computed at compile time ride the telemetry JSONL
# stream as kind="op_profile" records (step numbering stays step-only)
_ledger.set_aux_sink(_session.emit_record)
_enabled = False
# kind="lint" records from the static verifier (ISSUE 7): kept here so
# snapshot consumers can read them without re-parsing the JSONL
_lint_records = []
# kind="serving" records from the serving runtime (ISSUE 8), same idea
_serving_records = []
# kind="pass_pipeline" records from the graph optimizer (ISSUE 9):
# per-pass op counts + wall time, and the trace-time dp grad-bucketing
_pass_records = []
# kind="fleet_skew" records from the straggler probe (ISSUE 10): the
# rolling per-rank skew table, emitted at loop end / flight dump
_fleet_records = []
# kind="elastic" records from the elastic fleet runtime (ISSUE 11):
# topology transitions, rank join/leave/death, policy decisions — the
# topology history telemetry_report renders
_elastic_records = []
# kind="fleet_serving" records from the fleet router (ISSUE 19): the
# merged router+replica outcome ledger, failover counts, per-replica
# health/version — emitted at router close / on demand
_fleet_serving_records = []
# kind="trace" records from request tracing (ISSUE 18): each retained
# span tree (SLO violators + head-sampled), emitted at trace finish
_trace_records = []
# kind="goodput" records from the wall-clock attribution ledger
# (ISSUE 20): one per finished run — integer-ns category buckets that
# sum exactly to the run's wall time, goodput fraction, effective MFU
_goodput_records = []


def enable(jsonl_path=None):
    """Turn telemetry on.  With `jsonl_path`, every step record is also
    appended there as one JSON line (`read_jsonl` parses it back —
    rank-stamped and size-cap-rotated per the FLAGS_telemetry_* policy).
    Session entry also starts the live /metrics exporter iff
    FLAGS_metrics_port says so (never per step, never raising)."""
    global _enabled
    if jsonl_path is not None:
        _session.attach_writer(JsonlWriter(jsonl_path))
    _enabled = True
    exporter.ensure_started()


def disable():
    """Stop recording (recorded data stays readable until `reset`).
    Also detaches the JSONL writer: a later `enable()` without a
    `jsonl_path` records in-process only instead of silently appending
    to the previous path."""
    global _enabled
    _enabled = False
    _session.attach_writer(None)


def is_enabled():
    return _enabled


def reset():
    """Drop all recorded telemetry: step records, compile events,
    per-op samples, and every counter/gauge (in place — held handles
    stay valid).  The flight recorder's ring is NOT cleared: it is an
    independent always-on post-mortem window (clear it explicitly with
    flight_recorder.get().clear())."""
    _session.clear()
    _ledger.clear()
    _registry.reset()
    op_profile.clear_samples()
    fleet.clear()
    del _lint_records[:]
    del _serving_records[:]
    del _pass_records[:]
    del _fleet_records[:]
    del _elastic_records[:]
    del _fleet_serving_records[:]
    del _trace_records[:]
    del _goodput_records[:]
    tracing.get().reset()


# -- recording entry points (no-ops while disabled) ---------------------

def counter(name):
    return _registry.counter(name)


def gauge(name):
    return _registry.gauge(name)


def record_step(**kwargs):
    if not _enabled:
        return None
    return _session.record_step(**kwargs)


def observe_steps(n, seconds, examples=0, label=None):
    if not _enabled:
        return None
    return _session.observe_steps(n, seconds, examples=examples,
                                  label=label)


def record_lint(record):
    """Write one kind="lint" record (a LintResult.to_record() dict from
    the static verifier) onto the telemetry JSONL stream and keep it
    addressable in-process (lint_records()).  No step bookkeeping —
    like op_profile records, lint rides the same stream without
    touching step numbering."""
    if not _enabled or not record:
        return None
    _lint_records.append(dict(record))
    _session.emit_record(record)
    return record


def lint_records():
    """kind="lint" records seen since enable()/reset(), newest last."""
    return list(_lint_records)


def record_serving(record):
    """Write one kind="serving" record (a ServingStats.to_record()
    dict from the serving runtime) onto the telemetry JSONL stream and
    keep it addressable in-process (serving_records()).  Like lint and
    op_profile records, it rides the stream without touching step
    numbering."""
    if not _enabled or not record:
        return None
    _serving_records.append(dict(record))
    _session.emit_record(record)
    return record


def serving_records():
    """kind="serving" records seen since enable()/reset(), newest
    last."""
    return list(_serving_records)


def record_trace(record):
    """Write one kind="trace" record (a retained request span tree
    from monitor/tracing.py) onto the telemetry JSONL stream and keep
    it addressable in-process (trace_records()).  Like lint/serving
    records it rides the stream without touching step numbering.  The
    TraceStore itself is gate-free like the serving stats ledger —
    this is only the JSONL/export mirror."""
    if not _enabled or not record:
        return None
    _trace_records.append(dict(record))
    _session.emit_record(record)
    return record


def trace_records():
    """kind="trace" records (retained span trees) seen since
    enable()/reset(), newest last."""
    return list(_trace_records)


def record_pass_pipeline(record):
    """Write one kind="pass_pipeline" record (a pass-pipeline report
    from paddle_tpu.passes, or the trace-time dp grad-bucketing note
    from transpiler.collective) onto the telemetry JSONL stream and
    keep it addressable in-process (pass_pipeline_records()).  Like
    lint/op_profile records, it rides the stream without touching step
    numbering."""
    if not _enabled or not record:
        return None
    record = dict(record)
    record.setdefault("kind", "pass_pipeline")
    import time as _time

    record.setdefault("ts_us", _time.perf_counter_ns() / 1000.0)
    record.setdefault("wall_time", _time.time())
    _pass_records.append(record)
    _session.emit_record(record)
    return record


def pass_pipeline_records():
    """kind="pass_pipeline" records seen since enable()/reset(),
    newest last."""
    return list(_pass_records)


def record_fleet_skew(table=None, key=None):
    """Write one kind="fleet_skew" record — the current rolling skew
    table (fleet.fleet_skew()) unless an explicit table is passed —
    onto the telemetry JSONL stream and keep it addressable in-process
    (fleet_skew_records()).  Called at train-loop end and by the flight
    recorder before a dump; like lint/serving records it rides the
    stream without touching step numbering.  None (and no record) when
    no dp step has carried the probe yet."""
    if not _enabled:
        return None
    if table is None:
        table = fleet.fleet_skew()
    if not table:
        return None
    record = {"kind": "fleet_skew", **table}
    if key is not None:
        record["key"] = key
    import time as _time

    record.setdefault("ts_us", _time.perf_counter_ns() / 1000.0)
    record.setdefault("wall_time", _time.time())
    _fleet_records.append(record)
    _session.emit_record(record)
    return record


def fleet_skew_records():
    """kind="fleet_skew" records seen since enable()/reset(), newest
    last."""
    return list(_fleet_records)


def record_elastic(record):
    """Write one kind="elastic" record (a topology-transition /
    rank-membership / policy event from resilience.elastic) onto the
    telemetry JSONL stream and keep it addressable in-process
    (elastic_records()).  Like lint/serving/fleet records it rides the
    stream without touching step numbering; a no-op while telemetry is
    off — the gate-free `resilience.elastic_*` counters still record
    that the transition happened."""
    if not _enabled or not record:
        return None
    record = dict(record)
    record.setdefault("kind", "elastic")
    import time as _time

    record.setdefault("ts_us", _time.perf_counter_ns() / 1000.0)
    record.setdefault("wall_time", _time.time())
    _elastic_records.append(record)
    _session.emit_record(record)
    return record


def elastic_records():
    """kind="elastic" records seen since enable()/reset(), newest
    last."""
    return list(_elastic_records)


def record_fleet_serving(record):
    """Write one kind="fleet_serving" record (the FleetRouter's merged
    outcome ledger + per-replica health/version/breaker view) onto the
    telemetry JSONL stream and keep it addressable in-process
    (fleet_serving_records()).  A no-op while telemetry is off — the
    router's registered ServingStats still carries the live ledger."""
    if not _enabled or not record:
        return None
    record = dict(record)
    record.setdefault("kind", "fleet_serving")
    import time as _time

    record.setdefault("ts_us", _time.perf_counter_ns() / 1000.0)
    record.setdefault("wall_time", _time.time())
    _fleet_serving_records.append(record)
    _session.emit_record(record)
    return record


def fleet_serving_records():
    """kind="fleet_serving" records seen since enable()/reset(),
    newest last."""
    return list(_fleet_serving_records)


def record_goodput(record):
    """Write one kind="goodput" record (a finished GoodputLedger's
    wall-clock attribution: integer-ns category buckets summing exactly
    to wall_ns, goodput_fraction, effective_mfu) onto the telemetry
    JSONL stream and keep it addressable in-process
    (goodput_records()).  Like lint/serving/fleet records it rides the
    stream without touching step numbering; the record is kept even
    while telemetry is off — the ledger only exists when FLAGS_goodput
    armed it, and dropping its one record because enable() wasn't
    called would silently lose the whole run's attribution."""
    if not record:
        return None
    record = dict(record)
    record.setdefault("kind", "goodput")
    import time as _time

    record.setdefault("ts_us", _time.perf_counter_ns() / 1000.0)
    record.setdefault("wall_time", _time.time())
    _goodput_records.append(record)
    if _enabled:
        _session.emit_record(record)
    return record


def goodput_records():
    """kind="goodput" records seen since enable()/reset(), newest
    last."""
    return list(_goodput_records)


def serving_table():
    """One summary row per live ServingRuntime — request outcomes
    (completed / shed / expired / rejected / failed / stalled /
    cancelled), exact p50/p99 latency, bucket mix, queue/in-flight
    gauges, breaker state + transitions, watchdog stalls.  Empty list
    when no runtime is alive.  Works with telemetry off: the serving
    stats ledger is gate-free like the flight recorder's counters."""
    from ..serving import stats as _serving_stats

    return _serving_stats.serving_table()


def record_compile(key, compile_s, flops=None, bytes_accessed=None,
                   memory=None, trace_s=None, source="manual"):
    if not _enabled:
        return None
    return _ledger.record(key, compile_s, flops=flops,
                          bytes_accessed=bytes_accessed, memory=memory,
                          trace_s=trace_s, source=source)


def aot_compile(jitfn, *args, key="jit"):
    """Timed lower+compile with cost/memory analysis recorded; returns
    the compiled executable (None if AOT is unavailable)."""
    return _ledger.aot_compile(jitfn, *args, key=key)


def instrument_jit(jitfn, key="jit", var_info=None):
    """Wrap a jitted callable so its compiles land in the ledger while
    telemetry is enabled; a plain pass-through call otherwise.
    `var_info` (the executor's param/persist var maps) classes the
    mem-profile's entry-argument buffers."""
    return _ledger.instrument_jit(jitfn, key=key, is_enabled=is_enabled,
                                  var_info=var_info)


# -- reading ------------------------------------------------------------

def step_records():
    return _session.records()


def compile_events():
    return _ledger.events()


def jsonl_path():
    w = _session.writer()
    return w.path if w is not None else None


def mfu(step_time_s=None, key=None, peak=None):
    """MFU from the compile ledger's cost analysis.  step_time_s
    defaults to the session's mean recorded step time.  None on a CPU
    (no peak on record, see peak_flops) unless `peak` is passed."""
    if step_time_s is None:
        step_time_s = _session.mean_step_time()
    return _ledger.mfu(step_time_s, key=key, peak=peak)


def op_profile_split(key=None):
    """The newest per-op static attribution (monitor/op_profile.py
    split structure: totals, per-scope FLOPs/bytes, unattributed
    residual), optionally restricted to compile-ledger key `key`.
    None until a compile has been analyzed."""
    for e in reversed(_ledger.events()):
        if key is not None and e.get("key") != key:
            continue
        if e.get("op_profile"):
            return e["op_profile"]
    return None


def op_table(key=None):
    """Fluid-parity per-op rows: the static FLOPs/bytes split merged
    with any sampled per-op timings — what stop_profiler prints and
    snapshot() embeds."""
    return op_profile.op_table(static=op_profile_split(key),
                               sampled=op_profile.sampled_rows(),
                               step_time_s=_session.mean_step_time())


def mem_profile_split(key=None):
    """The newest peak-memory attribution (monitor/mem_profile.py
    structure: peak, timeline, per-scope peak bytes, classes, top
    buffers, unattributed residual), optionally restricted to
    compile-ledger key `key`.  None until a compile has been
    analyzed."""
    for e in reversed(_ledger.events()):
        if key is not None and e.get("key") != key:
            continue
        if e.get("mem_profile"):
            return e["mem_profile"]
    return None


def mem_table(key=None):
    """Ordered per-scope peak-HBM rows of the newest memory profile —
    what stop_profiler's "Peak HBM" section prints."""
    return mem_profile.mem_table(mem_profile_split(key))


def peak_breakdown(key=None):
    """Compact peak-HBM view of the newest memory profile: headline
    peak bytes, per-variable-class split, the top peak scopes, the
    peak snapshot table, and the unattributed residual — json-safe
    (what snapshot()["mem_profile"] embeds)."""
    prof = mem_profile_split(key)
    if not prof:
        return None
    return {
        "peak": prof.get("peak"),
        "totals": prof.get("totals"),
        "classes": prof.get("classes"),
        "scopes": mem_profile.mem_table(prof),
        "top_buffers": prof.get("top_buffers"),
        "unattributed": prof.get("unattributed"),
        "donated": prof.get("donated"),
    }


def flight_dump(reason="manual"):
    """Force a flight-recorder post-mortem dump now; returns the JSONL
    path (None when the recorder is disabled)."""
    return flight_recorder.dump(reason)


def snapshot():
    """Point-in-time telemetry snapshot — json.dump-safe: session
    aggregates (steps, step_time_s, host_dispatch_us, examples/s, byte
    totals), the full counter/gauge registry, the compile ledger
    summary (count, time, FLOPs, memory bytes), the derived MFU, and —
    once a compile has been attributed — the per-op profile rows."""
    # drain the fleet skew ring FIRST: materializing pending probe
    # vectors bumps fleet.* counters/gauges, and the registry snapshot
    # below must already include them — same ordering the /metrics
    # exporter uses, so scrape and snapshot agree
    skew = fleet.fleet_skew()
    out = _session.snapshot()
    out.update(_registry.snapshot())
    out["compile"] = _ledger.summary()
    out["mfu"] = mfu()
    rows = op_table()
    if rows:
        out["op_profile"] = rows
    mem = peak_breakdown()
    if mem:
        out["mem_profile"] = mem
    serving = serving_table()
    if serving:
        out["serving"] = serving
    store = tracing.get()
    tr = [s for s in (store.summary(lb) for lb in store.labels())
          if s is not None]
    if tr:
        out["tracing"] = tr
    if skew:
        out["fleet"] = {"rank": fleet.rank_tag(), "skew": skew}
    # the ACTIVE run's in-flight breakdown wins over a past finished
    # record — a snapshot is the now-state; history stays addressable
    # via goodput_records()
    if goodput.active() is not None:
        out["goodput"] = goodput.active().flight_record()
    elif _goodput_records:
        out["goodput"] = dict(_goodput_records[-1])
    return out


def merged_trace_events(host_events):
    """Build the unified trace event list from the profiler's host
    spans plus this session's step records, compile events, gauge
    time-series tracks, and retained request-trace trees."""
    from .trace import merged_trace_events as _merge

    return _merge(host_events, step_records=_session.records(),
                  compile_events=_ledger.events(),
                  gauge_series=_registry.gauge_series(),
                  trace_trees=tracing.get().retained_trees())
