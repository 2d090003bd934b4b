"""Compile & memory accounting — XLA's own numbers, not hand-coded ones.

Every jit compile the executor (or bench harness) performs is recorded
here as a compile event: wall time, program key, and what the compiled
executable itself reports — `cost_analysis()` FLOPs/bytes-accessed and
`memory_analysis()` (argument/output/temp/generated-code bytes).  MFU is
then `flops_per_step / step_time / peak_flops` with the numerator taken
from the HLO cost analysis of the program actually running, so it cannot
drift from the model the way a per-model FLOP formula can.

The AOT path (`aot_compile`) uses jax's lower()/compile() split so the
compile wall time is measured alone (trace time is separate) and the
executable handle is available for analysis; `instrument_jit` wraps an
implicitly-jitted callable with a per-signature memo of AOT-compiled
executables; a callable that is not a jit (no .lower) is called as
it is, and its first-call wall time, which includes trace+compile, is
recorded with the analysis fields absent.  A lower or compile error
propagates from the first attempt.
"""

import threading
import time

__all__ = ["CompileLedger", "PEAK_FLOPS", "peak_flops",
           "parse_cost_analysis", "parse_memory_analysis", "live_bytes"]

# Peak dense-matmul FLOP/s of one chip in bf16, keyed by the
# `device_kind` string jax reports.  A kind is added with its source
# once its spelling has been read off a device.
PEAK_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
}


def peak_flops(device=None):
    """Peak bf16 FLOP/s of `device` (default: jax.devices()[0]).

    None for a CPU: the host has no published matmul peak, so nothing
    derived from it (an MFU) is a number.  An accelerator that is not
    in the table is an error, not a default."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = getattr(device, "device_kind", "")
    if kind not in PEAK_FLOPS:
        raise KeyError(
            f"no peak FLOP/s on record for device kind {kind!r} "
            f"(platform {device.platform!r}); add it to "
            f"monitor.compile_ledger.PEAK_FLOPS with its source")
    return PEAK_FLOPS[kind]


def parse_cost_analysis(cost):
    """Normalize Compiled.cost_analysis() output — a dict on newer jax,
    a list of per-computation dicts on older — into
    {"flops": float|None, "bytes_accessed": float|None}."""
    if cost is None:
        return {"flops": None, "bytes_accessed": None}
    entries = cost if isinstance(cost, (list, tuple)) else [cost]
    flops = 0.0
    bytes_accessed = 0.0
    seen = False
    for d in entries:
        if not isinstance(d, dict):
            continue
        seen = True
        flops += float(d.get("flops", 0.0) or 0.0)
        bytes_accessed += float(d.get("bytes accessed", 0.0) or 0.0)
    if not seen:
        return {"flops": None, "bytes_accessed": None}
    return {"flops": flops or None, "bytes_accessed": bytes_accessed or None}


def parse_memory_analysis(mem):
    """CompiledMemoryStats -> plain byte counts (device side only; host
    offload fields are zero on every backend this repo targets)."""
    if mem is None:
        return None
    out = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        v = getattr(mem, field, None)
        if v is not None:
            out[field.replace("_size_in_bytes", "_bytes")] = int(v)
    return out or None


def live_bytes(memory):
    """High-water live-bytes estimate of one compiled program —
    arguments + temps — the ONE definition both the registry gauge and
    the chrome-trace counter track use."""
    if not memory or memory.get("temp_bytes") is None:
        return None
    return memory.get("argument_bytes", 0) + memory["temp_bytes"]


def _abstract_sig(args):
    """Hashable shape/dtype signature of a pytree of call args."""
    import jax

    return tuple(
        (getattr(a, "shape", None) and tuple(a.shape),
         str(getattr(a, "dtype", type(a).__name__)))
        for a in jax.tree_util.tree_leaves(args))


class CompileLedger:
    """Per-program compile ledger: events + counters + MFU."""

    def __init__(self, registry):
        self._registry = registry
        self._lock = threading.Lock()
        self._events = []
        # optional JSONL sink for auxiliary (non-step) records: the
        # monitor wires this to MetricsSession.emit_record so per-op
        # attribution splits land in the same telemetry stream
        self._aux_sink = None

    def set_aux_sink(self, sink):
        self._aux_sink = sink

    # -- recording ------------------------------------------------------
    def record(self, key, compile_s, flops=None, bytes_accessed=None,
               memory=None, trace_s=None, source="aot", op_profile=None,
               mem_profile=None):
        event = {
            "kind": "compile",
            "key": key,
            "ts_us": time.perf_counter_ns() / 1000.0,
            "wall_time": time.time(),
            "compile_ms": round(compile_s * 1e3, 3),
            "source": source,
        }
        if trace_s is not None:
            event["trace_ms"] = round(trace_s * 1e3, 3)
        if flops is not None:
            event["flops"] = flops
        if bytes_accessed is not None:
            event["bytes_accessed"] = bytes_accessed
        if memory is not None:
            event["memory"] = memory
        if op_profile is not None:
            event["op_profile"] = op_profile
        if mem_profile is not None:
            event["mem_profile"] = mem_profile
        with self._lock:
            self._events.append(event)
        self._registry.counter("compile.count").add(1)
        self._registry.counter("compile.time_ms").add(
            round(compile_s * 1e3, 3))
        live = live_bytes(memory)
        if live is not None:
            self._registry.gauge("compile.live_bytes").set(live)
        try:
            from . import flight_recorder

            # mirror into the always-on post-mortem ring (full analysis
            # attached); the recorder also keeps the newest attribution
            # split as its "what was the step made of" section and the
            # newest memory profile as the peak-HBM section an OOM
            # post-mortem writes
            flight_recorder.get().note_compile(event)
            if op_profile is not None:
                flight_recorder.get().note_op_table(op_profile)
            if mem_profile is not None:
                # keyed like the aux-sink record, so a dump's
                # kind="mem_profile" line names its program too
                flight_recorder.get().note_mem_profile(
                    {"key": key, **mem_profile})
        except Exception:
            pass
        if self._aux_sink is not None:
            if op_profile is not None:
                self._aux_sink({"kind": "op_profile", "key": key,
                                "ts_us": event["ts_us"],
                                "wall_time": event["wall_time"],
                                **op_profile})
            if mem_profile is not None:
                self._aux_sink({"kind": "mem_profile", "key": key,
                                "ts_us": event["ts_us"],
                                "wall_time": event["wall_time"],
                                **mem_profile})
        return event

    def events(self):
        with self._lock:
            return list(self._events)

    def clear(self):
        with self._lock:
            del self._events[:]

    # -- AOT compile + instrumentation ---------------------------------
    def aot_compile(self, jitfn, *args, key="jit", var_info=None):
        """lower+compile `jitfn` at `args`, recording one compile event
        (wall-clocked compile, cost_analysis, memory_analysis).  Returns
        the compiled executable, or None when the callable is not a
        jit (no .lower; the caller then calls it directly).  A lower or
        compile error propagates: a second attempt through the
        implicit-jit path would only pay the refused compile twice.

        `var_info` ({"params": ..., "persist": ...} — the executor's
        param/persist var maps) feeds the mem-profile's variable-class
        attribution; the analysis runs without it, with entry arguments
        classed by their state/feeds container only."""
        lower = getattr(jitfn, "lower", None)
        if lower is None:
            return None
        t0 = time.perf_counter()
        lowered = lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        try:
            cost = parse_cost_analysis(compiled.cost_analysis())
        except Exception:
            cost = {"flops": None, "bytes_accessed": None}
        try:
            memory = parse_memory_analysis(compiled.memory_analysis())
        except Exception:
            memory = None
        # the optimized-HLO pretty-print is the expensive shared input
        # of both attribution passes (multi-MB for real models): fetch
        # it ONCE and hand it to each
        try:
            hlo_text = compiled.as_text()
        except Exception:
            hlo_text = None
        try:
            # per-op attribution: parse the optimized HLO's named-scope
            # metadata and split the cost-analysis totals per ProgramDesc
            # op (monitor/op_profile.py).  A one-time cost per compile —
            # milliseconds of text parsing next to seconds of XLA.
            from .op_profile import static_split

            op_profile = static_split(compiled, text=hlo_text)
        except Exception:
            op_profile = None
        try:
            # peak-memory attribution from the same HLO text: buffer
            # liveness + peak snapshot + live-bytes timeline
            # (monitor/mem_profile.py), scaled to memory_analysis
            from .mem_profile import static_mem_profile

            mem_profile = static_mem_profile(compiled, var_info=var_info,
                                             text=hlo_text)
        except Exception:
            mem_profile = None
        self.record(key, compile_s=t2 - t1, trace_s=t1 - t0,
                    flops=cost["flops"],
                    bytes_accessed=cost["bytes_accessed"], memory=memory,
                    op_profile=op_profile, mem_profile=mem_profile)
        return compiled

    def instrument_jit(self, jitfn, key="jit", is_enabled=None,
                       var_info=None):
        """Wrap a jitted callable so its compile goes through
        `aot_compile` (timed + analyzed) while telemetry is on.  Off
        before any compile happened, or when AOT fails, the call goes
        straight to `jitfn` — implicit jit, zero ledger cost.

        Hot-path contract: every wrapper instance in this codebase is
        signature-pinned (the executor's compiled-fn cache keys on the
        feed/state signature; each bench harness builds a fresh wrapper
        per shape), so after the first compile the stored executable is
        called DIRECTLY — no per-call pytree hashing inflating the very
        host-dispatch numbers being recorded.  A changed signature
        raises TypeError from the AOT executable's argument check
        (before execution, so donation is untouched) and falls through
        to the per-signature slow path.  Once compiled through the
        ledger, the executable keeps serving even after telemetry is
        disabled — toggling telemetry off must not re-trace the step.
        The inverse toggle (enable after an implicit-jit warmup) pays
        one AOT compile of the already-compiled program: the analysis
        numbers have to come from somewhere."""
        memo = {}
        last = []          # [fn] — the signature-pinned fast path
        _FALLBACK = object()

        def wrapped(*args):
            if last:
                fn = last[0]
                if fn is _FALLBACK:
                    return jitfn(*args)
                try:
                    return fn(*args)
                except TypeError:
                    pass   # new abstract signature: re-resolve below
            if is_enabled is not None and not is_enabled():
                return jitfn(*args)
            sig = _abstract_sig(args)
            fn = memo.get(sig)
            if fn is None:
                fn = self.aot_compile(jitfn, *args, key=key,
                                      var_info=var_info)
                if fn is None:
                    # no AOT for this callable: time the first (implicit
                    # compile) call so the ledger still counts it
                    t0 = time.perf_counter()
                    out = jitfn(*args)
                    self.record(key, compile_s=time.perf_counter() - t0,
                                source="first_call")
                    memo[sig] = _FALLBACK
                    last[:] = [_FALLBACK]
                    return out
                memo[sig] = fn
            last[:] = [fn]
            if fn is _FALLBACK:
                return jitfn(*args)
            return fn(*args)

        return wrapped

    # -- derived numbers ------------------------------------------------
    def flops_per_step(self, key=None):
        """FLOPs of the most recent compile event carrying cost-analysis
        numbers (optionally restricted to events for `key`) — the
        numerator of the MFU computation."""
        with self._lock:
            for e in reversed(self._events):
                if key is not None and e["key"] != key:
                    continue
                if e.get("flops"):
                    return e["flops"]
        return None

    def mfu(self, step_time_s, key=None, peak=None):
        """Model FLOPs utilization from XLA's own cost analysis:
        flops_per_step / step_time / peak.  None when no compile event
        carries FLOPs or step_time is unusable."""
        if not step_time_s or step_time_s <= 0:
            return None
        flops = self.flops_per_step(key)
        if not flops:
            return None
        if peak is None:
            peak = peak_flops()
        if peak is None:
            return None
        return flops / step_time_s / peak

    def summary(self):
        """Aggregate view for snapshots: count, total/last compile ms,
        last event's analysis numbers, and the per-key ledger."""
        with self._lock:
            events = list(self._events)
        if not events:
            return {"count": 0}
        per_key = {}
        for e in events:
            row = per_key.setdefault(e["key"], {"count": 0,
                                                "compile_ms": 0.0})
            row["count"] += 1
            row["compile_ms"] = round(row["compile_ms"] + e["compile_ms"],
                                      3)
            for field in ("flops", "bytes_accessed", "memory"):
                if e.get(field) is not None:
                    row[field] = e[field]
        last = events[-1]
        out = {
            "count": len(events),
            "total_compile_ms": round(
                sum(e["compile_ms"] for e in events), 3),
            "last_compile_ms": last["compile_ms"],
            "programs": per_key,
        }
        # headline analysis numbers: most recent event that has them
        for field in ("flops", "bytes_accessed", "memory"):
            for e in reversed(events):
                if e.get(field) is not None:
                    out[field] = e[field]
                    break
        for e in reversed(events):
            if e.get("mem_profile"):
                pk = e["mem_profile"].get("peak") or {}
                out["peak_hbm_bytes"] = (pk.get("hbm_bytes")
                                         or pk.get("model_bytes"))
                break
        return out
