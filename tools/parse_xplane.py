"""Parse a profiler capture into a per-op / per-track time table.

Accepts BOTH trace formats this repo produces, so the two paths cannot
silently diverge:

- a jax.profiler ``xplane.pb`` (device-side XSpace proto): TPU device
  plane -> XLA-op lines -> aggregate duration by HLO op name / category.
  (The tensorboard_plugin_profile converter in this image is broken
  against the installed TF — missing xspace_to_tools_data symbol — so
  this walks the XSpace proto directly.)
- the merged chrome-trace JSON that ``profiler.export_chrome_tracing``
  writes (host RecordEvent spans + monitor step spans + counter
  tracks): aggregate span duration per (process, track) and list the
  counter tracks' last samples.  Memory counter tracks (the
  mem-profile's ``hbm_live_bytes`` program timeline and the
  ``compile.live_bytes`` gauge watermark) additionally get a per-track
  peak/mean table.

Anything else exits with an error naming the two expected formats.

Both formats additionally get a **per-op attribution** section (ISSUE
5): spans/events whose names or op_name stats carry an executor scope
("{section}/{op_type}_{idx}" — see paddle_tpu/monitor/op_profile.py)
are grouped per ProgramDesc op, so a capture answers "which conv in my
program is eating the step" directly.

Fleet mode (ISSUE 10): ``--fleet <dir>`` merges every per-rank chrome
trace in a shared directory onto ONE timeline — pids remapped
rank-major, process rows prefixed ``rank{r}@{host}`` from the
rank-stamped trace metadata, each trace aligned to its own window
start (span clocks are per-process perf_counter) — writes
``<dir>/fleet_merged.trace.json`` (Perfetto-loadable) and prints the
per-track summary over the merged events.

Usage: python tools/parse_xplane.py <xplane.pb | trace.json> [top_n]
       python tools/parse_xplane.py --fleet <trace-dir> [top_n]
"""
import collections
import glob
import json
import os
import sys


def _op_profile_mod():
    """Load monitor/op_profile.py by FILE PATH: the scope regex and
    grouping live there (one definition for the whole repo), but
    importing the paddle_tpu package would pull in jax — this tool
    stays runnable on a bare host next to a capture file."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "paddle_tpu", "monitor",
                        "op_profile.py")
    spec = importlib.util.spec_from_file_location("_pt_op_profile", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def print_scope_table(spans, top_n, unit_div=1e3, unit="ms"):
    """Group (name, duration_us) spans by executor scope and print the
    per-op table; quiet when nothing carries a scope (a capture from
    outside the executor)."""
    try:
        grouped = _op_profile_mod().group_spans_by_scope(spans)
    except Exception:
        return
    if not grouped:
        return
    total = sum(v["total_us"] for v in grouped.values())
    print(f"== per-op attribution: {total/unit_div:.3f} {unit} over "
          f"{len(grouped)} program ops")
    rows = sorted(grouped.items(), key=lambda kv: -kv[1]["total_us"])
    for scope, v in rows[:top_n]:
        pct = v["total_us"] / total * 100.0 if total else 0.0
        print(f"  {v['total_us']/unit_div:9.3f} {unit}  "
              f"x{v['calls']:<5d} {pct:5.1f}%  {scope}")


def load_xspace(path):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def device_plane(xs):
    for p in xs.planes:
        if p.name.startswith("/device:TPU"):
            return p
    raise SystemExit(f"no TPU plane in {[p.name for p in xs.planes]}")


def agg(plane):
    """Return ({line_name: {event_name: (total_ps, count, category)}},
    spans) where spans is a per-event (attribution_name, duration_us)
    list — attribution_name prefers the 'tf_op'/'op_name' metadata stat
    (the named-scope path XLA threads through to the device plane) over
    the bare HLO instruction name, so the per-op grouping can see the
    executor's ProgramDesc scopes."""
    md = {m.id: m for m in plane.event_metadata.values()}
    smd = {m.id: m.name for m in plane.stat_metadata.values()}
    out = {}
    spans = []
    for line in plane.lines:
        table = collections.defaultdict(lambda: [0, 0, ""])
        for ev in line.events:
            m = md.get(ev.metadata_id)
            name = m.name if m else str(ev.metadata_id)
            row = table[name]
            row[0] += ev.duration_ps
            row[1] += 1
            op_name = None
            if m:
                for st in m.stats:
                    sname = smd.get(st.metadata_id)
                    if sname == "hlo_category" and not row[2]:
                        row[2] = st.str_value
                    elif sname in ("tf_op", "op_name") and not op_name:
                        op_name = st.str_value
            spans.append((op_name or name, ev.duration_ps / 1e6))
        out[line.name] = table
    return out, spans


def main_xplane(path, top_n):
    xs = load_xspace(path)
    plane = device_plane(xs)
    tables, spans = agg(plane)
    for lname, table in tables.items():
        total = sum(v[0] for v in table.values())
        if total == 0:
            continue
        print(f"== line {lname!r}: total {total/1e9:.3f} ms over "
              f"{sum(v[1] for v in table.values())} events")
        rows = sorted(table.items(), key=lambda kv: -kv[1][0])[:top_n]
        for name, (ps, n, cat) in rows:
            print(f"  {ps/1e9:9.3f} ms  x{n:<5d} {cat:12s} {name[:110]}")
    print_scope_table(spans, top_n)


def _load_chrome_events(path):
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc) if isinstance(doc, dict) else doc
    if not isinstance(events, list):
        raise SystemExit(
            f"{path}: JSON but not a chrome trace (no traceEvents list)")
    return events


def main_chrome_trace(path, top_n):
    """The merged host+steps+counters trace from export_chrome_tracing:
    per-track span aggregates + counter-track summary."""
    summarize_chrome_events(_load_chrome_events(path), top_n)


def summarize_chrome_events(events, top_n):
    pid_names, tid_names = {}, {}
    spans = collections.defaultdict(
        lambda: collections.defaultdict(lambda: [0.0, 0]))
    counters = collections.defaultdict(list)
    flat_spans = []
    for e in events:
        if not isinstance(e, dict):
            continue
        ph = e.get("ph")
        if ph == "M":
            # foreign traces may carry metadata without args — skip,
            # don't crash (the track then shows its numeric id)
            name = (e.get("args") or {}).get("name")
            if name is None:
                continue
            if e.get("name") == "process_name":
                pid_names[e.get("pid")] = name
            elif e.get("name") == "thread_name":
                tid_names[(e.get("pid"), e.get("tid"))] = name
        elif ph == "X":
            key = (e.get("pid", 0), e.get("tid", 0))
            row = spans[key][e.get("name", "?")]
            row[0] += float(e.get("dur", 0.0))
            row[1] += 1
            flat_spans.append((e.get("name", "?"),
                               float(e.get("dur", 0.0))))
        elif ph == "C":
            counters[e.get("name", "?")].append(
                (float(e.get("ts", 0.0)), e.get("args", {})))
    for (pid, tid), table in sorted(spans.items()):
        track = (f"{pid_names.get(pid, pid)}/"
                 f"{tid_names.get((pid, tid), tid)}")
        total = sum(v[0] for v in table.values())
        print(f"== track {track}: total {total/1e3:.3f} ms over "
              f"{sum(v[1] for v in table.values())} spans")
        rows = sorted(table.items(), key=lambda kv: -kv[1][0])[:top_n]
        for name, (us, n) in rows:
            print(f"  {us/1e3:9.3f} ms  x{n:<5d} {name[:110]}")
    for name, samples in sorted(counters.items()):
        samples.sort(key=lambda s: s[0])   # args dicts don't compare
        print(f"== counter {name!r}: {len(samples)} samples, "
              f"last {samples[-1][1]}")
    print_memory_tracks(counters)
    # per-op grouping: the sampling mode records per-op spans named by
    # scope, so a merged trace from an eager profiling session gets the
    # same attribution table an XPlane capture does
    print_scope_table(flat_spans, top_n)


def print_memory_tracks(counters):
    """Per-track peak/mean table for the memory counter tracks the
    merged trace carries (`hbm_live_bytes` — the mem-profile's
    live-bytes-over-program timeline — and the `*live_bytes`/`*bytes`
    gauge tracks); quiet when the trace has none."""
    rows = []
    for name, samples in sorted(counters.items()):
        if "bytes" not in name:
            continue
        vals = [float(v) for _, args in samples
                for v in (args or {}).values()
                if isinstance(v, (int, float))
                and not isinstance(v, bool)]
        if vals:
            rows.append((name, max(vals), sum(vals) / len(vals),
                         len(vals)))
    if not rows:
        return
    print(f"== memory counter tracks ({len(rows)})")
    for name, peak, mean, n in rows:
        print(f"  {name:<24} peak {peak / 2**20:10.3f} MiB  "
              f"mean {mean / 2**20:10.3f} MiB  x{n}")


def _trace_rank(events, fallback):
    """The fleet rank a trace was recorded by, read from the rank-
    stamped process_name metadata (monitor/trace.py puts {host,
    process_index} in the args); (fallback, None) for untagged
    traces so pre-fleet captures still merge."""
    for e in events:
        if not isinstance(e, dict) or e.get("ph") != "M" \
                or e.get("name") != "process_name":
            continue
        args = e.get("args") or {}
        if "process_index" in args:
            return int(args["process_index"]), args.get("host")
    return fallback, None


# rank-major pid remap stride: above Linux's largest pid_max (2**22)
# so a foreign trace carrying a real OS pid can never collide with
# another rank's remapped rows
_PID_STRIDE = 1 << 23


def merge_fleet_traces(paths, events_by_path=None):
    """Merge N rank-tagged chrome traces onto one timeline with
    per-rank process rows.  Each trace's span clock is that process's
    perf_counter — monotonic but not shared — so every trace is
    aligned to its own earliest event (the common window start); pids
    are remapped rank-major (rank*_PID_STRIDE + pid) and process names get a
    "rank{r}@{host}" prefix, so Perfetto shows one process group per
    rank.  ``events_by_path`` lets a caller that already parsed a
    trace (the --fleet validity probe) avoid re-reading it."""
    merged = []
    ranks = []
    for i, path in enumerate(sorted(paths)):
        events = (events_by_path or {}).get(path)
        if events is None:
            events = _load_chrome_events(path)
        rank, host = _trace_rank(events, i)
        ranks.append(rank)
        t0 = min((float(e["ts"]) for e in events
                  if isinstance(e, dict) and "ts" in e), default=0.0)
        label = f"rank{rank}" + (f"@{host}" if host else "")
        for e in events:
            if not isinstance(e, dict):
                continue
            e = dict(e)
            if "pid" in e:
                # stride must clear any REAL os pid a foreign trace in
                # the shared dir may carry (pid_max is <= 2**22), not
                # just paddle's own constant pids 0/1 — a collision
                # silently overlaps two ranks on one Perfetto row
                e["pid"] = rank * _PID_STRIDE + int(e["pid"])
            if "ts" in e:
                e["ts"] = float(e["ts"]) - t0
            if e.get("ph") == "M" and e.get("name") == "process_name":
                args = dict(e.get("args") or {})
                name = args.get("name", "")
                if not name.startswith("rank"):
                    args["name"] = f"{label}:{name}"
                e["args"] = args
            elif e.get("ph") == "C":
                # counter tracks are keyed by name within a pid; the
                # rank prefix keeps per-rank series separable when a
                # viewer flattens them
                e = {**e, "name": f"{label}:{e.get('name', '?')}"}
            merged.append(e)
    if len(set(ranks)) != len(ranks):
        print(f"warning: duplicate rank tags across traces {ranks} — "
              f"rows may overlap", file=sys.stderr)
    return merged


def main_fleet(directory, top_n):
    """--fleet <dir>: merge every chrome trace in the directory (the
    per-rank flight dumps / export_chrome_tracing outputs a shared
    telemetry dir accumulates), write <dir>/fleet_merged.trace.json,
    and print the per-track summary over the merged timeline."""
    paths = sorted(
        p for p in glob.glob(os.path.join(directory, "*.json"))
        if not p.endswith("fleet_merged.trace.json"))
    loaded = {}
    for p in paths:
        try:
            loaded[p] = _load_chrome_events(p)
        except (SystemExit, ValueError, json.JSONDecodeError):
            continue
    if not loaded:
        raise SystemExit(f"no chrome traces (*.json) in {directory}")
    merged = merge_fleet_traces(sorted(loaded), events_by_path=loaded)
    out_path = os.path.join(directory, "fleet_merged.trace.json")
    with open(out_path, "w") as f:
        json.dump({"traceEvents": merged, "displayTimeUnit": "ms"}, f)
    print(f"== fleet merge: {len(loaded)} rank traces -> {out_path}")
    summarize_chrome_events(merged, top_n)


def _format_error(path, e):
    return SystemExit(
        f"{path}: not a parseable capture ({type(e).__name__}: {e}).\n"
        "Expected one of:\n"
        "  - jax.profiler xplane.pb (XSpace protobuf, device trace)\n"
        "  - merged chrome-trace JSON from "
        "profiler.export_chrome_tracing (traceEvents list)")


def main():
    if len(sys.argv) < 2:
        raise SystemExit(__doc__)
    if sys.argv[1] == "--fleet":
        if len(sys.argv) < 3 or not os.path.isdir(sys.argv[2]):
            raise SystemExit("--fleet wants a directory of per-rank "
                             "chrome traces")
        top_n = int(sys.argv[3]) if len(sys.argv) > 3 else 40
        return main_fleet(sys.argv[2], top_n)
    path = sys.argv[1]
    top_n = int(sys.argv[2]) if len(sys.argv) > 2 else 40
    with open(path, "rb") as f:
        head = f.read(64).lstrip()
    if head.startswith(b"{") or head.startswith(b"["):
        try:
            return main_chrome_trace(path, top_n)
        except (SystemExit, BrokenPipeError):
            raise
        except Exception as e:
            raise _format_error(path, e)
    try:
        return main_xplane(path, top_n)
    except (SystemExit, BrokenPipeError):
        raise
    except Exception as e:
        raise _format_error(path, e)


if __name__ == "__main__":
    main()
