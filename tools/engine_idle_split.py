"""Lay the decode engine's own spans against the device's idle gaps.

Runs one traced run of a serving cell of BENCHMARK.json in this process
(`benchmarks.run`, unchanged), reads the run's xplane file before the
benchmark removes it, and prints, for each `paddle_tpu:engine.*` phase:
how often it ran, the host time it took, and how much of the device's
idle time (the gaps between the device's ops inside the benchmark's
window) lay under it.  Both are events of one trace, so they share a
clock.  What the benchmark folds into the one label `engine_step`
(`breakdown.idle_gaps`) is split by phase here.  The `*_wait` phases
are those in which the host is blocked: `engine.decode_wait` and
`engine.prefill_wait` on the device's answer, `engine.listen_wait` (the
loop thread, since ISSUE 32) on the submit condition while the device
works; idle time under the others is the device waiting for the host.
Beside the table stands the engine's `lookahead` counter at the
window's close (`DecodeStats.summary()["decode"]["lookahead"]`): decode
steps, those enqueued while the step before them was unanswered, and
admissions in time and late; and, where the model's decode kernel walks
its cache in tiles (`cfg.cache_walk`: `latent_tiles` of `latent_grid`
for `models/kimi_k2.py`, `full_tiles` of `full_grid` and `window_tiles`
of `window_grid` for `models/afmoe.py`), the tiles walked of the
rectangle's, summed over the run's decode steps; and the means of the
counters each kind of `*_wait` span carries where the model gives them
(`COUNTERS`: a state's `state_bytes` and a prefill's `chunks`, the
cached positions read, the experts' load and kept rows).

Also printed: the device's idle time under `host.gc` (the program's
span for a garbage collection, on whatever thread it ran; since ISSUE
38), beside the phases and not among them; the spread of `start_ns -
pc_ns` over the spans (how well `profiler.trace_clock_offset_ns` ties
`perf_counter_ns` to the trace's clock); `engine_host_ms_per_step.serve`
x decode steps over the seconds of `engine_step` in the same run's
`idle_gaps`; and the clock check of the engine's own device times
(ISSUE 38) against the trace's programs, over the flights answered in
the window: the median `device_s` of the decode steps against the
median `jit_decode_step` run, the summed `device_s` of the prefills
against the `jit_prefill_b*` runs they answered, and the percentiles of
the decode steps' `behind_s`.

    python tools/engine_idle_split.py [--workload W] [--seed N]
        [--seconds S] [--rehearse] [--out FILE.json]

Needs the chip, as the benchmark does; `--rehearse` runs the cell's tiny
sizes on the CPU, where the trace has no device plane and only the
spans' own table is printed.
"""

import argparse
import bisect
import contextlib
import io
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks import trace_reduce  # noqa: E402

STEP = "engine.step"
GC = "host.gc"


def read_trace(path):
    """(program spans [(name, start, end)] without their prefix, clock
    offsets `start_ns - pc_ns`, device op intervals of the first chip,
    the benchmark's window or None, the runs of each program on that
    chip by name: [(start, end)]), all on the trace's clock in ns."""
    from jax.profiler import ProfileData

    from paddle_tpu import profiler

    spans, offsets, ops, window, programs = [], [], [], None, {}
    chips = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace_reduce.WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(profiler.TRACE_PREFIX):
                        spans.append((e.name[len(profiler.TRACE_PREFIX):],
                                      e.start_ns,
                                      e.start_ns + e.duration_ns))
                        offsets.append(
                            profiler.trace_clock_offset_ns([e]))
        elif trace_reduce.DEVICE_PLANE.match(plane.name):
            chips.append(plane)
    if chips:
        first = min(chips, key=lambda p: int(
            trace_reduce.DEVICE_PLANE.match(p.name).group(1)))
        for line in first.lines:
            if line.name == trace_reduce.OPS_LINE:
                ops = [(e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
            elif line.name == trace_reduce.MODULES_LINE:
                for e in line.events:
                    programs.setdefault(
                        trace_reduce.program_name(e.name), []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return (spans, [o for o in offsets if o is not None], ops, window,
            programs)


def idle_under(span, gaps, starts):
    """Length of `gaps` (sorted, disjoint; `starts` their starts) that
    lies inside `span`."""
    s, e = span
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    total = 0.0
    while i < len(gaps) and gaps[i][0] < e:
        total += max(0.0, min(e, gaps[i][1]) - max(s, gaps[i][0]))
        i += 1
    return total


def idle_gaps(ops, window):
    """The device's idle gaps inside `window` (sorted, disjoint)."""
    lo, hi = window
    clipped = [(max(s, lo), min(e, hi)) for s, e in ops
               if min(e, hi) > max(s, lo)]
    _, gaps = trace_reduce.busy_union(clipped)
    if clipped:
        gaps = [(lo, min(s for s, _ in clipped))] + gaps + \
               [(max(e for _, e in clipped), hi)]
    else:
        gaps = [(lo, hi)]
    return [g for g in gaps if g[1] > g[0]]


def split(spans, ops, window):
    """Rows [phase, count, host seconds, idle seconds under it], the
    device's idle seconds in the window, and the part of them under no
    `engine.step` at all."""
    gaps = idle_gaps(ops, window)
    idle = sum(e - s for s, e in gaps)
    starts = [g[0] for g in gaps]

    # a phase of an iteration begun before the session has no step span
    # around it: its share goes to "outside any engine.step"
    whole = [(s, e) for name, s, e in spans if name == STEP]
    rows = {}
    for name, s, e in spans:
        if name != STEP and not any(lo <= s and e <= hi for lo, hi in whole):
            continue
        row = rows.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += e - s
        row[2] += idle_under((s, e), gaps, starts)
    steps = rows.pop(STEP, [0, 0.0, 0.0])
    phases = sorted(rows.items(), key=lambda kv: -kv[1][2])
    # an iteration's time outside its phases: loop control, the breaker
    glue = [STEP + " outside its phases", steps[0],
            steps[1] - sum(r[1] for _, r in phases),
            steps[2] - sum(r[2] for _, r in phases)]
    table = [[n, c, h / 1e9, i / 1e9] for n, (c, h, i) in phases]
    table.append([glue[0], glue[1], glue[2] / 1e9, glue[3] / 1e9])
    return table, idle / 1e9, (idle - steps[2]) / 1e9


def idle_under_all(spans, ops, window):
    """(count, host seconds, the device's idle seconds under them) of
    `spans` [(start, end)], which do not overlap one another."""
    gaps = idle_gaps(ops, window)
    starts = [g[0] for g in gaps]
    return (len(spans), sum(e - s for s, e in spans) / 1e9,
            sum(idle_under(s, gaps, starts) for s in spans) / 1e9)


def _ms(values, q):
    from paddle_tpu.serving.stats import exact_percentile

    return 1e3 * exact_percentile(sorted(values), q)


def clock_check(waits, programs, offset, window):
    """The engine's own device times against the trace's programs.
    `waits`: the session's `engine.prefill_wait` / `engine.decode_wait`
    spans from `profiler.spans` (perf_counter_ns, attributes and all),
    laid on the trace's clock by `offset`; those that end in `window`
    count.  Decode: the median `device_s` against the median
    `jit_decode_step` run in the window.  Prefill: each `jit_prefill_b*`
    run inside the window is paired with the first prefill answered
    after it ended (the queue is FIFO and an answer follows its run),
    and the paired flights' `device_s` summed against the runs'.  And
    the decode steps' `behind_s` percentiles, ms, beside how many steps
    waited behind a prefill and how many prefills that was (`behind`)."""
    lo, hi = window
    inside = [(n, s + offset, e + offset, a) for n, s, e, a in waits
              if "device_s" in a and lo <= e + offset <= hi]
    steps = [a for n, _, _, a in inside if n == "engine.decode_wait"]
    fills = sorted((e, a["device_s"]) for n, _, e, a in inside
                   if n == "engine.prefill_wait")
    step_runs = [(e - s) / 1e9 for s, e in programs.get("jit_decode_step", [])
                 if lo <= s and e <= hi]
    fill_runs = sorted((e, (e - s) / 1e9) for name, runs in programs.items()
                       if name.startswith("jit_prefill_b")
                       for s, e in runs if lo <= s and e <= hi)
    out = {}
    if steps and step_runs:
        mine = 1e3 * statistics.median(a["device_s"] for a in steps)
        trace = 1e3 * statistics.median(step_runs)
        out["decode"] = {"flights": len(steps), "runs": len(step_runs),
                         "device_s_p50_ms": mine, "run_p50_ms": trace,
                         "ratio": mine / trace}
        out["behind_ms"] = {f"p{int(q * 100)}": _ms(
            [a["behind_s"] for a in steps], q) for q in (0.5, 0.9, 0.99)}
        out["behind_ms"]["max"] = 1e3 * max(a["behind_s"] for a in steps)
        out["behind"] = {"steps": sum(a["behind"] > 0 for a in steps),
                         "prefills": sum(a["behind"] for a in steps),
                         "max": max(a["behind"] for a in steps)}
    paired, i = [], 0
    for end, run_s in fill_runs:
        while i < len(fills) and fills[i][0] < end:
            i += 1
        if i == len(fills):
            break
        paired.append((fills[i][1], run_s))
        i += 1
    if paired:
        mine = sum(p for p, _ in paired)
        trace = sum(r for _, r in paired)
        out["prefill"] = {"paired": len(paired), "runs": len(fill_runs),
                          "device_s_sum": mine, "run_sum_s": trace,
                          "ratio": mine / trace}
    return out


def cache_walk(cache):
    """{name: (tiles walked, tiles of the rectangle)} of the counts a
    model's `cache_walk` left in the summary's `cache`."""
    names = [k.removesuffix("_tiles") for k in cache if k.endswith("_tiles")]
    return {n: (cache[n + "_tiles"], cache[n + "_grid"]) for n in names
            if n + "_grid" in cache}


# the counters a model's programs leave on the `*_wait` spans (serving/
# decode.py, "The seam"): a state's traffic and its prefill's chunks, the
# cached positions read, the experts' load and the kept rows
COUNTERS = ("state_bytes", "chunks", "live_full", "live_window",
            "expert_tokens", "expert_load_max", "expert_layers_kept",
            "expert_layers")


def span_counters(waits):
    """{span name: {"spans": n, counter: mean over the spans that carry
    it}} of the traced `*_wait` spans."""
    out = {}
    for name in sorted({w[0] for w in waits}):
        mine = [w[3] for w in waits if w[0] == name]
        row = {"spans": len(mine)}
        for key in COUNTERS:
            vals = [a[key] for a in mine if key in a]
            if vals:
                row[key] = sum(vals) / len(vals)
        out[name] = row
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="gpt2-medium.serve-closed-c64")
    ap.add_argument("--seed", type=int, default=2700000001)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None, help="also write the numbers "
                    "to this JSON file")
    args = ap.parse_args(argv)

    from paddle_tpu.serving import DecodeEngine

    from paddle_tpu import profiler

    captured = {}
    load = trace_reduce.load_xplane
    summary = DecodeEngine.summary

    def load_and_keep(path, *a, **kw):
        captured["trace"] = read_trace(path)
        return load(path, *a, **kw)

    def summary_and_keep(engine):
        out = summary(engine)
        captured["lookahead"] = out["decode"].get("lookahead")
        captured["walk"] = cache_walk(out["decode"].get("cache", {}))
        captured["device"] = out["decode"].get("device")
        return out

    trace_reduce.load_xplane = load_and_keep
    DecodeEngine.summary = summary_and_keep
    cmd = ["--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", "1"]
    if args.rehearse:
        cmd.append("--rehearse")
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            code = bench_run.main(cmd)
    finally:
        trace_reduce.load_xplane = load
        DecodeEngine.summary = summary
    if code or "trace" not in captured:
        print(out.getvalue(), file=sys.stderr)
        return code or 1
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    spans, offsets, ops, window, programs = captured["trace"]
    engine = [s for s in spans if s[0].startswith("engine.")]
    # the store's spans of the same session, with what is known only
    # once an answer is in (`device_s`, `behind_s`)
    waits = [s for s in profiler.spans("engine.")
             if s[0] in ("engine.prefill_wait", "engine.decode_wait")]

    report = {"workload": args.workload, "seed": args.seed,
              "device": line["device"], "metrics": line["metrics"],
              "breakdown": line.get("breakdown", {}),
              "programs": {n: len(r) for n, r in programs.items()},
              "spans_in_trace": len(spans),
              "lookahead": captured.get("lookahead"),
              "cache_walk": captured.get("walk", {}),
              "summary_device": captured.get("device")}
    report["span_counters"] = span_counters(waits)
    for name, row in report["span_counters"].items():
        print(f"{name}: " + ", ".join(
            f"{k} {v:.6g}" + (" (mean)" if k != "spans" else "")
            for k, v in row.items()))
    for name, (tiles, grid) in report["cache_walk"].items():
        print(f"cache walk: {name}_tiles {tiles} of {name}_grid {grid} "
              f"({100 * tiles / max(grid, 1):.2f}%), one layer, all decode "
              f"steps")
    look = report["lookahead"]
    if look:
        print(f"lookahead: {look['ahead']} of {look['steps']} decode steps "
              f"enqueued ahead ({100 * look['ahead'] / max(look['steps'], 1):.2f}%); "
              f"admissions {look['in_time']} in time, {look['late']} late "
              f"({100 * look['late'] / max(look['in_time'] + look['late'], 1):.2f}% late)")
    if programs:
        print("programs run (XLA Modules): " + ", ".join(
            f"{n} x{c}" for n, c in sorted(report["programs"].items())))
    if report["summary_device"]:
        print(f"summary device (whole run): {report['summary_device']}")
    if offsets:
        report["clock_offset_ns"] = {
            "spans": len(offsets), "min": min(offsets),
            "median": statistics.median(offsets),
            "max": max(offsets), "spread_ns": max(offsets) - min(offsets)}
        print(f"clock: start_ns - pc_ns over {len(offsets)} spans spreads "
              f"by {(max(offsets) - min(offsets)) / 1e3:.1f} us")
    if ops and window:
        table, idle_s, outside_s = split(engine, ops, window)
        report.update(idle_s=idle_s, idle_outside_engine_step_s=outside_s,
                      window_s=(window[1] - window[0]) / 1e9, phases=table)
        print(f"window {report['window_s']:.3f} s, device idle "
              f"{idle_s:.4f} s, of it outside any engine.step "
              f"{outside_s:.4f} s")
        print(f"{'phase':<34}{'count':>7}{'host s':>10}{'idle s':>10}"
              f"{'idle %':>8}")
        for name, count, host_s, under_s in table:
            print(f"{name:<34}{count:>7}{host_s:>10.4f}{under_s:>10.4f}"
                  f"{100 * under_s / idle_s if idle_s else 0.0:>8.1f}")
        blocked = sum(i for n, _, _, i in table if n.endswith("_wait"))
        report["idle_under_waits_s"] = blocked
        print(f"idle under the *_wait phases (host blocked) {blocked:.4f} s, "
              f"under the host's own work "
              f"{idle_s - outside_s - blocked:.4f} s")
        count, host_s, under_s = idle_under_all(
            [(s, e) for n, s, e in spans if n == GC], ops, window)
        report["host_gc"] = {"count": count, "host_s": host_s,
                             "idle_s": under_s}
        print(f"{GC + ' (any thread)':<34}{count:>7}{host_s:>10.4f}"
              f"{under_s:>10.4f}"
              f"{100 * under_s / idle_s if idle_s else 0.0:>8.1f}")
        if offsets:
            check = clock_check(waits, programs, statistics.median(offsets),
                                window)
            report["clock_check"] = check
            if "decode" in check:
                d = check["decode"]
                print(f"clock check, decode: median device_s "
                      f"{d['device_s_p50_ms']:.3f} ms over {d['flights']} "
                      f"flights, median jit_decode_step "
                      f"{d['run_p50_ms']:.3f} ms over {d['runs']} runs: "
                      f"{100 * (d['ratio'] - 1):+.2f}%")
                b = check["behind"]
                print("behind_s of the decode steps, ms: " + ", ".join(
                    f"{k} {v:.3f}" for k, v in check["behind_ms"].items())
                      + f"; {b['steps']} of {d['flights']} steps behind "
                      f"{b['prefills']} prefills, at most {b['max']} at once")
            if "prefill" in check:
                p = check["prefill"]
                print(f"clock check, prefill: {p['paired']} of {p['runs']} "
                      f"jit_prefill_b* runs paired, device_s "
                      f"{p['device_s_sum']:.4f} s against their "
                      f"{p['run_sum_s']:.4f} s: "
                      f"{100 * (p['ratio'] - 1):+.2f}%")
        host = line["metrics"].get("engine_host_ms_per_step.serve")
        waits = sum(c for n, c, _, _ in table if n == "engine.decode_wait")
        labelled = dict(map(tuple, report["breakdown"].get(
            "idle_gaps", []))).get("engine_step")
        if host and labelled:
            report["host_over_engine_step_gap"] = \
                host["value"] / 1e3 * waits / labelled
            print(f"engine_host_ms_per_step.serve {host['value']:.3f} ms x "
                  f"{waits} decode steps / idle_gaps engine_step "
                  f"{labelled:.4f} s = "
                  f"{report['host_over_engine_step_gap']:.2f}")
    else:
        print("no device plane or window in the trace: spans only")
        for name in sorted({s[0] for s in spans}):
            durs = [e - s for n, s, e in spans if n == name]
            print(f"{name:<34}{len(durs):>7}{sum(durs) / 1e9:>10.4f}")
    print(json.dumps(line["metrics"]))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
