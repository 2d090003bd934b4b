"""From a profiler trace to numbers: the benchmark's one reduction.

A trace is handled as plain data, `{plane: {line: [[name, start_ns,
dur_ns], ...]}}`, so that the arithmetic below can be checked on a small
recorded trace (`testdata/`) without a TPU library: `load_xplane` is the
only function that touches jax, and imports it when called.

What a v5e trace from this tree holds (looked at by hand, PR 26): one
plane `/device:TPU:<n>` per chip with the lines `XLA Ops` (one event per
HLO instruction run, named by its HLO text), `XLA Modules` (one event
per program run) and `Steps`; and `/host:CPU` with one line per host
thread, where `jax.profiler.TraceAnnotation` spans appear under their
own names on the same clock as the device events.
"""

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_CONTAINERS = ("while", "conditional", "call")  # their bodies' ops are listed too
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def load_xplane(path, name_chars=400):
    """Read an `.xplane.pb` file into the plain form.  Keeps the device
    planes' op and module lines and the host's `bench:` spans; names are
    cut to `name_chars` (an HLO text can run to kilobytes)."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            keep = {OPS_LINE, MODULES_LINE}
        elif plane.name == HOST_PLANE:
            keep = None
        else:
            continue
        lines = {}
        for line in plane.lines:
            if keep is not None and line.name not in keep:
                continue
            events = [[_cut(e.name, name_chars), float(e.start_ns),
                       float(e.duration_ns)] for e in line.events
                      if keep is not None or e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            out[plane.name] = lines
    return out


def _cut(name, chars):
    """An HLO text cut to `chars`; a Mosaic call keeps its mark."""
    if len(name) <= chars:
        return name
    mark = " ... " + MOSAIC_TARGET if MOSAIC_TARGET in name else " ..."
    return name[:chars] + mark


def busy_union(intervals):
    """Total length of the union of `(start, end)` intervals, and the
    gaps between its pieces as `(start, end)`."""
    busy, gaps, cur_s, cur_e = 0.0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def op_kind(name):
    """`%fusion.580 = s32[...] fusion(...)` -> `fusion`."""
    head = name.split(" = ", 1)[0].lstrip("%")
    stem, _, num = head.rpartition(".")
    return stem if stem and num.isdigit() else head


def op_shape(name):
    """The result's type of an HLO text, layout dropped: `bf16[8,1024]`."""
    m = re.search(r" = \(?([a-z0-9]+\[[0-9,]*\])", name)
    return m.group(1) if m else ""


def is_mosaic(name):
    return MOSAIC_TARGET in name


def is_collective(name):
    return op_kind(name).startswith(_COLLECTIVES)


def _clip(events, lo, hi):
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def window_of(trace):
    """(start_ns, end_ns) of the host's `bench:window` span; without
    one, the extent of the device events."""
    for events in trace.get(HOST_PLANE, {}).values():
        for name, start, dur in events:
            if name == WINDOW_SPAN:
                return start, start + dur
    starts, ends = [], []
    for plane, lines in trace.items():
        if DEVICE_PLANE.match(plane):
            for name, start, dur in lines.get(OPS_LINE, []):
                starts.append(start)
                ends.append(start + dur)
    if not starts:
        raise ValueError("trace has neither a window span nor device ops")
    return min(starts), max(ends)


def host_spans(trace):
    """The benchmark's own spans (window excluded): [(name, start, end)]."""
    out = []
    for events in trace.get(HOST_PLANE, {}).values():
        for name, start, dur in events:
            if name.startswith(SPAN_PREFIX) and name != WINDOW_SPAN:
                out.append((name[len(SPAN_PREFIX):], start, start + dur))
    return out


def _label_gap(gap, spans, modules):
    """What the host was doing in an idle gap: the shortest of the
    benchmark's spans that covers its middle; else the program that ran
    next (the host was on its way to launching it)."""
    mid = (gap[0] + gap[1]) / 2
    covering = [(e - s, n) for n, s, e in spans if s <= mid <= e]
    if covering:
        return min(covering)[1]
    later = [(s, n) for n, s, _ in modules if s >= gap[1] - 1]
    if later:
        return "before:" + program_name(min(later)[1])
    return "no_span"


def program_name(module):
    """`jit_step(3989209314445633605)` -> `jit_step`: the event of a
    program run carries the program's fingerprint, which tells apart
    programs that share a name."""
    return re.sub(r"\(.*\)$", "", module)


def _module_at(modules, t):
    """The program run (name with fingerprint) that covers time `t`."""
    for name, s, e in modules:
        if s <= t < e:
            return name
    return ""


def busiest_program(reduced):
    """The program (name with fingerprint) with the most device time in
    the window; None where no program ran."""
    runs = reduced["module_runs"]
    return max(runs, key=lambda name: sum(runs[name])) if runs else None


def reduce_trace(trace, top=10):
    """All the benchmark reads from a trace, times in seconds.

    busy_s, idle_share: union of op intervals inside the window, mean
    over chips.  Of the first chip: op sums by kind, Mosaic and
    collective sums, the runs of each program (by name with
    fingerprint), idle gaps summed by label."""
    lo, hi = window_of(trace)
    window_s = (hi - lo) / 1e9
    chips = sorted((int(DEVICE_PLANE.match(p).group(1)), p)
                   for p in trace if DEVICE_PLANE.match(p))
    if not chips:
        raise ValueError("trace has no device plane")
    busy_by_chip, first = [], None
    for _, plane in chips:
        ops = list(_clip(trace[plane].get(OPS_LINE, []), lo, hi))
        busy, gaps = busy_union([(s, e) for _, s, e in ops])
        if ops:
            gaps = [(lo, min(s for _, s, _ in ops))] + gaps + \
                   [(max(e for _, _, e in ops), hi)]
        busy_by_chip.append(busy / 1e9)
        if first is None:
            first = (plane, ops, gaps)
    plane, ops, gaps = first
    modules = list(_clip(trace[plane].get(MODULES_LINE, []), lo, hi))

    kinds = {}
    for name, s, e in ops:
        if op_kind(name) in _CONTAINERS:
            continue
        k = kinds.setdefault(op_kind(name), [0.0, 0, 0.0, ""])
        k[0] += e - s
        k[1] += 1
        if e - s > k[2]:
            k[2], k[3] = e - s, name
    device_ops = []
    for kind, (total, count, _, biggest) in sorted(
            kinds.items(), key=lambda kv: -kv[1][0])[:top]:
        label = f"{kind} x{count} largest {op_shape(biggest)}"
        if is_mosaic(biggest):
            label += " mosaic"
        device_ops.append([label, total / 1e9])

    spans = host_spans(trace)
    by_label = {}
    for gap in gaps:
        if gap[1] > gap[0]:
            label = _label_gap(gap, spans, modules)
            by_label[label] = by_label.get(label, 0.0) + (gap[1] - gap[0])
    idle_gaps = [[k, v / 1e9] for k, v in sorted(
        by_label.items(), key=lambda kv: -kv[1])[:top]]

    module_runs = {}
    for name, s, e in modules:
        module_runs.setdefault(name, []).append((e - s) / 1e9)
    mosaic = [(_module_at(modules, s), (e - s) / 1e9)
              for name, s, e in ops if is_mosaic(name)]
    busy_s = sum(busy_by_chip) / len(busy_by_chip)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "chips": len(chips),
        "device_ops": device_ops,
        "idle_gaps": idle_gaps,
        "module_runs": module_runs,
        "mosaic_s": sum(t for _, t in mosaic),
        "mosaic_calls": mosaic,       # (program run it lies in, seconds)
        "collective_s": sum((e - s) / 1e9 for name, s, e in ops
                            if is_collective(name)),
    }
