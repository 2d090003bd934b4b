"""Host time the decode engine spends on one iteration of its loop, in
milliseconds: the program's own `engine.*` spans of the traced stretch
(`paddle_tpu.profiler.spans`) that are not `*_wait` (sweep, admit, each
prefill's host side and book-keeping, the decode step's host side, the
walk over the slots, telemetry), summed over the iterations that ran a
decode step to its end, over the number of those iterations.  The
`*_wait` spans, in which the host is blocked on the device's answer, are
the device's time and left out.  Reads nothing where the program records
no such spans."""

from paddle_tpu import profiler

STEP, LAST = "engine.step", "engine.emit"


def read(run, name):
    spans = getattr(profiler, "spans", lambda prefix: [])("engine.")
    steps = [(s, e) for n, s, e, _ in spans if n == STEP]
    phases = [(n, s, e) for n, s, e, _ in spans if n != STEP]
    host_ns, whole = 0, 0
    for lo, hi in steps:
        # an iteration cut short by the session's end lacks its last
        # phases; one begun before the session has no step span at all
        inside = [(n, e - s) for n, s, e in phases if lo <= s and e <= hi]
        if any(n == LAST for n, _ in inside):
            whole += 1
            host_ns += sum(d for n, d in inside if not n.endswith("_wait"))
    return host_ns / whole / 1e6 if whole else None
