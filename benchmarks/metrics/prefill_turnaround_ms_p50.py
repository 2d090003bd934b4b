"""Median time from the engine's turning to a request to the first
token's time as the engine stamps it (its prefill program launched), in
milliseconds: `turnaround_s` of the program's `engine.prefill_wait`
spans of the traced stretch.  `queue_wait_s` + `turnaround_s` is the
`ttft_s` the engine's stats get.  Reads nothing where the program
records no such spans."""

import statistics

from paddle_tpu import profiler

SPAN, ATTR = "engine.prefill_wait", "turnaround_s"


def read(run, name):
    spans = getattr(profiler, "spans", lambda prefix: [])(SPAN)
    values = [a[ATTR] for _, _, _, a in spans if ATTR in a]
    return 1e3 * statistics.median(values) if values else None
