"""Median time a request waited in the engine's queue before the engine
turned to it, in milliseconds: `queue_wait_s` of the program's
`engine.prefill_wait` spans of the traced stretch (the engine clock at
admission less the request's `enqueue_t`).  With
`prefill_turnaround_ms_p50.serve` it splits the time to first token.
Reads nothing where the program records no such spans."""

import statistics

from paddle_tpu import profiler

SPAN, ATTR = "engine.prefill_wait", "queue_wait_s"


def read(run, name):
    spans = getattr(profiler, "spans", lambda prefix: [])(SPAN)
    values = [a[ATTR] for _, _, _, a in spans if ATTR in a]
    return 1e3 * statistics.median(values) if values else None
