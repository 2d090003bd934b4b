"""Median device time of a prefill program's run in the trace (all
buckets together).

The engine runs one decode program and one prefill program for each
bucket, and the trace names all of them `jit__unknown` (they are jitted
`functools.partial` objects), so they are told apart by fingerprint: the
decode program is the one with the most device time in the window, the
prefill programs are the others."""

import statistics

from benchmarks.trace_reduce import busiest_program


def read(run, name):
    if run.trace is None:
        return None
    decode = busiest_program(run.trace)
    runs = [t for k, v in run.trace["module_runs"].items()
            if k != decode for t in v]
    return 1e3 * statistics.median(runs) if runs else None
