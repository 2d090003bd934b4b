"""Median device time of a run of the engine's decode program in the
trace, in milliseconds: the program the engine jits from a function
named `decode_step`, picked by that name.  Beside
`decode_step_ms_p50.serve`, the same step as a request sees it, the
difference is what the host adds between two steps.  Reads nothing
where no program of that name ran (the device's plane is missing, or
the program names none of its programs)."""

import statistics

from benchmarks.trace_reduce import program_name

PROGRAM = "jit_decode_step"


def read(run, name):
    if run.trace is None:
        return None
    runs = [t for k, v in run.trace["module_runs"].items()
            if program_name(k) == PROGRAM for t in v]
    return 1e3 * statistics.median(runs) if runs else None
