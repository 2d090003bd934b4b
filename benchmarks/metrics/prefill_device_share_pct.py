"""Share of the device's program time that went to prefills, in
percent: over the program runs of the traced stretch (the trace's
`module_runs`), the time of the runs of the decode engine's prefill
programs (jitted as `prefill_b<bucket>`, so named `jit_prefill_b...`)
over that of all runs.  Reads nothing where the run has no device trace
(untraced, or a rehearsal off the chip) or no program ran in it."""

from benchmarks.trace_reduce import program_name

PREFIX = "jit_prefill_b"


def read(run, name):
    if run.trace is None:
        return None
    runs = run.trace["module_runs"]
    total = sum(sum(v) for v in runs.values())
    if not total:
        return None
    prefill = sum(sum(v) for k, v in runs.items()
                  if program_name(k).startswith(PREFIX))
    return 100.0 * prefill / total
