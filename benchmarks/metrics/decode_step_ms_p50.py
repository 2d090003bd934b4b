"""Median of all the gaps between a request's consecutive tokens that
the benchmark recorded in the window: the decode step as a request sees
it, beside the tail that `itl_p99_ms` judges."""

import statistics


def read(run, name):
    gaps = run.result.get("gaps")
    if not gaps:
        return None
    return 1e3 * statistics.median(gaps)
