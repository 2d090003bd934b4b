"""Median host time of a training step, from the previous step's loss
on the host to this one's, over every step of the window."""

import statistics


def read(run, name):
    step_s = run.result.get("step_s")
    if not step_s:
        return None
    return 1e3 * statistics.median(step_s)
