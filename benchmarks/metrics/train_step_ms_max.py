"""Longest time between two steps' losses on the host: a stall shows
here that a median hides."""


def read(run, name):
    step_s = run.result.get("step_s")
    if not step_s:
        return None
    return 1e3 * max(step_s)
