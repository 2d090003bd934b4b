"""Seconds jax spent tracing and lowering during set-up; no cache saves
these.  Layer: start-up."""


def read(run, name):
    return run.at_window["trace_and_lower_s"]
