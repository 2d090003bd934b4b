"""Seconds jax spent in backend compilation during set-up (a persistent
cache hit costs its retrieval).  Layer: start-up."""


def read(run, name):
    return run.at_window["backend_compile_s"]
