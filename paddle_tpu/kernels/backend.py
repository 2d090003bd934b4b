"""Platform test shared by the Pallas kernels and their dispatch.

A kernel compiles through Mosaic on the "tpu" platform and runs in the
Pallas interpreter everywhere else (the CPU test suite).  Nothing here
catches a backend start-up failure: a process that cannot reach its
accelerator must fail, not quietly take the interpreted path.
"""

import jax


def is_tpu_backend():
    return jax.default_backend() == "tpu"


def interpret():
    """The `interpret=` argument of every pallas_call in this package."""
    return not is_tpu_backend()
