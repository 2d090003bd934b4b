"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): CPUPlace is the
simulator backend for all op logic, and a forced host-device count stands
in for the multi-process localhost cluster of test_dist_base.py.

The platform is pinned through jax.config so the suite runs on the CPU
whatever JAX_PLATFORMS says (the driver sets it to cpu as well).
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")


import zlib

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_global_numpy_rng(request):
    """Deterministic per-test global numpy seed.

    Many op tests draw via the legacy np.random.* global stream; without
    this, their draws depend on how much earlier tests consumed, so a
    new test file can surface a tolerance flake in an unrelated one
    (this happened: margin_rank_loss, f32-vs-f64 at rtol 1e-6).  Seeding
    per nodeid makes every test's data identical regardless of which
    subset or order runs."""
    np.random.seed(zlib.crc32(request.node.nodeid.encode()) & 0x7FFFFFFF)
