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

# for tests/test_debugger_profiler.py, which shows the last fixture
# below at work in a pytest run of its own
pytest_plugins = ["pytester"]


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


@pytest.fixture(scope="session", autouse=True)
def _traces_and_dumps_land_in_this_runs_tmp(tmp_path_factory):
    """`start_profiler("All")` writes its device trace under
    `FLAGS_profiler_dir`, the flight recorder its post-mortems under
    `FLAGS_flight_recorder_dir`; here those are this pytest process's
    own directories (under xdist each worker has its own), so no two
    runs, and no two workers, meet in one."""
    from paddle_tpu import flags

    old = flags.get_flags(["FLAGS_profiler_dir", "FLAGS_flight_recorder_dir"])
    flags.set_flags({
        "FLAGS_profiler_dir": str(tmp_path_factory.mktemp("profile")),
        "FLAGS_flight_recorder_dir": str(tmp_path_factory.mktemp("flight"))})
    yield
    flags.set_flags(old)


@pytest.fixture(autouse=True)
def _profiler_session_ends_with_its_test():
    """A profiler session is process-wide: one that outlives its test
    makes the NEXT test's `start_trace` fail, in whatever file the
    worker runs next.  So a leak (a `start_profiler` session, or a bare
    `jax.profiler` trace) is stopped here and fails the test that left
    it, by name."""
    from paddle_tpu import profiler

    yield
    found = []
    if profiler.is_profiling():
        found.append("start_profiler session started at "
                     f"{profiler._active['owner']}")
        profiler.stop_profiler(profile_path=None)
    if jax.profiler.TraceAnnotation.is_enabled():
        found.append("jax.profiler trace")
        jax.profiler.stop_trace()
    if found:
        pytest.fail("left open at the end of the test: " + "; ".join(found),
                    pytrace=False)
