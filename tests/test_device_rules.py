"""The start-up rules of ISSUE 22: strict device detection, a peak
table that knows the chip and guesses nothing, one compile-cache rule."""

import inspect
import os
import types

import jax
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache
from paddle_tpu.kernels import backend
from paddle_tpu.monitor import peak_flops


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_peak_flops_knows_the_v5e():
    assert peak_flops(_device("tpu", "TPU v5 lite")) == 197e12


def test_peak_flops_raises_on_an_unknown_accelerator():
    with pytest.raises(KeyError, match="TPU v9"):
        peak_flops(_device("tpu", "TPU v9"))
    with pytest.raises(KeyError):
        peak_flops(_device("gpu", "NVIDIA H100"))


def test_a_cpu_has_no_peak_and_no_mfu():
    assert peak_flops(_device("cpu", "cpu")) is None
    assert peak_flops() is None                  # this host
    ledger = fluid.monitor.CompileLedger(fluid.monitor.MetricsRegistry())
    ledger.record("step", 0.1, flops=1e9)
    assert ledger.mfu(0.01) is None
    assert ledger.mfu(0.01, peak=1e12) == pytest.approx(0.1)


def test_is_tpu_backend_is_the_platform_and_catches_nothing():
    assert backend.is_tpu_backend() is False
    assert backend.interpret() is True
    src = inspect.getsource(backend)
    assert "try:" not in src and "except" not in src
    assert "device_kind" not in src


def test_tpu_place_does_not_resolve_to_a_cpu_device():
    with pytest.raises(RuntimeError):
        fluid.TPUPlace(0).jax_device()
    assert fluid.CPUPlace().jax_device().platform == "cpu"


def test_cache_rule_leaves_a_set_directory_alone():
    env = {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    assert compile_cache.install(env) is env
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/else"
    assert compile_cache.cache_dir(env) == "/somewhere/else"


def test_cache_rule_defaults_to_the_checkout():
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = compile_cache.install({})
    assert env["JAX_COMPILATION_CACHE_DIR"] == os.path.join(
        checkout, ".jax_cache")
    assert compile_cache.cache_dir({}) == env["JAX_COMPILATION_CACHE_DIR"]


def test_importing_the_package_applied_the_rule():
    assert jax.config.jax_compilation_cache_dir == compile_cache.cache_dir()
