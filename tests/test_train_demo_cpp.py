"""C++ train demo build-and-run test (parity model: the reference's
fluid/train/demo — train a model from a native binary)."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_cpp_train_demo_builds_and_converges(tmp_path):
    cfg = shutil.which("python3-config")
    if cfg is None:
        pytest.skip("no python3-config")
    includes = subprocess.check_output([cfg, "--includes"], text=True).split()
    ldflags = subprocess.check_output([cfg, "--embed", "--ldflags"],
                                      text=True).split()
    binary = str(tmp_path / "train_demo")
    subprocess.check_call(
        ["g++", "-O2", os.path.join(REPO, "csrc", "train_demo.cpp"),
         *includes, *ldflags, "-o", binary])
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # force the CPU backend inside the embedded interpreter (the demo
    # must not depend on a chip being free); the in-script jax.config
    # override beats JAX_PLATFORMS
    env["TRAIN_DEMO_PLATFORM"] = "cpu"
    out = subprocess.run([binary], cwd=REPO, env=env, text=True,
                         capture_output=True, timeout=300)
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "train demo OK" in out.stdout
