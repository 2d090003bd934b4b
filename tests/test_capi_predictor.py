"""C inference API test (parity: inference/capi + the reference's
capi tests): build the standalone C predictor, point it at a model saved
by fluid.io.save_inference_model, and check the C-side prediction equals
the Python-side one."""

import os
import re
import shutil
import subprocess

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_capi_predictor_matches_python(tmp_path):
    import paddle_tpu as fluid

    # save a tiny inference model with a deterministic weight
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [None, 4])
        w = fluid.layers.create_parameter([4, 3], "float32", name="capi_w")
        out = fluid.layers.mul(x, w)
    exe = fluid.Executor()
    exe.run(startup)
    fluid.global_scope().set_var(
        "capi_w", np.arange(12, dtype=np.float32).reshape(4, 3))
    model_dir = str(tmp_path / "model")
    fluid.io.save_inference_model(model_dir, ["x"], [out], exe,
                                  main_program=main)

    feed = np.ones((1, 4), np.float32)
    expect = exe.run(main, feed={"x": feed}, fetch_list=[out])[0]

    # build the standalone C binary (PD_CAPI_DEMO_MAIN main included)
    binary = str(tmp_path / "capi_demo")
    includes = subprocess.run(
        ["python3-config", "--includes"], capture_output=True,
        text=True).stdout.split()
    ldflags = subprocess.run(
        ["python3-config", "--embed", "--ldflags"], capture_output=True,
        text=True).stdout.split()
    subprocess.run(
        ["g++", "-O1", "-DPD_CAPI_DEMO_MAIN",
         os.path.join(REPO, "csrc", "predictor_capi.cpp")]
        + includes + ldflags + ["-o", binary],
        check=True, cwd=REPO)

    env = dict(os.environ)
    env["PADDLE_TPU_ROOT"] = REPO
    env["PD_DEMO_FEED_DIM"] = "4"
    # a chip belongs to one process, and this test is one; serve on CPU
    env["PADDLE_TPU_CAPI_PLATFORM"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + os.environ.get("PYTHONPATH", "")
    r = subprocess.run([binary, model_dir], capture_output=True, text=True,
                       env=env, cwd=REPO, timeout=600)
    assert r.returncode == 0, (r.stdout, r.stderr)
    m = re.search(r"out\[0\] dims=(\d+) first=([-\d.]+)", r.stdout)
    assert m, r.stdout
    assert int(m.group(1)) == expect.ndim
    np.testing.assert_allclose(float(m.group(2)), expect.reshape(-1)[0],
                               rtol=1e-5)
