"""Measure the pipeline bubble: GPipe vs interleaved virtual stages
(VERDICT r3 #9 done-criterion) on the virtual CPU mesh.

Same model (S*V chunks of blocks), same microbatch count — only the
schedule differs.  Reports analytic bubble fractions and measured
fwd+bwd wall-clock; on the serial CPU backend the wall-clock mostly
tracks total COMPUTE (ticks x per-tick work, which is schedule-
invariant), so the structural win is the analytic column — the
wall-clock column mainly confirms the interleaved schedule adds no
overhead.  On real chips the fill ticks are idle hardware and the
analytic fraction IS the wall-clock saving.

Usage: python tools/pipeline_bubble.py [pp] [virtual] [microbatches]
"""

import os
import sys
import time

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def run(pp=2, v=4, m=8, layers=None, reps=5):
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.pipeline import (bubble_fraction,
                                                 build_gpt_pipeline)
    from paddle_tpu.models.gpt import GPT, GPTConfig

    layers = layers or pp * v
    model = GPT(GPTConfig(vocab_size=512, hidden_size=128,
                          num_layers=layers, num_heads=4, max_seq_len=64,
                          dropout=0.0))
    mesh = build_mesh(dp=1, tp=1, pp=pp, sp=1,
                      devices=jax.devices()[:pp])
    r = np.random.default_rng(0)
    x = jnp.asarray(r.integers(0, 512, (m * 2, 64)), jnp.int32)
    y = jnp.asarray(r.integers(0, 512, (m * 2, 64)), jnp.int32)

    out = {}
    for name, kw in (("gpipe", {}), ("interleaved", {"interleave": v})):
        apply_fn, params = build_gpt_pipeline(model, mesh,
                                              num_microbatches=m, **kw)
        step = jax.jit(jax.value_and_grad(apply_fn))
        loss, _ = step(params, x, y)
        jax.block_until_ready(loss)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            loss, grads = step(params, x, y)
            jax.block_until_ready((loss, grads))
            best = min(best, time.perf_counter() - t0)
        out[name] = {"wall_ms": round(best * 1e3, 1),
                     "loss": float(loss)}
    out["gpipe"]["bubble_analytic"] = round(bubble_fraction(pp, m), 4)
    out["interleaved"]["bubble_analytic"] = round(
        bubble_fraction(pp, m, v), 4)
    assert abs(out["gpipe"]["loss"] - out["interleaved"]["loss"]) < 1e-5
    return out


if __name__ == "__main__":
    args = [int(a) for a in sys.argv[1:4]]
    pp = args[0] if len(args) > 0 else 2
    v = args[1] if len(args) > 1 else 4
    m = args[2] if len(args) > 2 else 8
    import json
    print(json.dumps({"pp": pp, "virtual": v, "microbatches": m,
                      **run(pp, v, m)}))
