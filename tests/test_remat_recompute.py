"""Recompute (remat) regression tests — the UnexpectedTracerError class.

BENCH_r05's resnet50_sweep recorded every remat config dying with
`UnexpectedTracerError: ... A function transformed by JAX had a side
effect` (sha 596d705): jax.checkpoint wrapped a stateful model call, so
the backward-pass recompute trace touched tracers owned by the outer
trace.  The fix keeps the checkpointed callable pure IN ITS ARGUMENTS —
make_train_step passes params, buffers, rng, and the batch explicitly —
and these tests pin that property on the CPU mesh:

- a recompute-wrapped ResNet block trains under jit (fwd+bwd) without a
  tracer leak, inside a jit(scan(donate)) harness;
- gradients match the unrecomputed path (remat changes scheduling, not
  math);
- the bf16 / NHWC / ghost-BN-stats combination of the on-chip sweep
  executes end to end.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401  — op registry + jax compat
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.parameter import seed as param_seed


def _make(remat, dtype="float32", data_format="NCHW", bn_stats_sample=0,
          depth="18"):
    from paddle_tpu.models.resnet import resnet18, resnet50
    from paddle_tpu.models.train import init_train_state, make_train_step
    from paddle_tpu.optimizer.functional import Momentum

    param_seed(5)
    fn = resnet18 if depth == "18" else resnet50
    model = fn(num_classes=10, dtype=dtype, data_format=data_format,
               bn_stats_sample=bn_stats_sample)
    opt = Momentum(0.01, 0.9)
    state = init_train_state(model, opt, rng_seed=0)

    def loss_fn(m, x, y):
        return F.cross_entropy(m(x), y).mean()

    step = make_train_step(model, opt, loss_fn=loss_fn, jit=False,
                           remat=remat)
    return model, state, step


# remat and plain are two XLA schedules of ONE float32 program, so they
# differ only by reduction order: a reduction of n terms moves by about
# sqrt(n) eps when reordered, ResNet-18 stacks ~20 conv/BN layers of
# >= 3*3*64 = 576-term reductions, and the errors add: 5.7e-5 of the
# result's scale for one pass through the net.  The installed XLA lands
# at 1.05e-5 on the loss (one pass) and 1.2e-4 on a parameter update
# (forward and backward: two passes), so the bound carries a factor 2.
F32_REORDER_RTOL = 20 * float(np.sqrt(576)) * float(np.finfo(np.float32).eps)


def _assert_same_step(new0, new1, old, passes):
    """Two schedules moved every leaf of `old` to the same place, up to
    float32 reordering over `passes` trips through the net.  Judged on
    the update's norm: a ReLU gate that flips on a last-bit difference
    moves single elements by more than any elementwise bound allows."""
    for n in new0:
        a, b = np.asarray(new0[n]), np.asarray(new1[n])
        moved = np.linalg.norm(a - np.asarray(old[n]))
        assert np.linalg.norm(a - b) <= \
            2 * passes * F32_REORDER_RTOL * moved + 1e-7, n


def _batch(dtype=jnp.float32, batch=4, ch=3, size=16):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, ch, size, size)), dtype)
    y = jnp.asarray(rng.integers(0, 10, (batch,)), jnp.int32)
    return x, y


@pytest.mark.parametrize("remat", [True, "conv_outs"])
def test_remat_grad_parity_with_plain_path(remat):
    """remat must be a scheduling decision only: identical loss, updated
    params, and BN buffers vs the unrecomputed step."""
    x, y = _batch()
    _, state0, step0 = _make(False)
    _, state1, step1 = _make(remat)

    s0, l0 = jax.jit(step0)(state0, x, y)
    s1, l1 = jax.jit(step1)(state1, x, y)
    assert float(l0) == pytest.approx(float(l1), rel=F32_REORDER_RTOL)
    _assert_same_step(s0.params, s1.params, state0.params, passes=2)
    _assert_same_step(s0.buffers, s1.buffers, state0.buffers, passes=1)


def test_remat_inside_scan_with_donation():
    """The bench harness shape that produced the on-chip tracer error:
    jit(donate_argnums=0) around a lax.scan over the remat step."""
    import functools

    x, y = _batch()
    model, state, step = _make(True)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(state, *batch):
        def body(st, _):
            st, loss = step(st, *batch)
            return st, loss
        return jax.lax.scan(body, state, None, length=3)

    st, losses = run(state, x, y)
    assert np.isfinite(float(losses[-1]))
    # run again from the returned state: a leaked tracer would surface
    # as UnexpectedTracerError on re-dispatch
    st2, losses2 = run(st, x, y)
    assert np.isfinite(float(losses2[-1]))
    # the model's OWN buffers must still be concrete arrays (a side
    # effect writing trace-time values onto the layer would leave
    # tracers behind after tracing finished)
    for name, buf in model.named_buffers():
        assert not isinstance(buf, jax.core.Tracer), name


def test_remat_sweep_config_bf16_nhwc_ghost_stats():
    """The exact lever combination of the on-chip resnet50_sweep remat
    rows (bf16 + NHWC + bn_stats_sample) executes fwd+bwd under jit."""
    x, y = _batch(jnp.bfloat16)
    _, state, step = _make(True, dtype="bfloat16", data_format="NHWC",
                           bn_stats_sample=2, depth="50")
    st, loss = jax.jit(step)(state, x, y)
    assert np.isfinite(float(loss.astype(jnp.float32)))


def test_remat_with_accum_steps():
    """Gradient accumulation lax.scans the checkpointed microbatch loss;
    the explicit-args form must hold there too, and match the
    unrecomputed accumulation numerically."""
    from paddle_tpu.models.resnet import resnet18
    from paddle_tpu.models.train import init_train_state, make_train_step
    from paddle_tpu.optimizer.functional import Momentum

    # batch 8 -> microbatch 4: BN stats over 2 samples would be
    # ill-conditioned enough to amplify legal rounding differences
    x, y = _batch(batch=8)

    def build(remat):
        param_seed(5)
        model = resnet18(num_classes=10)
        opt = Momentum(0.01, 0.9)
        state = init_train_state(model, opt, rng_seed=0)

        def loss_fn(m, xb, yb):
            return F.cross_entropy(m(xb), yb).mean()

        step = make_train_step(model, opt, loss_fn=loss_fn, jit=True,
                               donate=False, remat=remat, accum_steps=2)
        return state, step

    state0, step0 = build(False)
    state1, step1 = build(True)
    s0, l0 = step0(state0, x, y)
    s1, l1 = step1(state1, x, y)
    assert float(l0) == pytest.approx(float(l1), rel=F32_REORDER_RTOL)
    # slightly looser than the single-step parity: the accumulation scan
    # reorders the recompute, which legally perturbs fp32 rounding
    for n in s0.params:
        np.testing.assert_allclose(np.asarray(s0.params[n]),
                                   np.asarray(s1.params[n]),
                                   rtol=1e-3, atol=1e-4, err_msg=n)
