"""NHWC (channels-last, TPU-native) vs NCHW numeric parity.

The train step's layout lever (README, "Train-step levers") is only
trustworthy if the two layouts compute the same math — this pins forward
AND backward (gradient) parity in fp32 on the CPU mesh at tolerance
<= 1e-3, for both the dygraph model path (models/resnet.py data_format=)
and the static-graph builder path (layers/nn.py conv2d / pool2d /
batch_norm data_format=).

Parity is asserted on outputs, loss, and per-parameter GRADIENTS of one
step — not on params after several optimizer steps: through batch-norm a
1-ulp reduction-order difference between layouts amplifies chaotically
across iterated updates, which would test conditioning, not layout
correctness.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.parameter import seed as param_seed

RTOL = 1e-3


def _assert_close(a, b, name):
    a, b = np.asarray(a), np.asarray(b)
    # relative to the tensor's own magnitude (grads span ~1e-4..1e2
    # across a resnet; a fixed atol would be meaningless for both ends)
    scale = max(float(np.max(np.abs(a))), 1.0)
    np.testing.assert_allclose(a, b, rtol=RTOL, atol=RTOL * scale,
                               err_msg=name)


def _build_model(data_format, depth="18"):
    from paddle_tpu.models.resnet import resnet18, resnet50

    # identical init across layouts: the param draw sequence restarts at
    # the same seed and the weight layout (OIHW) is layout-independent
    param_seed(1234)
    fn = resnet18 if depth == "18" else resnet50
    return fn(num_classes=10, data_format=data_format, dtype="float32")


class _BlockNet:
    """One BottleneckBlock (the ResNet-50 unit) + mean head — deep
    enough to cover the conv/BN/residual plumbing per layout, shallow
    enough that fp32 parity at 1e-3 is a meaningful bound.  (Full
    ResNet-50 at random init is numerically chaotic: same-layout
    jit-vs-eager gradient spread is already ~1e-1, so a layout A/B at
    that depth would measure conditioning, not correctness.)"""

    def __init__(self, data_format, stride, in_ch, ch):
        from paddle_tpu.models.resnet import BottleneckBlock

        param_seed(77)
        self.df = data_format
        self.block = BottleneckBlock(in_ch, ch, stride=stride,
                                     data_format=data_format)

    def __call__(self, x):
        if self.df == "NHWC":
            x = jnp.transpose(x, (0, 2, 3, 1))
        y = self.block(x)
        axes = (2, 3) if self.df == "NCHW" else (1, 2)
        return y.mean(axis=axes)


@pytest.mark.parametrize("stride,in_ch,ch",
                         [(1, 16, 4),    # identity shortcut
                          (1, 8, 4),     # stride-1 projection
                          (2, 16, 4)])   # stride-2 transition
def test_bottleneck_block_fwd_bwd_parity(stride, in_ch, ch):
    from paddle_tpu.nn.layers import buffer_dict, param_dict

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, in_ch, 8, 8)), jnp.float32)

    nets = {df: _BlockNet(df, stride, in_ch, ch)
            for df in ("NCHW", "NHWC")}
    outs, grads = {}, {}
    for df, net in nets.items():
        net.block.train()
        params = param_dict(net.block, trainable_only=True)
        bufs = buffer_dict(net.block)

        @jax.jit
        def f(p, bufs, x, _net=net):
            from paddle_tpu.nn.layers import functional_call_with_state

            def loss_of(pp):
                out, nb = functional_call_with_state(
                    _net.block, pp, bufs,
                    jnp.transpose(x, (0, 2, 3, 1))
                    if _net.df == "NHWC" else x)
                axes = (2, 3) if _net.df == "NCHW" else (1, 2)
                return (out.astype(jnp.float32) ** 2).mean(), \
                    (out.mean(axis=axes), nb)

            (l, (o, nb)), g = jax.value_and_grad(
                loss_of, has_aux=True)(p)
            return l, o, g

        l, o, g = f(params, bufs, x)
        outs[df], grads[df] = np.asarray(o), g
    _assert_close(outs["NCHW"], outs["NHWC"], "block forward")
    for n in grads["NCHW"]:
        _assert_close(grads["NCHW"][n], grads["NHWC"][n], f"grad {n}")


def _loss_and_grads(model, x, y):
    from paddle_tpu.models.train import _loss_with_buffers
    from paddle_tpu.nn.layers import buffer_dict, param_dict

    model.train()
    params = param_dict(model, trainable_only=True)
    bufs = buffer_dict(model)

    def loss_fn(m, xb, yb):
        return F.cross_entropy(m(xb), yb).mean()

    @jax.jit
    def gradfn(p, bufs, x, y):
        def loss_of(pp):
            return _loss_with_buffers(model, pp, bufs,
                                      jax.random.PRNGKey(0), loss_fn,
                                      (x, y))

        (l, nb), g = jax.value_and_grad(loss_of, has_aux=True)(p)
        return l, g, nb

    loss, grads, new_bufs = gradfn(params, bufs, x, y)
    return float(loss), grads, new_bufs


@pytest.mark.parametrize("depth", ["18"])
def test_model_path_fwd_bwd_parity(depth):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 3, 32, 32)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, (8,)), jnp.int32)

    m_nchw = _build_model("NCHW", depth)
    m_nhwc = _build_model("NHWC", depth)
    p1 = {n: p.value for n, p in m_nchw.named_parameters()}
    p2 = {n: p.value for n, p in m_nhwc.named_parameters()}
    for n in p1:
        np.testing.assert_array_equal(np.asarray(p1[n]),
                                      np.asarray(p2[n]), err_msg=n)

    # forward parity (eval mode: running stats, no batch-stats noise)
    m_nchw.eval(), m_nhwc.eval()
    _assert_close(m_nchw(x), m_nhwc(x), "eval forward")

    # backward parity: loss + every parameter gradient of one train-mode
    # step (the jitted fwd+bwd the bench times)
    loss1, g1, b1 = _loss_and_grads(m_nchw, x, y)
    loss2, g2, b2 = _loss_and_grads(m_nhwc, x, y)
    assert loss1 == pytest.approx(loss2, rel=RTOL)
    for n in g1:
        _assert_close(g1[n], g2[n], f"grad {n}")
    # BN batch-stat buffer updates reduce over the same elements in
    # both layouts
    for n in b1:
        _assert_close(b1[n], b2[n], f"buffer {n}")


def _build_static(data_format):
    main, startup = fluid.Program(), fluid.Program()
    ch_shape = ([None, 3, 16, 16] if data_format == "NCHW"
                else [None, 16, 16, 3])
    with fluid.program_guard(main, startup):
        x = fluid.data("x", ch_shape)
        yv = fluid.data("y", [None, 1], dtype="int64")
        h = fluid.layers.conv2d(
            x, 8, 3, padding=1, act=None, data_format=data_format,
            param_attr=fluid.ParamAttr(name="cw"),
            bias_attr=fluid.ParamAttr(name="cb"))
        h = fluid.layers.batch_norm(h, act="relu",
                                    data_layout=data_format,
                                    param_attr=fluid.ParamAttr(name="bns"),
                                    bias_attr=fluid.ParamAttr(name="bnb"),
                                    moving_mean_name="bn_m",
                                    moving_variance_name="bn_v")
        h = fluid.layers.pool2d(h, 2, "max", 2, data_format=data_format)
        # global-pool to [N, C] so the fc sees the same feature ORDER in
        # both layouts (flatten would interleave channels differently)
        h = fluid.layers.pool2d(h, pool_type="avg", global_pooling=True,
                                data_format=data_format)
        h = fluid.layers.flatten(h)
        pred = fluid.layers.fc(h, 10, param_attr=fluid.ParamAttr(name="fw"),
                               bias_attr=fluid.ParamAttr(name="fb"))
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(pred, yv))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def test_static_builder_fwd_bwd_parity():
    """One executor step per layout from identical weights: loss parity
    plus conv weight/bias gradient parity (fetched @GRAD vars) — covers
    the conv2d bias-add axis, pool2d, and batch_norm data_layout plumb
    in layers/nn.py."""
    rng = np.random.RandomState(0)
    xb = rng.rand(8, 3, 16, 16).astype(np.float32)
    yb = rng.randint(0, 10, (8, 1)).astype(np.int64)

    param_names = ("cw", "cb", "bns", "bnb", "fw", "fb", "bn_m", "bn_v")
    grad_names = ["cw@GRAD", "cb@GRAD", "bns@GRAD", "fw@GRAD"]
    results = {}
    init_vars = None
    for df in ("NCHW", "NHWC"):
        with fluid.unique_name.guard():
            main, startup, loss = _build_static(df)
        exe = fluid.Executor()
        sc = fluid.Scope()
        exe._root_key = jax.random.PRNGKey(11)
        exe.run(startup, scope=sc)
        # identical starting point: conv weights are OIHW in BOTH
        # layouts, so the NCHW run's initial values drop straight in
        if init_vars is None:
            init_vars = {vn: np.asarray(sc.find_var(vn))
                         for vn in param_names}
        else:
            for vn, v in init_vars.items():
                sc.set_var(vn, v)
        feed_x = xb if df == "NCHW" else xb.transpose(0, 2, 3, 1)
        out = exe.run(main, feed={"x": feed_x, "y": yb},
                      fetch_list=[loss] + grad_names, scope=sc)
        results[df] = {
            "loss": float(out[0]),
            "grads": dict(zip(grad_names, out[1:])),
            "bn_stats": {vn: np.asarray(sc.find_var(vn))
                         for vn in ("bn_m", "bn_v")},
        }

    assert results["NCHW"]["loss"] == pytest.approx(
        results["NHWC"]["loss"], rel=RTOL)
    for gn in grad_names:
        _assert_close(results["NCHW"]["grads"][gn],
                      results["NHWC"]["grads"][gn], gn)
    for vn in ("bn_m", "bn_v"):
        _assert_close(results["NCHW"]["bn_stats"][vn],
                      results["NHWC"]["bn_stats"][vn], vn)
