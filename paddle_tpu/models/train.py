"""Train-step factories: Layer + functional optimizer -> pure jitted step.

The TPU answer to the reference's Executor hot loop + ParallelExecutor
(SURVEY.md §3.1/§3.2): the whole (forward, backward, optimizer-update)
iteration is ONE jitted function with donated state, so XLA owns fusion,
scheduling, memory planning, and (under a mesh) collective insertion.

TrainState is the explicit pytree of everything that mutates per step —
the analogue of the reference's persistable variables in a Scope
(framework/scope.h:46).
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..nn.layers import (
    _swap_params, buffer_dict, functional_call_with_state, param_dict,
)
from ..nn.parameter import default_rng

@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    buffers: Any
    step: Any
    rng: Any


jax.tree_util.register_dataclass(
    TrainState,
    data_fields=["params", "opt_state", "buffers", "step", "rng"],
    meta_fields=[],
)


def init_train_state(model, optimizer, rng_seed=0):
    params = param_dict(model, trainable_only=True)
    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        buffers=buffer_dict(model),
        step=jnp.zeros((), jnp.int32),
        rng=jax.random.PRNGKey(rng_seed),
    )


def _loss_with_buffers(model, params, buffers, rng, loss_fn, batch):
    """Pure loss evaluation: params/buffers substituted, stochastic ops
    (dropout) drawing from the traced rng key."""
    with default_rng.key_context(rng):
        if buffers:
            return functional_call_with_state(model, params, buffers,
                                              *batch, _method=loss_fn)
        with _swap_params(model, params):
            return loss_fn(model, *batch), buffers


def make_train_step(model, optimizer, loss_fn=None, jit=True, donate=True,
                    grad_psum_axis=None, remat=False, accum_steps=1,
                    precision=None, amp=None):
    """Build `step(state, *batch) -> (state, loss)`.

    loss_fn(model, *batch) -> scalar; defaults to model.loss.
    grad_psum_axis: mesh axis name(s) to pmean grads over (for use inside
    shard_map); plain pjit DP needs no explicit psum — XLA inserts it.
    accum_steps=k > 1 splits the batch's leading dim into k microbatches
    and lax.scans grad accumulation over them inside the ONE compiled
    step (mean of microbatch grads, one optimizer update) — the
    activation-memory lever for batch sizes whose activations don't fit,
    with buffers (BN running stats) threaded through the scan exactly as
    k sequential small steps would update them.
    remat: True rematerializes the whole forward in the backward pass
    (activations are not stored; ~1/3 more FLOPs for O(layer-io) memory).
    remat="conv_outs" saves ONLY conv outputs (the checkpoint_name tags
    the conv2d kernel emits) and recomputes the elementwise tail
    (BN affine / relu / residual add) during backward.  This is a
    MEMORY lever, not a speed lever: the recompute re-materializes the
    elementwise outputs in HBM during backward, where XLA's default
    residual selection already keeps that traffic low, and full
    remat=True re-runs the convs as well.  Use remat when activations
    don't fit, not to go faster.
    jax.checkpoint must wrap the PURE params->loss function — wrapping a
    stateful `model(...)` call would leak buffer-update tracers across
    the re-trace and die with UnexpectedTracerError.  Belt-and-braces
    for the same failure class: the checkpointed function here takes
    EVERY traced value (params, buffers, rng, batch) as an explicit
    argument rather than a closure capture, so the recompute trace can
    never hold a reference into the outer trace no matter how strict
    the jax release is about closed-over tracers.
    precision: jax matmul/conv precision for the whole compiled step
    ("bfloat16" | "tensorfloat32" | "float32" | "highest" | None).
    None defers to FLAGS_conv_matmul_precision ("" = jax default) —
    the explicit bf16-MXU knob for perf A/Bs; numerics-sensitive runs
    pass "highest".
    amp: True routes the loss computation through amp.auto_cast —
    white-list ops (matmul/conv/fc functional kernels) compute in
    FLAGS_amp_dtype (bf16 on TPU) against fp32 master params, black
    ops pinned fp32.  None (the default) reads FLAGS_amp: "on" enables
    it globally; the "train" default keeps the dygraph step fp32 (the
    dataset train loop is the AMP-by-default path — see
    amp.rewrite_train_program); False forces it off.  Compose with
    make_amp_train_step for fp16 dynamic loss scaling.
    """
    if isinstance(remat, str) and remat != "conv_outs":
        raise ValueError(
            f"unknown remat mode {remat!r}; use True or 'conv_outs'")
    if int(accum_steps) < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if loss_fn is None:
        loss_fn = lambda m, *b: m.loss(*b)
    model.train()
    if precision is None:
        from ..framework.compiler import resolve_precision

        precision = resolve_precision()

    # The checkpointed callable: pure in its ARGUMENTS — params, buffers,
    # rng, and the batch all enter as explicit inputs (saved residuals),
    # never as closure-captured tracers, so the backward-pass recompute
    # trace owns every value it touches.
    if amp is None:
        from .. import flags as _flags

        amp = _flags.flag("amp") == "on"

    def _loss_args(params, bufs, rng_key, *xs):
        if amp:
            # eager autocast around the whole forward: the functional
            # kernels consult the list-driven dispatch per op, so the
            # step traces with bf16 white ops and fp32 black ops while
            # params (the grad targets) stay fp32 masters
            from .. import amp as _amp

            with _amp.auto_cast(enable=True):
                return _loss_with_buffers(model, params, bufs, rng_key,
                                          loss_fn, xs)
        return _loss_with_buffers(model, params, bufs, rng_key, loss_fn,
                                  xs)

    if remat == "conv_outs":
        _loss_args = jax.checkpoint(
            _loss_args,
            policy=jax.checkpoint_policies.save_only_these_names(
                "conv_out"))
    elif remat:
        _loss_args = jax.checkpoint(_loss_args)
    _grad = jax.value_and_grad(_loss_args, has_aux=True)

    def step(state, *batch):
        rng, new_rng = jax.random.split(state.rng)

        if accum_steps > 1:
            k = accum_steps
            for b in batch:
                if b.shape[0] % k != 0:
                    raise ValueError(
                        f"batch leading dim {b.shape[0]} not divisible "
                        f"into accum_steps={k} microbatches")
            micro = tuple(
                b.reshape(k, b.shape[0] // k, *b.shape[1:])
                for b in batch)

            def body(carry, xs):
                gsum, bufs, lsum, i = carry
                (l, newb), g = _grad(state.params, bufs,
                                     jax.random.fold_in(rng, i), *xs)
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (gsum, newb, lsum + l.astype(jnp.float32),
                        i + 1), None

            gzero = jax.tree.map(jnp.zeros_like, state.params)
            (gsum, new_buffers, lsum, _), _ = jax.lax.scan(
                body,
                (gzero, state.buffers, jnp.zeros((), jnp.float32),
                 jnp.zeros((), jnp.int32)),
                micro)
            grads = jax.tree.map(lambda g: g / k, gsum)
            loss = lsum / k
        else:
            (loss, new_buffers), grads = _grad(state.params,
                                               state.buffers, rng, *batch)
        if grad_psum_axis:
            grads = jax.lax.pmean(grads, grad_psum_axis)
            loss = jax.lax.pmean(loss, grad_psum_axis)
        params, opt_state = optimizer.update(state.params, grads,
                                             state.opt_state)
        new_state = TrainState(params=params, opt_state=opt_state,
                               buffers=new_buffers, step=state.step + 1,
                               rng=new_rng)
        return new_state, loss

    if precision:
        # active during tracing, so every dot/conv the step stages
        # inherits the policy (jit traces under this context)
        from ..framework.compiler import apply_precision_policy

        step = apply_precision_policy(step, precision)

    if jit:
        step = jax.jit(step, donate_argnums=(0,) if donate else ())
    return step


def make_eval_step(model, forward_fn=None, jit=True):
    if forward_fn is None:
        forward_fn = lambda m, *b: m(*b)

    def step(params, buffers, *batch):
        was_training = model.training
        model.eval()
        try:
            out, _ = _loss_with_buffers(model, params, buffers,
                                        jax.random.PRNGKey(0), forward_fn,
                                        batch)
        finally:
            if was_training:
                model.train()
        return out

    if jit:
        step = jax.jit(step)
    return step
