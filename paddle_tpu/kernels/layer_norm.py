"""Pallas TPU fused LayerNorm (forward + custom-VJP backward).

Parity target: the reference's fused layer-norm CUDA kernels
(/root/reference/paddle/fluid/operators/layer_norm_op.cu and the fused
variants in operators/fused/fused_fc_elementwise_layernorm_op.cc) — one
kernel that reads x once, computes mean/rstd in f32, and writes the
normalized output, instead of the unfused mean/var/normalize chain.

Kernel shape: grid over row blocks; each step loads a [block_rows, D]
tile into VMEM, reduces mean and variance along D in f32 on the VPU, and
writes y = (x - mean) * rstd * gamma + beta in the input dtype.  Mean and
rstd are saved for the backward, which fuses the three reference grad
terms (dx, dgamma partial, dbeta partial) into one data pass; the dgamma/
dbeta row-partials are reduced with a plain XLA sum outside the kernel
(a [rows, D] -> [D] reduction XLA already does at line rate).

On non-TPU backends the kernels run in interpret mode (numerics tests);
dispatch (ops/nn_ops.py layer_norm) only selects the Pallas path on TPU
for last-axis norms with D % 128 == 0 under FLAGS_use_pallas_layer_norm.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import interpret

DEFAULT_BLOCK_ROWS = 256


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                  # [R, D]
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd * g_ref[...].astype(jnp.float32)[None, :] \
        + b_ref[...].astype(jnp.float32)[None, :]
    y_ref[...] = y.astype(y_ref.dtype)
    # mean/rstd are NOT materialized: 1-D f32 outputs tile at T(1024)
    # and clash with row blocks (Mosaic layout-verify failure on chip);
    # the backward recomputes them from the x block it already holds
    # in VMEM — identical numerics, and the forward writes less HBM.


def _bwd_kernel(x_ref, g_ref, dy_ref,
                dx_ref, dg_acc_ref, db_acc_ref, *, rows, block, groups,
                eps):
    x = x_ref[...].astype(jnp.float32)                  # [R, D]
    dy = dy_ref[...].astype(jnp.float32)
    gamma = g_ref[...].astype(jnp.float32)[None, :]
    # recompute row stats from the block already in VMEM (see fwd)
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    wdy = dy * gamma
    # dx = rstd * (wdy - mean(wdy) - xhat * mean(wdy * xhat))
    c1 = jnp.mean(wdy, axis=1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=1, keepdims=True)
    dx_ref[...] = (rstd * (wdy - c1 - xhat * c2)).astype(dx_ref.dtype)
    # a partial final block carries out-of-bounds padded rows: mask them
    # out of the cross-row partial sums (dx rows beyond `rows` are
    # discarded on write, but sums would absorb the garbage)
    row_idx = pl.program_id(0) * block \
        + jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    valid = row_idx < rows
    d = x.shape[1]
    # dgamma/dbeta partials: reduce the block's rows down to `groups`
    # rows (8 keeps the accumulator TPU-tileable — a (1, D) block
    # violates the (8, 128) minimum) and ACCUMULATE into one
    # VMEM-resident [groups, D] output shared by every grid step; the
    # final [groups, D] -> [D] sum happens outside in XLA.
    # jnp.where, not a multiply: padded rows may hold NaN (NaN * 0 = NaN)
    dgp = jnp.sum(jnp.where(valid, dy * xhat, 0.0)
                  .reshape(groups, -1, d), axis=1)
    dbp = jnp.sum(jnp.where(valid, dy, 0.0)
                  .reshape(groups, -1, d), axis=1)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_acc_ref[...] = jnp.zeros_like(dg_acc_ref)
        db_acc_ref[...] = jnp.zeros_like(db_acc_ref)

    dg_acc_ref[...] += dgp
    db_acc_ref[...] += dbp


def _fwd(x, gamma, beta, eps, block_rows):
    rows, d = x.shape
    block = min(block_rows, rows)
    grid = (pl.cdiv(rows, block),)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret(),
        name="layer_norm_fwd",
    )
    with jax.named_scope("layer_norm_fwd"):
        y = call(x, gamma, beta)
    return y


def _bwd(x, gamma, dy, eps, block_rows):
    rows, d = x.shape
    block = min(block_rows, rows)
    nblocks = pl.cdiv(rows, block)
    groups = 8 if block % 8 == 0 else 1
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, rows=rows, block=block,
                          groups=groups, eps=eps),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((block, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            # every grid step maps the SAME full-array block: the
            # accumulator stays VMEM-resident across the whole grid
            pl.BlockSpec((groups, d), lambda i: (0, 0)),
            pl.BlockSpec((groups, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x.dtype),
            jax.ShapeDtypeStruct((groups, d), jnp.float32),
            jax.ShapeDtypeStruct((groups, d), jnp.float32),
        ],
        interpret=interpret(),
        name="layer_norm_bwd",
    )
    with jax.named_scope("layer_norm_bwd"):
        dx, dg_acc, db_acc = call(x, gamma, dy)
    return dx, dg_acc.sum(axis=0), db_acc.sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_layer_norm(x, gamma, beta, eps=1e-5,
                     block_rows=DEFAULT_BLOCK_ROWS):
    """LayerNorm over the last axis of a 2-D [rows, D] input."""
    return _fwd(x, gamma, beta, eps, block_rows)


def _fused_ln_fwd(x, gamma, beta, eps, block_rows):
    return _fwd(x, gamma, beta, eps, block_rows), (x, gamma)


def _fused_ln_bwd(eps, block_rows, res, dy):
    x, gamma = res
    dx, dgamma, dbeta = _bwd(x, gamma, dy, eps, block_rows)
    return dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype)


fused_layer_norm.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def layer_norm_pallas(x, gamma, beta, eps=1e-5):
    """Any-rank wrapper: normalizes over the last axis."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y = fused_layer_norm(x2, gamma, beta, eps)
    return y.reshape(shape)
