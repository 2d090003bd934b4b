"""Layer pieces the served decoders share (`models/kimi_k2.py`,
`models/afmoe.py`): RMSNorm and the SwiGLU feed-forward."""

import jax
import jax.numpy as jnp

__all__ = ["rms_norm", "swiglu"]


def rms_norm(cfg, x, gain):
    """RMSNorm over the last axis with `cfg.rms_norm_eps`, computed in
    float32 and returned in x's type."""
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        + cfg.rms_norm_eps)
    return (x32 * gain.astype(jnp.float32)).astype(x.dtype)


def swiglu(h, gate_up, down):
    """(silu(h Wg) * (h Wu)) Wd, `gate_up` holding Wg and Wu side by
    side."""
    gu = jnp.dot(h, gate_up, preferred_element_type=jnp.float32)
    f = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]).astype(h.dtype) @ down
