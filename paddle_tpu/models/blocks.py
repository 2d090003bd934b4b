"""Layer pieces the served decoders share (`models/kimi_k2.py`,
`models/afmoe.py`, `models/brumby.py`): RMSNorm, the SwiGLU
feed-forward, heads normalised one by one, the rotation, and the expert
layer's routed and shared parts with the counters it adds to."""

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["rms_norm", "swiglu", "normed_heads", "rope", "expert_counters",
           "expert_ffn", "expert_layers_kept"]


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


def normed_heads(cfg, a, w, heads, gain):
    """a [N, H] through w [H, heads * d] -> [N, heads, d], each head
    RMS-normalised over d with the learned `gain` [d] (QK-norm)."""
    return rms_norm(cfg, (a @ w).reshape(a.shape[0], heads, cfg.head_dim),
                    gain)


def rope(cfg, x, pos):
    """Rotate x [N, heads, d] at positions pos [N], as HF's
    `apply_rotary_pos_emb`: lane i pairs with lane i + d / 2."""
    half = cfg.head_dim // 2
    inv_freq = cfg.rope_theta ** (
        -np.arange(half, dtype=np.float64) / half)
    angle = pos.astype(jnp.float32)[:, None, None] \
        * inv_freq.astype(np.float32)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def expert_counters(held):
    """A program's counters before its first expert layer:
    `expert_counts`, the assignments on each of the `held` experts, and
    `expert_layers_kept`, the expert layers that ran over
    `routed_experts`' kept rows."""
    return {"expert_counts": jnp.zeros(held, jnp.int32),
            "expert_layers_kept": jnp.zeros((), jnp.int32)}


def expert_ffn(h, lp, bias, route, counters, valid=None):
    """An expert layer's FFN of tokens h [N, H]: the routed part
    (`distributed/moe.py` `routed_experts` with `route` = (first_expert,
    n_routed, top_k, scale)) plus the shared expert; the layer's own
    counts added to `counters`."""
    from ..distributed.moe import routed_experts

    routed, counts, kept = routed_experts(
        h, lp["router"], bias, (lp["experts_gate_up"], lp["experts_down"]),
        *route, valid=valid)
    return routed + swiglu(h, lp["shared_gate_up"], lp["shared_down"]), {
        "expert_counts": counters["expert_counts"] + counts,
        "expert_layers_kept": counters["expert_layers_kept"] + kept}


def expert_layers_kept(tokens, top_k, held, n_routed, layers):
    """Of `layers` expert layers in a program over `tokens` tokens, those
    whose shape has `routed_experts`' kept case (all or none); on the
    host, from the shape."""
    from ..distributed.moe import _rows_kept

    return 0 if _rows_kept(tokens * top_k, held / n_routed) is None \
        else layers
