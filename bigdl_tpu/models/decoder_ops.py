"""What the decoder families that serve through their own block function
share (``models/falcon_h1.py``, ``models/afmoe.py``): RMSNorm with
float32 statistics, the rotate-half rotary embedding and the SwiGLU MLP.
One spelling, so that a family added later brings no third copy."""

from __future__ import annotations


def rms_norm(x, weight, eps):
    """RMSNorm over the last axis: float32 statistics, the weight
    applied in float32, ``eps`` inside the square root."""
    import jax.numpy as jnp
    from jax import lax

    x32 = x.astype(jnp.float32)
    x32 = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * weight.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta):
    """Rotate-half rotary embedding over the whole head: ``x`` (B, T,
    heads, d), ``pos`` (B, T) absolute positions."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv_freq = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None, None] * inv_freq   # B,T,1,half
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def swiglu(u, p, gate_multiplier=None):
    """``W_down(W_up u * SiLU(W_gate u))`` with ``p = {"gate", "up",
    "down"}`` stored (in, out); ``gate_multiplier`` scales the gate's
    pre-activation where a family has one."""
    import jax

    pre = u @ p["gate"]
    if gate_multiplier is not None:
        pre = pre * gate_multiplier
    gate = jax.nn.silu(pre)
    return ((u @ p["up"]) * gate).astype(u.dtype) @ p["down"]
