"""What the decoder families that serve through their own block function
share (``models/falcon_h1.py``, ``models/afmoe.py``,
``models/glm_moe_lite.py``): RMSNorm with float32 statistics, the
rotate-half rotary embedding and the SwiGLU MLP; and, of the two that
prefill whole waves and route experts, the blocked causal attention of a
prompt block over its own keys, its fresh cache rows, and the shared
expert beside the held share of the routed ones. One spelling, so that a
family added later brings no further copy."""

from __future__ import annotations

#: queries a block of the prefill's attention: a wave's scores exist
#: one block at a time (16,384 tokens x 48 heads x 128 x the key span)
QUERY_BLOCK = 128


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


def blocked_attention(q, k, v, window, scale):
    """Causal grouped-query attention of a block over its OWN keys,
    queries in blocks of :data:`QUERY_BLOCK` so that the scores of a
    wave never exist whole. ``q`` (B, T, nq, d), ``k`` (B, T, nkv, d)
    and ``v`` (B, T, nkv, dv); ``window``: None, or the number of last keys a query sees
    (itself included), and then a block reads only the key span it can
    see. Each block is one batched matrix product a K/V head: its
    ``QUERY_BLOCK x (nq / nkv)`` query rows against the span's keys.
    Returns (B, T, nq * dv)."""
    import jax.numpy as jnp
    from jax import lax

    B, T, nq, d = q.shape
    nkv, dv = k.shape[2], v.shape[3]
    J = nq // nkv
    Bq = min(QUERY_BLOCK, T)
    n_blocks = -(-T // Bq)
    span = T if window is None else min(T, window + Bq)
    # (B, nkv, T, J * d): a block of queries is then (B, nkv, Bq * J, d)
    qg = (q * scale).astype(q.dtype).reshape(B, T, nkv, J * d)
    qg = jnp.moveaxis(qg, 1, 2)
    if n_blocks * Bq != T:
        qg = jnp.pad(qg, [(0, 0), (0, 0), (0, n_blocks * Bq - T), (0, 0)])
    kg, vg = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)   # (B, nkv, T, d)

    def one(i):
        qs = lax.dynamic_slice_in_dim(qg, i * Bq, Bq, axis=2)
        qs = qs.reshape(B, nkv, Bq * J, d)
        start = jnp.clip((i + 1) * Bq - span, 0, T - span)
        ks = lax.dynamic_slice_in_dim(kg, start, span, axis=2)
        vs = lax.dynamic_slice_in_dim(vg, start, span, axis=2)
        s = jnp.einsum("bgmd,bgkd->bgmk", qs, ks,
                       preferred_element_type=jnp.float32)
        qp = (i * Bq + jnp.arange(Bq * J) // J)[:, None]
        kp = (start + jnp.arange(span))[None, :]
        seen = kp <= qp
        if window is not None:
            seen = seen & (qp - kp < window)
        s = jnp.where(seen, s, -1e30)
        # the softmax by hand: the row maximum behind a barrier (left
        # to itself the compiler turns "x - max(x)" into a windowed
        # reduction over the whole span, 7.8 ms a block at a span of
        # 4,224 against 0.3 for the product), and the division after
        # the second product, on (rows, d) instead of (rows, span)
        top = lax.optimization_barrier(jnp.max(s, axis=-1, keepdims=True))
        e = jnp.exp(s - top)
        ctx = jnp.einsum("bgmk,bgkd->bgmd", e.astype(v.dtype), vs,
                         preferred_element_type=jnp.float32)
        ctx = ctx / jnp.sum(e, axis=-1, keepdims=True)
        return ctx.astype(q.dtype).reshape(B, nkv, Bq, J * dv)

    ctx = lax.map(one, jnp.arange(n_blocks))    # (n_blocks, B, nkv, Bq, .)
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(B, nkv, n_blocks * Bq, J * dv)
    return jnp.moveaxis(ctx, 1, 2).reshape(B, n_blocks * Bq, nq * dv)[:, :T]


def fresh_rows(x, valid, length: int):
    """A fresh cache leaf from a block's keys or values ``x`` (B, T,
    c): zeros beyond a row's length; ``min(T, length)`` positions long
    (the pool's scatter writes the columns it is given). A block longer
    than the leaf fills a RING: entry ``j`` holds the row's LAST
    position ``p`` with ``p % length == j``."""
    import jax.numpy as jnp

    T = x.shape[1]
    x = jnp.where(valid[:, :, None], x, 0)
    if T <= length:
        return x
    last = jnp.sum(valid, axis=1, dtype=jnp.int32)[:, None] - 1   # (B, 1)
    idx = last - (last - jnp.arange(length, dtype=jnp.int32)[None]) % length
    return jnp.take_along_axis(x, jnp.clip(idx, 0, T - 1)[:, :, None],
                               axis=1)


def shared_and_routed(p, m, valid, held: int, k: int, route_norm: bool,
                      route_scale: float):
    """A serving MoE layer on a chip that holds a share of the experts:
    the shared expert plus this chip's part of the routed experts
    (``parallel/moe.py``: :func:`~bigdl_tpu.parallel.moe.routed_experts`,
    experts ``held ..`` of the layer). ``p = {"router", "shared",
    "experts"}``; ``m`` (B, T, H); ``valid`` (B, T). Returns the sum
    and the (held,) count of tokens each held expert received."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.parallel.moe import routed_experts

    B, T, H = m.shape
    flat = m.reshape(B * T, H)
    with jax.named_scope("moe.shared"):
        shared = swiglu(flat, p["shared"])
    routed, counts = routed_experts(
        flat, p["router"], p["experts"], held, k,
        valid=valid.reshape(B * T), route_norm=route_norm,
        route_scale=route_scale)
    out = (shared.astype(jnp.float32) + routed).astype(m.dtype)
    return out.reshape(B, T, H), counts
