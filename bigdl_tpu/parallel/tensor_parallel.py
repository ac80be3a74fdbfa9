"""Tensor (model) parallelism: Megatron-style column/row-parallel layers.

No reference counterpart (SURVEY.md §2.4 parallelism inventory: the
reference's only distributed strategy is data parallelism — tensor/model
parallel is listed as the natural TPU extension via param sharding). This
module supplies that extension as first-class primitives designed for the
TPU interconnect:

* **Column-parallel linear** — weight ``(out, in)`` sharded on ``out``
  across the mesh axis. Each chip computes its output-feature slice with a
  full copy of the activations; no communication on the forward pass
  (optionally an ``all_gather`` to rematerialize the full output). The
  backward pass ``psum``s the activation gradient — XLA emits the collective
  from the transpose of the replication, nothing hand-written.
* **Row-parallel linear** — weight sharded on ``in``; activations arrive
  feature-sharded (e.g. from a column-parallel predecessor), each chip
  computes a partial product and one ``psum`` over ICI completes the sum.
* **tp_mlp** — the canonical Megatron block: column-parallel expansion →
  nonlinearity → row-parallel projection, exactly one collective (the
  closing psum) per block.
* **tp_attention** — multi-head attention with heads sharded across the
  axis: column-parallel QKV, local attention per head group, row-parallel
  output projection.

All functions are pure and run inside a ``shard_map`` over the TP mesh axis;
``split_*`` helpers produce the host-side sharded views for ``in_specs``.
Tested on the 8-virtual-device CPU mesh (SURVEY.md §4 pattern).

The SERVING plane consumes these primitives too: the KV-cached
decode/prefill steps (``models/transformer.py``, ``mesh=`` on
``make_batch_decode_step``/``make_batch_prefill_step``) thread
:func:`row_parallel_linear` through the attention-output and fc2
projections under ``utils.compat.shard_map`` — column-parallel QKV/fc1
arrive pre-sliced via ``tp_param_specs``'s in_specs, so each block costs
exactly the two closing psums, with the per-layer K/V cache sharded on
its head axis (``bigdl_tpu.serving.sharded``). Product code wraps these
functions in ``compat.shard_map``, not ``jax.shard_map`` (SPMD101).
"""

from __future__ import annotations

import math
from typing import Optional


def column_parallel_linear(x, w_shard, b_shard=None, axis_name: str = "model",
                           gather_output: bool = False):
    """y_local = x @ w_shard.T (+ b_shard).

    ``x``: replicated activations ``(..., in)``; ``w_shard``: this chip's
    output-row slice ``(out/n, in)``; returns ``(..., out/n)`` — or the full
    ``(..., out)`` when ``gather_output`` (one all_gather). Note the gathered
    value is still device-varying to shard_map's replication checker; prefer
    the ungathered form with ``out_specs`` carrying the feature axis, or pass
    ``check_vma=False`` to shard_map when gathering.
    """
    import jax.lax as lax
    import jax.numpy as jnp

    y = jnp.matmul(x, w_shard.T)
    if b_shard is not None:
        y = y + b_shard
    if gather_output:
        y = lax.all_gather(y, axis_name, axis=y.ndim - 1, tiled=True)
    return y


def row_parallel_linear(x_shard, w_shard, b=None, axis_name: str = "model",
                        accum_dtype=None, partial_add=None):
    """y = psum_over_axis(x_shard @ w_shard.T) (+ b).

    ``x_shard``: feature-sharded activations ``(..., in/n)``; ``w_shard``:
    this chip's input-column slice ``(out, in/n)``. The single ``psum`` is
    the block's only collective; the bias is added once (post-psum).

    ``accum_dtype`` (e.g. ``jnp.float32``) carries each chip's partial
    product AND the psum in that dtype, rounding to ``x_shard.dtype``
    once after the reduction — without it, low-precision activations
    (bf16 serving) round per chip and again per psum addend, so the
    sharded result drifts a full low-precision ulp from the unsharded
    matmul (enough to flip a greedy argmax on near-tied logits; the
    serving plane's TP steps pass fp32 here for exactly that reason).

    ``partial_add`` (requires ``accum_dtype``): an extra per-chip partial
    contribution in the accumulation dtype, folded into the SAME closing
    psum — the serving plane's per-row LoRA delta rides here, so adapted
    projections keep the one-collective-per-projection budget (an
    all-zeros partial passes through exactly: ``acc + 0.0 == acc``).
    """
    import jax.lax as lax
    import jax.numpy as jnp

    if accum_dtype is not None:
        acc = lax.dot_general(
            x_shard, w_shard,
            (((x_shard.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=accum_dtype)
        if partial_add is not None:
            acc = acc + partial_add.astype(accum_dtype)
        y = lax.psum(acc, axis_name).astype(x_shard.dtype)
    else:
        if partial_add is not None:
            raise ValueError("partial_add requires accum_dtype")
        y = lax.psum(jnp.matmul(x_shard, w_shard.T), axis_name)
    if b is not None:
        y = y + b
    return y


def tp_mlp(x, w1_shard, b1_shard, w2_shard, b2, axis_name: str = "model",
           activation=None):
    """Megatron MLP block: column-parallel W1 → act → row-parallel W2.

    ``w1_shard``: ``(hidden/n, in)``, ``w2_shard``: ``(out, hidden/n)``.
    The intermediate stays sharded on hidden features — no collective until
    the closing psum in the row-parallel projection.
    """
    import jax.nn

    act = activation or jax.nn.gelu
    h = column_parallel_linear(x, w1_shard, b1_shard, axis_name)
    return row_parallel_linear(act(h), w2_shard, b2, axis_name)


def tp_attention(x, wq, wk, wv, wo, axis_name: str, n_heads_local: int,
                 causal: bool = False, bo=None):
    """Head-sharded multi-head self-attention.

    ``x``: replicated ``(B, T, d_model)``. ``wq/wk/wv``: column-parallel
    shards ``(d_local, d_model)`` where ``d_local = n_heads_local * head_dim``;
    ``wo``: row-parallel shard ``(d_model, d_local)``. ``n_heads_local`` is
    required (``total_heads / tp_size``) — defaulting it would silently merge
    a chip's heads into one. Each chip attends over its own head group (zero
    communication), then one psum closes the output projection — the standard
    Megatron attention layout mapped onto ICI.
    """
    from bigdl_tpu.parallel.ring_attention import attention

    q = column_parallel_linear(x, wq, axis_name=axis_name)
    k = column_parallel_linear(x, wk, axis_name=axis_name)
    v = column_parallel_linear(x, wv, axis_name=axis_name)
    B, T, d_local = q.shape
    h = n_heads_local
    hd = d_local // h
    q = q.reshape(B, T, h, hd)
    k = k.reshape(B, T, h, hd)
    v = v.reshape(B, T, h, hd)
    o = attention(q, k, v, causal=causal,
                  scale=1.0 / math.sqrt(hd)).reshape(B, T, d_local)
    return row_parallel_linear(o, wo, bo, axis_name)


# There are deliberately no host-side weight-splitting helpers: pass the
# UNSPLIT weights through shard_map and let in_specs do the sharding —
# ``P(axis, None)`` for column-parallel (output rows), ``P(None, axis)`` for
# row-parallel (input columns). shard_map hands each chip exactly the slice
# these functions expect.
