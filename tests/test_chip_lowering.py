"""Cross-lower the Pallas kernels for the TPU, on the CPU.

Tier-1 runs every kernel with ``interpret=True``, which never builds a
Mosaic program: a BlockSpec the TPU lowering refuses (as the per-head
``(1, block, 1, d)`` tiles of the first pooled decode kernel were) passes
every interpret-mode test and fails on the chip.
``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the
Pallas→Mosaic lowering without a device, at the shapes the 137M model
serves and trains at, with ``interpret=False`` passed explicitly. (The
Mosaic compiler proper runs in ``chip_smoke.py``, on the chip.)"""

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops.decode_attention import pooled_decode_attention
from bigdl_tpu.ops.flash_attention import flash_attention

B, T, H, D = 8, 2048, 12, 64        # 137M training: batch 8, T 2048
N, L = 32, 2048                     # 137M serving: 32 slots, window 2048


def _lower_for_tpu(fn, *shapes):
    lowered = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the lowering"
    return text


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_flash_forward_lowers_for_tpu():
    qkv = _sds((B, T, H, D), jnp.bfloat16)
    _lower_for_tpu(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), qkv, qkv, qkv)


def test_flash_backward_lowers_for_tpu():
    qkv = _sds((B, T, H, D), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=False).astype(jnp.float32))

    text = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    # forward + dq + dk/dv kernels
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("kv_shape", [(N, L, H * D), (N, L, H, D)],
                         ids=["stored", "view"])
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_pooled_decode_attention_lowers_for_tpu(kv_dtype, kv_shape):
    q = _sds((N, H, D), jnp.bfloat16)
    kv = _sds(kv_shape, kv_dtype)
    pos = _sds((N,), jnp.int32)
    if kv_dtype == jnp.int8:
        scale = _sds((N, H), jnp.float32)
        _lower_for_tpu(
            lambda q, k, v, pos, ks, vs: pooled_decode_attention(
                q, k, v, pos, k_scale=ks, v_scale=vs, interpret=False),
            q, kv, kv, pos, scale, scale)
    else:
        _lower_for_tpu(
            lambda q, k, v, pos: pooled_decode_attention(
                q, k, v, pos, interpret=False), q, kv, kv, pos)


@pytest.mark.parametrize("n,length,h,g,d", [
    (32, 1024, 16, 16, 64),       # gpt2m-serve-chat
    (32, 1024, 20, 4, 128),       # falconh1-serve-reason
    (16, 4096, 48, 8, 128),       # trinity-serve-mixed, a window's ring
    (16, 8192, 48, 8, 128),       # ... and its full-attention layer
], ids=["h16", "h20g4", "h48g8_ring", "h48g8_full"])
def test_pooled_decode_attention_lowers_at_the_cells_shapes(n, length, h,
                                                            g, d):
    """Grouped queries over the stored bf16 cache with the rows that
    decode passed in, at the shapes the three serving cells bring."""
    kv = _sds((n, length, g * d), jnp.bfloat16)
    _lower_for_tpu(
        lambda q, k, v, pos, active: pooled_decode_attention(
            q, k, v, pos, active=active, interpret=False),
        _sds((n, h, d), jnp.bfloat16), kv, kv, _sds((n,), jnp.int32),
        _sds((n,), bool))


@pytest.mark.parametrize("n,length,h,d,v_width", [
    (32, 16384, 20, 640, 512),    # glm47flash-serve-longctx, as stored
    (8, 256, 4, 128, 32),
], ids=["h20_640", "toy"])
def test_latent_decode_attention_lowers_for_tpu(n, length, h, d, v_width):
    """A latent cache: ONE stored bf16 leaf of one K/V head under all
    query heads, its leading ``v_width`` columns the values."""
    from bigdl_tpu.ops.decode_attention import decode_attention

    text = _lower_for_tpu(
        lambda q, k, pos, active: decode_attention(
            q, k, None, pos, active=active, impl="kernel", interpret=False,
            v_width=v_width),
        _sds((n, h, d), jnp.bfloat16), _sds((n, length, d), jnp.bfloat16),
        _sds((n,), jnp.int32), _sds((n,), bool))
    assert text.count("tpu_custom_call") == 1
