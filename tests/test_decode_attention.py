"""Pallas pooled decode-attention kernel (ops/decode_attention.py) vs
its jnp reference (differential-testing pattern, SURVEY.md §4): masked
single-query attention over the pooled (n_rows, max_len) KV cache with
per-row inclusive ``pos``, fp32 and bf16, quantized (int8 K/V + per-
(row, head) fp32 scales) and unquantized. Runs the kernel in Pallas
INTERPRETER mode on the CPU backend — the compiled Mosaic path is
exercised by the TPU/multichip dryrun flow, and both resolve their
dispatch through the shared ``utils.compat.auto_interpret`` probe."""

import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.decode_attention import (
    decode_attention, decode_attention_reference, folded_decode_attention,
    pooled_decode_attention,
)


def _pooled(n=4, L=48, h=4, d=16, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((n, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((n, L, h, d)), dtype)
    v = jnp.asarray(rng.standard_normal((n, L, h, d)), dtype)
    # every interesting pos: fresh row (0), mid-cache, last column
    pos = jnp.asarray(rng.integers(0, L, size=(n,)), jnp.int32)
    pos = pos.at[0].set(0).at[-1].set(L - 1)
    return q, k, v, pos


def _quantize(k, v):
    """Per-(row, head) symmetric int8, the serving carry's layout."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    ks = jnp.max(jnp.abs(k32), axis=(1, 3)) / 127.0
    vs = jnp.max(jnp.abs(v32), axis=(1, 3)) / 127.0
    kq = jnp.clip(jnp.round(k32 / ks[:, None, :, None]), -127, 127
                  ).astype(jnp.int8)
    vq = jnp.clip(jnp.round(v32 / vs[:, None, :, None]), -127, 127
                  ).astype(jnp.int8)
    return kq, vq, ks, vs


def _dense_oracle(q, k, v, pos):
    """Independent dense spelling (no shared code with the module)."""
    q32, k32, v32 = (np.asarray(x, np.float64) for x in (q, k, v))
    n, h, d = q32.shape
    L = k32.shape[1]
    out = np.zeros((n, h, d))
    for r in range(n):
        w = int(pos[r]) + 1
        s = np.einsum("hd,lhd->hl", q32[r], k32[r, :w]) * d ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[r] = np.einsum("hl,lhd->hd", p, v32[r, :w])
    return out


# -- reference vs an independent dense oracle ------------------------------

def test_reference_matches_dense_oracle():
    q, k, v, pos = _pooled()
    ref = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(ref), _dense_oracle(q, k, v, pos),
                               atol=2e-5, rtol=2e-5)


def test_reference_quantized_is_factored_dequant():
    """The int8 reference must equal dequantize-then-attend exactly (the
    scale factors out of both contractions — no extra approximation
    beyond the quantization itself)."""
    q, k, v, pos = _pooled()
    kq, vq, ks, vs = _quantize(k, v)
    got = decode_attention_reference(q, kq, vq, pos, k_scale=ks, v_scale=vs)
    kd = kq.astype(jnp.float32) * ks[:, None, :, None]
    vd = vq.astype(jnp.float32) * vs[:, None, :, None]
    want = decode_attention_reference(q, kd, vd, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)
    # and the quantization error itself is small at this scale
    base = decode_attention_reference(q, k, v, pos)
    assert float(jnp.max(jnp.abs(got - base))) < 0.05


# -- kernel (interpret mode) vs reference ----------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("quantized", [False, True])
def test_kernel_matches_reference(dtype, quantized):
    q, k, v, pos = _pooled(dtype=dtype)
    if quantized:
        k, v, ks, vs = _quantize(k, v)
    else:
        ks = vs = None
    ref = decode_attention_reference(q, k, v, pos, k_scale=ks, v_scale=vs,
                                     out_dtype=jnp.float32)
    ker = pooled_decode_attention(q, k, v, pos, k_scale=ks, v_scale=vs,
                                  interpret=True, out_dtype=jnp.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=tol, rtol=tol)


def test_kernel_pads_non_block_multiple_window():
    """Cache windows that don't divide the KV tile are right-padded in
    the wrapper; padded columns sit past every pos and must not leak."""
    q, k, v, pos = _pooled(L=37)
    ref = decode_attention_reference(q, k, v, pos)
    ker = pooled_decode_attention(q, k, v, pos, block=16, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_block_size_invariant():
    """Same numbers for any KV tile length (the online softmax carries
    exactly across block boundaries)."""
    q, k, v, pos = _pooled(L=64)
    outs = [pooled_decode_attention(q, k, v, pos, block=b, interpret=True)
            for b in (16, 32, 64)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=1e-6, rtol=1e-6)


def test_pos_zero_attends_only_first_column():
    """pos is INCLUSIVE (the decode step's wpos — the column just
    written): pos=0 must return exactly v[:, 0]."""
    q, k, v, _ = _pooled(n=2)
    pos = jnp.zeros((2,), jnp.int32)
    for fn in (decode_attention_reference,
               lambda *a, **kw: pooled_decode_attention(
                   *a, interpret=True, **kw)):
        out = fn(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(v[:, 0]),
                                   atol=2e-5, rtol=2e-5)


# -- the stored (N, L, H*D) cache, read without a 4-D view ------------------

def _folded(x):
    n, L, h, d = x.shape
    return x.reshape(n, L, h * d)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_folded_matches_reference(dtype, tol, seed):
    """The decode steps' float read: block-diagonal query against the
    3-D array, the reference's sum plus exact zeros. ``_pooled`` draws
    pos 0, mid-cache and L-1."""
    q, k, v, pos = _pooled(n=5, L=64, dtype=dtype, seed=seed)
    ref = decode_attention_reference(q, k, v, pos, out_dtype=jnp.float32)
    got = folded_decode_attention(q, _folded(k), _folded(v), pos,
                                  out_dtype=jnp.float32)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=tol, rtol=tol)


def test_folded_honours_scale_and_refuses_the_view():
    q, k, v, pos = _pooled(n=2, L=16)
    ref = decode_attention_reference(q, k, v, pos, scale=0.3)
    got = folded_decode_attention(q, _folded(k), _folded(v), pos, scale=0.3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)
    with pytest.raises(ValueError, match="stored"):
        folded_decode_attention(q, k, v, pos)


@pytest.mark.parametrize("quantized", [False, True])
def test_stored_shape_equals_its_view(quantized):
    """Reference and kernel take the pool's 3-D K/V as it is stored and
    give what they give for its 4-D view, bit for bit."""
    q, k, v, pos = _pooled()
    ks = vs = None
    if quantized:
        k, v, ks, vs = _quantize(k, v)
    for fn in (decode_attention_reference,
               lambda *a, **kw: pooled_decode_attention(
                   *a, interpret=True, **kw)):
        want = fn(q, k, v, pos, k_scale=ks, v_scale=vs)
        got = fn(q, _folded(k), _folded(v), pos, k_scale=ks, v_scale=vs)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- dispatch + validation -------------------------------------------------

def test_auto_impl_uses_reference_off_tpu():
    """On this CPU box the auto path must route to the jnp reference
    (interpret-mode Pallas is an emulator, far too slow for the serving
    loop) — and the probe is the SHARED compat.auto_interpret, so flash
    and decode kernels cannot drift on the dispatch decision."""
    from bigdl_tpu.utils.compat import auto_interpret

    assert auto_interpret() is True       # tier-1 runs on CPU
    q, k, v, pos = _pooled(n=2, L=16)
    auto = decode_attention(q, k, v, pos, impl="auto")
    ref = decode_attention(q, k, v, pos, impl="reference")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))


def test_validation_errors():
    q, k, v, pos = _pooled(n=2, L=16)
    kq, vq, ks, vs = _quantize(k, v)
    with pytest.raises(ValueError, match="BOTH k_scale and v_scale"):
        decode_attention_reference(q, kq, vq, pos, k_scale=ks)
    with pytest.raises(ValueError, match="must be int8"):
        decode_attention_reference(q, k, v, pos, k_scale=ks, v_scale=vs)
    with pytest.raises(ValueError, match="per-\\(row, head\\)"):
        decode_attention_reference(q, kq, vq, pos, k_scale=ks[:1],
                                   v_scale=vs[:1])
    with pytest.raises(ValueError, match="do not match q"):
        decode_attention_reference(q, k[:, :, :2], v[:, :, :2], pos)
    with pytest.raises(ValueError, match="do not match q"):
        decode_attention_reference(q, k.reshape(2, 16, -1)[:, :, :-1],
                                   v.reshape(2, 16, -1)[:, :, :-1], pos)
    with pytest.raises(ValueError, match="unknown impl"):
        decode_attention(q, k, v, pos, impl="magic")
