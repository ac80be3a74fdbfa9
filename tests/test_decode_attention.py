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
    _decode_schedule, decode_attention, decode_attention_reference,
    fetched_blocks, folded_decode_attention, pooled_decode_attention,
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


# -- grouped queries, rows that do not decode, the compacted grid -----------

GL, GBLOCK = 384, 128


def _grouped(h, g, d, pos_value, dtype=jnp.float32, n=4, seed=0):
    """A stored (n, GL, g*d) cache under h query heads; every row at
    ``pos_value``; row 1 and the last row do not decode."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((n, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((n, GL, g * d)), dtype)
    v = jnp.asarray(rng.standard_normal((n, GL, g * d)), dtype)
    pos = jnp.full((n,), pos_value, jnp.int32)
    active = np.ones((n,), bool)
    active[[1, n - 1]] = False
    return q, k, v, pos, active


@pytest.mark.parametrize("pos_value", [0, 200, 255, 256, GL - 1],
                         ids=["first", "mid_block", "block_end",
                              "block_start", "whole_ring"])
@pytest.mark.parametrize("h,g,d", [(16, 16, 64), (20, 4, 128),
                                   (48, 8, 128)])
def test_grouped_kernel_matches_reference(h, g, d, pos_value):
    """The heads the serving cells bring (ungrouped, 5 and 6 query
    heads a K/V head), a row's first column, the middle, the last and
    the first column of a block and ``L - 1`` (what a wrapped ring
    passes), with rows that decode beside rows that do not: those that
    do read the reference's sum, the others come back as zeros."""
    q, k, v, pos, active = _grouped(h, g, d, pos_value)
    ref = decode_attention_reference(q, k, v, pos)
    ker = pooled_decode_attention(q, k, v, pos, block=GBLOCK,
                                  interpret=True,
                                  active=jnp.asarray(active))
    assert ker.shape == q.shape
    np.testing.assert_allclose(np.asarray(ker)[active],
                               np.asarray(ref)[active],
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(ker)[~active].any()


@pytest.mark.parametrize("h,g,d", [(20, 4, 128), (6, 2, 16)])
def test_grouped_reference_and_folded_agree_with_repeated_heads(h, g, d):
    """A grouped cache reads as the ungrouped one whose K/V head ``c``
    is repeated for its ``h / g`` query heads."""
    q, k, v, pos, _ = _grouped(h, g, d, 200)
    pos = pos.at[0].set(0).at[2].set(GL - 1)

    def repeated(x):
        return jnp.repeat(x.reshape(4, GL, g, d), h // g, axis=2)

    want = decode_attention_reference(q, repeated(k), repeated(v), pos)
    for fn in (decode_attention_reference, folded_decode_attention):
        np.testing.assert_allclose(np.asarray(fn(q, k, v, pos)),
                                   np.asarray(want), atol=2e-6, rtol=2e-6)


def _pr32_kernel(q, k, v, pos, block, k_scale=None, v_scale=None):
    """The ungrouped kernel as it stood before grouped queries and the
    compacted grid (PR 32's ``_decode_kernel`` and wrapper, interpret
    mode: grid ``(N, L / block)``, the query spread and the head pick
    inside the kernel): the oracle for "bit for bit what it was"."""
    import functools

    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, d = q.shape
    L, hd = k.shape[1], h * d
    quantized = k_scale is not None

    def kernel(*refs):
        if quantized:
            (pos_ref, _, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
             m_scr, l_scr, acc_scr) = refs
        else:
            (pos_ref, _, q_ref, k_ref, v_ref, o_ref,
             m_scr, l_scr, acc_scr) = refs
        j = pl.program_id(1)
        row_pos = pos_ref[pl.program_id(0)]
        lo = jax.lax.broadcasted_iota(jnp.int32, (h, hd), 0) * d
        col = jax.lax.broadcasted_iota(jnp.int32, (h, hd), 1)
        diag = jnp.logical_and(col >= lo, col < lo + d)

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, -1e30, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        kt, vt = k_ref[0], v_ref[0]
        q_bd = jnp.where(diag, q_ref[0].astype(jnp.float32), 0.0)
        if quantized:
            s = jax.lax.dot_general(
                q_bd, kt.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * (
                    d ** -0.5 * ks_ref[0])
        else:
            s = jax.lax.dot_general(
                q_bd.astype(kt.dtype), kt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * d ** -0.5
        cols = j * block + jax.lax.broadcasted_iota(jnp.int32,
                                                    (1, block), 1)
        s = jnp.where(cols <= row_pos, s, -1e30)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p, vt.astype(jnp.float32),
                     preferred_element_type=jnp.float32) if quantized \
            else jnp.dot(p.astype(vt.dtype), vt,
                         preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

        @pl.when(j == pl.num_programs(1) - 1)
        def _finish():
            out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
            if quantized:
                out = out * vs_ref[0]
            o_ref[0] = jnp.sum(jnp.where(diag, out, 0.0), axis=0,
                               keepdims=True).astype(o_ref.dtype)

    qblk = pl.BlockSpec((1, 1, hd), lambda n_, j, pos_, last_: (n_, 0, 0))
    kblk = pl.BlockSpec(
        (1, block, hd),
        lambda n_, j, pos_, last_: (n_, jnp.minimum(j, last_[n_]), 0))
    sblk = pl.BlockSpec((1, h, 1), lambda n_, j, pos_, last_: (n_, 0, 0))
    operands, in_specs = [q.reshape(n, 1, hd), k, v], [qblk, kblk, kblk]
    if quantized:
        operands += [k_scale.reshape(n, h, 1), v_scale.reshape(n, h, 1)]
        in_specs += [sblk, sblk]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n, L // block),
            in_specs=in_specs, out_specs=qblk,
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, 1, hd), q.dtype),
        interpret=True)(pos, pos // block, *operands)
    return out.reshape(n, h, d)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_ungrouped_kernel_is_bit_for_bit_what_it_was(dtype, quantized):
    """``G == H`` — the int8 pooled decode's path, and GPT-2's — gives
    the bits it gave before the kernel took grouped queries and the
    compacted grid: the same blocks in the same order, the same sums."""
    q, k, v, pos = _pooled(n=5, L=64, dtype=dtype)
    pos = jnp.asarray([0, 15, 16, 40, 63], jnp.int32)
    ks = vs = None
    if quantized:
        k, v, ks, vs = _quantize(k, v)
    k, v = _folded(k), _folded(v)
    want = _pr32_kernel(q, k, v, pos, 16, ks, vs)
    got = pooled_decode_attention(q, k, v, pos, k_scale=ks, v_scale=vs,
                                  block=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fetched_blocks_against_a_hand_count():
    """Whole blocks up to ``min(pos, length - 1)`` for a row that
    decodes, none for one that does not; numpy in, numpy out (the
    serving counter), and the same numbers from jax arrays (the
    kernel's wrapper)."""
    pos = np.asarray([0, 127, 128, 300, 383, 500, 9000, 200])
    active = np.asarray([1, 1, 1, 1, 1, 1, 1, 0], bool)
    want = [1, 1, 2, 3, 3, 3, 3, 0]        # a ring of 384 holds 3 blocks
    got = fetched_blocks(pos, active, 384, 128)
    assert isinstance(got, np.ndarray) and got.tolist() == want
    on_device = fetched_blocks(jnp.asarray(pos, jnp.int32),
                               jnp.asarray(active), 384, 128)
    assert np.asarray(on_device).tolist() == want
    assert fetched_blocks(pos, True, 512, 512).tolist() == [1] * 8


def test_pool_counts_fetched_bytes_in_whole_blocks_of_each_leaf():
    """``KVPool.kv_fetched_bytes`` (the ``serving/kv_fetched_bytes``
    sample): per decoding row and K/V leaf the kernel's whole blocks up
    to the row's position, a ring never past its length."""
    from bigdl_tpu.serving import KVPool

    def init_carry(n):
        carry = {"pos": jnp.zeros((n,), jnp.int32)}
        for i, length in enumerate((1024, 2048)):   # a ring, a window
            carry[f"k{i}"] = jnp.zeros((n, length, 256), jnp.bfloat16)
            carry[f"v{i}"] = jnp.zeros((n, length, 256), jnp.bfloat16)
        return carry

    pool = KVPool(init_carry, 2)
    tile = 512 * 256 * 2            # one K or V block of 512 positions
    assert pool.kv_fetched_bytes([]) == 0
    assert pool.kv_fetched_bytes([0]) == (1 + 1) * 2 * tile
    assert pool.kv_fetched_bytes([511]) == (1 + 1) * 2 * tile
    assert pool.kv_fetched_bytes([512]) == (2 + 2) * 2 * tile
    assert pool.kv_fetched_bytes([1500]) == (2 + 3) * 2 * tile
    assert pool.kv_fetched_bytes([0, 1500]) == (2 + 5) * 2 * tile
    assert pool.kv_fetched_bytes([5000]) == (2 + 4) * 2 * tile
    assert pool.kv_fetched_bytes([5000]) == pool.kv_bytes_per_slot


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_schedule_addresses_only_held_blocks_of_rows_that_decode(seed):
    """The prefetch arrays the kernel's index maps read: every step,
    the flagless tail included, addresses a block at or under its
    row's ``pos`` of a row that decodes; each such row's blocks appear
    once, in order, between one FIRST and one LAST flag."""
    rng = np.random.default_rng(seed)
    n, length, block = 6, 1024, 128
    pos = rng.integers(0, length, size=n)
    active = rng.random(n) < 0.6
    active[seed % n] = True
    row, blk, flag, total = (np.asarray(x) for x in _decode_schedule(
        jnp.asarray(pos, jnp.int32), jnp.asarray(active), length, block))
    nb = fetched_blocks(pos, active, length, block)
    assert total == nb.sum() and len(row) == n * length // block
    assert active[row].all()
    assert (blk * block <= pos[row]).all() and (blk >= 0).all()
    steps = [(r, b) for r in range(n) for b in range(nb[r])]
    assert list(zip(row[:total], blk[:total])) == steps
    first, run, last = (flag & bit != 0 for bit in (1, 2, 4))
    assert run[:total].all() and not flag[total:].any()
    assert (first[:total] == (blk[:total] == 0)).all()
    assert (last[:total] == (blk[:total] == nb[row[:total]] - 1)).all()
    # the tail re-addresses the last step's block: no new DMA
    assert (row[total:] == row[total - 1]).all()
    assert (blk[total:] == blk[total - 1]).all()


def test_schedule_with_no_row_decoding_writes_one_row_of_zeros():
    row, blk, flag, total = (np.asarray(x) for x in _decode_schedule(
        jnp.asarray([5, 700], jnp.int32), jnp.zeros((2,), bool), 1024, 512))
    assert total == 0 and flag.tolist() == [1 + 4, 0, 0, 0]
    assert not blk.any() and (row == 1).all()
    q, k, v, pos, _ = _grouped(4, 2, 16, 200)
    out = pooled_decode_attention(q, k, v, pos, block=GBLOCK,
                                  interpret=True,
                                  active=jnp.zeros((4,), bool))
    assert not np.asarray(out).any()


def test_kernel_runs_by_rows_under_a_data_mesh():
    """A data-parallel serving plane is a plain jit over a pool whose
    slots XLA shards by itself, and a Mosaic kernel refuses to be
    partitioned automatically: the decode step calls it under a
    ``shard_map`` by rows (``models/transformer.py:_token_view``).
    There the kernel runs on each device over the rows it holds (their
    own schedule) with no collective, to the bits of the whole call."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bigdl_tpu.utils.compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1),
                ("data", "model"))
    q, k, v, pos, active = _grouped(4, 2, 16, 200, n=8)
    pos = pos.at[0].set(0).at[3].set(GL - 1)
    operands = (q, k, v, pos, jnp.asarray(active))

    def attend(q, k, v, pos, active):
        return decode_attention(q, k, v, pos, active=active, block=GBLOCK,
                                impl="kernel", interpret=True)

    placed = [jax.device_put(x, NamedSharding(
        mesh, P("data", *[None] * (x.ndim - 1)))) for x in operands]
    split = jax.jit(shard_map(attend, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False))
    got = split(*placed)
    assert got.sharding.spec[0] == "data"
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(attend(*operands)))
    text = split.lower(*placed).compile().as_text()
    assert "all-gather" not in text and "all-reduce" not in text


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
    kq, vq, ks, vs = _quantize(k, v)
    auto = decode_attention(q, _folded(kq), _folded(vq), pos, k_scale=ks,
                            v_scale=vs, active=pos > 0)
    ref = decode_attention_reference(q, kq, vq, pos, k_scale=ks, v_scale=vs)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))


def test_auto_impl_keeps_the_folded_sum_over_a_stored_float_cache():
    """What the three pooled decode programs ran before they went
    through the dispatch is what they run off the TPU: the folded
    whole-window sum, rows that do not decode included."""
    q, k, v, pos = _pooled(n=3, L=16)
    got = decode_attention(q, _folded(k), _folded(v), pos, scale=0.2,
                           active=jnp.asarray([True, False, True]))
    want = folded_decode_attention(q, _folded(k), _folded(v), pos,
                                   scale=0.2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


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
        decode_attention_reference(q, k[:, :, :3], v[:, :, :3], pos)
    with pytest.raises(ValueError, match="do not match q"):
        decode_attention_reference(q, k.reshape(2, 16, -1)[:, :, :-1],
                                   v.reshape(2, 16, -1)[:, :, :-1], pos)
    with pytest.raises(ValueError, match="not read through grouped"):
        decode_attention_reference(q, kq[:, :, :2], vq[:, :, :2], pos,
                                   k_scale=ks, v_scale=vs)
    with pytest.raises(ValueError, match="unknown impl"):
        decode_attention(q, k, v, pos, impl="magic")


# -- a latent cache: the values are the leading columns of the keys ---------


def _latent(h, d, pos_value, dtype=jnp.float32, n=4, seed=0):
    """ONE stored (n, GL, d) cache of one K/V head under h query heads;
    every row at ``pos_value``; row 1 and the last row do not decode."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((n, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((n, GL, d)), dtype)
    pos = jnp.full((n,), pos_value, jnp.int32)
    active = np.ones((n,), bool)
    active[[1, n - 1]] = False
    return q, k, pos, active


def _latent_oracle(q, k, pos, v_width, scale):
    """Independent dense spelling: the keys are the whole row, the
    values its leading ``v_width`` columns."""
    q64, k64 = np.asarray(q, np.float64), np.asarray(k, np.float64)
    out = np.zeros(q64.shape[:2] + (v_width,))
    for r in range(q64.shape[0]):
        w = int(pos[r]) + 1
        s = q64[r] @ k64[r, :w].T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[r] = (p / p.sum(-1, keepdims=True)) @ k64[r, :w, :v_width]
    return out


@pytest.mark.parametrize("active_rows", ["some", "all"])
@pytest.mark.parametrize("pos_value", [0, 200, 255, 256, GL - 1],
                         ids=["first", "mid_block", "block_end",
                              "block_start", "last"])
@pytest.mark.parametrize("h,d,v_width", [(20, 576, 512), (20, 640, 512),
                                         (4, 128, 32)])
def test_latent_kernel_matches_reference(h, d, v_width, pos_value,
                                         active_rows):
    """``v_width``: GLM-4.7-Flash's row as published (576 values, the
    leading 512 the latent), as the pool stores it (640 columns) and a
    toy; one K/V head under all query heads; rows that decode read the
    oracle's sum from ONE fetched tile, the others come back as zeros."""
    q, k, pos, active = _latent(h, d, pos_value)
    if active_rows == "all":
        active[:] = True
    want = _latent_oracle(q, k, pos, v_width, 1 / 16)
    ref = decode_attention_reference(q, k, None, pos, scale=1 / 16,
                                     v_width=v_width)
    fold = folded_decode_attention(q, k, None, pos, scale=1 / 16,
                                   v_width=v_width)
    ker = decode_attention(q, k, None, pos, scale=1 / 16, block=GBLOCK,
                           impl="kernel", interpret=True,
                           active=jnp.asarray(active), v_width=v_width)
    assert ker.shape == ref.shape == fold.shape == (4, h, v_width)
    for got in (ref, fold):
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5,
                                   rtol=2e-5)
    np.testing.assert_allclose(np.asarray(ker)[active], want[active],
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(ker)[~active].any()


def test_latent_kernel_is_the_grouped_kernel_handed_the_leaf_twice():
    """The single fetch against the least that counts as support: the
    leaf as keys AND as values through the grouped kernel (G = 1), the
    context cut to ``v_width``."""
    q, k, pos, active = _latent(20, 640, 300, dtype=jnp.bfloat16)
    once = decode_attention(q, k, None, pos, block=GBLOCK, impl="kernel",
                            interpret=True, active=jnp.asarray(active),
                            v_width=512)
    twice = pooled_decode_attention(q, k, k, pos, block=GBLOCK,
                                    interpret=True,
                                    active=jnp.asarray(active))[..., :512]
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))


def test_auto_impl_slices_the_keys_off_the_tpu():
    q, k, pos, _ = _latent(4, 128, 200)
    got = decode_attention(q, k, None, pos, v_width=32)
    want = folded_decode_attention(q, k, None, pos, v_width=32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case", ["a_v_array", "a_view", "too_wide",
                                  "another_width", "scales"])
def test_latent_validation_errors(case):
    q, k, pos, _ = _latent(4, 128, 10)
    kw = dict(v_width=32)
    v = None
    if case == "a_v_array":
        v = k
    elif case == "a_view":
        k = k.reshape(4, GL, 1, 128)
    elif case == "too_wide":
        kw = dict(v_width=129)
    elif case == "another_width":
        q = q[..., :64]
    else:
        kw.update(k_scale=jnp.ones((4, 4)), v_scale=jnp.ones((4, 4)))
    with pytest.raises(ValueError, match="v_width="):
        decode_attention_reference(q, k, v, pos, **kw)
