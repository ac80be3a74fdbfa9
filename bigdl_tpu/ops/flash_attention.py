"""Flash attention as Pallas TPU kernels (forward + backward).

No reference counterpart (SURVEY.md §5.7 — the reference is attention-free);
this is part of the framework's long-context extension. The dense
``attention`` in ``bigdl_tpu.parallel.ring_attention`` materialises the
(T, T) score matrix in HBM; these kernels keep scores in VMEM tiles with an
online softmax (running max / normaliser), so memory is linear in T and the
QK^T / PV gemms stay on the MXU back-to-back without round-tripping HBM.

Layout: public API takes (B, T, H, D) to match the attention layers; the
kernels run on (B*H, T, D) with a (batch*heads, seq-block) grid. The
backward pass is the FlashAttention-2 split: a dq kernel gridded over query
blocks and a dk/dv kernel gridded over key blocks, both replaying the
online softmax from the saved logsumexp.

Numerics: accumulation is f32 regardless of input dtype (bf16 in, f32
softmax state, cast on write) — the `jax.default_matmul_precision` analog
of the reference's fp32 MKL paths.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30  # finite sentinel: keeps exp() well-defined for masked rows


def _auto_interpret() -> bool:
    # shared platform probe (utils.compat.auto_interpret): one dispatch
    # decision for every Pallas kernel in ops/, so flash and the pooled
    # decode kernel can't drift on the CPU/TPU interpret choice
    from bigdl_tpu.utils.compat import auto_interpret

    return auto_interpret()


# ---------------------------------------------------------------- forward


def _dot_nt(a, b):
    """a @ b.T without materializing the transpose: dot_general contracting
    the trailing (lane) dims — the layout Mosaic feeds the MXU directly."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, off_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, causal, kv_len, kp_len, skip):
    """Grid (BH, n_q, n_k) — the KV axis is a GRID dimension, so only one
    (block_q, d) q tile and one (block_k, d) k/v tile are VMEM-resident per
    step (O(block²) VMEM at any T); the online-softmax state lives in
    scratch that persists across the inner kv steps.

    Interior blocks skip ALL masking work (statically when the sequence is
    unpadded and non-causal; via a separate unmasked pl.when branch for
    causal blocks fully below the diagonal) — the iota/compare/select chain
    on a block² tile otherwise rivals the softmax itself in VPU time."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    padded = kp_len != kv_len  # static: does any key block need a tail mask?
    # diagonal offset: 0 = standard causal (col <= row), -1 = STRICT causal
    # (col < row) — striped ring attention's future-originated blocks.
    # full-block read, not [0, 0]: the HLO interpreter's vma check rejects
    # a dynamic_slice of a device-varying operand with invariant indices
    off = jnp.reshape(off_ref[...], ())

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # causal: key blocks entirely above the (offset) diagonal contribute
    # nothing
    needed = True
    if causal and skip:
        needed = kj * bk <= (qi + 1) * bq - 1 + off

    def _accumulate(s):
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)

    def _scores():
        # dots run on the INPUT dtype (bf16 stays on the fast MXU path)
        # with f32 accumulation; softmax state is always f32
        return _dot_nt(q_ref[0], k_ref[0]) * scale

    def _masked_step():
        s = _scores()
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        if padded:
            mask = cols < kv_len
            if causal:
                mask = jnp.logical_and(mask, cols <= rows + off)
        else:
            mask = cols <= rows + off
        _accumulate(jnp.where(mask, s, _NEG_INF))

    if not skip:
        # interpret mode: traced pl.when predicates are rejected inside
        # shard_map — run one unconditional step (mask when anything at
        # all needs masking)
        if causal or padded:
            _masked_step()
        else:
            _accumulate(_scores())
    elif not causal and not padded:
        _accumulate(_scores())
    elif not causal:  # padded, non-causal: only the LAST key block is masked
        pl.when(kj < n_k - 1)(lambda: _accumulate(_scores()))
        pl.when(kj == n_k - 1)(_masked_step)
    else:
        # causal: full (entirely below-diagonal, untouched by padding)
        # blocks take the unmasked path; diagonal/tail blocks pay the mask
        full_below = (kj + 1) * bk - 1 <= qi * bq + off
        if padded:
            full_below = jnp.logical_and(full_below, kj < n_k - 1)
        pl.when(full_below)(lambda: _accumulate(_scores()))
        pl.when(jnp.logical_and(needed, jnp.logical_not(full_below)))(
            _masked_step)

    @pl.when(kj == n_k - 1)
    def _finish():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[...] + jnp.log(l_safe)).astype(jnp.float32)


# --------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, off_ref,
               dq_ref, dq_scr, *, scale, causal, kv_len, kp_len, skip):
    """Grid (BH, n_q, n_k): dq accumulates in scratch across kv steps.
    Same masked/unmasked step split as the forward kernel."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    padded = kp_len != kv_len
    off = jnp.reshape(off_ref[...], ())

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    needed = True
    if causal and skip:
        needed = kj * bk <= (qi + 1) * bq - 1 + off

    def _step(with_mask):
        q = q_ref[0]
        do = do_ref[0]                                  # (BQ, D)
        lse = lse_ref[0]                                # (BQ, 1)
        delta = delta_ref[0]                            # (BQ, 1)
        k = k_ref[0]
        v = v_ref[0]
        s = _dot_nt(q, k) * scale
        if with_mask:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            if padded:
                mask = cols < kv_len
                if causal:
                    mask = jnp.logical_and(mask, cols <= rows + off)
            else:
                mask = cols <= rows + off
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)                            # (BQ, BK) f32
        dp = _dot_nt(do, v)
        ds = p * (dp - delta)
        dq_scr[...] = dq_scr[...] + jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    if not skip:
        _step(causal or padded)
    elif not causal and not padded:
        _step(False)
    elif not causal:
        pl.when(kj < n_k - 1)(lambda: _step(False))
        pl.when(kj == n_k - 1)(lambda: _step(True))
    else:
        full_below = (kj + 1) * bk - 1 <= qi * bq + off
        if padded:
            full_below = jnp.logical_and(full_below, kj < n_k - 1)
        pl.when(full_below)(lambda: _step(False))
        pl.when(jnp.logical_and(needed, jnp.logical_not(full_below)))(
            lambda: _step(True))

    @pl.when(kj == n_k - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, off_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, skip):
    """Grid (BH, n_k, n_q): dk/dv accumulate in scratch across query steps.
    Padded query rows are safe: q and delta are zero-padded so ds and do
    vanish there."""
    ki = pl.program_id(1)
    qj = pl.program_id(2)
    n_q = pl.num_programs(2)
    bk = k_ref.shape[1]
    bq = q_ref.shape[1]
    off = jnp.reshape(off_ref[...], ())

    @pl.when(qj == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    needed = True
    if causal and skip:  # query blocks entirely above the diagonal contribute 0
        needed = (qj + 1) * bq - 1 + off >= ki * bk

    def _step(with_mask):
        k = k_ref[0]                                    # (BK, D)
        v = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = _dot_nt(q, k) * scale
        if with_mask:
            rows = qj * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            s = jnp.where(cols <= rows + off, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = _dot_nt(do, v)
        ds = p * (dp - delta)
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if not skip:
        _step(causal)
    elif not causal:
        _step(False)
    else:
        # query block entirely BELOW the diagonal (all rows >= all cols):
        # no causal mask needed
        full_below = qj * bq + off >= (ki + 1) * bk - 1
        pl.when(full_below)(lambda: _step(False))
        pl.when(jnp.logical_and(needed, jnp.logical_not(full_below)))(
            lambda: _step(True))

    @pl.when(qj == n_q - 1)
    def _finish():
        # the q·k^T scale folds into dk once here (ds was computed on the
        # unscaled s gradient path)
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ----------------------------------------------------------- host wrappers


def _pad_seq(x, block):
    t = x.shape[1]
    pad = (-t) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _out_struct(shape, dtype, *refs):
    """ShapeDtypeStruct carrying the UNION of the operands' varying-manual-
    axes sets, so pallas_call type-checks inside shard_map (check_vma) even
    when operands vary over different axes."""
    from bigdl_tpu.utils.compat import varying_axes

    vma = frozenset()
    for ref in refs:
        vma = vma | varying_axes(ref)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _off_arr(causal_offset):
    """Diagonal-offset operand: (1, 1) int32, 0 unless given (a possibly
    TRACED scalar — striped ring passes src-vs-rank dependent offsets)."""
    if causal_offset is None:
        return jnp.zeros((1, 1), jnp.int32)
    return jnp.asarray(causal_offset, jnp.int32).reshape(1, 1)


def _flash_fwd(q3, k3, v3, scale, causal, block, interpret,
               causal_offset=None):
    from jax.experimental.pallas import tpu as pltpu

    from bigdl_tpu.utils.compat import pallas_tpu_compiler_params

    bh, t, d = q3.shape
    tp = t + (-t) % block
    qp, kp, vp = (_pad_seq(x, block) for x in (q3, k3, v3))
    off = _off_arr(causal_offset)
    kv_len = k3.shape[1]
    kp_len = kp.shape[1]
    # grid: kv axis INNERmost so the scratch softmax state carries across it
    grid = (bh, tp // block, kp_len // block)
    qblk = lambda n: pl.BlockSpec((1, block, n), lambda b, i, j: (b, i, 0))
    kblk = lambda n: pl.BlockSpec((1, block, n), lambda b, i, j: (b, j, 0))
    oblk = pl.BlockSpec((1, 1), lambda b, i, j: (0, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          kv_len=kv_len, kp_len=kp_len, skip=not interpret),
        grid=grid,
        in_specs=[qblk(d), kblk(d), kblk(d), oblk],
        out_specs=[qblk(d), qblk(1)],
        out_shape=[
            _out_struct((bh, tp, d), q3.dtype, q3, k3, v3, off),
            _out_struct((bh, tp, 1), jnp.float32, q3, k3, v3, off),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32),
        ],
        compiler_params=None if interpret else pallas_tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(qp, kp, vp, off)
    return o[:, :t], lse[:, :t]


def _flash_bwd(q3, k3, v3, o3, lse, do3, scale, causal, block, interpret,
               causal_offset=None):
    from jax.experimental.pallas import tpu as pltpu

    from bigdl_tpu.utils.compat import pallas_tpu_compiler_params

    bh, t, d = q3.shape
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)             # (BH, T, 1)
    qp, kp, vp, dop = (_pad_seq(x, block) for x in (q3, k3, v3, do3))
    off = _off_arr(causal_offset)
    lsep = jnp.pad(lse, ((0, 0), (0, qp.shape[1] - t), (0, 0)))
    deltap = jnp.pad(delta, ((0, 0), (0, qp.shape[1] - t), (0, 0)))
    tp = qp.shape[1]
    kp_len = kp.shape[1]
    qblk = lambda n: pl.BlockSpec((1, block, n), lambda b, i, j: (b, i, 0))
    kblk = lambda n: pl.BlockSpec((1, block, n), lambda b, i, j: (b, j, 0))
    oblk = pl.BlockSpec((1, 1), lambda b, i, j: (0, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          kv_len=k3.shape[1], kp_len=kp_len,
                          skip=not interpret),
        grid=(bh, tp // block, kp_len // block),
        in_specs=[qblk(d), kblk(d), kblk(d), qblk(d), qblk(1), qblk(1),
                  oblk],
        out_specs=qblk(d),
        out_shape=_out_struct((bh, tp, d), q3.dtype, q3, k3, v3, off),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=None if interpret else pallas_tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lsep, deltap, off)

    # dk/dv: key axis is the carried (outer-block) dim, queries innermost
    kblk2 = lambda n: pl.BlockSpec((1, block, n), lambda b, i, j: (b, i, 0))
    qblk2 = lambda n: pl.BlockSpec((1, block, n), lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          skip=not interpret),
        grid=(bh, kp_len // block, tp // block),
        in_specs=[qblk2(d), kblk2(d), kblk2(d), qblk2(d), qblk2(1), qblk2(1),
                  oblk],
        out_specs=[kblk2(d), kblk2(d)],
        out_shape=[_out_struct((bh, kp_len, d), k3.dtype, q3, k3, v3, off),
                   _out_struct((bh, kp_len, d), v3.dtype, q3, k3, v3, off)],
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=None if interpret else pallas_tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lsep, deltap, off)
    return dq[:, :t], dk[:, :k3.shape[1]], dv[:, :v3.shape[1]]


# ------------------------------------------------------------- public API


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q3, k3, v3, scale, causal, block, interpret):
    o, _ = _flash_fwd(q3, k3, v3, scale, causal, block, interpret)
    return o


def _flash_vjp_fwd(q3, k3, v3, scale, causal, block, interpret):
    o, lse = _flash_fwd(q3, k3, v3, scale, causal, block, interpret)
    return o, (q3, k3, v3, o, lse)


def _flash_vjp_bwd(scale, causal, block, interpret, res, do3):
    q3, k3, v3, o3, lse = res
    return _flash_bwd(q3, k3, v3, o3, lse, do3, scale, causal, block,
                      interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _bthd_plumbing(q, k, v, scale, interpret):
    """Shared layout/default handling: (B,T,H,D) API ↔ (B*H,T,D) kernels.
    Returns (q3, k3, v3, scale, interpret, from3, to3): from3 restores the
    public layout, to3 maps further (B,T,H,D) operands (o, do) down."""
    if interpret is None:
        interpret = _auto_interpret()
    b, t, h, d = q.shape
    if scale is None:
        scale = d ** -0.5

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    def from3(o3):
        return o3.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    return (to3(q), to3(k), to3(v), float(scale), bool(interpret), from3,
            to3)


def _check_causal_offset(causal, causal_offset):
    if causal_offset is not None and not causal:
        raise ValueError(
            "causal_offset requires causal=True — the non-causal kernel "
            "branches apply no mask, so the offset would be silently "
            "ignored")


def _auto_block(t_max: int) -> int:
    """Pick the VMEM tile length: as large as the scoped-VMEM budget allows
    (the block² f32 score tile caps at 1024 → 4 MB) — big tiles amortize
    grid-step overhead, the dominant cost at long T (measured on v5e:
    T=32k causal fwd+bwd 215 ms at block 128 → 52 ms at block 1024)."""
    padded = ((max(t_max, 1) + 127) // 128) * 128
    return max(128, min(1024, padded))


def flash_attention_with_lse(q, k, v, scale: Optional[float] = None,
                             block: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             causal: bool = False,
                             causal_offset=None):
    """Forward-only fused attention returning ``(out, lse)`` — the
    per-query log-sum-exp lets callers merge partial attention blocks with
    the online-softmax rule (ring attention's flash path; ``causal=True``
    for the diagonal block of a causal ring).
    ``causal_offset`` shifts the diagonal: -1 = strict causal
    (``col < row``), as striped ring attention needs for blocks from
    later-ranked stripes; may be a traced scalar.
    ``out``: (B, T, H, D); ``lse``: (B, H, T) float32.
    """
    _check_causal_offset(causal, causal_offset)
    b, t, h, d = q.shape
    if block is None:
        block = _auto_block(max(q.shape[1], k.shape[1]))
    q3, k3, v3, scale, interpret, from3, _ = _bthd_plumbing(
        q, k, v, scale, interpret)
    o3, lse = _flash_fwd(q3, k3, v3, scale, bool(causal), int(block),
                         interpret, causal_offset=causal_offset)
    return from3(o3), lse[..., 0].reshape(b, h, t)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Fused attention over (B, T, H, D) tensors; differentiable.

    Drop-in for ``bigdl_tpu.parallel.ring_attention.attention`` with
    O(T) memory. ``block`` is the VMEM tile length (MXU-aligned multiple of
    128; ``None`` auto-sizes, see :func:`_auto_block`).
    ``interpret=None`` auto-selects Pallas interpreter mode off-TPU.
    """
    if block is None:
        block = _auto_block(max(q.shape[1], k.shape[1]))
    q3, k3, v3, scale, interpret, from3, _ = _bthd_plumbing(
        q, k, v, scale, interpret)
    return from3(_flash(q3, k3, v3, scale, bool(causal), int(block),
                        interpret))


def flash_attention_block_grads(q, k, v, o, lse, do,
                                scale: Optional[float] = None,
                                block: Optional[int] = None,
                                interpret: Optional[bool] = None,
                                causal: bool = False,
                                causal_offset=None):
    """Per-block backward against GLOBAL softmax statistics — the ring
    backward's building block.

    ``q/o/do``: (B, Tq, H, D); ``k/v``: (B, Tk, H, D); ``lse``: (B, H, Tq)
    — the log-sum-exp of the FULL (all-blocks) softmax, so the block's
    probabilities ``exp(s − lse)`` are the true global ones and block
    gradients sum exactly across blocks. Returns ``(dq, dk, dv)`` shaped
    like q/k/v.
    """
    _check_causal_offset(causal, causal_offset)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if block is None:
        block = _auto_block(max(tq, tk))
    q3, k3, v3, scale, interpret, from3, to3 = _bthd_plumbing(
        q, k, v, scale, interpret)
    o3, do3 = to3(o), to3(do)
    lse3 = lse.reshape(b * h, tq, 1)
    dq3, dk3, dv3 = _flash_bwd(q3, k3, v3, o3, lse3, do3, scale,
                               bool(causal), int(block), interpret,
                               causal_offset=causal_offset)
    dq = from3(dq3)
    dk = dk3.reshape(b, h, tk, d).transpose(0, 2, 1, 3)
    dv = dv3.reshape(b, h, tk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv
