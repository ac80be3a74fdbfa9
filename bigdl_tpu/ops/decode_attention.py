"""Pooled decode attention as a Pallas TPU kernel (+ jnp reference).

The serving engine's decode step is memory-bandwidth-bound: every token
re-reads the whole pooled KV cache, stored ``(n_slots, max_len,
heads*head_dim)`` (head-major lanes), to score ONE query per row. This
module owns that inner loop:

* :func:`decode_attention_reference` — the plain jnp spelling: masked
  single-query attention over each row's own cache prefix
  ``0..pos[r]``, fp32 score/softmax accumulation, per-head einsums over
  the ``(N, L, H, D)`` view;
* :func:`folded_decode_attention` — the same sum computed against the
  STORED ``(N, L, H*D)`` array (block-diagonal query, no 4-D view), so
  the program that holds it never re-lays the pool out: the float
  decode steps' path;
* :func:`pooled_decode_attention` — the Pallas kernel (grid
  ``(n_rows, kv_blocks)``, online softmax in VMEM scratch, one
  ``(block_l, heads*head_dim)`` K/V tile resident per step) with the
  same ``interpret``-mode pattern off-TPU as ``ops.flash_attention``
  (the dispatch probe is shared: ``utils.compat.auto_interpret``). On
  a TPU it compiles or raises; it never drops to the interpreter or
  the reference.

Quantized KV (the int8 serving path — see docs/serving.md "Quantized KV
cache"): K/V arrive as int8 with ONE fp32 scale per (row, head)
(``k_scale``/``v_scale``, shape ``(N, H)``). Because the scale is
constant over the positions and lanes being contracted, dequantization
FACTORS OUT of both matmuls exactly —

    scores[n,h,l] = (q . k_int8) * (qk_scale * k_scale[n,h])
    out[n,h,d]    = (p . v_int8) * v_scale[n,h]

so the kernel's K/V loads stay int8 end-to-end (half the HBM traffic of
bf16) and the dequant costs two scalar multiplies per (row, head), not
an elementwise pass over the cache. The reference computes the
identically-factored expression, so interpret-mode numerics match to
float round-off (pinned by tests/test_decode_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.ops.flash_attention import _out_struct

_NEG_INF = -1e30  # finite sentinel, same convention as flash/decode steps


def _auto_interpret() -> bool:
    from bigdl_tpu.utils.compat import auto_interpret

    return auto_interpret()


def _check_qkv(q, k, v, k_scale, v_scale):
    """K/V come as the stored ``(N, L, H*D)`` array or its
    ``(N, L, H, D)`` view — the same bytes, head-major lanes."""
    if q.ndim != 3 or k.ndim not in (3, 4) or v.ndim != k.ndim:
        raise ValueError(
            f"expected q (N, H, D) and k/v (N, L, H*D) or (N, L, H, D), "
            f"got {q.shape} / {k.shape} / {v.shape}")
    n, h, d = q.shape
    tail = (h, d) if k.ndim == 4 else (h * d,)
    if k.shape != v.shape or k.shape[0] != n or k.shape[2:] != tail:
        raise ValueError(
            f"k/v {k.shape}/{v.shape} do not match q {q.shape}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            "quantized KV needs BOTH k_scale and v_scale (or neither)")
    if k_scale is not None:
        if k_scale.shape != (n, h) or v_scale.shape != (n, h):
            raise ValueError(
                f"per-(row, head) scales must be ({n}, {h}), got "
                f"{k_scale.shape} / {v_scale.shape}")
        if k.dtype != jnp.int8 or v.dtype != jnp.int8:
            raise ValueError(
                f"scaled K/V must be int8, got {k.dtype}/{v.dtype}")


# --------------------------------------------------------------- reference


def decode_attention_reference(q, k, v, pos, k_scale=None, v_scale=None,
                               scale: Optional[float] = None,
                               out_dtype=None):
    """Masked single-query pooled attention, plain jnp — the numerics
    contract the kernel is tested against AND the CPU serving path.

    ``q``: (N, H, D) one query per pooled row; ``k``/``v``:
    (N, L, H*D) per-row caches as stored, or their (N, L, H, D) view
    (float, or int8 with (N, H) fp32 ``k_scale``/``v_scale``);
    ``pos``: (N,) int32 — row ``r`` attends
    over its own cache columns ``0..pos[r]`` INCLUSIVE (the decode
    step's ``wpos``, where the new K/V was just written). Scores and
    softmax accumulate fp32 regardless of input dtype; the int8 path
    runs the q.k and p.v contractions on the RAW int8 values (cast to
    f32) and applies the per-(row, head) scales as factored-out scalar
    multiplies — exactly the kernel's fused-dequant math. Returns
    (N, H, D) in ``out_dtype`` (default: q's dtype)."""
    _check_qkv(q, k, v, k_scale, v_scale)
    n, h, d = q.shape
    L = k.shape[1]
    k = k.reshape(n, L, h, d)
    v = v.reshape(n, L, h, d)
    if scale is None:
        scale = d ** -0.5
    if out_dtype is None:
        out_dtype = q.dtype
    valid = jnp.arange(L)[None, None, :] <= \
        jnp.asarray(pos, jnp.int32)[:, None, None]
    if k_scale is not None:
        s = jnp.einsum("nhd,nlhd->nhl", q.astype(jnp.float32),
                       k.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        s = s * (scale * k_scale.astype(jnp.float32))[:, :, None]
        p = jax.nn.softmax(jnp.where(valid, s, _NEG_INF), axis=-1)
        ctx = jnp.einsum("nhl,nlhd->nhd", p, v.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        ctx = ctx * v_scale.astype(jnp.float32)[:, :, None]
    else:
        # dots run on the cache dtype (bf16 stays on the fast MXU path)
        # with f32 accumulation — the flash-kernel convention
        s = jnp.einsum("nhd,nlhd->nhl", q.astype(k.dtype), k,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(valid, s, _NEG_INF), axis=-1)
        ctx = jnp.einsum("nhl,nlhd->nhd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
    return ctx.astype(out_dtype)


def folded_decode_attention(q, k, v, pos, scale: Optional[float] = None,
                            out_dtype=None):
    """:func:`decode_attention_reference`'s float sum against the STORED
    cache ``(N, L, H*D)``, never through a 4-D view of it — what the
    float decode steps call every token.

    A 4-D view of the pool costs the program that holds it two
    pool-sized copies per tensor on the TPU (the device lays a
    ``(..., H, 64)`` bf16 array out ``max_len``-minor, the row scatter
    wants it the other way). So the row's query is spread into a
    block-diagonal ``(H*D, H)`` matrix (column ``h`` holds ``q[h]`` in
    lanes ``h*D..(h+1)*D``, zeros elsewhere — :func:`_decode_kernel`'s
    trick): ``K (L, H*D) @ q_bd`` IS the per-head scores, and the
    diagonal ``D``-wide blocks of ``p (H, L) @ V (L, H*D)`` are the
    per-head contexts. The off-diagonal products are exact zeros in the
    scores and discarded in the context, so this is the reference's sum
    with extra zero addends: same ``scale``-then-cast query, f32
    accumulation, ``-1e30`` mask and softmax, ``p`` cast to the cache
    dtype. ``q``: (N, H, D); ``pos``: (N,) inclusive last column.
    Returns (N, H, D) in ``out_dtype`` (default: q's dtype).

    GROUPED queries: a cache of ``G < H`` heads ``(N, L, G*D)`` serves
    query head ``j`` from K/V head ``j // (H // G)`` the same way:
    column ``j`` of the query matrix holds ``q[j]`` in its K/V head's
    lanes, and head ``j``'s context is that head's block of row ``j``."""
    n, h, d = q.shape
    if k.ndim != 3:
        raise ValueError(
            f"the folded form reads the stored (N, L, H*D) cache, got "
            f"{k.shape}")
    g = k.shape[-1] // d
    if g == h:
        _check_qkv(q, k, v, None, None)
    elif k.shape != v.shape or k.shape[0] != n or k.shape[-1] != g * d \
            or g == 0 or h % g:
        raise ValueError(
            f"k/v {k.shape}/{v.shape} hold no whole number of K/V heads "
            f"that divides q's {h} heads of {d}")
    L = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    if out_dtype is None:
        out_dtype = q.dtype
    qs = (q * scale).astype(k.dtype)
    if g == h:
        q_bd = (qs[:, :, :, None] * jnp.eye(h, dtype=k.dtype)[:, None, :]
                ).reshape(n, h * d, h)
    else:
        # own[j, c]: query head j reads K/V head c
        own = (jnp.arange(h)[:, None] // (h // g)
               == jnp.arange(g)[None, :])
        q_bd = (qs[:, :, None, :] * own.astype(k.dtype)[None, :, :, None]
                ).transpose(0, 2, 3, 1).reshape(n, g * d, h)
    s = jnp.einsum("nlc,nch->nhl", k, q_bd,
                   preferred_element_type=jnp.float32)
    valid = jnp.arange(L)[None, None, :] <= \
        jnp.asarray(pos, jnp.int32)[:, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, _NEG_INF), axis=-1)
    full = jnp.einsum("nhl,nlc->nhc", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)
    # head j's context is its own K/V head's D-wide block of row j
    if g == h:
        ctx = jnp.einsum("nhhd->nhd", full.reshape(n, h, h, d))
    else:
        ctx = jnp.einsum("nhgd,hg->nhd", full.reshape(n, h, g, d),
                         own.astype(jnp.float32))
    return ctx.astype(out_dtype)


# ------------------------------------------------------------------ kernel


def _decode_kernel(*refs, scale, quantized, skip, heads, head_dim):
    """Grid (N, n_l) — one pooled row per outer step, the KV-position
    axis INNER, so one ``(block_l, H*D)`` K tile and one V tile are
    VMEM-resident per step and the online-softmax state carries across
    the position blocks in scratch (the flash-forward recipe).

    Every head of a row is computed from the SAME lane-dense tile: the
    row's query is spread into a block-diagonal ``(H, H*D)`` matrix
    (row ``h`` holds ``q[h]`` in lanes ``h*D..(h+1)*D`` and zeros
    elsewhere), so ``q_bd . k_tile^T`` IS the per-head score matrix
    ``(H, block_l)`` and the diagonal ``D``-wide blocks of
    ``p . v_tile`` are the per-head contexts — two plain 2-D MXU
    matmuls, no in-kernel reshape or per-head strided load. Mosaic
    wants blocks whose last two dims are tile-aligned or whole;
    ``(block_l, H*D)`` is, where the per-head ``(block_l, 1, D)`` tile
    it replaces was refused by the compiler. The off-diagonal products
    are redundant MXU work; whether the MXU or the HBM read bounds the
    step has not been measured (ROADMAP S2).

    ``pos`` and each row's last needed block index arrive by scalar
    prefetch (SMEM): the first gates the block skip here, the second
    clamps the K/V index maps, so blocks past a row's ``pos`` are
    neither computed nor fetched.

    Quantized layout: int8 K/V tiles are loaded RAW; the (row, head)
    scales enter as ``(H, 1)`` column factors — k_scale folds into the
    score scaling, v_scale multiplies the accumulated context once at
    the end (exact: both are constant over the contracted axes)."""
    if quantized:
        (pos_ref, _, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (pos_ref, _, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    j = pl.program_id(1)
    n_l = pl.num_programs(1)
    bl = k_ref.shape[1]
    hd = heads * head_dim
    pos = pos_ref[pl.program_id(0)]

    def _head_diag():
        # (H, H*D) mask of each head's own D-wide lane block
        lo = jax.lax.broadcasted_iota(jnp.int32, (heads, hd), 0) * head_dim
        col = jax.lax.broadcasted_iota(jnp.int32, (heads, hd), 1)
        return jnp.logical_and(col >= lo, col < lo + head_dim)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _step():
        k = k_ref[0]                                    # (BL, H*D)
        v = v_ref[0]
        q_bd = jnp.where(_head_diag(), q_ref[0].astype(jnp.float32), 0.0)
        if quantized:
            s = jax.lax.dot_general(
                q_bd, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * (scale * ks_ref[0])
        else:
            s = jax.lax.dot_general(
                q_bd.astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
        cols = j * bl + jax.lax.broadcasted_iota(jnp.int32, (1, bl), 1)
        s = jnp.where(cols <= pos, s, _NEG_INF)         # (H, BL)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                          # (H, BL) f32
        alpha = jnp.exp(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        if quantized:
            pv = jnp.dot(p, v.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        else:
            pv = jnp.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv        # (H, H*D)

    if skip:
        # compiled path: key blocks entirely past the row's pos
        # contribute nothing — skip their gemms (most of the grid when
        # the pool is young). Interpret mode runs unconditionally: a
        # traced pl.when predicate is rejected there under shard_map
        # (same constraint the flash kernel documents).
        pl.when(j * bl <= pos)(_step)
    else:
        _step()

    @pl.when(j == n_l - 1)
    def _finish():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        out = acc_scr[...] / l_safe
        if quantized:
            out = out * vs_ref[0]
        # row h's own lane block is head h's context; the rest of the
        # row is the redundant cross-head product
        out = jnp.sum(jnp.where(_head_diag(), out, 0.0), axis=0,
                      keepdims=True)
        o_ref[0] = out.astype(o_ref.dtype)


#: VMEM the K and V tiles may take together, double-buffered (4 tiles)
#: — a quarter of the 16 MiB scoped default, leaving room for the f32
#: casts of the int8 path and the score rows
_KV_TILE_BUDGET = 4 * 1024 * 1024


def _auto_block_l(L: int, row_bytes: int) -> int:
    """KV-position tile length: the LARGEST of 512/384/256/128 that
    divides the 128-padded cache window and keeps the four resident
    ``(block, H*D)`` K/V tiles inside ``_KV_TILE_BUDGET`` (bigger tiles
    amortize grid-step overhead on the short-query decode grid).
    Divisibility is the load-bearing part: a non-dividing block forces
    :func:`pooled_decode_attention` to ``jnp.pad`` the K/V operands,
    and on the per-step decode hot path that pad is a full copy of the
    entire pooled cache — the exact HBM traffic this kernel exists to
    avoid. Any 128-multiple window (every real serving ``max_len``)
    gets pad 0 here; only sub-128 or ragged windows pay the
    (small-cache) pad."""
    padded = ((max(L, 1) + 127) // 128) * 128
    for b in (512, 384, 256, 128):
        if padded % b == 0 and 4 * b * row_bytes <= _KV_TILE_BUDGET:
            return b
    return 128


def pooled_decode_attention(q, k, v, pos, k_scale=None, v_scale=None,
                            scale: Optional[float] = None,
                            block: Optional[int] = None,
                            interpret: Optional[bool] = None,
                            out_dtype=None):
    """Pallas pooled decode attention over slot-indexed KV.

    Same contract as :func:`decode_attention_reference` (q ``(N, H, D)``,
    k/v ``(N, L, H*D)`` as stored or their ``(N, L, H, D)`` view, float
    or int8-with-``(N, H)``-scales, per-row inclusive ``pos``), computed
    by the tiled online-softmax kernel.
    ``block`` is the KV-position tile length (None = auto);
    ``interpret=None`` auto-selects Pallas interpreter mode off-TPU via
    the shared ``utils.compat.auto_interpret`` probe. The cache window
    is right-padded to a block multiple when needed — padded columns
    sit beyond every row's ``pos`` and are masked like any other
    out-of-window position. The kernel reads the cache as
    ``(N, L, H*D)`` (heads folded into the lane axis — see
    :func:`_decode_kernel`), which is how the pool stores it."""
    from jax.experimental.pallas import tpu as pltpu

    from bigdl_tpu.utils.compat import pallas_tpu_compiler_params

    _check_qkv(q, k, v, k_scale, v_scale)
    n, h, d = q.shape
    L = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    if out_dtype is None:
        out_dtype = q.dtype
    if interpret is None:
        interpret = _auto_interpret()
    if block is None:
        block = _auto_block_l(L, h * d * k.dtype.itemsize)
    quantized = k_scale is not None
    k = k.reshape(n, L, h * d)
    v = v.reshape(n, L, h * d)
    pad = (-L) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    pos1 = jnp.asarray(pos, jnp.int32).reshape(n)
    last_blk = pos1 // block
    # every block's last two dims are whole array dims or (block_l:
    # a 128-multiple, H*D: whole) — what the Mosaic lowering accepts
    qblk = pl.BlockSpec((1, 1, h * d), lambda n_, j, pos_, last_: (n_, 0, 0))
    # blocks past the row's pos re-address the last needed one: the
    # pipeline sees an unchanged block index and issues no DMA
    kblk = pl.BlockSpec(
        (1, block, h * d),
        lambda n_, j, pos_, last_: (n_, jnp.minimum(j, last_[n_]), 0))
    sblk = pl.BlockSpec((1, h, 1), lambda n_, j, pos_, last_: (n_, 0, 0))
    operands = [q.reshape(n, 1, h * d), k, v]
    in_specs = [qblk, kblk, kblk]
    if quantized:
        operands += [k_scale.astype(jnp.float32).reshape(n, h, 1),
                     v_scale.astype(jnp.float32).reshape(n, h, 1)]
        in_specs += [sblk, sblk]
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale),
                          quantized=quantized, skip=not interpret,
                          heads=h, head_dim=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, (L + pad) // block),
            in_specs=in_specs,
            out_specs=qblk,
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, h * d), jnp.float32),
            ]),
        out_shape=_out_struct((n, 1, h * d), out_dtype, pos1, *operands),
        compiler_params=None if interpret else pallas_tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="pooled_decode_attention",
    )(pos1, last_blk, *operands)
    return out.reshape(n, h, d)


def decode_attention(q, k, v, pos, k_scale=None, v_scale=None,
                     scale: Optional[float] = None,
                     block: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     impl: str = "auto", out_dtype=None):
    """The serving steps' dispatch point: ``impl="auto"`` runs the
    compiled Pallas kernel on TPU and the jnp reference elsewhere
    (interpret-mode Pallas is an emulator — correct but far too slow
    for the CPU CI serving loop); ``"kernel"``/``"reference"`` force a
    path (tests pin kernel-vs-reference numerics with
    ``impl="kernel", interpret=True``)."""
    if impl not in ("auto", "kernel", "reference"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl == "auto":
        impl = "reference" if _auto_interpret() else "kernel"
    if impl == "reference":
        return decode_attention_reference(
            q, k, v, pos, k_scale=k_scale, v_scale=v_scale, scale=scale,
            out_dtype=out_dtype)
    return pooled_decode_attention(
        q, k, v, pos, k_scale=k_scale, v_scale=v_scale, scale=scale,
        block=block, interpret=interpret, out_dtype=out_dtype)
