"""Pooled decode attention as a Pallas TPU kernel (+ jnp reference).

The serving engine's decode step is memory-bandwidth-bound: every token
reads the pooled KV cache, stored ``(n_slots, max_len,
kv_heads*head_dim)`` (head-major lanes), to score ONE query per row.
This module owns that inner loop:

* :func:`decode_attention_reference` — the plain jnp spelling: masked
  single-query attention over each row's own cache prefix
  ``0..pos[r]``, fp32 score/softmax accumulation, per-head einsums over
  the ``(N, L, G, D)`` view (``G <= H`` K/V heads: grouped queries);
* :func:`folded_decode_attention` — the same sum computed against the
  STORED ``(N, L, G*D)`` array (block-diagonal query, no 4-D view), so
  the program that holds it never re-lays the pool out: the whole
  window of every row is scored, then masked. The lockstep decode step
  runs it, and the pooled ones off the TPU;
* :func:`pooled_decode_attention` — the Pallas kernel, which fetches
  and scores only the blocks a DECODING row holds: one flat grid
  compacted over those blocks through scalar prefetch
  (:func:`_decode_schedule`), online softmax in VMEM scratch, one
  ``(block_l, G*D)`` K/V tile resident per step; the same
  ``interpret``-mode pattern off-TPU as ``ops.flash_attention`` (the
  dispatch probe is shared: ``utils.compat.auto_interpret``). On a TPU
  it compiles or raises; it never drops to the interpreter or the
  reference;
* :func:`decode_attention` — the pooled decode programs' dispatch
  between them.

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


def _check_qkv(q, k, v, k_scale, v_scale, v_width=None) -> int:
    """K/V come as the stored ``(N, L, G*D)`` array or its
    ``(N, L, G, D)`` view — the same bytes, head-major lanes — with
    ``G`` K/V heads, each read by ``H / G`` query heads. Returns ``G``.
    With ``v_width`` there is ONE stored array ``k`` (N, L, D) of one
    head that every query head reads, whose leading ``v_width`` columns
    are the values (``v`` is None): returns 1."""
    if v_width is not None:
        if v is not None or k_scale is not None or v_scale is not None \
                or q.ndim != 3 or k.ndim != 3 \
                or (k.shape[0], k.shape[2]) != (q.shape[0], q.shape[2]) \
                or not 0 < v_width <= k.shape[2]:
            raise ValueError(
                f"v_width={v_width}: expected q (N, H, D), ONE float cache "
                f"k (N, L, D) whose leading v_width columns are the values, "
                f"and no v, got {q.shape} / {k.shape} / "
                f"{None if v is None else v.shape}")
        return 1
    if q.ndim != 3 or k.ndim not in (3, 4) or v.ndim != k.ndim:
        raise ValueError(
            f"expected q (N, H, D) and k/v (N, L, G*D) or (N, L, G, D), "
            f"got {q.shape} / {k.shape} / {v.shape}")
    n, h, d = q.shape
    g = k.shape[2] if k.ndim == 4 else k.shape[2] // d
    tail = (g, d) if k.ndim == 4 else (g * d,)
    if k.shape != v.shape or k.shape[0] != n or k.shape[2:] != tail \
            or g == 0 or h % g:
        raise ValueError(
            f"k/v {k.shape}/{v.shape} do not match q {q.shape}: no whole "
            f"number of K/V heads of {d} that divides its {h} heads")
    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            "quantized KV needs BOTH k_scale and v_scale (or neither)")
    if k_scale is not None:
        if g != h:
            raise ValueError(
                f"quantized K/V of {g} heads under {h} query heads: the "
                "int8 cache is not read through grouped queries")
        if k_scale.shape != (n, h) or v_scale.shape != (n, h):
            raise ValueError(
                f"per-(row, head) scales must be ({n}, {h}), got "
                f"{k_scale.shape} / {v_scale.shape}")
        if k.dtype != jnp.int8 or v.dtype != jnp.int8:
            raise ValueError(
                f"scaled K/V must be int8, got {k.dtype}/{v.dtype}")
    return g


def _own_heads(h: int, g: int):
    """``own[j, c]``: query head ``j`` of ``h`` reads K/V head ``c`` of
    ``g`` (head ``j // (h // g)``)."""
    return jnp.arange(h)[:, None] // (h // g) == jnp.arange(g)[None, :]


# --------------------------------------------------------------- reference


def decode_attention_reference(q, k, v, pos, k_scale=None, v_scale=None,
                               scale: Optional[float] = None,
                               out_dtype=None, v_width=None):
    """Masked single-query pooled attention, plain jnp — the numerics
    contract the kernel is tested against AND the CPU serving path.

    ``q``: (N, H, D) one query per pooled row; ``k``/``v``:
    (N, L, G*D) per-row caches as stored, or their (N, L, G, D) view
    (float, or int8 with (N, H) fp32 ``k_scale``/``v_scale``); with
    ``G < H`` K/V heads, query head ``j`` reads K/V head
    ``j // (H // G)``; ``pos``: (N,) int32 — row ``r`` attends
    over its own cache columns ``0..pos[r]`` INCLUSIVE (the decode
    step's ``wpos``, where the new K/V was just written). Scores and
    softmax accumulate fp32 regardless of input dtype; the int8 path
    runs the q.k and p.v contractions on the RAW int8 values (cast to
    f32) and applies the per-(row, head) scales as factored-out scalar
    multiplies — exactly the kernel's fused-dequant math. Returns
    (N, H, D) in ``out_dtype`` (default: q's dtype). ``v_width``: the
    values are the leading ``v_width`` columns of the ONE cache ``k``
    (N, L, D) that all heads read (``v`` None); returns (N, H,
    v_width)."""
    g = _check_qkv(q, k, v, k_scale, v_scale, v_width)
    n, h, d = q.shape
    L = k.shape[1]
    k = k.reshape(n, L, g, d)
    v = k[..., :v_width] if v_width is not None else v.reshape(n, L, g, d)
    if g != h:
        k, v = (jnp.repeat(x, h // g, axis=2) for x in (k, v))
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
                            out_dtype=None, v_width=None):
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
    lanes, and head ``j``'s context is that head's block of row ``j``.

    ``v_width``: ONE cache ``k`` (N, L, D) that every head reads, whose
    leading ``v_width`` columns are the values (``v`` None): the query
    matrix is the queries themselves and the values a slice of the
    keys; returns (N, H, v_width)."""
    n, h, d = q.shape
    if k.ndim != 3:
        raise ValueError(
            f"the folded form reads the stored (N, L, G*D) cache, got "
            f"{k.shape}")
    g = _check_qkv(q, k, v, None, None, v_width)
    L = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    if out_dtype is None:
        out_dtype = q.dtype
    qs = (q * scale).astype(k.dtype)
    if v_width is not None:
        q_bd, v = qs.transpose(0, 2, 1), k[..., :v_width]
    elif g == h:
        q_bd = (qs[:, :, :, None] * jnp.eye(h, dtype=k.dtype)[:, None, :]
                ).reshape(n, h * d, h)
    else:
        own = _own_heads(h, g)
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
    if v_width is not None:
        ctx = full
    elif g == h:
        ctx = jnp.einsum("nhhd->nhd", full.reshape(n, h, h, d))
    else:
        ctx = jnp.einsum("nhgd,hg->nhd", full.reshape(n, h, g, d),
                         own.astype(jnp.float32))
    return ctx.astype(out_dtype)


# ------------------------------------------------------------------ kernel


def fetched_blocks(pos, active, length: int, block: int):
    """K/V blocks of ``block`` positions the kernel fetches for each row
    of one cache leaf ``length`` long: the blocks that hold columns
    ``0..min(pos, length - 1)`` of a row that decodes, none of a row
    that does not. ``pos``: (N,) inclusive last column; ``active``:
    (N,) bool. Plain arithmetic on numpy or jax arrays alike:
    :func:`pooled_decode_attention` lays its grid out from it, and the
    serving engine's ``serving/kv_fetched_bytes`` counts the same blocks
    from host state (``KVPool.kv_fetched_bytes``)."""
    held = (pos + 1).clip(0, length)
    return (held + (block - 1)) // block * active


#: what a grid step does, by bit of its ``flag``
_FIRST, _RUN, _LAST = 1, 2, 4


def _decode_schedule(pos, active, length: int, block: int):
    """The kernel's grid, compacted over the blocks the rows hold: step
    ``t`` works on block ``blk[t]`` of row ``row[t]``, the rows that
    decode in order and each one's :func:`fetched_blocks` in order, so
    no step addresses a block past a row's ``pos`` or any block of a
    row that does not decode. ``flag[t]``: ``_FIRST`` on a row's first
    block (reset the running softmax), ``_RUN`` (score the block),
    ``_LAST`` on its last (write the row out). Steps from ``total`` on
    (a static grid has ``N * length / block`` of them) re-address the
    last one with no flag: an unchanged block index, so no DMA. With no
    row decoding, step 0 resets and writes the last row, as zeros.
    Returns ``(row, blk, flag, total)``."""
    n = pos.shape[0]
    nb = fetched_blocks(pos, active, length, block).astype(jnp.int32)
    ends = jnp.cumsum(nb)
    total = ends[-1]
    steps = jnp.arange(n * (length // block), dtype=jnp.int32)
    t = jnp.minimum(steps, jnp.maximum(total - 1, 0))
    done = t[:, None] >= ends[None, :]        # rows wholly before step t
    row = jnp.minimum(jnp.sum(done, axis=1, dtype=jnp.int32), n - 1)
    blk = t - jnp.sum(jnp.where(done, nb[None, :], 0), axis=1)
    last = jnp.any(t[:, None] + 1 == ends[None, :], axis=1)
    flag = jnp.where(steps < total,
                     _RUN + _FIRST * (blk == 0) + _LAST * last, 0)
    flag = jnp.where((total == 0) & (steps == 0), _FIRST + _LAST, flag)
    return row, blk, flag.astype(jnp.int32), total


def _decode_kernel(*refs, scale, v_width=None):
    """One grid step of :func:`_decode_schedule`: one ``(block_l, C)``
    K tile and one V tile of one row are VMEM-resident (``C = G*D``,
    the K/V heads folded into the lanes), and the online-softmax state
    of the row carries across its blocks in scratch (the flash-forward
    recipe).

    Every head of a row is computed from the SAME lane-dense tile: the
    wrapper spreads the row's query into a block-diagonal ``(H, C)``
    matrix (row ``j`` holds ``q[j]`` in the lanes of its K/V head and
    zeros elsewhere), so ``q_bd . k_tile^T`` IS the per-head score
    matrix ``(H, block_l)`` and row ``j``'s own ``D``-wide block of
    ``p . v_tile`` is head ``j``'s context — two plain 2-D MXU matmuls,
    no in-kernel reshape or per-head strided load. Mosaic wants blocks
    whose last two dims are tile-aligned or whole; ``(block_l, C)`` is,
    where the per-head ``(block_l, 1, D)`` tile it replaces was refused
    by the compiler. The off-diagonal products cost the MXU nothing
    while ``H`` fits one pass of its columns.

    ``pos`` and the schedule arrive by scalar prefetch (SMEM): the
    index maps read the step's row and block from them, so only held
    blocks are ever fetched.

    Quantized layout: int8 K/V tiles are loaded RAW; the (row, head)
    scales enter as ``(H, 1)`` column factors — k_scale folds into the
    score scaling, v_scale multiplies the accumulated context once at
    the end (exact: both are constant over the contracted axes).

    ``v_width`` (a latent cache): there is no V tile. The ONE fetched
    tile is the keys, and its leading ``v_width`` lanes are the values
    of the second product."""
    pos_ref, row_ref, blk_ref, flag_ref, q_ref, k_ref, *rest = refs
    v_ref = rest.pop(0) if v_width is None else None
    *scale_refs, _, o_ref, m_scr, l_scr, acc_scr = rest
    quantized = bool(scale_refs)
    if quantized:
        ks_ref, vs_ref = scale_refs
    t = pl.program_id(0)
    flag = flag_ref[t]
    bl = k_ref.shape[1]
    # a bf16 product has one precision, and Mosaic refuses a process-
    # wide jax_default_matmul_precision that asks it for more
    precision = jax.lax.Precision.DEFAULT \
        if k_ref.dtype == jnp.bfloat16 else None

    @pl.when(flag & _FIRST != 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(flag & _RUN != 0)
    def _step():
        k = k_ref[0]                                    # (BL, C)
        v = k[:, :v_width] if v_width is not None else v_ref[0]
        q_bd = q_ref[0]                                 # (H, C)
        if quantized:
            s = jax.lax.dot_general(
                q_bd, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * (scale * ks_ref[0])
        else:
            s = jax.lax.dot_general(
                q_bd, k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * scale
        cols = blk_ref[t] * bl + jax.lax.broadcasted_iota(
            jnp.int32, (1, bl), 1)
        s = jnp.where(cols <= pos_ref[row_ref[t]], s, _NEG_INF)  # (H, BL)
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
            pv = jnp.dot(p.astype(v.dtype), v, precision=precision,
                         preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv        # (H, C)

    @pl.when(flag & _LAST != 0)
    def _finish():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        out = acc_scr[...] / l_safe
        if quantized:
            out = out * vs_ref[0]
        o_ref[0] = out.astype(o_ref.dtype)


#: VMEM the K and V tiles may take together, double-buffered (4 tiles)
#: — a quarter of the 16 MiB scoped default, leaving room for the f32
#: casts of the int8 path and the score rows
_KV_TILE_BUDGET = 4 * 1024 * 1024


def auto_block_l(L: int, row_bytes: int) -> int:
    """KV-position tile length: the LARGEST of 512/384/256/128 that
    divides the 128-padded cache window and keeps the four resident
    ``(block, G*D)`` K/V tiles inside ``_KV_TILE_BUDGET`` (bigger tiles
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
                            out_dtype=None, active=None, v_width=None):
    """Pallas pooled decode attention over slot-indexed KV.

    Same contract as :func:`decode_attention_reference` (q ``(N, H, D)``,
    k/v ``(N, L, G*D)`` as stored or their ``(N, L, G, D)`` view, float
    or int8-with-``(N, H)``-scales, per-row inclusive ``pos``), computed
    by the tiled online-softmax kernel, which fetches and scores only
    the blocks a row holds (:func:`_decode_schedule`). ``active``
    (N,) bool, default all: a row that does not decode costs no fetch
    and no grid step, and its output row is zeros. Compiled, it is a
    Mosaic kernel, which XLA does not partition by itself: a program
    that shards the rows over a mesh calls it under a ``shard_map`` by
    rows (``models/transformer.py:_token_view``).
    ``block`` is the KV-position tile length (None = auto);
    ``interpret=None`` auto-selects Pallas interpreter mode off-TPU via
    the shared ``utils.compat.auto_interpret`` probe. The cache window
    is right-padded to a block multiple when needed — padded columns
    sit beyond every row's ``pos`` and are masked like any other
    out-of-window position. The kernel reads the cache as
    ``(N, L, G*D)`` (heads folded into the lane axis — see
    :func:`_decode_kernel`), which is how the pool stores it.

    ``v_width`` (a latent cache): ``k`` (N, L, D) is the ONE stored
    array, read by every query head, and its leading ``v_width``
    columns are the values (``v`` None). Each held block is fetched
    ONCE and used for both products (the kernel's name in a profile is
    then ``latent_decode_attention``); returns (N, H, v_width)."""
    from jax.experimental.pallas import tpu as pltpu

    from bigdl_tpu.utils.compat import pallas_tpu_compiler_params

    g = _check_qkv(q, k, v, k_scale, v_scale, v_width)
    n, h, d = q.shape
    L, c = k.shape[1], g * d
    c_out = c if v_width is None else v_width     # lanes of the context
    if interpret is None:
        interpret = _auto_interpret()
    if block is None:
        block = auto_block_l(L, c * k.dtype.itemsize)
    scale = float(d ** -0.5 if scale is None else scale)
    if out_dtype is None:
        out_dtype = q.dtype
    kv = [k.reshape(n, L, c)] + ([] if v is None else [v.reshape(n, L, c)])
    pos = jnp.asarray(pos, jnp.int32).reshape(n)
    if active is None:
        active = jnp.ones((n,), bool)
    scales = () if k_scale is None else (k_scale, v_scale)
    pad = (-L) % block
    if pad:
        kv = [jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in kv]
    row, blk, flag, total = _decode_schedule(pos, active, L + pad, block)
    # own[j, c]: query head j reads K/V head c; row j of the
    # block-diagonal query holds q[j] in that head's lanes
    own = _own_heads(h, g)
    q_bd = jnp.where(
        own[None, :, :, None],
        q.astype(jnp.float32 if scales else k.dtype)[:, :, None, :],
        0).reshape(n, h, c)

    def at_row(t, pos_, row_, blk_, flag_):
        return (row_[t], 0, 0)

    # every block's last two dims are whole array dims or (block_l:
    # a 128-multiple, C: whole) — what the Mosaic lowering accepts
    qblk = pl.BlockSpec((1, h, c), at_row)
    oblk = pl.BlockSpec((1, h, c_out), at_row)
    kblk = pl.BlockSpec(
        (1, block, c), lambda t, pos_, row_, blk_, flag_: (row_[t], blk_[t], 0))
    sblk = pl.BlockSpec((1, h, 1), at_row)
    operands = [q_bd, *kv] + [
        s.astype(jnp.float32).reshape(n, h, 1) for s in scales]
    in_specs = [qblk] + [kblk] * len(kv) + [sblk] * len(scales)
    # the output starts as zeros and aliases them: the rows no step
    # writes (those that do not decode) stay zeros
    operands.append(jnp.zeros((n, h, c_out), out_dtype))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    n_prefetch = 4
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, v_width=v_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            # compiled, the grid is as long as the schedule; the
            # interpreter takes no dynamic bound and runs the flagless
            # tail
            grid=(row.shape[0] if interpret else jnp.maximum(total, 1),),
            in_specs=in_specs,
            out_specs=oblk,
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, c_out), jnp.float32),
            ]),
        out_shape=_out_struct((n, h, c_out), out_dtype, pos, *operands),
        input_output_aliases={n_prefetch + len(operands) - 1: 0},
        compiler_params=None if interpret else pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="pooled_decode_attention" if v_width is None
        else "latent_decode_attention",
    )(pos, row, blk, flag, *operands)
    if v_width is not None:
        return out
    # head j's context is its own K/V head's D-wide block of row j
    return jnp.sum(jnp.where(own[None, :, :, None],
                             out.reshape(n, h, g, d), 0), axis=2)


def decode_attention(q, k, v, pos, k_scale=None, v_scale=None,
                     scale: Optional[float] = None,
                     block: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     impl: str = "auto", out_dtype=None, active=None,
                     v_width=None):
    """The pooled decode steps' dispatch point: ``impl="auto"`` runs
    the compiled Pallas kernel on a TPU. Elsewhere (interpret-mode
    Pallas is an emulator — correct but far too slow for the CPU CI
    serving loop) it runs the whole-window jnp sums, which compute the
    rows that do not decode too: :func:`folded_decode_attention` over a
    stored float cache, :func:`decode_attention_reference` over an int8
    one or a 4-D view. ``"kernel"``/``"reference"`` force a path
    (tests pin kernel-vs-reference numerics with ``impl="kernel",
    interpret=True``). ``active`` (N,) bool: the rows that decode; the
    output rows of the others are ballast.

    ``v_width``: a LATENT cache. ``k`` (N, L, D) is the one stored
    array of one K/V head that all ``H`` query heads (N, H, D) read,
    the values are its leading ``v_width`` columns, and ``v`` is None:
    the kernel fetches each held block once for both products, the
    folded sum slices the keys. Returns (N, H, v_width)."""
    if impl not in ("auto", "kernel", "reference"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl == "auto" and not _auto_interpret():
        impl = "kernel"
    if impl == "kernel":
        return pooled_decode_attention(
            q, k, v, pos, k_scale=k_scale, v_scale=v_scale, scale=scale,
            block=block, interpret=interpret, out_dtype=out_dtype,
            active=active, v_width=v_width)
    if impl == "auto" and k_scale is None and k.ndim == 3:
        return folded_decode_attention(q, k, v, pos, scale=scale,
                                       out_dtype=out_dtype, v_width=v_width)
    return decode_attention_reference(
        q, k, v, pos, k_scale=k_scale, v_scale=v_scale, scale=scale,
        out_dtype=out_dtype, v_width=v_width)
