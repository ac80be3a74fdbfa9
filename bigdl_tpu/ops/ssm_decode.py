"""One decode step of a Mamba-2 scan state as a Pallas TPU kernel (+ jnp
reference).

A hybrid model's decode step carries, per layer and slot, a float32
scan state ``S`` of ``heads x head_dim x d_state`` (Falcon-H1: 32 x 128
x 256, 4 MB a slot). One token advances it by

    S' = S * exp(dt * A) + (dt * x) (x) B        y = S' . C

per head, with ``x`` the head's input, ``B`` / ``C`` its scan group's
input and output projections and ``dt`` its step. The state dwarfs
everything else a row reads in the step, so the update is a pass over
bytes: read once, written once.

* :func:`ssm_decode_reference` — the plain jnp spelling over every row
  of the stored leaf, rows that do not decode selected back: the
  numerics contract the kernel is tested against AND the serving path
  off the TPU;
* :func:`ssm_decode_kernel` — the Pallas kernel, which passes the
  DECODING rows only: one grid step a (row, scan group), the rows
  compacted through scalar prefetch, each block read once, updated and
  written once in VMEM, the leaf updated in place
  (``input_output_aliases``), so a row that does not decode costs no
  byte and keeps its own;
* :func:`ssm_decode` — the decode step's dispatch between them (the
  probe ``utils.compat.auto_interpret`` that ``ops.decode_attention``
  shares).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.ops.flash_attention import _out_struct


def _check(x, Bm, Cm, dt, A, S, active):
    """``x`` (N, G, hg, d), ``Bm`` / ``Cm`` (N, G, n), ``dt`` (N, G,
    hg), ``A`` (G, hg), ``S`` (N, G*hg, d, n) float32 as stored,
    ``active`` (N,)."""
    n_rows, G, hg, d = x.shape
    want = {"Bm": (n_rows, G, S.shape[-1]), "Cm": (n_rows, G, S.shape[-1]),
            "dt": (n_rows, G, hg), "A": (G, hg),
            "S": (n_rows, G * hg, d, S.shape[-1]), "active": (n_rows,)}
    for name, arr in (("Bm", Bm), ("Cm", Cm), ("dt", dt), ("A", A),
                      ("S", S), ("active", active)):
        if arr.shape != want[name]:
            raise ValueError(f"{name} {arr.shape} does not match x "
                             f"{x.shape}: expected {want[name]}")
    if S.dtype != jnp.float32:
        raise ValueError(f"the scan state is float32, got {S.dtype}")


def ssm_decode_reference(x, Bm, Cm, dt, A, S, active):
    """One step of the recurrence for every row of ``S`` (shapes as
    :func:`_check`), in float32. Returns ``y`` (N, G, hg, d) float32 and
    the new state (N, G*hg, d, n): rows where ``active`` is false keep
    their state bitwise and read ``y`` zero."""
    _check(x, Bm, Cm, dt, A, S, active)
    n_rows, G, hg, d = x.shape
    S5 = S.reshape(n_rows, G, hg, d, -1)
    x0 = x.astype(jnp.float32)
    B0 = Bm.astype(jnp.float32)[:, :, None, None]              # N,G,1,1,n
    C0 = Cm.astype(jnp.float32)[:, :, None, None]
    S_new = S5 * jnp.exp(dt * A)[..., None, None] \
        + (dt[..., None] * x0)[..., None] * B0
    y = jnp.sum(S_new * C0, axis=-1)
    on = active.astype(bool)
    y = jnp.where(on[:, None, None, None], y, 0.0)
    S_new = jnp.where(on[:, None, None, None],
                      S_new.reshape(S.shape), S)
    return y, S_new


# ------------------------------------------------------------------ kernel


def _ssm_kernel(rows_ref, count_ref, decay_ref, s_ref, dtx_ref, b_ref, c_ref,
                _, s_out, y_out):
    """One grid step: scan group ``j`` (``hg`` heads) of row
    ``rows[t]``.

    ``decay_ref`` (N * heads,), in SMEM beside the row order: ``exp(dt
    A)`` of every row's heads; ``s_ref`` (1, hg, d, n): the group's
    state, the state's ``d`` on sublanes and ``n`` on lanes; ``dtx_ref``
    (1, 1, hg, d): ``dt x`` of its heads, a row of lanes each; ``b_ref``
    / ``c_ref`` (1, 1, 1, n): the group's ``B`` / ``C``.

    Head ``h``'s update needs ``dt x`` as a COLUMN (one value a
    sublane, broadcast along ``n``) and gives ``y`` as one (a lane
    reduction), where both are stored as rows: the block's ``dt x`` is
    transposed once, a head's column taken from it by a masked lane
    sum, its ``y`` column put into a ``(d, hg)`` matrix that is
    transposed back once at the end. A step past the count (the
    interpreter's, or the one step a compiled grid keeps when nothing
    decodes) copies its block through and writes ``y`` zeros."""
    t = pl.program_id(0)
    hg, d = s_ref.shape[1], s_ref.shape[2]
    # the group's first head in the flat (row, head) order of decay_ref
    first = (rows_ref[t] * pl.num_programs(1) + pl.program_id(1)) * hg

    @pl.when(t < count_ref[0])
    def _update():
        lanes = jax.lax.broadcasted_iota(jnp.int32, (d, hg), 1)
        dtx_cols = dtx_ref[0, 0].T                             # (d, hg)
        b, c = b_ref[0, 0], c_ref[0, 0]                        # (1, n)

        def head(h, ys):
            col = jnp.sum(jnp.where(lanes == h, dtx_cols, 0.0), axis=1,
                          keepdims=True)                       # (d, 1)
            s_new = s_ref[0, h] * decay_ref[first + h] + col * b
            s_out[0, h] = s_new
            y = jnp.sum(s_new * c, axis=1, keepdims=True)      # (d, 1)
            return jnp.where(lanes == h, y, ys)

        ys = jax.lax.fori_loop(0, hg, head, jnp.zeros((d, hg), jnp.float32))
        y_out[0, 0] = ys.T

    @pl.when(t >= count_ref[0])
    def _keep():
        s_out[...] = s_ref[...]
        y_out[...] = jnp.zeros(y_out.shape, y_out.dtype)


def ssm_decode_kernel(x, Bm, Cm, dt, A, S, active,
                      interpret: Optional[bool] = None):
    """:func:`ssm_decode_reference`'s step as a Pallas kernel over the
    rows that decode (shapes as :func:`_check`). ``S`` is updated in
    place: call it on a donated leaf and the program keeps ONE copy of
    the state; the rows it does not visit keep their bytes, and their
    ``y`` is zeros. A grid step takes one scan group's heads, whose
    ``B`` and ``C`` it shares, and keeps four ``hg x d x n`` float32
    tiles in VMEM (in and out, double-buffered): 8 MB at Falcon-H1's
    group of 16 heads. On a v5e a whole row's 32 heads a step ran as
    fast, and 8 heads a step 3% slower."""
    from jax.experimental.pallas import tpu as pltpu

    from bigdl_tpu.utils.compat import auto_interpret, \
        pallas_tpu_compiler_params

    _check(x, Bm, Cm, dt, A, S, active)
    n_rows, G, hg, d = x.shape
    n = S.shape[-1]
    if interpret is None:
        interpret = auto_interpret()
    # the decoding rows first, in order, then the others: a compiled
    # grid is as long as the count, the interpreter's copies the rest
    on = active.astype(bool)
    rows = jnp.argsort(jnp.logical_not(on), stable=True).astype(jnp.int32)
    count = jnp.sum(on, dtype=jnp.int32).reshape(1)
    decay = jnp.exp(dt * A).reshape(n_rows * G * hg)
    dtx = (dt[..., None] * x.astype(jnp.float32))             # N,G,hg,d
    b4, c4 = (m.astype(jnp.float32).reshape(n_rows, G, 1, n)
              for m in (Bm, Cm))

    def at_group(t, j, rows_, count_, decay_):
        return (rows_[t], j, 0, 0)

    # every block's last two dims are whole dims of its array, which
    # Mosaic takes at any size
    sblk = pl.BlockSpec((1, hg, d, n), at_group)
    yblk = pl.BlockSpec((1, 1, hg, d), at_group)
    in_specs = [sblk, yblk,
                pl.BlockSpec((1, 1, 1, n), at_group),
                pl.BlockSpec((1, 1, 1, n), at_group),
                # y starts as zeros and aliases them: the rows no step
                # writes (those that do not decode) stay zeros
                pl.BlockSpec(memory_space=pl.ANY)]
    y0 = jnp.zeros((n_rows, G, hg, d), jnp.float32)
    operands = (S, dtx, b4, c4, y0)
    n_prefetch = 3
    # four state tiles and the per-head temporaries beside them, never
    # under the compiler's own 16 MiB
    vmem = max(16 << 20, 6 * hg * d * n * 4)
    S_new, y = pl.pallas_call(
        _ssm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            # compiled, the grid is as long as the rows that decode; the
            # interpreter takes no dynamic bound and copies the others
            grid=(n_rows if interpret else jnp.maximum(count[0], 1), G),
            in_specs=in_specs,
            out_specs=[sblk, yblk]),
        out_shape=[_out_struct(S.shape, S.dtype, *operands),
                   _out_struct(y0.shape, y0.dtype, *operands)],
        input_output_aliases={n_prefetch: 0,
                              n_prefetch + len(operands) - 1: 1},
        compiler_params=None if interpret else pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="ssm_decode_step",
    )(rows, count, decay, *operands)
    return y, S_new


def ssm_decode(x, Bm, Cm, dt, A, S, active, impl: str = "auto",
               **kernel_kw):
    """The hybrid decode step's dispatch point: ``impl="auto"`` runs the
    compiled kernel on a TPU and the jnp reference elsewhere (the
    interpreter is an emulator, far too slow for a serving loop);
    ``"kernel"`` / ``"reference"`` force one (tests pin the kernel
    against the reference with ``impl="kernel", interpret=True``)."""
    from bigdl_tpu.utils.compat import auto_interpret

    if impl not in ("auto", "kernel", "reference"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl == "auto":
        impl = "reference" if auto_interpret() else "kernel"
    if impl == "kernel":
        return ssm_decode_kernel(x, Bm, Cm, dt, A, S, active, **kernel_kw)
    return ssm_decode_reference(x, Bm, Cm, dt, A, S, active)
