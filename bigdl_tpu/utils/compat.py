"""The one place the SPMD plane spells jax APIs that have moved before.

Written for the single installation this repo runs on (jax 0.9.0):
``jax.shard_map`` with ``check_vma``, ``lax.axis_size``,
``lax.pcast(..., to="varying")``, ``jax.typeof(x).vma`` and
``pltpu.CompilerParams``. Product code calls these helpers instead of
the jax names (the analyzer's SPMD101 enforces it), so the next move
is an edit to this file only. jax is imported inside each function:
the analyzer imports this package without paying jax's start-up.
"""

from __future__ import annotations


def resolve_shard_map():
    """The ``shard_map`` callable (``jax.shard_map``)."""
    import jax

    return jax.shard_map


def shard_map(f, **kwargs):
    """``jax.shard_map(f, mesh=..., in_specs=..., out_specs=...,
    check_vma=...)`` — callers pass everything by keyword."""
    import jax

    return jax.shard_map(f, **kwargs)


def axis_size(axis_name: str):
    """Static size of a mapped axis inside a ``shard_map`` body."""
    from jax import lax

    return lax.axis_size(axis_name)


def auto_interpret() -> bool:
    """Whether Pallas kernels should run in INTERPRET mode on this
    backend: True anywhere but a real TPU. THE one copy of the
    CPU-vs-TPU kernel dispatch decision — both ``ops.flash_attention``
    and ``ops.decode_attention`` resolve their ``interpret=None``
    default through here, so the two kernels can never drift on when
    the compiled Mosaic path engages (tier-1 runs everything in
    interpret mode on CPU and cross-lowers the compiled path in
    tests/test_chip_lowering.py; ``chip_smoke.py`` runs it on the
    chip). On a TPU the answer is always False: a kernel the compiler
    refuses raises, it never drops back to the interpreter."""
    import jax

    return jax.default_backend() != "tpu"


def pallas_tpu_compiler_params(**kwargs):
    """A Mosaic compiler-params object for ``pl.pallas_call``
    (``pltpu.CompilerParams``)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def varying_axes(x):
    """The varying-manual-axes (vma) set of ``x``'s type: the mesh axes
    over which a value inside a ``shard_map`` body differs per device."""
    import jax

    return jax.typeof(x).vma


def varying_marker_kind() -> str:
    """Which primitive :func:`device_varying_marker` uses: ``"pcast"``."""
    return "pcast"


def device_varying_marker(axis_name: str):
    """A function marking an array device-varying over ``axis_name``
    inside a ``shard_map`` body — ``lax.pcast(x, axis, to="varying")``.
    jax auto-psums cotangents of unvaried inputs; the marker is what
    keeps gradients of replicated inputs LOCAL (per-shard), so the
    step's one explicit ``pmean`` stays the only all-reduce."""
    from jax import lax

    return lambda x: lax.pcast(x, axis_name, to="varying")
