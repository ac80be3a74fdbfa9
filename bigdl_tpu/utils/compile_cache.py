"""Where compiled programs are kept between runs, and a count of them.

One rule for every entry point (``chip_smoke.py``, ``bench.py``, the
scripts in ``benchmarks/``, ``tests/conftest.py``): when
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing
here sets a directory; otherwise the cache is ``<checkout>/.cache/jax``,
computed from this file's own location. The directory is part of the
cache key's environment — one that moved (a home directory, a temporary
name, a pid, a time) would never hit, so none of those is ever used.
"""

from __future__ import annotations

import os
import pathlib

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]

_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


def enable_compile_cache(min_compile_time_secs: float | None = None) -> str:
    """Turn on jax's persistent compilation cache and return the
    directory it uses. Call before the first compilation.
    ``min_compile_time_secs`` lowers jax's write threshold (1 s by
    default) for callers whose programs are many and small."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT / ".cache" / "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    if min_compile_time_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_compile_time_secs)
    return path


class CompileLog:
    """Counts what jax compiles from the moment it is created: programs
    handed to the backend, how many of those the persistent cache
    answered, and the seconds the backend took (XLA compilation, or the
    cache read that replaced it — tracing and lowering are host time
    and are not in it; jax reports those per nested jit, so their sum
    overcounts). Read :meth:`snapshot` before and after a phase and
    subtract."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == _HIT:
            self.cache_hits += 1

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == _BACKEND:
            self.seconds += secs
            self.programs += 1

    def snapshot(self) -> tuple[int, int, float]:
        """``(programs, cache_hits, compile_seconds)`` so far."""
        return self.programs, self.cache_hits, self.seconds
