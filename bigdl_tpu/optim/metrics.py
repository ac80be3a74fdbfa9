"""Metrics — named performance counters, and the span that feeds them.

Reference (UNVERIFIED, SURVEY.md §0): ``.../bigdl/optim/Metrics.scala`` —
driver-local + Spark-accumulator-backed counters printed every iteration
(``computing time average``, ``aggregate gradient time``, …). SURVEY.md §5.1.

TPU-native: one process drives the chips, so plain dict counters suffice;
set/add/mean surface kept. Deep profiling is ``jax.profiler``:
:meth:`Metrics.span` brackets a phase ONCE for both planes — a
``TraceAnnotation`` that lands on the dispatching thread's line of the
profile (on the device events' timebase) and, where a series is named, a
duration sample on the Metrics' clock. The profile ``stop_trace`` writes
is the span record; nothing is kept in memory but the series.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation


class Span:
    """One bracket of host code: a ``jax.profiler.TraceAnnotation``
    (a disabled ``TraceMe`` when no profile is running — near free) and,
    where ``key`` is given, the bracket's duration on ``clock`` handed to
    ``record(key, seconds)`` on a clean exit (an exception leaves no
    sample). Spans nest as the ``with`` blocks do: the profile gives the
    parent.

    A host span around an un-fenced device dispatch measures the LAUNCH,
    never the work: name it ``*.launch`` or its series ``*_host_s``.
    Device time comes from the trace."""

    __slots__ = ("_ann", "_clock", "_record", "_key", "_t0")

    def __init__(self, name: str, clock: Callable[[], float],
                 record: Callable[[str, float], None], key: Optional[str],
                 ids: dict) -> None:
        # step_num= marks a step of the profiler's own step analysis
        kind = StepTraceAnnotation if "step_num" in ids else TraceAnnotation
        self._ann = kind(name, **ids)
        self._clock, self._record, self._key = clock, record, key

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        if self._key is not None:
            self._t0 = self._clock()
        return self

    def note(self, **ids) -> None:
        """Arguments known only inside the bracket (request ids bound
        by an admission) join the annotation; free when not profiling."""
        self._ann.set_metadata(**ids)

    def drop(self) -> None:
        """Keep the profile event, record no sample (a bracket that
        turned out to hold none of the work its series counts)."""
        self._key = None

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._key is not None and exc_type is None:
            self._record(self._key, self._clock() - self._t0)
        self._ann.__exit__(exc_type, exc, tb)


class Metrics:
    def __init__(self, clock: Callable[[], float] = time.perf_counter) -> None:
        self._lock = threading.Lock()
        self._values: Dict[str, List[float]] = {}
        #: what :meth:`span` times on; the serving engine hands its own
        #: (a ``VirtualClock`` in tests)
        self.clock = clock

    def span(self, name: str, series: Optional[str] = None, **ids) -> Span:
        """``with metrics.span("train.fetch", "data fetch time"):`` — the
        block is one event named ``name`` in a running profile (``ids``
        ride as its arguments; ``step_num=`` makes it a
        ``StepTraceAnnotation``) and, with ``series``, one duration
        sample appended to it. One call site feeds both, so a sample and
        its event on the ``python3`` line bracket the same code."""
        return Span(name, self.clock, self.add, series, ids)

    def set(self, name: str, value: float) -> None:
        with self._lock:
            self._values[name] = [float(value)]

    def add(self, name: str, value: float) -> None:
        with self._lock:
            self._values.setdefault(name, []).append(float(value))

    def get(self, name: str) -> Tuple[float, int]:
        """(sum, count) — reference ``Metrics.get``."""
        with self._lock:
            vals = self._values.get(name, [])
            return sum(vals), len(vals)

    def values(self, name: str) -> List[float]:
        """Copy of the raw recorded samples (percentile consumers — e.g.
        serving TTFT — need more than get()'s (sum, count))."""
        with self._lock:
            return list(self._values.get(name, []))

    def mean(self, name: str) -> float:
        total, n = self.get(name)
        return total / n if n else 0.0

    def summary(self) -> Dict[str, float]:
        with self._lock:
            return {k: (sum(v) / len(v) if v else 0.0) for k, v in self._values.items()}

    def clear(self) -> None:
        with self._lock:
            self._values.clear()
