"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but
jax's own reader.

Plane ``/device:TPU:<n>`` has the lines ``XLA Modules`` (one event per
executed program, named ``jit_<fn>(<hash>)``) and ``XLA Ops``; plane
``/host:CPU`` has one line per thread, and the lines named ``python3``
carry ``jax.profiler.TraceAnnotation`` names and Python frames. Events
have a name, a start and a duration in nanoseconds.

* busy: the union of the ``XLA Modules`` intervals of a device; the
  window is the span from the first module's start to the last one's
  end, so ``idle = 1 - busy / window``; over several chips the mean.
* a program's device time: its module events, by name without the hash.
* top operations: ``XLA Ops`` grouped by name with the trailing ``.N``
  dropped, so the copies of one kind read as one row.
* gaps: the longest intervals in which no module ran on device 0, each
  attributed to the innermost host event that covers most of it.
"""

from __future__ import annotations

import pathlib
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
HOST_LINE = "python3"
TOP = 10


def _union_ns(intervals) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _gaps(intervals):
    """(start, end) of every interval in which nothing ran, between the
    first start and the last end."""
    out, end = [], None
    for a, b in sorted(intervals):
        if end is not None and a > end:
            out.append((end, a))
        end = b if end is None else max(end, b)
    return out


def program_name(event_name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%copy.12 = bf16[32,1024,16,64]{3,2,1,0} copy(...)`` ->
    ``copy bf16[32,1024,16,64]{3,2,1,0}``: the instruction's name with
    the trailing ``.N`` dropped, and its (first) result shape."""
    m = re.match(r"^%?([\w\-\.]+?)(?:\.\d+)* = \(?(\w+\[[^\]]*\](?:\{[^}]*\})?)",
                 event_name)
    if not m:
        return event_name[:80]
    # without the tiling: {3,2,1,0:T(8,128)(2,1)} -> {3,2,1,0}
    return f"{m.group(1)} {re.sub(r':[^}]*', '', m.group(2))}"[:120]


def _attribute(gap, host_events):
    """The innermost host event (the shortest) that covers more than
    half of the gap; failing that the one that overlaps it most."""
    a, b = gap
    best_cover, best_any = None, None
    for name, s, d in host_events:
        overlap = min(b, s + d) - max(a, s)
        if overlap <= 0:
            continue
        if overlap > 0.5 * (b - a) and \
                (best_cover is None or d < best_cover[1]):
            best_cover = (name, d)
        if best_any is None or overlap > best_any[1]:
            best_any = (name, overlap)
    if best_cover:
        return best_cover[0]
    return best_any[0] if best_any else "(no event on the python3 lines)"


def _top_seconds(ns_by_name: dict):
    return [[name, ns / 1e9] for name, ns in
            sorted(ns_by_name.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce_profile(profile, chips: int = 1) -> dict:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    modules = defaultdict(list)          # device -> [(start, end, name)]
    ops = defaultdict(float)
    host_lines = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[dev] += [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
                elif line.name == "XLA Ops" and dev == 0:
                    for e in line.events:
                        ops[op_name(e.name)] += e.duration_ns
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if line.name == HOST_LINE:
                    host_lines.append([(e.name, e.start_ns, e.duration_ns)
                                       for e in line.events])
    devices = sorted(modules)[:chips]
    if not devices:
        return None
    # several threads are named python3; the one that dispatches the
    # programs is the one whose gaps are to be explained
    host_events = max(host_lines, default=[], key=lambda events: (
        sum(name.startswith("PjitFunction(") for name, _, _ in events),
        len(events)))
    busy, window = [], []
    for dev in devices:
        spans = [(a, b) for a, b, _ in modules[dev]]
        busy.append(_union_ns(spans))
        window.append(max(b for _, b in spans) - min(a for a, _ in spans))
    programs = defaultdict(lambda: [0, 0.0])
    for a, b, name in modules[devices[0]]:
        p = programs[program_name(name)]
        p[0] += 1
        p[1] += b - a
    gaps = defaultdict(float)
    first = [(a, b) for a, b, _ in modules[devices[0]]]
    longest = sorted(_gaps(first), key=lambda g: g[0] - g[1])
    for gap in longest[:200]:
        gaps[_attribute(gap, host_events)] += gap[1] - gap[0]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": sum(window) / len(window) / 1e9,
        "programs": {k: {"count": n, "total_s": t / 1e9,
                         "mean_ms": t / n / 1e6}
                     for k, (n, t) in programs.items()},
        "device_ops": _top_seconds(ops),
        "idle_gaps": _top_seconds(gaps),
        "gaps_total_s": sum(b - a for a, b in _gaps(first)) / 1e9,
    }


def reduce_file(path, chips: int = 1) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), chips)


def reduce_dir(trace_dir, chips: int = 1):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace
    directory, reduced; None where there is none."""
    found = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return reduce_file(found[-1], chips) if found else None


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
