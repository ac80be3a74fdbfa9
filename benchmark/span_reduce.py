"""From a profiler trace to what the program's own spans say, beside
``trace_reduce.py`` and with its helpers.

The program brackets its phases with ``jax.profiler.TraceAnnotation``s
named ``serving.*`` and ``train.*`` (``bigdl_tpu/optim/metrics.py``
``Metrics.span``). They land on the dispatching thread's ``python3``
line of plane ``/host:CPU``, on the device events' timebase. This
module reads the run's ``.xplane.pb`` once more and gives:

* device time of operations by the program they ran in (the ``XLA
  Modules`` event of device 0 that holds their start) and by a part of
  their name (``trace_reduce.op_name``: the instruction without its
  trailing ``.N``, and its result shape). A part, not a prefix: a
  transform wraps a kernel's name, so ``flash_fwd`` runs as
  ``jvp_flash_fwd_`` under ``jax.grad``.
* every idle gap on device 0 (between ``XLA Modules`` events) cut at
  the program spans' edges. Each piece counts once for the INNERMOST
  program span covering it (``idle_innermost_s``; what no span covers is
  ``UNATTRIBUTED``) and once for every span covering it
  (``idle_under_s``, which answers "idle under serving.admit" whatever
  is nested in it).
* the host time of each span name (``spans``: count and seconds).

A trace with no program span in it (the parent of the PR that added
them, a rehearsal on the CPU) reduces to None: its readers then report
nothing.
"""

from __future__ import annotations

import bisect
import functools
import pathlib
from collections import defaultdict

from benchmark.trace_reduce import (
    DEVICE_PLANE, HOST_LINE, HOST_PLANE, _gaps, op_name, program_name,
)

PROGRAM_SPANS = ("serving.", "train.")
UNATTRIBUTED = "(no program span)"


def attribute_idle(gaps, spans):
    """``gaps``: (start, end); ``spans``: (name, start, end), nested as
    ``with`` blocks nest. Returns (innermost, under): nanoseconds of
    idle by the innermost span covering them, and by every span name
    covering them."""
    innermost, under = defaultdict(float), defaultdict(float)
    spans = sorted(spans, key=lambda s: s[1])
    for a, b in gaps:
        near = [s for s in spans if s[1] < b and s[2] > a]
        cuts = sorted({a, b, *(t for _, s, e in near for t in (s, e)
                               if a < t < b)})
        for x, y in zip(cuts, cuts[1:]):
            over = [s for s in near if s[1] <= x and s[2] >= y]
            if not over:
                innermost[UNATTRIBUTED] += y - x
                continue
            innermost[min(over, key=lambda s: s[2] - s[1])[0]] += y - x
            for name in {s[0] for s in over}:
                under[name] += y - x
    return dict(innermost), dict(under)


def reduce_profile(profile):
    """``profile`` is a ``jax.profiler.ProfileData``."""
    modules, op_events, lines = [], [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == 0:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         program_name(e.name)) for e in line.events)
                elif line.name == "XLA Ops":
                    op_events = [(e.start_ns, e.duration_ns, e.name)
                                 for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if line.name == HOST_LINE:
                    # a span's arguments (step=, rids=) are the event's
                    # stats; its name comes bare
                    lines.append([
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                        if e.name.startswith(PROGRAM_SPANS)])
    # the thread that runs the program's loop is the one with its spans
    spans = max(lines, default=[], key=len)
    if not modules or not spans:
        return None
    gaps = _gaps([(a, b) for a, b, _ in modules])
    innermost, under = attribute_idle(gaps, spans)
    starts = [a for a, _, _ in modules]
    ops = defaultdict(lambda: defaultdict(float))
    for start, duration, name in op_events:
        i = bisect.bisect_right(starts, start) - 1
        inside = i >= 0 and start < modules[i][1]
        ops[modules[i][2] if inside else "(no program)"][
            op_name(name)] += duration
    by_name = defaultdict(lambda: [0, 0.0])
    for name, s, e in spans:
        by_name[name][0] += 1
        by_name[name][1] += e - s
    return {
        "window_s": (max(b for _, b, _ in modules)
                     - min(a for a, _, _ in modules)) / 1e9,
        "idle_s": sum(b - a for a, b in gaps) / 1e9,
        "idle_innermost_s": {k: v / 1e9 for k, v in innermost.items()},
        "idle_under_s": {k: v / 1e9 for k, v in under.items()},
        "spans": {k: {"count": n, "total_s": t / 1e9}
                  for k, (n, t) in by_name.items()},
        "op_s": {program: {k: v / 1e9 for k, v in by_op.items()}
                 for program, by_op in ops.items()},
    }


@functools.lru_cache(maxsize=1)
def reduce_file(path: str):
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def newest_trace(root):
    """The newest ``.xplane.pb`` under ``root`` (``run.py`` clears the
    cell's directory before a run, so it is this run's), or None."""
    found = list(pathlib.Path(root).glob("*/plugins/profile/*/*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime) if found else None


def op_seconds(reduced: dict, parts, program: str = None) -> float:
    """Device seconds of the operations whose name holds one of
    ``parts``, in every program or in ``program`` alone."""
    return sum(t for prog, by_op in reduced["op_s"].items()
               if program in (None, prog)
               for name, t in by_op.items() if any(p in name for p in parts))


if __name__ == "__main__":
    import json
    import sys

    out = reduce_file(sys.argv[1])
    if out:                      # the ten longest operations of a program
        out["op_s"] = {program: dict(sorted(by_op.items(),
                                            key=lambda kv: -kv[1])[:10])
                       for program, by_op in out["op_s"].items()}
    print(json.dumps(out, indent=1))
