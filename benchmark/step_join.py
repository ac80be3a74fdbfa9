"""From a profiler trace to ONE record per decode dispatch, beside
``span_reduce.py`` and with ``trace_reduce.py``'s helpers.

The engine numbers its decode dispatches and puts the number on every
span a dispatch causes (``bigdl_tpu/serving/engine.py``):
``serving.decode.launch(seq=, rows=, chained=, waves=)`` where it is
launched, ``serving.fence(seq=)`` and ``serving.consume(seq=, kv_held=,
kv_fetched=, experts_hit=)`` where it is read back, one step later. The
runtime says which execution on the device a launch became: the host's
``DoEnqueueProgram`` event and the ``XLA Modules`` event of the program
carry the same ``run_id``. So a dispatch ``k`` is joined

* launch -> enqueue: the runtime enqueues on a thread of its own, in
  launch order, after the launch span has closed (behind a wave, after
  the NEXT launch has begun), and a program has run before its fence
  returns: so the first enqueue of the cell's decode program not yet
  taken by an earlier launch that starts between the start of launch
  ``k`` and the end of fence ``k``, both on the host's clock (the
  device's runs ~1.3 ms apart from it in the recorded traces, so no
  rule here compares a host time with a device time);
* enqueue -> program: by ``run_id``;
* launch -> fence and consume: by ``seq``.

A launch that lacks one of the three is unmatched. At most ``EDGE``
unmatched launches at each end of the span are the ragged edges (a
program was in flight when the trace started, another when it stopped)
and are dropped; of the rest at least ``MATCHED_SHARE`` have to be
matched, and every execution of the program on the device between the
first and the last matched one has to belong to a matched launch (one to
one), or there is no join (None) and the readers over it report nothing.

For a joined dispatch ``k`` whose predecessor ``k - 1`` is joined too,
the device's timeline from the end of program ``k - 1`` to the end of
program ``k`` is cut into program ``k``'s own time, the time of OTHER
programs by name (a wave's ``jit_prefill``, the pool's
``jit__scatter_impl``, uploads that run as programs), and idle (what no
``XLA Modules`` event covers; a transfer that is no program counts
here). On the host's clock the same step is the interval between the
ends of the two fences. A dispatch is PLAIN when it was chained, no
prefill was launched since the dispatch before it, and none while it was
in flight either (the next dispatch counts none): an admission's host
time, the wave's launch, stands before the fence of the dispatch then in
flight and in no program of its interval, so only such a step reads the
same on the two clocks. ``readers/steps.py`` holds the window's samples
to the same three conditions.

A trace without the arguments (the parent of the PR that added them, a
rehearsal on the CPU, which has no device plane) joins to None.
"""

from __future__ import annotations

import bisect
from collections import defaultdict

from benchmark.trace_reduce import (
    DEVICE_PLANE, HOST_LINE, HOST_PLANE, _union_ns, program_name,
)

LAUNCH, FENCE, CONSUME = \
    "serving.decode.launch", "serving.fence", "serving.consume"
ENQUEUE = "DoEnqueueProgram"
EDGE = 2
MATCHED_SHARE = 0.99


def read_events(profile, program: str):
    """What the join reads of a ``jax.profiler.ProfileData``: the decode
    launches ``(start, end, args)`` in order, the fences and consumes by
    ``seq``, the starts and ``run_id``s of the enqueues of ``program``,
    and device 0's modules ``(start, end, name, run_id)`` in order."""
    modules, spans, enqueues = [], [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == 0:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         program_name(e.name), dict(e.stats).get("run_id"))
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                mine = []
                for e in line.events:
                    if e.name == ENQUEUE:
                        enqueues.append(
                            (e.start_ns, dict(e.stats).get("run_id")))
                    elif line.name == HOST_LINE and \
                            e.name in (LAUNCH, FENCE, CONSUME):
                        mine.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns,
                                     dict(e.stats)))
                # the thread that runs the engine's loop has its spans
                spans = max(spans, mine, key=len)
    by_run = {run: (a, b) for a, b, name, run in modules
              if name == program and run is not None}
    launches = sorted((a, b, args) for name, a, b, args in spans
                      if name == LAUNCH and "seq" in args)
    by_seq = {name: {args["seq"]: (a, b, args)
                     for n, a, b, args in spans
                     if n == name and "seq" in args}
              for name in (FENCE, CONSUME)}
    return dict(
        launches=launches, fences=by_seq[FENCE], consumes=by_seq[CONSUME],
        enqueues=sorted((t, run) for t, run in enqueues if run in by_run),
        programs=by_run, modules=modules)


def _cut(modules, starts, a, b, own):
    """The device's interval ``(a, b]`` by what ran in it: nanoseconds
    of the program ``own`` ``(start, end)``, of every other module by
    name, and idle."""
    mine, other, covered = 0.0, defaultdict(float), []
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(modules) and modules[i][0] < b:
        s, e, name, _ = modules[i]
        s, e = max(s, a), min(e, b)
        if e > s:
            covered.append((s, e))
            if (modules[i][0], modules[i][1]) == own:
                mine += e - s
            else:
                other[name] += e - s
        i += 1
    return mine, dict(other), (b - a) - _union_ns(covered)


def join(events):
    """``(steps, census)``: one record per matched decode dispatch, in
    launch order, or None where the trace has no numbered launch or too
    few of them match (see the module's text); ``census`` counts the
    launches seen, dropped at the edges and matched."""
    launches = events["launches"]
    if not launches:
        return None, dict(launches=0, dropped_at_edges=0, matched=0)
    enqueues, j = events["enqueues"], 0
    matched = []
    for a, b, args in launches:
        seq = args["seq"]
        fence, consume = events["fences"].get(seq), \
            events["consumes"].get(seq)
        while j < len(enqueues) and enqueues[j][0] < a:
            j += 1               # of a launch before the span, or lost
        if fence is None or consume is None or j == len(enqueues) \
                or enqueues[j][0] > fence[1]:
            matched.append(None)
            continue
        run = enqueues[j][1]
        j += 1
        matched.append(dict(
            seq=seq, rows=args["rows"], chained=bool(args["chained"]),
            waves=args["waves"], launch=(a, b), fence=fence[:2], run_id=run,
            program=events["programs"][run],
            **{k: consume[2].get(k)
               for k in ("kv_held", "kv_fetched", "experts_hit")}))
    first, last = 0, len(matched)
    while first < min(EDGE, last) and matched[first] is None:
        first += 1
    while last > max(first, len(matched) - EDGE) and matched[last - 1] is None:
        last -= 1
    rest = matched[first:last]
    steps = [s for s in rest if s is not None]
    census = dict(launches=len(launches),
                  dropped_at_edges=len(matched) - len(rest),
                  matched=len(steps))
    if not rest or len(steps) < MATCHED_SHARE * len(rest):
        return None, census
    runs = sorted(events["programs"], key=events["programs"].get)
    ran = runs[runs.index(steps[0]["run_id"]):
               runs.index(steps[-1]["run_id"]) + 1]
    if ran != [s["run_id"] for s in steps]:
        return None, dict(census, executions=len(ran))
    modules = events["modules"]
    starts = [m[0] for m in modules]
    by_seq = {s["seq"]: s for s in steps}
    waves = {args["seq"]: args["waves"] for _, _, args in launches}
    for s in steps:
        prev = by_seq.get(s["seq"] - 1)
        s["plain"] = s["chained"] and s["waves"] == 0 \
            and waves.get(s["seq"] + 1) == 0
        if prev is None:
            continue
        a, b = prev["program"][1], s["program"][1]
        own, other, idle = _cut(modules, starts, a, b, s["program"])
        s.update(interval_ns=b - a, own_ns=own, other_ns=other, idle_ns=idle,
                 fence_to_fence_ns=s["fence"][1] - prev["fence"][1])
    return steps, census


def join_file(path: str, program: str):
    from jax.profiler import ProfileData

    return join(read_events(ProfileData.from_file(path), program))


if __name__ == "__main__":
    import json
    import sys

    steps, census = join_file(
        sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else "jit_sample_step")
    print(json.dumps({"census": census, "steps": steps}, indent=1))
