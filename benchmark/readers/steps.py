"""Readers over the engine's per-dispatch record: the four aligned
series of the window (``serving/decode_gap_s`` beside ``step_rows``,
``step_waves``, ``step_chained``, one sample a consumed decode dispatch
while rows stayed in flight) and the traced span's dispatches joined to
their programs (``step_join.py``). Where the program has no such series
or span arguments (the parent of the PR that added them), each returns
None and the metric is left out."""

import functools
import json

import numpy as np

from benchmark import harness, span_reduce, step_join

GAP, ROWS, WAVES, CHAINED = ("serving/decode_gap_s", "serving/step_rows",
                             "serving/step_waves", "serving/step_chained")


def _window(obs):
    """The window's aligned samples as arrays ``(gap, rows, waves,
    chained)``, or None where a series is missing or they are not of
    one length (they gain their samples in ONE hook, so they are)."""
    cols = [obs["series"].get(name) for name in (GAP, ROWS, WAVES, CHAINED)]
    if not all(cols) or len({len(c) for c in cols}) != 1:
        return None
    return [np.asarray(c, np.float64) for c in cols]


def _plain_s(w):
    """Median read-back interval (seconds) of the window's PLAIN steps,
    by ``step_join``'s definition: chained, no prefill launched since
    the dispatch before, and none while it was in flight (the NEXT
    sample counts none: an admission's host time, the wave's launch,
    stands before the fence of the dispatch then in flight, so it lands
    on the sample before the one with ``step_waves`` > 0). The
    window's last sample has no successor to say so and is left out.
    None where there is no window or no plain step in it."""
    if w is None:
        return None
    gap, _, waves, chained = w
    calm_after = np.append(waves[1:] == 0, False)
    plain = gap[(chained > 0) & (waves == 0) & calm_after]
    return float(np.median(plain)) if len(plain) else None


def engine_gap_p50_ms(obs, args):
    """Median of the read-back interval with each sample counted once
    for every row it decoded: the benchmark's median gap between tokens
    as the engine's own clock saw it."""
    w = _window(obs)
    if w is None or not w[1].sum():
        return None
    return harness.percentile(np.repeat(w[0], w[1].astype(int)), 50) * 1e3


def plain_step_ms(obs, args):
    """Median read-back interval of the window's plain steps."""
    plain = _plain_s(_window(obs))
    return None if plain is None else plain * 1e3


def wave_stall_ms(obs, args):
    """What one admission costs every running row: per step that
    followed a prefill launch, the excess of its read-back interval
    over a plain step's (the wave on the device) plus the excess of
    the sample before it where that is no such step itself (the
    dispatch in flight during the admission: the wave's launch on the
    host), as a mean over those steps."""
    w = _window(obs)
    plain = _plain_s(w)
    if plain is None or not (w[2] > 0).any():
        return None
    excess, behind = w[0] - plain, w[2] > 0
    in_flight = np.append(behind[1:], False) & ~behind
    stall = excess[behind].sum() + np.maximum(0.0, excess[in_flight]).sum()
    return stall / behind.sum() * 1e3


def stalled_share(obs, args):
    """What of the decoding time was spent behind something other than a
    plain step: the sum of every interval's excess over the plain
    median, over the sum of the intervals. A MEAN, so a slow run shows."""
    w = _window(obs)
    plain = _plain_s(w)
    if plain is None:
        return None
    return 100.0 * np.maximum(0.0, w[0] - plain).sum() / w[0].sum()


@functools.lru_cache(maxsize=1)
def _joined(path: str, program: str):
    """The run's join, made and announced once (THE cache: every reader
    below asks for it)."""
    steps, census = step_join.join_file(path, program)
    harness.say("info", json.dumps({"step_join": dict(
        census, joined=steps is not None, plain=len(_plain(steps)))}))
    return steps


def _plain(steps):
    """The joined steps a plain step's parts are averaged over: plain
    (``step_join``'s definition) and with a joined predecessor to cut
    the device's timeline from."""
    return [s for s in steps or [] if s["plain"] and "own_ns" in s]


def _steps(obs):
    """This run's traced decode dispatches, joined; None where no device
    was traced or the join did not hold."""
    if not obs.get("trace"):
        return None
    path = span_reduce.newest_trace(harness.ROOT / ".cache" / "bench_trace")
    return _joined(str(path), obs["settings"]["decode_program"]) \
        if path else None


def plain_step(obs, args):
    """Mean over the traced span's plain joined steps of one
    part of the step, in ms: ``args["part"]`` is ``traced`` (fence end
    to fence end, the host's clock), ``program`` (the step's own
    program), ``other`` (other programs inside it; an information line
    names the two largest) or ``idle``; the last three cut the device's
    timeline from the end of the previous decode program to the end of
    this one."""
    plain = _plain(_steps(obs))
    if not plain:
        return None
    part = args["part"]
    if part == "other":
        by_name = {}
        for s in plain:
            for name, ns in s["other_ns"].items():
                by_name[name] = by_name.get(name, 0.0) + ns
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:2]
        harness.say("info", json.dumps({"plain_step_other_ms_by_program": {
            name: ns / len(plain) / 1e6 for name, ns in top}}))
        values = [sum(s["other_ns"].values()) for s in plain]
    else:
        key = {"traced": "fence_to_fence_ns", "program": "own_ns",
               "idle": "idle_ns"}[part]
        values = [s[key] for s in plain]
    return sum(values) / len(values) / 1e6


def traced_load_gap_pct(obs, args):
    """How far the traced span's load lay from the window's: the
    distance between the mean rows of the span's joined dispatches and
    the mean ``step_rows`` of the window, over the latter."""
    steps, w = _steps(obs), _window(obs)
    if not steps or w is None or not w[1].mean():
        return None
    traced = sum(s["rows"] for s in steps) / len(steps)
    return 100.0 * abs(traced - w[1].mean()) / w[1].mean()


def traced_note(obs, args):
    """Mean over the traced span's joined dispatches of one note on
    ``serving.consume(seq=)``, the load the dispatch read as of its
    launch: ``args["note"]`` is ``kv_held``, ``kv_fetched`` (bytes) or
    ``experts_hit`` (held experts that got a token; routed families),
    times ``args["scale"]``. The span's twins of the window's
    ``kv_held_gb``, ``kv_fetched_gb`` and ``experts_hit_share*``."""
    values = [s[args["note"]] for s in _steps(obs) or []
              if s[args["note"]] is not None]
    return sum(values) / len(values) * args["scale"] if values else None
