"""Readers over the program's own metric series, sliced to the window."""

import statistics

from benchmark import harness


def _stat(values, stat: str):
    """``median``, ``mean`` or ``p<q>`` of a list; None of an empty one."""
    if not values:
        return None
    if stat == "median":
        return statistics.median(values)
    if stat == "mean":
        return sum(values) / len(values)
    return harness.percentile(values, float(stat[1:]))


def stat(obs, args):
    """``args``: ``series`` (a series of the program's metrics) or
    ``span`` (one of the benchmark's own span lists), ``stat`` (median |
    mean | p95 ...), ``scale``."""
    values = obs["series"].get(args["series"]) if "series" in args \
        else obs["spans"].get(args["span"])
    value = _stat(values, args["stat"])
    return None if value is None else value * args.get("scale", 1.0)


def counter(obs, args):
    """A count the runner took: ``args``: ``counter``, ``scale``."""
    value = obs["counters"].get(args["counter"])
    return None if value is None else value * args.get("scale", 1.0)
