"""Readers that hold a routed-expert serving cell's decode program, and
its whole window, against the chip's peaks. Like ``serve_roofline.py``,
but the load comes from what the step really touched: K/V from
``serving/kv_held_bytes`` (``kv_used_share x max_len`` overstates a
ring) and the experts from ``serving/experts_hit`` /
``serving/expert_pairs``. Where the run has no trace, no such program
or no such series (a rehearsal; a program that lacks them) each returns
None and the metric is left out."""

from benchmark import harness, kernel_costs, serve_costs_afmoe, traffic
from benchmark.readers.serve_roofline import _mean


def _load(obs):
    """The window's mean active rows, held K/V bytes, experts hit and
    (token, expert) pairs a decode step; None where a series is
    missing or no row was decoding."""
    series = obs["series"]
    occupancy = _mean(series.get("serving/slot_occupancy"))
    load = {"kv_held_bytes": _mean(series.get("serving/kv_held_bytes")),
            "experts_hit": _mean(series.get("serving/experts_hit")),
            "expert_pairs": _mean(series.get("serving/expert_pairs"))}
    if not occupancy or None in load.values():
        return None
    load["rows"] = occupancy * obs["settings"]["engine"]["n_slots"]
    return load


def decode_roofline(obs, args):
    """The least time the chip could take for one decode step's bytes
    and operations (``args["costs"]``) over the mean device time of the
    decode program."""
    program = (obs.get("trace") or {}).get("programs", {}).get(
        obs["settings"].get("decode_program"))
    load = _load(obs)
    if not program or load is None:
        return None
    cost = harness.resolve(args["costs"])(obs["config"], obs["settings"],
                                          load)
    least, _ = kernel_costs.roofline_seconds(cost["flops"], cost["bytes"],
                                             obs["peaks"])
    return 100.0 * least / (program["mean_ms"] * 1e-3)


def serve_mfu(obs, args):
    """Model operations of the window's emitted tokens and prefilled
    prompt tokens over the span of its steps times the bf16 peak. A
    prompt token is counted at the keys ITS position holds (a sliding
    layer's at most the window) and at the decode steps' mean (token,
    held expert) pairs a token."""
    emitted = obs["series"].get("serving/batch_active")
    admitted = obs["series"].get("serving/prefill_batch")
    steps = obs["spans"].get("steps")
    load = _load(obs)
    if not emitted or admitted is None or not steps or load is None:
        return None
    flops = harness.resolve(args["flops"])
    held_keys = harness.resolve(args["held_keys"])
    config, max_len = obs["config"], obs["settings"]["max_len"]
    pairs = load["expert_pairs"] / load["rows"]
    kv_position = serve_costs_afmoe.kv_position_bytes(config,
                                                      obs["settings"])
    decode = sum(emitted) * flops(
        config, load["kv_held_bytes"] / kv_position / load["rows"], pairs)
    mix = obs["traffic"]
    # a prompt's last token is the first decode input: len - 1 prefilled
    prompts = [n - 1 for n in traffic.length_set(mix["prompt_len"],
                                                 traffic.block_size(mix))]
    one_prompt = sum(
        sum(flops(config, held_keys(config, max_len, t + 1), pairs)
            for t in range(n)) for n in prompts) / len(prompts)
    total = decode + sum(admitted) * one_prompt
    seconds = steps[-1][1] - steps[0][0]
    return 100.0 * total / (seconds * obs["peaks"]["bf16_flops"]
                            * obs["chips"])
