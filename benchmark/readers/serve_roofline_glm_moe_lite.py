"""Readers that hold a latent-attention serving cell's decode program
and its whole window against the chip's peaks. Like
``serve_roofline_afmoe.py``, but a cached position is counted at its
PUBLISHED row whatever the pool stores: the positions the active rows
hold come from ``serving/kv_held_bytes`` over ``serving/kv_position_bytes``
(what ONE position costs in the pool, lane padding included). Where the
run has no trace, no such program or series (a rehearsal; a
program that lacks them, as the parent of the PR that added them) each
returns None and the metric is left out."""

from benchmark import harness, kernel_costs, traffic
from benchmark.readers.serve_roofline import _mean


def _load(obs):
    """The window's mean active rows, cache positions they hold,
    experts hit and (token, expert) pairs a decode step; None where a
    series is missing or no row was decoding."""
    series = obs["series"]
    occupancy = _mean(series.get("serving/slot_occupancy"))
    held = _mean(series.get("serving/kv_held_bytes"))
    position = _mean(series.get("serving/kv_position_bytes"))
    load = {"experts_hit": _mean(series.get("serving/experts_hit")),
            "expert_pairs": _mean(series.get("serving/expert_pairs"))}
    if not occupancy or not position or held is None \
            or None in load.values():
        return None
    load["rows"] = occupancy * obs["settings"]["engine"]["n_slots"]
    load["held_positions"] = held / position
    return load


def _decode_program(obs):
    return (obs.get("trace") or {}).get("programs", {}).get(
        obs["settings"].get("decode_program"))


def decode_roofline(obs, args):
    """The least time the chip could take for one decode step's bytes
    and operations (``args["costs"]``) over the mean device time of the
    decode program."""
    program, load = _decode_program(obs), _load(obs)
    if not program or load is None:
        return None
    cost = harness.resolve(args["costs"])(obs["config"], obs["settings"],
                                          load)
    least, _ = kernel_costs.roofline_seconds(cost["flops"], cost["bytes"],
                                             obs["peaks"])
    return 100.0 * least / (program["mean_ms"] * 1e-3)


def serve_mfu(obs, args):
    """Model operations of the window's emitted tokens (each at the
    decode steps' mean held keys, absorbed form) and of the prompts
    prefilled in it (expanded form, each token at the keys its position
    holds), both at the decode steps' mean (token, held expert) pairs a
    token, over the span of its steps times the bf16 peak."""
    emitted = obs["series"].get("serving/batch_active")
    admitted = obs["series"].get("serving/prefill_batch")
    steps = obs["spans"].get("steps")
    load = _load(obs)
    if not emitted or admitted is None or not steps or load is None:
        return None
    config = obs["config"]
    pairs = load["expert_pairs"] / load["rows"]
    decode = sum(emitted) * harness.resolve(args["flops"])(
        config, config["num_hidden_layers"] * load["held_positions"]
        / load["rows"], pairs)
    mix = obs["traffic"]
    # a prompt's last token is the first decode input: len - 1 prefilled
    prompts = [n - 1 for n in traffic.length_set(mix["prompt_len"],
                                                 traffic.block_size(mix))]
    prompt_flops = harness.resolve(args["prompt_flops"])
    one_prompt = sum(prompt_flops(config, n, pairs)
                     for n in prompts) / len(prompts)
    total = decode + sum(admitted) * one_prompt
    seconds = steps[-1][1] - steps[0][0]
    return 100.0 * total / (seconds * obs["peaks"]["bf16_flops"]
                            * obs["chips"])
