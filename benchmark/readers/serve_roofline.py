"""Readers that hold a serving cell's decode program, and its whole
window, against the chip's peaks. Where the run has no trace, no such
program or no such series (a rehearsal; a program that lacks them) each
returns None and the metric is left out."""

from benchmark import harness, kernel_costs, traffic


def _mean(values):
    return sum(values) / len(values) if values else None


def _rows_and_positions(obs):
    """Mean rows decoding a step and mean cache positions they hold,
    from the window's ``slot_occupancy`` and ``kv_used_share``."""
    occupancy = _mean(obs["series"].get("serving/slot_occupancy"))
    used = _mean(obs["series"].get("serving/kv_used_share"))
    if occupancy is None or used is None:
        return None
    n_slots = obs["settings"]["engine"]["n_slots"]
    return occupancy * n_slots, used * n_slots * obs["settings"]["max_len"]


def decode_roofline(obs, args):
    """The least time the chip could take for one decode step's bytes
    and operations (``args["costs"]``, from the cell's shapes and the
    window's mean active rows and positions) over the mean device time
    of the decode program."""
    program = (obs.get("trace") or {}).get("programs", {}).get(
        obs["settings"].get("decode_program"))
    load = _rows_and_positions(obs)
    if not program or load is None or not load[0]:
        return None
    cost = harness.resolve(args["costs"])(obs["config"], obs["settings"],
                                          *load)
    least, _ = kernel_costs.roofline_seconds(cost["flops"], cost["bytes"],
                                             obs["peaks"])
    return 100.0 * least / (program["mean_ms"] * 1e-3)


def serve_mfu(obs, args):
    """Model operations of the window's emitted tokens and prefilled
    prompt tokens over the span of its steps times the bf16 peak."""
    emitted = obs["series"].get("serving/batch_active")
    admitted = obs["series"].get("serving/prefill_batch")
    steps = obs["spans"].get("steps")
    load = _rows_and_positions(obs)
    if not emitted or admitted is None or not steps or load is None:
        return None
    flops = harness.resolve(args["flops"])
    mix = obs["traffic"]
    # a prompt's last token is the first decode input: len - 1 prefilled
    prompts = [n - 1 for n in traffic.length_set(mix["prompt_len"],
                                                 traffic.block_size(mix))]
    prefilled = sum(admitted) * sum(prompts) / len(prompts)
    # a prompt token attends over half its prompt on average
    prompt_context = sum(n * n / 2 for n in prompts) / max(1, sum(prompts))
    rows, positions = load
    total = sum(emitted) * flops(obs["config"],
                                 positions / rows if rows else 0.0) \
        + prefilled * flops(obs["config"], prompt_context)
    seconds = steps[-1][1] - steps[0][0]
    return 100.0 * total / (seconds * obs["peaks"]["bf16_flops"]
                            * obs["chips"])
