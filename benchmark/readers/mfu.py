"""Model FLOP/s utilisation of a training cell: items/s/chip times the
operations an item needs (a function the configuration names, kept in
``benchmark/flops.py``) over the chip's published bf16 peak."""

from benchmark import harness


def train_mfu(obs, args):
    settings = obs["settings"]
    flops = harness.resolve(settings["flops_per_item"])(obs["config"],
                                                        settings)
    rate = obs["counters"]["items_per_s_per_chip"]
    return 100.0 * rate * flops / obs["peaks"]["bf16_flops"]
