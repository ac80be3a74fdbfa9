"""Operations and bytes the ALGORITHM of one decode step needs, from the
cell's shapes and the rows that were really decoding. Kept with the
benchmark, beside ``kernel_costs.py``, so that no PR that claims a gain
can change what the decode program is held against.

Work the algorithm does not need does not count: an inactive slot's
state and cache, and cache columns beyond a row's position, count for
nothing, whatever the program reads. Each operand is read once and each
result written once.
"""

from __future__ import annotations

from benchmark import serve_flops

_BYTES = {"bfloat16": 2, "float32": 4}
F32 = 4             # the scan state, whatever the compute dtype


def falcon_h1_decode_step(config: dict, settings: dict, active_rows: float,
                          positions: float) -> dict:
    """One token for each of ``active_rows`` rows holding ``positions``
    cache positions between them: ``{"flops", "bytes"}``.

    Bytes: the weights of the layers and the head once (and one
    embedding row a token); per layer and active row the scan state and
    the convolution window read and written once; K/V of the active
    rows up to their positions read once and one new row written; the
    logits of the active rows written and read once by the sampler."""
    el = _BYTES[settings["param_dtype"]]
    layers, hidden = config["num_hidden_layers"], config["hidden_size"]
    conv = config["mamba_d_ssm"] \
        + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    state = config["mamba_n_heads"] * config["mamba_d_head"] \
        * config["mamba_d_state"] * F32 \
        + (config["mamba_d_conv"] - 1) * conv * el
    kv_row = 2 * config["num_key_value_heads"] * config["head_dim"] * el
    weights = serve_flops.falcon_h1_matmul_params(config) * el
    nbytes = weights + active_rows * hidden * el \
        + layers * active_rows * 2 * state \
        + layers * kv_row * (positions + active_rows) \
        + 2 * active_rows * config["vocab_size"] * F32
    context = positions / active_rows if active_rows else 0.0
    flops = active_rows * serve_flops.falcon_h1_flops_per_token(
        config, context)
    return {"flops": flops, "bytes": nbytes}
