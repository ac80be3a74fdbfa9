"""Operations and bytes the ALGORITHM of one ``afmoe`` decode step needs,
from the cell's shapes and what the step really touched. Kept with the
benchmark, beside ``serve_costs.py``, so that no PR that claims a gain
can change what the decode program is held against.

Work the algorithm does not need does not count: an inactive slot's
cache, ring entries and cache columns beyond a row's position, and the
held experts no token of the step chose count for nothing, whatever the
program reads. Each operand is read once and each result written once.
"""

from __future__ import annotations

from benchmark import serve_flops_afmoe as flops

_BYTES = {"bfloat16": 2, "float32": 4}
F32 = 4


def kv_position_bytes(config: dict, settings: dict) -> int:
    """Bytes of one cache position of one layer: K and V."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] \
        * _BYTES[settings["param_dtype"]]


def afmoe_decode_step(config: dict, settings: dict, load: dict) -> dict:
    """One token for each of ``load["rows"]`` active rows:
    ``{"flops", "bytes"}``. ``load``: the window's means of the active
    rows, ``kv_held_bytes`` (K/V the active rows hold, ``min(pos,
    len_i)`` positions a layer), ``experts_hit`` (held experts with a
    token, summed over the expert layers) and ``expert_pairs`` (token,
    held expert pairs, summed over the layers).

    Bytes: the matrices every token passes through (attention, dense
    MLP, router, shared expert, head) once, and one embedding row a
    token; ONLY the held experts that received a token once; K/V of the
    active rows up to what they hold read once and one new position a
    layer written; the logits of the active rows written and read once
    by the sampler."""
    el = _BYTES[settings["param_dtype"]]
    rows = load["rows"]
    kv_position = kv_position_bytes(config, settings)
    weights = (flops.afmoe_dense_matmul_params(config)
               + load["experts_hit"] * flops.afmoe_expert_params(config)) * el
    nbytes = weights + rows * config["hidden_size"] * el \
        + load["kv_held_bytes"] \
        + rows * config["num_hidden_layers"] * kv_position \
        + 2 * rows * config["vocab_size"] * F32
    held_keys = load["kv_held_bytes"] / kv_position       # over rows, layers
    per_token = flops.afmoe_flops_per_token(
        config, held_keys / rows, load["expert_pairs"] / rows)
    return {"flops": rows * per_token, "bytes": nbytes}
