"""Operations and bytes the ALGORITHM of one ``glm4_moe_lite`` decode step
needs, from the cell's shapes and what the step really touched. Kept
with the benchmark, beside ``serve_costs.py``, so that no PR that claims
a gain can change what the decode program is held against.

Work the algorithm does not need does not count: an inactive slot's
cache, cache columns beyond a row's position, the lane padding of a
stored cache row, a second fetch of the leaf and the held experts no
token of the step chose count for nothing, whatever the program reads.
Each operand is read once and each result written once: a cached
position of one layer is ``kv_lora_rank + qk_rope_head_dim`` values (576
as published, 1,152 bytes in bfloat16), read ONCE for both products.
"""

from __future__ import annotations

from benchmark import serve_flops_glm_moe_lite as flops

_BYTES = {"bfloat16": 2, "float32": 4}
F32 = 4


def latent_row_bytes(config: dict, settings: dict) -> int:
    """Bytes of one cache position of one layer as PUBLISHED: the latent
    and the shared rotary key."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        * _BYTES[settings["param_dtype"]]


def glm_decode_step(config: dict, settings: dict, load: dict) -> dict:
    """One token for each of ``load["rows"]`` active rows:
    ``{"flops", "bytes"}``. ``load``: the window's means of the active
    rows, ``held_positions`` (cache positions the active rows hold,
    summed over the rows), ``experts_hit`` (held experts with a token,
    summed over the expert layers) and ``expert_pairs`` (token, held
    expert pairs, summed over the layers).

    Bytes: the matrices every token passes through (attention, dense
    MLP, router, shared expert, head) once, and one embedding row a
    token; ONLY the held experts that received a token once; the held
    cache positions at the published row a layer read once and one
    position a layer written; the logits of the active rows written and
    read once by the sampler."""
    el = _BYTES[settings["param_dtype"]]
    rows, layers = load["rows"], config["num_hidden_layers"]
    row = latent_row_bytes(config, settings)
    weights = (flops.glm_dense_matmul_params(config)
               + load["experts_hit"] * flops.glm_expert_params(config)) * el
    nbytes = weights + rows * config["hidden_size"] * el \
        + layers * row * (load["held_positions"] + rows) \
        + 2 * rows * config["vocab_size"] * F32
    per_token = flops.glm_flops_per_token(
        config, layers * load["held_positions"] / rows,
        load["expert_pairs"] / rows)
    return {"flops": rows * per_token, "bytes": nbytes}
