"""Operations a served token of the ``afmoe`` family needs, computed from
the configuration's sizes. Kept with the benchmark, beside
``serve_flops.py``, so that no PR that claims a gain can change what a
token is counted as. Padding (ballast rows, bucket columns beyond a
prompt) and experts no token chose count for nothing.
"""

from __future__ import annotations


def afmoe_expert_params(config: dict) -> int:
    """Matrix parameters of ONE routed (or shared) expert: gate, up,
    down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def afmoe_dense_matmul_params(config: dict) -> int:
    """Parameters of the matrix multiplications EVERY token passes
    through: per layer the five attention projections (q, k, v, the
    gate, o); the dense MLP of the leading layers; per expert layer the
    router and the shared expert; the output head. The embedding lookup
    is no multiplication; norm weights are not matrices."""
    hidden = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    share = config.get("expert_share") or {"of": 1}
    attention = hidden * (2 * q + 2 * kv) + q * hidden
    dense = config["num_dense_layers"]
    routed = config["num_hidden_layers"] - dense
    return config["num_hidden_layers"] * attention \
        + dense * 3 * hidden * config["intermediate_size"] \
        + routed * (hidden * config["num_experts"] * share["of"]
                    + afmoe_expert_params(config)) \
        + hidden * config["vocab_size"]


def afmoe_held_keys(config: dict, max_len: int, pos: float) -> float:
    """Keys a token at position ``pos`` attends over, summed over the
    layers: ``min(pos, sliding_window)`` on a sliding layer, ``pos`` on
    a full one (both within the cache window)."""
    window = min(config["sliding_window"], max_len)
    return sum(min(pos, window) if kind == "sliding_attention"
               else min(pos, max_len) for kind in config["layer_types"])


def afmoe_flops_per_token(config: dict, held_keys: float,
                          expert_pairs: float) -> float:
    """``2 N`` for the matrix multiplications every token passes
    through, ``2 x`` one expert's matrices for each of the token's
    ``expert_pairs`` (token, held expert) pairs over the expert layers,
    and the attention term at ``held_keys`` keys summed over the layers
    (q.k and p.v: ``4 x heads x head_dim`` a key)."""
    heads, d = config["num_attention_heads"], config["head_dim"]
    return 2.0 * (afmoe_dense_matmul_params(config)
                  + expert_pairs * afmoe_expert_params(config)) \
        + 4.0 * heads * d * held_keys
