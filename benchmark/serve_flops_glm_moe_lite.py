"""Operations a served token of the ``glm4_moe_lite`` family needs,
computed from the configuration's sizes. Kept with the benchmark, beside
``serve_flops.py``, so that no PR that claims a gain can change what a
token is counted as. Padding (ballast rows, bucket columns beyond a
prompt, the lane padding of a cache row) and experts no token chose
count for nothing.
"""

from __future__ import annotations


def glm_expert_params(config: dict) -> int:
    """Matrix parameters of ONE routed (or shared) expert: gate, up,
    down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def glm_attention_params(config: dict) -> int:
    """One layer's five low-rank attention matrices: ``W_qa``, ``W_qb``,
    ``W_kva``, ``W_kvb`` and ``W_o``. A decode step applies ``W_kvb`` as
    the two absorbed products (``q~ = W_UK q_nope``: heads x nope x
    latent; ``ctx = W_UV^T ctx~``: heads x latent x v), which are the
    same multiplications, so it counts once either way."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    q_head = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    latent = config["kv_lora_rank"]
    return hidden * config["q_lora_rank"] \
        + config["q_lora_rank"] * heads * q_head \
        + hidden * (latent + config["qk_rope_head_dim"]) \
        + latent * heads * (config["qk_nope_head_dim"]
                            + config["v_head_dim"]) \
        + heads * config["v_head_dim"] * hidden


def glm_dense_matmul_params(config: dict) -> int:
    """Parameters of the matrix multiplications EVERY token passes
    through: per layer the attention's; the dense MLP of the leading
    layers; per expert layer the router (over all experts) and the
    shared expert; the output head. The embedding lookup is no
    multiplication; norm weights are not matrices."""
    hidden = config["hidden_size"]
    share = config.get("expert_share") or {"of": 1}
    dense = config["first_k_dense_replace"]
    routed = config["num_hidden_layers"] - dense
    return config["num_hidden_layers"] * glm_attention_params(config) \
        + dense * 3 * hidden * config["intermediate_size"] \
        + routed * (hidden * config["n_routed_experts"] * share["of"]
                    + glm_expert_params(config)) \
        + hidden * config["vocab_size"]


def glm_key_flops(config: dict, absorbed: bool) -> float:
    """Operations one (query token, key position) pair of one layer
    costs, all heads: ABSORBED (a decode step against the cache), the
    scores over the latent and the rotary key and the sum over the
    latent, ``2 x heads x ((latent + rope) + latent)``; EXPANDED (a
    prompt over its own keys), ``2 x heads x ((nope + rope) + v)``."""
    heads = config["num_attention_heads"]
    if absorbed:
        return 2.0 * heads * (2 * config["kv_lora_rank"]
                              + config["qk_rope_head_dim"])
    return 2.0 * heads * (config["qk_nope_head_dim"]
                          + config["qk_rope_head_dim"]
                          + config["v_head_dim"])


def glm_flops_per_token(config: dict, held_keys: float, expert_pairs: float,
                        absorbed: bool = True) -> float:
    """``2 N`` for the matrix multiplications every token passes
    through, ``2 x`` one expert's matrices for each of the token's
    ``expert_pairs`` (token, held expert) pairs over the expert layers,
    and the attention term at ``held_keys`` keys summed over the
    layers."""
    return 2.0 * (glm_dense_matmul_params(config)
                  + expert_pairs * glm_expert_params(config)) \
        + glm_key_flops(config, absorbed) * held_keys


def glm_prompt_flops(config: dict, n: int, expert_pairs: float) -> float:
    """A prompt of ``n`` prefilled tokens in the EXPANDED form: token
    ``t`` (0-based) attends over its ``t + 1`` keys in every layer."""
    keys = config["num_hidden_layers"] * n * (n + 1) / 2.0
    return n * glm_flops_per_token(config, 0.0, expert_pairs) \
        + glm_key_flops(config, absorbed=False) * keys
