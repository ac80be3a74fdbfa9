"""Operations a served token needs, computed from the configuration's
sizes. Kept with the benchmark, beside ``flops.py``, so that no PR that
claims a gain can change what a token is counted as. Padding (ballast
rows, bucket columns beyond a prompt) counts for nothing.
"""

from __future__ import annotations


def falcon_h1_matmul_params(config: dict) -> int:
    """Parameters of the matrix multiplications one token passes
    through: per layer ``in_proj`` and ``out_proj`` of the mixer, the
    four attention projections and the three of the MLP; the output
    head. The embedding lookup is no multiplication; norm weights, the
    convolution and the per-head scalars are not matrices."""
    hidden, mlp = config["hidden_size"], config["intermediate_size"]
    d_ssm = config["mamba_d_ssm"]
    gn = config["mamba_n_groups"] * config["mamba_d_state"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    layer = hidden * (2 * d_ssm + 2 * gn + config["mamba_n_heads"]) \
        + d_ssm * hidden + hidden * (q + 2 * kv) + q * hidden \
        + 3 * hidden * mlp
    return config["num_hidden_layers"] * layer \
        + hidden * config["vocab_size"]


def falcon_h1_flops_per_token(config: dict, context: float) -> float:
    """``2 N`` for the matrix multiplications, plus per layer the
    attention term at ``context`` keys (q.k and p.v: ``4 x heads x
    head_dim`` a key) and the scan term (decay, outer-product update and
    read-out of a ``heads x d_head x d_state`` state: 5 operations an
    element) and the convolution (2 a tap and channel)."""
    heads, d = config["num_attention_heads"], config["head_dim"]
    state = config["mamba_n_heads"] * config["mamba_d_head"] \
        * config["mamba_d_state"]
    conv = config["mamba_d_ssm"] \
        + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    per_layer = 4.0 * heads * d * context + 5.0 * state \
        + 2.0 * config["mamba_d_conv"] * conv
    return 2.0 * falcon_h1_matmul_params(config) \
        + config["num_hidden_layers"] * per_layer
