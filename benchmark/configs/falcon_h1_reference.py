"""Plain reference of the Falcon-H1 architecture: the forward pass in
straightforward ``jax.numpy`` and float32 at the highest matmul
precision, with no kernel, no cache, no chunks and no batching,
following the published ``config.json`` (huggingface.co/tiiuae/
Falcon-H1-34B-Instruct) and the public implementation
(``transformers``, ``models/falcon_h1/modeling_falcon_h1.py``). It reads
the program's parameter tree and nothing else of the program.

Every layer: ``h = RMSNorm(x)``; ``x = x + ssm_out_multiplier *
Mixer(h) + attention_out_multiplier * Attn(attention_in_multiplier *
h)``; ``x = x + MLP(RMSNorm(x))``. The mixer's recurrence runs token by
token (``lax.scan`` over positions), attention over the full causal
score matrix. The layers are upcast ONE AT A TIME in a Python loop: a
float32 stack of the cell's eight layers would be 13.8 GB.

Departures, as the configuration file lists them: random weights from
the seed, a slice of the vocabulary, no chat template and no EOS.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * weight


def _rotary(x, theta):
    """``x``: (T, heads, d) at positions 0..T-1; rotate-half form over
    the whole head."""
    t, _, d = x.shape
    inv_freq = 1.0 / float(theta) ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([freqs, freqs], -1)[:, None]          # (T, 1, d)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(emb) + jnp.concatenate([-x2, x1], -1) * jnp.sin(emb)


def _attention(p, u, c):
    t = u.shape[0]
    n_q, n_kv, d = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    q = (u @ p["wq"]).reshape(t, n_q, d)
    k = ((u @ p["wk"]) * c["key_multiplier"]).reshape(t, n_kv, d)
    v = (u @ p["wv"]).reshape(t, n_kv, d)
    q, k = _rotary(q, c["rope_theta"]), _rotary(k, c["rope_theta"])
    # query head j reads K/V head j // (n_q // n_kv)
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return ctx.reshape(t, n_q * d) @ p["wo"]


def _mixer(p, h, c):
    t = h.shape[0]
    d_ssm, n_heads, d_head = c["mamba_d_ssm"], c["mamba_n_heads"], \
        c["mamba_d_head"]
    groups, d_state, d_conv = c["mamba_n_groups"], c["mamba_d_state"], \
        c["mamba_d_conv"]
    gn = groups * d_state
    sizes = (d_ssm, d_ssm, gn, gn, n_heads)              # z, x, B, C, dt
    mup = jnp.concatenate([jnp.full((n,), m, jnp.float32)
                           for n, m in zip(sizes, c["ssm_multipliers"])])
    proj = ((h * c["ssm_in_multiplier"]) @ p["in_proj"]) * mup
    z, xbc, dt = jnp.split(proj, [d_ssm, 2 * d_ssm + 2 * gn], axis=-1)
    # depthwise causal convolution of width d_conv with bias, then SiLU
    padded = jnp.concatenate(
        [jnp.zeros((d_conv - 1, xbc.shape[1]), jnp.float32), xbc])
    xbc = p["conv_b"] + sum(padded[j:j + t] * p["conv_w"][j]
                            for j in range(d_conv))
    xbc = jax.nn.silu(xbc)
    x, b, cm = jnp.split(xbc, [d_ssm, d_ssm + gn], axis=-1)
    x = x.reshape(t, n_heads, d_head)
    # head i uses B, C of group i // (n_heads // groups)
    b = jnp.repeat(b.reshape(t, groups, d_state), n_heads // groups, axis=1)
    cm = jnp.repeat(cm.reshape(t, groups, d_state), n_heads // groups, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # (T, heads)
    a = -jnp.exp(p["A_log"])                                 # (heads,)

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hdn,hn->hd", state, c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((n_heads, d_head, d_state), jnp.float32),
        (x, b, cm, dt))
    y = (y + p["D"][:, None] * x).reshape(t, d_ssm)
    # gated RMSNorm, the gate BEFORE the norm, normalised within each
    # of the groups
    y = (y * jax.nn.silu(z)).reshape(t, groups, d_ssm // groups)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                     + c["rms_norm_eps"])
    return (y.reshape(t, d_ssm) * p["norm"]) @ p["out_proj"]


def _mlp(p, u, c):
    gate_m, down_m = c["mlp_multipliers"]
    return (((u @ p["up"]) * jax.nn.silu((u @ p["gate"]) * gate_m))
            @ p["down"]) * down_m


def hidden_states(params, tokens, config):
    """``tokens``: (T,) 1-based ids -> (T, H) after the final RMSNorm."""
    c, eps = config, config["rms_norm_eps"]
    x = params["embed"][tokens - 1].astype(jnp.float32) \
        * c["embedding_multiplier"]
    for layer in params["layers"]:
        p = _f32(layer)                  # one layer in float32 at a time
        h = _rms_norm(x, p["input_norm"], eps)
        x = x + c["ssm_out_multiplier"] * _mixer(p["mixer"], h, c) \
            + c["attention_out_multiplier"] * _attention(
                p["attn"], h * c["attention_in_multiplier"], c)
        x = x + _mlp(p["mlp"], _rms_norm(x, p["pre_ff_norm"], eps), c)
    return _rms_norm(x, params["final_norm"].astype(jnp.float32), eps)


def logits_at(params, tokens, at, config):
    """Float32 logits ``(len(at), vocab)`` at the positions ``at`` of one
    sequence."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, tokens, config)[at]
        return (h @ params["head"].astype(jnp.float32).T) \
            * config["lm_head_multiplier"]
