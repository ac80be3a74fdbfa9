"""Plain reference of the GPT-2 architecture: the forward pass in
straightforward ``jax.numpy`` and float32 at the highest matmul
precision, with no kernel, no cache and no batching, following the
published description (Radford et al. 2019; pre-norm blocks, learned
positions, ``gelu_new``). It reads the program's parameter tree and
nothing else of the program.

Departures, as the configuration file lists them: the output head is a
matrix of its own (not the transposed embedding), and there is no
dropout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _ordered(tree):
    """The children of a container in the order they were added: keys
    are ``"<index>:<ClassName><n>"``."""
    return [tree[k] for k in sorted(tree, key=lambda k: int(k.split(":")[0]))]


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["weight"] + p["bias"]


def _linear(x, p):
    return x @ p["weight"].T + p["bias"]


def hidden_states(params, tokens, config):
    """``tokens``: (T,) 1-based ids -> (T, H) after the last LayerNorm."""
    embed, pos, *blocks, ln_f, _head = _ordered(params)
    n_head, eps = config["n_head"], config["layer_norm_epsilon"]
    t = tokens.shape[0]
    x = embed["weight"][tokens - 1] + pos["pos"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    # the layers are alike: stack them and scan, so the reference
    # compiles in the time of one layer
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *[_ordered(b) for b in blocks])

    def block(x, p):
        ln1, attn, ln2, fc1, fc2 = p
        h = _layer_norm(x, ln1, eps)
        q, k, v = (_linear(h, attn[w]).reshape(t, n_head, -1)
                   for w in ("wq", "wk", "wv"))
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(q.shape[-1])
        scores = jnp.where(causal, scores, -jnp.inf)
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        x = x + _linear(ctx.reshape(t, -1), attn["wo"])
        h = jax.nn.gelu(_linear(_layer_norm(x, ln2, eps), fc1),
                        approximate=True)
        return x + _linear(h, fc2), None

    x, _ = jax.lax.scan(block, x, stacked)
    return _layer_norm(x, ln_f, eps)


def logits_at(params, tokens, at, config):
    """Float32 logits ``(len(at), vocab)`` at the positions ``at`` of one
    sequence."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, tokens, config)[at]
        return _linear(h, _ordered(params)[-1])


def mean_cross_entropy(params, tokens, labels, config):
    """Mean over all positions of ``logsumexp(logits) - logits[label]``
    for a batch ``(B, T)`` of 1-based ids and labels."""

    def one(tok, lab):
        logits = logits_at(params, tok, jnp.arange(tok.shape[0]), config)
        picked = jnp.take_along_axis(logits, lab[:, None] - 1, axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, -1) - picked

    return jnp.mean(jax.lax.map(lambda tl: one(*tl), (tokens, labels)))
