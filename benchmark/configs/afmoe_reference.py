"""Plain reference of the ``afmoe`` architecture (Trinity): the forward
pass in straightforward ``jax.numpy`` and float32 at the highest matmul
precision, with no kernel, no cache, no ring, no grouped product and no
batching, following the published ``config.json``
(huggingface.co/arcee-ai/Trinity-Large-Preview) and the public
implementation (``transformers``, ``models/afmoe/modeling_afmoe.py``).
It reads the program's parameter tree and nothing else of the program.

Every layer: ``a = RMSNorm(x)``; ``x = x + RMSNorm(Attn(a))``; ``m =
RMSNorm(x)``; ``x = x + RMSNorm(F(m))``, with ``F`` the dense SwiGLU for
the leading ``num_dense_layers`` and ``Shared(m) + sum_k w_k
Expert_{sel_k}(m)`` after. Attention masks by position (causal, and on a
sliding layer the last ``sliding_window`` keys); only sliding layers
rotate. Each expert is a masked dense product over ALL tokens: the
obvious form.

Computed in blocks so that it fits beside the parameters at the cell's
6,656 tokens, without changing a sum: layers are upcast ONE AT A TIME,
the held experts one at a time (``lax.scan`` over the stack), and the
attention's queries 512 at a time (a whole 48 x 6,656 x 6,656 float32
score tensor is 8.5 GB).

Departures, as the configuration file lists them: random weights from
the seed, a slice of the vocabulary, no chat template and no EOS, and
the chip's SHARE of the experts: the router scores, selects, normalises
and scales over all ``num_experts x expert_share.of`` experts as
published, and the sum runs over the selected experts that are held
(``expert_share.index``); what the absent experts would add is left out.

Positions it does not judge. The top-k is the one step of this forward
that is not continuous: where a held expert's score ties with the k-th
or the runner-up's, the bfloat16 program and this float32 forward may
each select a different, equally right set, and the token's logits then
differ by a mechanism's worth (a sixth of the residual stream), whatever
tolerance the arithmetic is held to. So every position carries its TIE
DISTANCE: the least change of one held expert's router logit that would
move that expert into or out of the selection, over the expert layers,
in units of the standard deviation of the token's router logits (rounding
is relative, so the distance at which it flips a choice is scale-free).
``logits_at`` answers a position whose distance is under ``TIE_MARGIN``
with a row of zeros, which the benchmark's comparison reads as no
shortfall and no spread: the position is not judged, and the slack
shrinks with the share that is (``logit_spread`` on the result line falls
by the share left out). Every other position is held to the comparison's
slack as it stands, ties among the tokens it attends over included.
``benchmark/tie_margin.py`` reads the margin's two limits on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
#: a position whose tie distance (see above) is under this is not
#: judged. Set between two readings of ``benchmark/tie_margin.py`` on the
#: chip at the published widths (PR 32, seeds 3000003401-03 and ...28,
#: 6,972 served tokens): (a) the bfloat16 program's largest tie distance
#: at which a served token misses the comparison's slack, 0.0040 /
#: 0.0111 / 0.0165 / 0.0055 (every shortfall over a third of the slack
#: sits under 0.017); (b) the same for the float8 control (the nearest
#: precision below), 0.131 / 0.161 / 0.199 / 0.245, which at this margin
#: still has 27-42 judged tokens over and reads not correct. 3.6 x (a),
#: under half of (b); it leaves 54-62% of the served tokens judged, whose
#: worst shortfall is 0.014-0.032 of an allowed 0.145-0.168 (18 runs).
#: With the gate or the window left out of the reference, 479-578 of
#: ~1,000 judged tokens miss (seed ...28).
TIE_MARGIN = 0.06


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


def _swiglu(p, u):
    return (jax.nn.silu(u @ p["gate"]) * (u @ p["up"])) @ p["down"]


def _attention(p, a, c, sliding):
    t = a.shape[0]
    n_q, n_kv, d = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    eps = c["rms_norm_eps"]
    q = _rms_norm((a @ p["wq"]).reshape(t, n_q, d), p["q_norm"], eps)
    k = _rms_norm((a @ p["wk"]).reshape(t, n_kv, d), p["k_norm"], eps)
    v = (a @ p["wv"]).reshape(t, n_kv, d)
    if sliding:                      # a full layer carries no position
        q, k = _rotary(q, c["rope_theta"]), _rotary(k, c["rope_theta"])
    # query head j reads K/V head j // (n_q // n_kv)
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    block = min(QUERY_BLOCK, t)
    n_blocks = -(-t // block)
    q = jnp.pad(q, [(0, n_blocks * block - t), (0, 0), (0, 0)])
    key_pos = jnp.arange(t)[None, :]

    def queries(i):                  # one block of queries, all keys
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=0)
        q_pos = (i * block + jnp.arange(block))[:, None]
        seen = key_pos <= q_pos
        if sliding:
            seen = seen & (q_pos - key_pos < c["sliding_window"])
        scores = jnp.einsum("qhd,khd->hqk", qs, k) / jnp.sqrt(d)
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    ctx = jax.lax.map(queries, jnp.arange(n_blocks))
    ctx = ctx.reshape(n_blocks * block, n_q * d)[:t]
    return (ctx * jax.nn.sigmoid(a @ p["wg"])) @ p["wo"]


def _moe(layer, m, c):
    """``layer["moe"]`` as the program stores it (the experts still in
    their stored dtype: upcast one at a time). Returns the layer's
    result and each token's tie distance (see ``TIE_MARGIN``)."""
    router = _f32(layer["router"])
    k = c["num_experts_per_tok"]
    share = c.get("expert_share") or {"index": 0, "of": 1}
    first = share["index"] * c["num_experts"]
    if c["score_func"] != "sigmoid":
        raise ValueError("only sigmoid scores are written down here")
    route_logits = m @ router["w"]                       # over ALL experts
    scores = jax.nn.sigmoid(route_logits)
    # the k selected and the runner-up
    top, sel = jax.lax.top_k(scores + router["bias"], k + 1)
    sel = sel[:, :k]
    weights = jnp.take_along_axis(scores, sel, axis=1)   # unbiased
    if c["route_norm"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    weights = weights * c["route_scale"]

    def expert(acc, held):
        index, p = held
        w = jnp.sum(jnp.where(sel == first + index, weights, 0.0), axis=1)
        return acc + w[:, None] * _swiglu(_f32(p), m), None

    n_held = layer["experts"]["gate"].shape[0]
    routed, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                             (jnp.arange(n_held), layer["experts"]))
    # how far an expert's router logit is from changing sides: a
    # selected one leaves at the runner-up's biased score, another
    # enters at the k-th's (never, where no score in (0, 1) gets there)
    chosen = (sel[:, :, None] == jnp.arange(scores.shape[1])).any(1)
    edge = jnp.where(chosen, top[:, k:], top[:, k - 1:k]) - router["bias"]
    move = jnp.abs(jax.scipy.special.logit(jnp.clip(edge, 0.0, 1.0))
                   - route_logits)
    tie = jnp.min(move[:, first:first + n_held], axis=1) \
        / jnp.std(route_logits, axis=1)
    return _swiglu(_f32(layer["shared"]), m) + routed, tie


def hidden_states(params, tokens, config):
    """``tokens``: (T,) 1-based ids -> (T, H) after the final RMSNorm,
    and (T,) each position's least tie distance over the expert
    layers."""
    c, eps = config, config["rms_norm_eps"]
    tie = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    x = params["embed"][tokens - 1].astype(jnp.float32)
    if c["mup_enabled"]:
        x = x * jnp.sqrt(jnp.float32(c["hidden_size"]))
    for i, layer in enumerate(params["layers"]):
        sliding = c["layer_types"][i] == "sliding_attention"
        norms = _f32({n: layer[n] for n in (
            "input_norm", "post_attn_norm", "pre_mlp_norm",
            "post_mlp_norm")})
        att = _attention(_f32(layer["attn"]),
                         _rms_norm(x, norms["input_norm"], eps), c, sliding)
        x = x + _rms_norm(att, norms["post_attn_norm"], eps)
        m = _rms_norm(x, norms["pre_mlp_norm"], eps)
        if i < c["num_dense_layers"]:
            out = _swiglu(_f32(layer["mlp"]), m)
        else:
            out, layer_tie = _moe(layer["moe"], m, c)
            tie = jnp.minimum(tie, layer_tie)
        x = x + _rms_norm(out, norms["post_mlp_norm"], eps)
    return _rms_norm(x, params["final_norm"].astype(jnp.float32), eps), tie


def logits_and_ties(params, tokens, at, config):
    """Float32 logits ``(len(at), vocab)`` at the positions ``at`` of one
    sequence, and ``(len(at),)`` their tie distances."""
    with jax.default_matmul_precision("highest"):
        h, tie = hidden_states(params, tokens, config)
        return h[at] @ params["head"].astype(jnp.float32).T, tie[at]


def logits_at(params, tokens, at, config):
    """What the benchmark's comparison reads: the logits at ``at``, and
    a row of zeros (no shortfall, no spread) where the position is not
    judged because its own selection of held experts is a tie."""
    logits, tie = logits_and_ties(params, tokens, at, config)
    return jnp.where((tie < TIE_MARGIN)[:, None], 0.0, logits)
