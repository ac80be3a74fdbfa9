"""Plain reference of the ``glm4_moe_lite`` architecture (GLM-4.7-Flash):
the forward pass in straightforward ``jax.numpy`` and float32 at the
highest matmul precision, in the EXPANDED form of its latent attention
(per-head keys and values made from the latents of every position), with
no kernel, no cache, no absorbed product, no grouped product and no
batching, following the published ``config.json``
(huggingface.co/zai-org/GLM-4.7-Flash) and the public implementation
family (DeepSeek-V2/V3's latent attention and ``noaux_tc`` router, which
``glm4_moe_lite`` follows key for key). It reads the program's parameter
tree and nothing else of the program.

Every layer: ``x = x + Attn(RMSNorm(x))``; ``x = x + F(RMSNorm(x))``,
with ``F`` the dense SwiGLU for the leading ``first_k_dense_replace``
layers and ``Shared(m) + sum_k w_k Expert_{sel_k}(m)`` after.
``Attn(a)``: ``c_q = RMSNorm(W_qa a)``, ``[q_nope | q_rope] = W_qb c_q``
a head; ``[c_kv | k_rope] = W_kva a``, ``c_kv = RMSNorm(c_kv)``, ``k_rope``
one head for all; rotary on ``q_rope`` and ``k_rope``; ``[k_nope | v] =
W_kvb c_kv`` a head; causal softmax of ``q . k / sqrt(nope + rope)``.
Each expert is a masked dense product over ALL tokens: the obvious form.

Computed in blocks so that it fits beside the parameters at the cell's
16,128 tokens, without changing a sum: layers are upcast ONE AT A TIME,
the held experts one at a time (``lax.scan`` over the stack), and the
attention's queries 256 at a time (a whole 20 x 16,128 x 16,128 float32
score tensor is 20.8 GB).

Departures, as the configuration file lists them: random weights from
the seed, a slice of the vocabulary, no chat template and no EOS, the
drafting layer not held, and the chip's SHARE of the experts: the router
scores, selects, normalises and scales over all ``n_routed_experts x
expert_share.of`` experts as published, and the sum runs over the
selected experts that are held; what the absent experts would add is
left out.

Positions it does not judge: exactly ``afmoe_reference.py``'s rule, by
its own function (a position whose least tie distance over the expert
layers is under ``TIE_MARGIN`` is answered with a row of zeros); see
there. ``leave_out`` names parts of the mathematics to leave out, for
the controls that show the comparison's tolerance at work
(``benchmark/controls_glm_moe_lite.py``, ``tests/test_glm_moe_lite.py``).
"""

from __future__ import annotations

import importlib.util
import pathlib

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
#: a position whose tie distance is under this is not judged. Set
#: between two readings of ``benchmark/controls_glm_moe_lite.py`` on the
#: chip at the published widths (PR 34, seed 3000003411, 3,057 served
#: tokens of 8 requests with contexts of 4,425-15,569): (a) the bfloat16
#: program's largest tie distance at which a served token misses the
#: comparison's slack, 0.0186 (15 misses, with every token judged the
#: worst reads 0.75 of an allowed 0.217); (b) the same for the float8
#: control (the nearest precision below), 0.169, which at this margin
#: still has 106 judged tokens over and reads not correct. 3.2 x (a),
#: 0.36 x (b). With 12 routed layers it leaves 28% of the served tokens
#: judged (43% at 0.04, 65% at 0.02, where the same sample still
#: passes), whose worst shortfall is 0.014-0.034 of an allowed
#: 0.057-0.062 (8 runs). With a latent norm left out, the shared key
#: unrotated or the rotary term dropped, 264-370 judged tokens miss.
TIE_MARGIN = 0.06

#: what ``leave_out`` may name
CONTROLS = ("kv_norm", "q_norm", "k_rope_rotation", "rope_term")


def _routed_reference():
    """``afmoe_reference.py``, whose routed layer and tie distance this
    forward uses as they stand."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_afmoe_reference",
        pathlib.Path(__file__).with_name("afmoe_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_ROUTED = _routed_reference()
_f32 = _ROUTED._f32


def _rms_norm(x, weight, eps):
    return _ROUTED._rms_norm(x, weight, eps)


def _swiglu(p, u):
    return _ROUTED._swiglu(p, u)


def _rotary(x, theta):
    return _ROUTED._rotary(x, theta)


def _attention(p, a, c, leave_out=()):
    t = a.shape[0]
    n, dn, dr, dv, r = c["num_attention_heads"], c["qk_nope_head_dim"], \
        c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    c_q = a @ p["wqa"]
    if "q_norm" not in leave_out:
        c_q = _rms_norm(c_q, p["q_norm"], eps)
    q = (c_q @ p["wqb"]).reshape(t, n, dn + dr)
    q_nope, q_rope = q[..., :dn], _rotary(q[..., dn:], theta)
    kva = a @ p["wkva"]
    c_kv, k_rope = kva[:, :r], kva[:, None, r:]          # one rotary head
    if "kv_norm" not in leave_out:
        c_kv = _rms_norm(c_kv, p["kv_norm"], eps)
    if "k_rope_rotation" not in leave_out:
        k_rope = _rotary(k_rope, theta)
    if "rope_term" in leave_out:
        k_rope = jnp.zeros_like(k_rope)
    # W_kvb's key columns and value columns, as the program stores them
    k_nope = (c_kv @ p["w_uk"]).reshape(t, n, dn)
    v = (c_kv @ p["w_uv"]).reshape(t, n, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (t, n, dr))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    block = min(QUERY_BLOCK, t)
    n_blocks = -(-t // block)
    q = jnp.pad(q, [(0, n_blocks * block - t), (0, 0), (0, 0)])
    key_pos = jnp.arange(t)[None, :]

    def queries(i):                  # one block of queries, all keys
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=0)
        q_pos = (i * block + jnp.arange(block))[:, None]
        scores = jnp.einsum("qhd,khd->hqk", qs, k) / jnp.sqrt(dn + dr)
        scores = jnp.where(key_pos <= q_pos, scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    ctx = jax.lax.map(queries, jnp.arange(n_blocks))
    return ctx.reshape(n_blocks * block, n * dv)[:t] @ p["wo"]


def _moe(layer, m, c):
    """The routed layer and each token's tie distance, by
    ``afmoe_reference._moe`` under that family's names for the same
    published quantities."""
    return _ROUTED._moe(layer, m, {
        "num_experts": c["n_routed_experts"],
        "num_experts_per_tok": c["num_experts_per_tok"],
        "expert_share": c.get("expert_share"), "score_func": "sigmoid",
        "route_norm": c["norm_topk_prob"],
        "route_scale": c["routed_scaling_factor"]})


def hidden_states(params, tokens, config, leave_out=()):
    """``tokens``: (T,) 1-based ids -> (T, H) after the final RMSNorm,
    and (T,) each position's least tie distance over the expert
    layers."""
    c, eps = config, config["rms_norm_eps"]
    if set(leave_out) - set(CONTROLS):
        raise ValueError(f"leave_out names {leave_out}, not of {CONTROLS}")
    tie = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    x = params["embed"][tokens - 1].astype(jnp.float32)
    for i, layer in enumerate(params["layers"]):
        norms = _f32({n: layer[n] for n in ("input_norm", "post_norm")})
        x = x + _attention(_f32(layer["attn"]),
                           _rms_norm(x, norms["input_norm"], eps), c,
                           leave_out)
        m = _rms_norm(x, norms["post_norm"], eps)
        if i < c["first_k_dense_replace"]:
            out = _swiglu(_f32(layer["mlp"]), m)
        else:
            out, layer_tie = _moe(layer["moe"], m, c)
            tie = jnp.minimum(tie, layer_tie)
        x = x + out
    return _rms_norm(x, params["final_norm"].astype(jnp.float32), eps), tie


def logits_and_ties(params, tokens, at, config, leave_out=()):
    """Float32 logits ``(len(at), vocab)`` at the positions ``at`` of one
    sequence, and ``(len(at),)`` their tie distances."""
    with jax.default_matmul_precision("highest"):
        h, tie = hidden_states(params, tokens, config, leave_out)
        return h[at] @ params["head"].astype(jnp.float32).T, tie[at]


def logits_at(params, tokens, at, config):
    """What the benchmark's comparison reads: the logits at ``at``, and
    a row of zeros (no shortfall, no spread) where the position is not
    judged because its own selection of held experts is a tie."""
    logits, tie = logits_and_ties(params, tokens, at, config)
    return jnp.where((tie < TIE_MARGIN)[:, None], 0.0, logits)
