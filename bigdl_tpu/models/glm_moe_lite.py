"""The ``glm4_moe_lite`` decoder family (GLM-4.7-Flash): multi-head
LATENT attention, whose cache row is one latent a token and layer, a
dense first layer and after it a routed-expert layer beside a shared
expert, of whose experts THIS CHIP HOLDS A SHARE.

    x0 = E[token]
    x  = x + Attn(RMSNorm_in(x));   x = x + F_i(RMSNorm_post(x))
    logits = RMSNorm(x_last) @ W_head^T

``Attn(a)``: ``c_q = RMSNorm(W_qa a)``; per head ``[q_nope | q_rope] =
W_qb c_q``; ``[c_kv | k_rope] = W_kva a``, ``c_kv = RMSNorm(c_kv)``,
``k_rope`` ONE head that all query heads share; the rotary embedding on
``q_rope`` and ``k_rope`` only; per head ``[k_nope | v] = W_kvb c_kv``
(stored as its key columns ``w_uk`` and its value columns ``w_uv``);
softmax of ``[q_nope | q_rope] . [k_nope | k_rope] / sqrt(nope + rope)``
over the earlier keys; ``out = W_o concat_h(sum p v_h)``. That is the
EXPANDED form: a whole sequence and the prefill compute it.

The ABSORBED form is the same sum regrouped, for one token against the
cache: with ``W_kvb`` split per head into ``W_UK_h`` and ``W_UV_h``,
``q~_h = W_UK_h q_nope_h``, ``score_h(t') = q~_h . c_kv(t') + q_rope_h .
k_rope(t')``, ``ctx_h = W_UV_h^T sum p c_kv(t')``. So a position's cache
row is ``[c_kv after its norm | k_rope after its rotation]`` and nothing
per head: ONE leaf ``k{i}`` a layer, ``(n_slots, max_len, row)``, the
values being its leading ``kv_lora_rank`` columns
(``ops.decode_attention.decode_attention(..., v_width=)`` fetches each
held block once for both products). ``row`` is ``kv_lora_rank +
qk_rope_head_dim`` rounded up to whole lanes of 128 (zeros): a 576-wide
bfloat16 array is laid out ``max_len``-minor by the device, and the
kernel would be handed a copy of the pool every step.

``F_i`` is a SwiGLU MLP for ``i < first_k_dense_replace`` and ``Shared(m)
+ sum_k w_k Expert_{sel_k}(m)`` after (``parallel/moe.py``: sigmoid
scores over ALL experts in float32, the top ``num_experts_per_tok`` of
``score + e_score_correction_bias``, the unbiased scores normalised and
scaled; the sum runs over the selected experts this chip holds,
``n_routed_experts`` of them, ``expert_share = {index, of}``).

ONE block function (:func:`_block`) serves the three query shapes: a
whole sequence without a cache (:meth:`GlmMoeLiteLM.apply`) and a
right-padded prompt block that makes fresh cache rows (the batched
prefill) take the expanded path; one token a row against the pooled
cache (the sampling decode step) takes the absorbed one.

The family serves through ``ServingEngine``'s default path only
(:class:`GlmMoeLiteServing`, whose programs are
``models/decoder_family.py``'s, shared with ``afmoe``); it does not
train.
"""

from __future__ import annotations

from typing import NamedTuple

from bigdl_tpu.models.decoder_family import DecoderLM, DecoderServing
from bigdl_tpu.models.decoder_ops import (blocked_attention, fresh_rows,
                                          rms_norm, rope, shared_and_routed,
                                          swiglu)

#: a cache row is padded to whole lanes of this many columns
LANES = 128


class GlmMoeLiteConfig(NamedTuple):
    """The published keys the layer's equations read, under their
    published names; ``n_routed_experts`` is the number HELD here,
    ``router_experts`` the router's width (all experts of a layer) and
    ``expert_offset`` the first held expert."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    router_experts: int
    expert_offset: int

    @classmethod
    def from_dict(cls, config: dict) -> "GlmMoeLiteConfig":
        for flag, want in (("n_shared_experts", 1), ("n_group", 1),
                           ("topk_group", 1), ("rope_scaling", None),
                           ("tie_word_embeddings", False),
                           ("hidden_act", "silu"),
                           ("attention_bias", False),
                           ("topk_method", "noaux_tc"),
                           ("partial_rotary_factor", 1),
                           ("num_nextn_predict_layers", 0),
                           ("num_key_value_heads",
                            config["num_attention_heads"])):
            if config.get(flag, want) != want:
                raise ValueError(f"{flag}={config[flag]!r} is not "
                                 f"implemented (only {want!r})")
        share = config.get("expert_share") or {"index": 0, "of": 1}
        if not 0 <= share["index"] < share["of"] \
                or config["qk_rope_head_dim"] % 2:
            raise ValueError("the share's index must lie in 0..of-1 and "
                             "the rotary part of a head be even")
        given = {k: config[k] for k in cls._fields if k in config}
        given.update(
            router_experts=config["n_routed_experts"] * share["of"],
            expert_offset=config["n_routed_experts"] * share["index"])
        return cls(**given)

    def is_dense(self, i: int) -> bool:
        return i < self.first_k_dense_replace

    @property
    def latent_width(self) -> int:
        """What a position's cache row holds: the latent and the shared
        rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """The cache row as stored: whole lanes."""
        return -(-self.latent_width // LANES) * LANES


# ------------------------------------------------------------ the layer


def _pad_lanes(x, width: int):
    """``x`` (..., c) with zero columns up to ``width``."""
    import jax.numpy as jnp

    pad = width - x.shape[-1]
    return x if not pad else jnp.concatenate(
        [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)


def _attention(cfg, p, a, qpos, valid, cache, fresh_len):
    """Latent attention of the block's input ``a`` (B, T, H). ``cache``
    (decode, T = 1; the layer's leaf): the row of each token is written
    at ``qpos`` and the ABSORBED form attends over the row's cache.
    Otherwise the block attends over its own keys in the EXPANDED form
    and, with ``fresh_len``, returns fresh cache rows."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.decode_attention import decode_attention

    B, T, _ = a.shape
    nh, dn, dr, dv, r = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    eps, scale = cfg.rms_norm_eps, (dn + dr) ** -0.5
    with jax.named_scope("attention.latent.project"):
        c_q = rms_norm(a @ p["wqa"], p["q_norm"], eps)
        q = (c_q @ p["wqb"]).reshape(B, T, nh, dn + dr)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:], qpos, cfg.rope_theta)
        kva = a @ p["wkva"]
        c_kv = rms_norm(kva[..., :r], p["kv_norm"], eps)
        k_rope = rope(kva[..., r:].reshape(B, T, 1, dr), qpos, cfg.rope_theta)
        # the cache row of every token of the block
        row = _pad_lanes(jnp.concatenate(
            [c_kv, k_rope.reshape(B, T, dr)], axis=-1), cfg.row_width)
    if cache is None:
        with jax.named_scope("attention.latent.project"):
            k_nope = (c_kv @ p["w_uk"]).reshape(B, T, nh, dn)
            v = (c_kv @ p["w_uv"]).reshape(B, T, nh, dv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, T, nh, dr))], -1)
        with jax.named_scope("attention.expanded"):
            ctx = blocked_attention(jnp.concatenate([q_nope, q_rope], -1), k,
                                    v, None, scale)
        new_cache = None if fresh_len is None \
            else fresh_rows(row, valid, fresh_len)
        return ctx @ p["wo"], new_cache
    length = cache.shape[1]
    rows, on, pos = jnp.arange(B), valid[:, 0], qpos[:, 0]
    wpos = jnp.clip(pos, 0, length - 1)
    # an inactive row writes its OLD value back: bitwise untouched
    cache = cache.at[rows, wpos].set(jnp.where(
        on[:, None], row[:, 0].astype(cache.dtype), cache[rows, wpos]))
    with jax.named_scope("attention.latent.absorb"):
        # float32 sums, rounded once
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0],
                           p["w_uk"].reshape(r, nh, dn),
                           preferred_element_type=jnp.float32).astype(a.dtype)
    q_row = _pad_lanes(jnp.concatenate([q_lat, q_rope[:, 0]], -1),
                       cfg.row_width)
    with jax.named_scope("attention.latent"):
        ctx_lat = decode_attention(q_row, cache, None, wpos, scale=scale,
                                   out_dtype=a.dtype, active=on, v_width=r)
    with jax.named_scope("attention.latent.absorb"):
        ctx = jnp.einsum("bhc,chd->bhd", ctx_lat,
                         p["w_uv"].reshape(r, nh, dv),
                         preferred_element_type=jnp.float32).astype(a.dtype)
    return ctx.reshape(B, 1, nh * dv) @ p["wo"], cache


def _block(cfg, i, p, x, qpos, valid, cache=None, fresh_len=None):
    """Layer ``i`` for every query shape. ``x`` (B, T, H); ``qpos`` (B,
    T) absolute positions; ``valid`` (B, T) marks real tokens (a prefix
    of each row). ``cache`` (decode): T = 1, every row continues from
    its cache at ``qpos``, and rows where ``valid`` is false leave the
    leaf bitwise untouched. ``fresh_len``: fresh cache rows are made
    (:func:`~bigdl_tpu.models.decoder_ops.fresh_rows`). Neither: no
    state is read or kept. Returns ``(x, cache, expert counts or
    None)``."""
    import jax

    eps = cfg.rms_norm_eps
    att, cache = _attention(cfg, p["attn"], rms_norm(x, p["input_norm"], eps),
                            qpos, valid, cache, fresh_len)
    x = x + att
    m = rms_norm(x, p["post_norm"], eps)
    if cfg.is_dense(i):
        with jax.named_scope("mlp"):
            out, counts = swiglu(m, p["mlp"]), None
    else:
        out, counts = shared_and_routed(
            p["moe"], m, valid, cfg.expert_offset, cfg.num_experts_per_tok,
            cfg.norm_topk_prob, cfg.routed_scaling_factor)
    return x + out, cache, counts


def _layers(cfg, params, tokens0, qpos, valid, carry=None, fresh_lens=None,
            dtype=None):
    """Embedding and every block. ``carry``: the pooled serving carry
    (decode). ``fresh_lens``: per layer, the cache leaf length to make
    fresh rows for (prefill). Returns the hidden states before the final
    norm, the new ``k{i}`` leaves and the expert layers' token counts
    ``(n_expert_layers, held)``."""
    import jax.numpy as jnp

    x = jnp.take(params["embed"], jnp.clip(tokens0, 0, cfg.vocab_size - 1),
                 axis=0).astype(dtype or params["embed"].dtype)
    leaves, counts = {}, []
    for i, lp in enumerate(params["layers"]):
        x, cache, n = _block(cfg, i, lp, x, qpos, valid,
                             None if carry is None else carry[f"k{i}"],
                             None if fresh_lens is None else fresh_lens[i])
        if cache is not None:
            leaves[f"k{i}"] = cache
        if n is not None:
            counts.append(n)
    return x, leaves, jnp.stack(counts) if counts else None


# ------------------------------------------------------------ the model


class GlmMoeLiteServing(DecoderServing):
    """The family's programs (``models/decoder_family.py``) over ONE
    cache leaf a layer: the values are columns of the keys."""

    #: engine option -> why this family cannot take it yet
    refuses = {
        "prefix_cache": "no prefill continues from a cached latent prefix "
                        "yet (the prompt block attends over its own keys "
                        "only)",
        "speculative": "there is no verify step over the latent leaf",
        "adapters": "the block has no adapter sites",
        "kv_dtype": "the int8 layout is not written for a latent row",
        "mesh": "the experts have no axis on the serving mesh yet",
        "parallelism": "the experts have no axis on the serving mesh yet",
        "admission": "only batched admission makes latent rows (no "
                     "chunked or per-request prefill)",
        "tier": "the host tier's payload codec assumes a K leaf and a V "
                "leaf a layer",
    }

    def leaf_shapes(self, i: int):
        return {"k": (self.max_len, self.model.config.row_width)}


class GlmMoeLiteLM(DecoderLM):
    """``glm4_moe_lite`` decoder over 1-based token ids ``(B, T)`` ->
    logits ``(B, T, vocab)``, built from the published ``config.json``
    keys (``n_routed_experts`` the experts held here, ``expert_share``
    which).

    ``max_len`` is the cache window a ``ServingEngine`` over this model
    reserves per slot (positions need no table). ``param_dtype`` is the
    dtype the parameters are CREATED in, layer by layer. Initialisation,
    the constructor's: matrices normal std 0.02, norm weights 1,
    ``e_score_correction_bias`` 0 (float32, a buffer)."""

    config_class = GlmMoeLiteConfig
    serving_class = GlmMoeLiteServing
    layers = staticmethod(_layers)

    def _init_layer(self, key, dense: bool):
        import jax.numpy as jnp

        cfg, dt = self.config, jnp.dtype(self.param_dtype)
        H, nh = cfg.hidden_size, cfg.num_attention_heads
        normal, stack, mlp = self._initialisers(key, cfg.n_routed_experts)
        layer = {
            "input_norm": jnp.ones((H,), dt),
            "attn": {
                "wqa": normal(H, cfg.q_lora_rank),
                "q_norm": jnp.ones((cfg.q_lora_rank,), dt),
                "wqb": normal(cfg.q_lora_rank, nh * (
                    cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
                "wkva": normal(H, cfg.latent_width),
                "kv_norm": jnp.ones((cfg.kv_lora_rank,), dt),
                # W_kvb's columns as two matrices: every head's key
                # part, every head's value part
                "w_uk": normal(cfg.kv_lora_rank, nh * cfg.qk_nope_head_dim),
                "w_uv": normal(cfg.kv_lora_rank, nh * cfg.v_head_dim),
                "wo": normal(nh * cfg.v_head_dim, H)},
            "post_norm": jnp.ones((H,), dt),
        }
        if dense:
            layer["mlp"] = mlp(cfg.intermediate_size)
        else:
            F = cfg.moe_intermediate_size
            layer["moe"] = {
                "router": {"w": normal(H, cfg.router_experts),
                           "bias": jnp.zeros((cfg.router_experts,),
                                             jnp.float32)},
                "shared": mlp(F), "experts": mlp(F, stack)}
        return layer
