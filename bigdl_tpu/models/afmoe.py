"""The ``afmoe`` decoder family (Trinity): gated grouped-query attention
with RMSNorm on each head's queries and keys, sliding-window layers
that carry the rotary positions beside full layers that carry none,
four norms a layer, and after the leading dense layers a routed-expert
layer beside a shared expert, of whose experts THIS CHIP HOLDS A SHARE.

    x0 = E[token] * sqrt(hidden_size)                     (mup_enabled)
    a  = RMSNorm_in(x);       x = x + RMSNorm_post_attn(Attn_i(a))
    m  = RMSNorm_pre_mlp(x);  x = x + RMSNorm_post_mlp(F_i(m))
    logits = RMSNorm(x_last) @ W_head^T

``Attn_i``: ``q, k`` pass an RMSNorm over the head; a
``sliding_attention`` layer rotates them (rotate-half over the whole
head) and attends over the last ``sliding_window`` keys, a full layer
rotates nothing and attends over all; ``out = W_o(ctx * sigmoid(W_g
a))``. ``F_i`` is a SwiGLU MLP for ``i < num_dense_layers`` and ``Shared(m)
+ sum_k w_k Expert_{sel_k}(m)`` after (``parallel/moe.py``:
:func:`~bigdl_tpu.parallel.moe.routed_experts`): sigmoid scores over
ALL experts in float32, the top ``num_experts_per_tok`` of ``score +
expert_bias``, the unbiased scores normalised and scaled; the sum runs
over the selected experts this chip holds (``num_experts`` of them,
``expert_share = {index, of}``), and what the absent ones would add is
left out.

ONE block function (:func:`_block`) serves the three query shapes: a
whole sequence without a cache (:meth:`AfmoeLM.apply`), a right-padded
prompt block that makes fresh cache rows (the batched prefill) and one
token a row against the pooled cache (the sampling decode step). A
serving carry holds per layer ``k{i}`` / ``v{i}`` ``(n_slots, len_i,
kv_heads*head_dim)``: ``len_i`` is the cache window for a full layer
and ``min(sliding_window, window)`` for a sliding one, a RING that holds
position ``p`` at ``p % len_i`` (keys are rotated before they are
stored, so ring order does not matter).

The family serves through ``ServingEngine``'s default path only
(:class:`AfmoeServing`, whose programs are
``models/decoder_family.py``'s); it does not train.
"""

from __future__ import annotations

from typing import NamedTuple

from bigdl_tpu.models.decoder_family import DecoderLM, DecoderServing
from bigdl_tpu.models.decoder_ops import (blocked_attention, fresh_rows,
                                          rms_norm, rope, shared_and_routed,
                                          swiglu)


class AfmoeConfig(NamedTuple):
    """The published keys the layer's equations read, under their
    published names; ``num_experts`` is the number HELD here,
    ``router_experts`` the router's width (all experts of a layer) and
    ``expert_offset`` the first held expert."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_dense_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    route_norm: bool
    route_scale: float
    sliding_window: int
    layer_types: tuple
    rms_norm_eps: float
    rope_theta: float
    mup_enabled: bool
    router_experts: int
    expert_offset: int

    @classmethod
    def from_dict(cls, config: dict) -> "AfmoeConfig":
        for flag, want in (("num_shared_experts", 1), ("n_group", 1),
                           ("topk_group", 1), ("rope_scaling", None),
                           ("tie_word_embeddings", False),
                           ("hidden_act", "silu"),
                           ("score_func", "sigmoid")):
            if config.get(flag, want) != want:
                raise ValueError(f"{flag}={config[flag]!r} is not "
                                 f"implemented (only {want!r})")
        share = config.get("expert_share") or {"index": 0, "of": 1}
        kinds = tuple(config["layer_types"])
        if len(kinds) != config["num_hidden_layers"] or set(kinds) - {
                "sliding_attention", "full_attention"}:
            raise ValueError("layer_types must name every layer "
                             "sliding_attention or full_attention")
        if config["num_attention_heads"] % config["num_key_value_heads"] \
                or not 0 <= share["index"] < share["of"]:
            raise ValueError("heads must divide into their groups and the "
                             "share's index lie in 0..of-1")
        given = {k: config[k] for k in cls._fields if k in config}
        given.update(layer_types=kinds,
                     router_experts=config["num_experts"] * share["of"],
                     expert_offset=config["num_experts"] * share["index"])
        return cls(**given)

    def is_sliding(self, i: int) -> bool:
        return self.layer_types[i] == "sliding_attention"

    def is_dense(self, i: int) -> bool:
        return i < self.num_dense_layers


# ------------------------------------------------------------ the layer


def _attention(cfg, p, a, qpos, valid, sliding, cache, fresh_len):
    """Gated grouped-query attention of the block's input ``a`` (B, T,
    H). ``cache`` (decode, T = 1): one key a row is written at ``qpos``
    (``qpos % len`` in a ring) and the row's cache attended over.
    Otherwise the block attends over its own keys (causal, banded on a
    sliding layer) and, with ``fresh_len``, returns fresh cache rows."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.decode_attention import decode_attention

    B, T, _ = a.shape
    nq, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    eps = cfg.rms_norm_eps
    q = rms_norm((a @ p["wq"]).reshape(B, T, nq, d), p["q_norm"], eps)
    k = rms_norm((a @ p["wk"]).reshape(B, T, nkv, d), p["k_norm"], eps)
    v = a @ p["wv"]                                   # (B, T, nkv*d)
    gate = jax.nn.sigmoid((a @ p["wg"]).astype(jnp.float32))
    if sliding:
        q = rope(q, qpos, cfg.rope_theta)
        k = rope(k, qpos, cfg.rope_theta)
    scale = d ** -0.5
    new_cache = None
    if cache is not None:
        length = cache["k"].shape[1]
        rows, on, pos = jnp.arange(B), valid[:, 0], qpos[:, 0]
        wpos = pos % length if sliding else jnp.clip(pos, 0, length - 1)
        k1 = k.reshape(B, nkv * d)
        # an inactive row writes its OLD value back: bitwise untouched
        k_wr = jnp.where(on[:, None], k1.astype(cache["k"].dtype),
                         cache["k"][rows, wpos])
        v_wr = jnp.where(on[:, None], v[:, 0].astype(cache["v"].dtype),
                         cache["v"][rows, wpos])
        kc = cache["k"].at[rows, wpos].set(k_wr)
        vc = cache["v"].at[rows, wpos].set(v_wr)
        # a ring that has wrapped is valid whole; before, up to pos
        ctx = decode_attention(
            q[:, 0], kc, vc, jnp.minimum(pos, length - 1), scale=scale,
            out_dtype=a.dtype, active=on).reshape(B, 1, nq * d)
        new_cache = {"k": kc, "v": vc}
    else:
        ctx = blocked_attention(q, k, v.reshape(B, T, nkv, d),
                                 cfg.sliding_window if sliding else None,
                                 scale)
        if fresh_len is not None:
            new_cache = {"k": fresh_rows(k.reshape(B, T, nkv * d), valid,
                                         fresh_len),
                         "v": fresh_rows(v, valid, fresh_len)}
    out = (ctx.astype(jnp.float32) * gate).astype(a.dtype) @ p["wo"]
    return out, new_cache


def _block(cfg, i, p, x, qpos, valid, cache=None, fresh_len=None):
    """Layer ``i`` for every query shape. ``x`` (B, T, H); ``qpos`` (B,
    T) absolute positions; ``valid`` (B, T) marks real tokens (a prefix
    of each row). ``cache`` (decode): T = 1, every row continues from
    its cache at ``qpos``, and rows where ``valid`` is false leave every
    leaf bitwise untouched. ``fresh_len``: fresh cache rows are made
    (:func:`~bigdl_tpu.models.decoder_ops.fresh_rows`). Neither: no state is read or kept. Returns
    ``(x, cache, expert counts or None)``."""
    import jax

    eps, sliding = cfg.rms_norm_eps, cfg.is_sliding(i)
    with jax.named_scope("attention.window" if sliding
                         else "attention.full"):
        att, cache = _attention(cfg, p["attn"],
                                rms_norm(x, p["input_norm"], eps), qpos,
                                valid, sliding, cache, fresh_len)
    x = x + rms_norm(att, p["post_attn_norm"], eps)
    m = rms_norm(x, p["pre_mlp_norm"], eps)
    if cfg.is_dense(i):
        with jax.named_scope("mlp"):
            out, counts = swiglu(m, p["mlp"]), None
    else:
        out, counts = shared_and_routed(
            p["moe"], m, valid, cfg.expert_offset, cfg.num_experts_per_tok,
            cfg.route_norm, cfg.route_scale)
    return x + rms_norm(out, p["post_mlp_norm"], eps), cache, counts


def _layers(cfg, params, tokens0, qpos, valid, carry=None, fresh_lens=None,
            dtype=None):
    """Embedding and every block. ``carry``: the pooled serving carry
    (decode). ``fresh_lens``: per layer, the cache leaf length to make
    fresh rows for (prefill). Returns the hidden states before the final
    norm, the new ``k{i}`` / ``v{i}`` leaves and the expert layers'
    token counts ``(n_expert_layers, held)``."""
    import jax.numpy as jnp

    x = jnp.take(params["embed"], jnp.clip(tokens0, 0, cfg.vocab_size - 1),
                 axis=0)
    if cfg.mup_enabled:
        x = x * cfg.hidden_size ** 0.5
    x = x.astype(dtype or params["embed"].dtype)
    leaves, counts = {}, []
    for i, lp in enumerate(params["layers"]):
        cache = None if carry is None else \
            {"k": carry[f"k{i}"], "v": carry[f"v{i}"]}
        x, cache, n = _block(
            cfg, i, lp, x, qpos, valid, cache,
            None if fresh_lens is None else fresh_lens[i])
        if cache is not None:
            leaves[f"k{i}"], leaves[f"v{i}"] = cache["k"], cache["v"]
        if n is not None:
            counts.append(n)
    return x, leaves, jnp.stack(counts) if counts else None


# ------------------------------------------------------------ the model


class AfmoeServing(DecoderServing):
    """The family's programs (``models/decoder_family.py``) over leaves
    of two lengths."""

    #: engine option -> why this family cannot take it yet
    refuses = {
        "prefix_cache": "a cached prefix longer than a ring cannot be "
                        "continued from (the ring keeps the last window "
                        "only)",
        "speculative": "there is no verify step over ring leaves",
        "adapters": "the block has no adapter sites",
        "kv_dtype": "the int8 K/V layout is not written by this family",
        "mesh": "the experts have no axis on the serving mesh yet",
        "parallelism": "the experts have no axis on the serving mesh yet",
        "admission": "only batched admission fills ring leaves (no "
                     "chunked or per-request prefill)",
        "tier": "the host tier's payload codec assumes K/V leaves of "
                "one length",
    }

    def leaf_len(self, i: int) -> int:
        """Cache positions layer ``i`` keeps a slot: a ring of the
        sliding window, or the whole cache window."""
        cfg = self.model.config
        return min(cfg.sliding_window, self.max_len) if cfg.is_sliding(i) \
            else self.max_len

    def leaf_shapes(self, i: int):
        cfg = self.model.config
        shape = (self.leaf_len(i), cfg.num_key_value_heads * cfg.head_dim)
        return {"k": shape, "v": shape}


class AfmoeLM(DecoderLM):
    """``afmoe`` decoder over 1-based token ids ``(B, T)`` -> logits
    ``(B, T, vocab)``, built from the published ``config.json`` keys
    (``num_experts`` the experts held here, ``expert_share`` which).

    ``max_len`` is the cache window a ``ServingEngine`` over this model
    reserves per slot for a full layer (a sliding layer reserves
    ``min(sliding_window, max_len)``; positions need no table).
    ``param_dtype`` is the dtype the parameters are CREATED in, layer by
    layer. Initialisation, the constructor's: matrices normal std 0.02,
    norm weights 1, ``expert_bias`` 0 (float32, a buffer)."""

    config_class = AfmoeConfig
    serving_class = AfmoeServing
    layers = staticmethod(_layers)

    def _init_layer(self, key, dense: bool):
        import jax.numpy as jnp

        cfg, dt = self.config, jnp.dtype(self.param_dtype)
        H, nq, nkv, d = cfg.hidden_size, cfg.num_attention_heads, \
            cfg.num_key_value_heads, cfg.head_dim
        normal, stack, mlp = self._initialisers(key, cfg.num_experts)
        layer = {
            "input_norm": jnp.ones((H,), dt),
            "attn": {"wq": normal(H, nq * d), "wk": normal(H, nkv * d),
                     "wv": normal(H, nkv * d), "wo": normal(nq * d, H),
                     "wg": normal(H, nq * d),
                     "q_norm": jnp.ones((d,), dt),
                     "k_norm": jnp.ones((d,), dt)},
            "post_attn_norm": jnp.ones((H,), dt),
            "pre_mlp_norm": jnp.ones((H,), dt),
            "post_mlp_norm": jnp.ones((H,), dt),
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
