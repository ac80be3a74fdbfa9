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
(:class:`AfmoeServing`); it does not train.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

from bigdl_tpu.models.decoder_ops import rms_norm, rope, swiglu
from bigdl_tpu.nn.module import AbstractModule

#: queries a block of the prefill's attention: a wave's scores exist
#: one block at a time (16,384 tokens x 48 heads x 128 x the key span)
QUERY_BLOCK = 128


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


def _blocked_attention(q, k, v, window, scale):
    """Causal grouped-query attention of a block over its OWN keys,
    queries in blocks of :data:`QUERY_BLOCK` so that the scores of a
    wave never exist whole. ``q`` (B, T, nq, d) and ``k`` / ``v`` (B, T,
    nkv, d); ``window``: None, or the number of last keys a query sees
    (itself included), and then a block reads only the key span it can
    see. Each block is one batched matrix product a K/V head: its
    ``QUERY_BLOCK x (nq / nkv)`` query rows against the span's keys.
    Returns (B, T, nq * d)."""
    import jax.numpy as jnp
    from jax import lax

    B, T, nq, d = q.shape
    nkv = k.shape[2]
    J = nq // nkv
    Bq = min(QUERY_BLOCK, T)
    n_blocks = -(-T // Bq)
    span = T if window is None else min(T, window + Bq)
    # (B, nkv, T, J * d): a block of queries is then (B, nkv, Bq * J, d)
    qg = (q * scale).astype(q.dtype).reshape(B, T, nkv, J * d)
    qg = jnp.moveaxis(qg, 1, 2)
    if n_blocks * Bq != T:
        qg = jnp.pad(qg, [(0, 0), (0, 0), (0, n_blocks * Bq - T), (0, 0)])
    kg, vg = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)   # (B, nkv, T, d)

    def one(i):
        qs = lax.dynamic_slice_in_dim(qg, i * Bq, Bq, axis=2)
        qs = qs.reshape(B, nkv, Bq * J, d)
        start = jnp.clip((i + 1) * Bq - span, 0, T - span)
        ks = lax.dynamic_slice_in_dim(kg, start, span, axis=2)
        vs = lax.dynamic_slice_in_dim(vg, start, span, axis=2)
        s = jnp.einsum("bgmd,bgkd->bgmk", qs, ks,
                       preferred_element_type=jnp.float32)
        qp = (i * Bq + jnp.arange(Bq * J) // J)[:, None]
        kp = (start + jnp.arange(span))[None, :]
        seen = kp <= qp
        if window is not None:
            seen = seen & (qp - kp < window)
        s = jnp.where(seen, s, -1e30)
        # the softmax by hand: the row maximum behind a barrier (left
        # to itself the compiler turns "x - max(x)" into a windowed
        # reduction over the whole span, 7.8 ms a block at a span of
        # 4,224 against 0.3 for the product), and the division after
        # the second product, on (rows, d) instead of (rows, span)
        top = lax.optimization_barrier(jnp.max(s, axis=-1, keepdims=True))
        e = jnp.exp(s - top)
        ctx = jnp.einsum("bgmk,bgkd->bgmd", e.astype(v.dtype), vs,
                         preferred_element_type=jnp.float32)
        ctx = ctx / jnp.sum(e, axis=-1, keepdims=True)
        return ctx.astype(q.dtype).reshape(B, nkv, Bq, J * d)

    ctx = lax.map(one, jnp.arange(n_blocks))    # (n_blocks, B, nkv, Bq, .)
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(B, nkv, n_blocks * Bq, J * d)
    return jnp.moveaxis(ctx, 1, 2).reshape(B, n_blocks * Bq, nq * d)[:, :T]


def _fresh_rows(x, valid, length: int):
    """A fresh cache leaf from a block's keys or values ``x`` (B, T,
    c): zeros beyond a row's length; ``min(T, length)`` positions long
    (the pool's scatter writes the columns it is given). A block longer
    than the leaf fills a RING: entry ``j`` holds the row's LAST
    position ``p`` with ``p % length == j``."""
    import jax.numpy as jnp

    T = x.shape[1]
    x = jnp.where(valid[:, :, None], x, 0)
    if T <= length:
        return x
    last = jnp.sum(valid, axis=1, dtype=jnp.int32)[:, None] - 1   # (B, 1)
    idx = last - (last - jnp.arange(length, dtype=jnp.int32)[None]) % length
    return jnp.take_along_axis(x, jnp.clip(idx, 0, T - 1)[:, :, None],
                               axis=1)


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
        ctx = _blocked_attention(q, k, v.reshape(B, T, nkv, d),
                                 cfg.sliding_window if sliding else None,
                                 scale)
        if fresh_len is not None:
            new_cache = {"k": _fresh_rows(k.reshape(B, T, nkv * d), valid,
                                          fresh_len),
                         "v": _fresh_rows(v, valid, fresh_len)}
    out = (ctx.astype(jnp.float32) * gate).astype(a.dtype) @ p["wo"]
    return out, new_cache


def _moe(cfg, p, m, valid):
    """The shared expert plus this chip's part of the routed experts;
    returns the sum and the (held,) count of tokens each held expert
    received."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.parallel.moe import routed_experts

    B, T, H = m.shape
    flat = m.reshape(B * T, H)
    with jax.named_scope("moe.shared"):
        shared = swiglu(flat, p["shared"])
    routed, counts = routed_experts(
        flat, p["router"], p["experts"], cfg.expert_offset,
        cfg.num_experts_per_tok, valid=valid.reshape(B * T),
        route_norm=cfg.route_norm, route_scale=cfg.route_scale)
    out = (shared.astype(jnp.float32) + routed).astype(m.dtype)
    return out.reshape(B, T, H), counts


def _block(cfg, i, p, x, qpos, valid, cache=None, fresh_len=None):
    """Layer ``i`` for every query shape. ``x`` (B, T, H); ``qpos`` (B,
    T) absolute positions; ``valid`` (B, T) marks real tokens (a prefix
    of each row). ``cache`` (decode): T = 1, every row continues from
    its cache at ``qpos``, and rows where ``valid`` is false leave every
    leaf bitwise untouched. ``fresh_len``: fresh cache rows are made
    (:func:`_fresh_rows`). Neither: no state is read or kept. Returns
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
        out, counts = _moe(cfg, p["moe"], m, valid)
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


def _logits(cfg, params, x):
    import jax.numpy as jnp

    return jnp.einsum("...h,vh->...v",
                      rms_norm(x, params["final_norm"], cfg.rms_norm_eps),
                      params["head"], preferred_element_type=jnp.float32)


# ------------------------------------------------------------ the model


class AfmoeLM(AbstractModule):
    """``afmoe`` decoder over 1-based token ids ``(B, T)`` -> logits
    ``(B, T, vocab)``, built from the published ``config.json`` keys
    (``num_experts`` the experts held here, ``expert_share`` which).

    ``max_len`` is the cache window a ``ServingEngine`` over this model
    reserves per slot for a full layer (a sliding layer reserves
    ``min(sliding_window, max_len)``; positions need no table).
    ``param_dtype`` is the dtype the parameters are CREATED in, layer by
    layer. Initialisation, the constructor's: matrices normal std 0.02,
    norm weights 1, ``expert_bias`` 0 (float32, a buffer)."""

    def __init__(self, config: dict, max_len: int = 1024,
                 param_dtype="float32") -> None:
        super().__init__()
        import jax.numpy as jnp

        self.config = AfmoeConfig.from_dict(config)
        self.max_len = int(max_len)
        self.param_dtype = jnp.dtype(param_dtype).name
        self._serving: Optional[AfmoeServing] = None

    def _init_layer(self, key, dense: bool):
        import jax
        import jax.numpy as jnp
        from jax import lax

        cfg, dt = self.config, jnp.dtype(self.param_dtype)
        H, nq, nkv, d = cfg.hidden_size, cfg.num_attention_heads, \
            cfg.num_key_value_heads, cfg.head_dim
        keys = iter(jax.random.split(key, 16))

        def normal(*shape):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * 0.02).astype(dt)

        def stack(*shape):
            # one expert at a time: the float32 draw of a whole stack
            # is never alive
            return lax.map(
                lambda k: (jax.random.normal(k, shape, jnp.float32)
                           * 0.02).astype(dt),
                jax.random.split(next(keys), cfg.num_experts))

        def mlp(width, make=normal):
            return {"gate": make(H, width), "up": make(H, width),
                    "down": make(width, H)}

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

    def init_params(self, rng):
        import jax
        import jax.numpy as jnp

        cfg, dt = self.config, jnp.dtype(self.param_dtype)
        keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
        # one compiled initialiser per layer kind, called once a layer:
        # every leaf is made in the parameter dtype, never as a float32
        # tree
        init_layer = jax.jit(self._init_layer, static_argnums=(1,))

        @jax.jit
        def table(key):
            return (jax.random.normal(
                key, (cfg.vocab_size, cfg.hidden_size), jnp.float32)
                * 0.02).astype(dt)

        return {"embed": table(keys[0]),
                "layers": [init_layer(k, cfg.is_dense(i))
                           for i, k in enumerate(keys[2:])],
                "final_norm": jnp.ones((cfg.hidden_size,), dt),
                "head": table(keys[1])}

    def _ensure_params(self) -> None:
        # no gradient buffers: the family serves, it does not train
        self._materialize_params()

    def apply(self, params, input, state=None, training=False, rng=None):
        import jax.numpy as jnp

        tokens0 = jnp.asarray(input, jnp.int32) - 1
        B, T = tokens0.shape
        qpos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x, _, _ = _layers(self.config, params, tokens0, qpos,
                          jnp.ones((B, T), bool))
        return _logits(self.config, params, x), state

    def serving_family(self) -> "AfmoeServing":
        if self._serving is None:
            self._serving = AfmoeServing(self)
        return self._serving


# ------------------------------------------------- the serving programs


class AfmoeServing:
    """What ``ServingEngine`` asks of a model's family
    (``serving/family.py``). The programs are built once per compute
    dtype and shared by every engine over the model.

    ``prefill_token_bound``: a wave of this family is at most so many
    tokens (rows x bucket), so its rows follow its bucket, and its
    prefill makes its fresh cache rows inside the program (it is handed
    no carry). ``decode_step``'s program returns, after the carry, the
    expert layers' token counts ``(n_expert_layers, held)`` of the
    ACTIVE rows, which the engine reads back at the decode fence."""

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

    #: rows x bucket of one prefill wave (16 rows up to 1,024 ... 2 at
    #: 8,192): what a wave's activations and its experts' grouped
    #: product are sized for
    prefill_token_bound = 16384

    def __init__(self, model: AfmoeLM) -> None:
        self.model = model
        self.max_len = model.max_len
        self.vocab = model.config.vocab_size
        self._built: Dict[tuple, object] = {}

    def _dtype(self, compute_dtype):
        import jax.numpy as jnp

        return jnp.dtype(compute_dtype or self.model.param_dtype)

    def leaf_len(self, i: int) -> int:
        """Cache positions layer ``i`` keeps a slot: a ring of the
        sliding window, or the whole cache window."""
        cfg = self.model.config
        return min(cfg.sliding_window, self.max_len) if cfg.is_sliding(i) \
            else self.max_len

    def params(self, compute_dtype=None):
        """The model's own tree where it already is in the serving
        dtype (no second copy); a cast copy otherwise (``expert_bias``
        stays the float32 buffer it is)."""
        import jax
        import jax.numpy as jnp

        self.model._ensure_params()
        dt = self._dtype(compute_dtype)
        if dt.name == self.model.param_dtype:
            return self.model.params
        return jax.tree_util.tree_map_with_path(
            lambda path, a: a if path[-1] == jax.tree_util.DictKey("bias")
            else a.astype(dt), self.model.params)

    def init_carry(self, compute_dtype=None):
        import jax.numpy as jnp

        cfg, dt = self.model.config, self._dtype(compute_dtype)
        kv = cfg.num_key_value_heads * cfg.head_dim

        def init_carry(n_slots: int):
            carry = {"pos": jnp.zeros((n_slots,), jnp.int32)}
            for i in range(cfg.num_hidden_layers):
                shape = (n_slots, self.leaf_len(i), kv)
                carry[f"k{i}"] = jnp.zeros(shape, dt)
                carry[f"v{i}"] = jnp.zeros(shape, dt)
            carry["rng"] = jnp.zeros((n_slots, 2), jnp.uint32)
            carry["tok_counts"] = jnp.zeros((n_slots, self.vocab), jnp.int32)
            carry["prompt_mask"] = jnp.zeros((n_slots, self.vocab), bool)
            return carry

        return init_carry

    def decode_step(self, compute_dtype=None, **variant):
        """``(step, init_carry)``: ``step(params, tokens, active, carry,
        knobs) -> (token, chosen_logp, carry, expert_counts)``: the
        contract of ``make_batch_decode_step(sampling=True)`` (one token
        a row, the carry donated, inactive rows bitwise untouched in
        every leaf) plus the counts."""
        assert not any(variant.values()), variant     # all refused
        key = ("decode", self._dtype(compute_dtype).name)
        if key not in self._built:
            self._built[key] = (self._make_decode(compute_dtype),
                                self.init_carry(compute_dtype))
        return self._built[key]

    def _make_decode(self, compute_dtype):
        import jax
        import jax.numpy as jnp

        cfg, dt = self.model.config, self._dtype(compute_dtype)

        def sample_step(params, tokens, active, carry, knobs):
            from bigdl_tpu.serving.sampling import sample_rows

            pos = carry["pos"]
            x, leaves, counts = _layers(
                cfg, params, tokens[:, None], pos[:, None], active[:, None],
                carry, dtype=dt)
            logp = jax.nn.log_softmax(_logits(cfg, params, x[:, 0]), axis=-1)
            tok, chosen, new_keys, new_counts = sample_rows(
                logp, carry["rng"], knobs, carry["tok_counts"],
                carry["prompt_mask"])
            new_carry = dict(
                carry, **leaves, pos=pos + active.astype(jnp.int32),
                rng=jnp.where(active[:, None], new_keys, carry["rng"]),
                tok_counts=jnp.where(active[:, None], new_counts,
                                     carry["tok_counts"]))
            return (tok, chosen, new_carry) + \
                (() if counts is None else (counts,))

        return jax.jit(sample_step, donate_argnums=(3,))

    def batch_prefill_step(self, compute_dtype=None, **variant):
        """``prefill(params, tokens, lengths, carry) -> (logprobs_last,
        rows)`` for FRESH rows: ``tokens`` (B, L) right-padded,
        ``lengths`` (B,). ``rows`` holds ``pos`` (= lengths) and every
        ``k{i}`` / ``v{i}`` ``min(L, len_i)`` positions long: row r's
        K/V at ``0..lengths[r]-1``, or in a ring shorter than the
        bucket its last ``len_i`` positions at ``p % len_i``; zeros
        beyond a row's length. ``carry`` is not read (None)."""
        assert not any(variant.values()), variant     # all refused
        key = ("prefill", self._dtype(compute_dtype).name)
        if key not in self._built:
            self._built[key] = self._make_prefill(compute_dtype)
        return self._built[key]

    def _make_prefill(self, compute_dtype):
        import jax
        import jax.numpy as jnp
        import numpy as np

        cfg, dt, max_len = self.model.config, self._dtype(compute_dtype), \
            self.max_len
        fresh_lens = [self.leaf_len(i) for i in range(cfg.num_hidden_layers)]

        def prefill(params, tokens, lengths):
            B, L = tokens.shape
            qpos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
            x, rows, _ = _layers(cfg, params, tokens, qpos,
                                 qpos < lengths[:, None],
                                 fresh_lens=fresh_lens, dtype=dt)
            last = jnp.clip(lengths - 1, 0, L - 1)
            logits = _logits(cfg, params, x[jnp.arange(B), last])
            rows["pos"] = lengths.astype(jnp.int32)
            return jax.nn.log_softmax(logits, axis=-1), rows

        jitted = jax.jit(prefill)

        def prefill_checked(params, tokens, lengths, carry=None):
            from bigdl_tpu.serving.metrics import span

            # the span wraps the BODY (fences.SPAN_NAMES): host guards
            # and the program's LAUNCH, never its device time
            with span("prefill.launch", padded=tokens.shape[0],
                      bucket=tokens.shape[-1]) as sp:
                ln = np.asarray(lengths, np.int32)
                if tokens.ndim != 2 or ln.shape != tokens.shape[:1]:
                    raise ValueError(
                        f"tokens must be (B, L) with lengths (B,): got "
                        f"{tokens.shape} / {ln.shape}")
                sp.note(rows=int(np.count_nonzero(ln)))
                if (ln < 0).any() or (ln > tokens.shape[1]).any() \
                        or tokens.shape[1] > max_len:
                    raise ValueError(
                        f"lengths must lie in 0..L={tokens.shape[1]} <= "
                        f"max_len {max_len} (got {ln.tolist()})")
                return jitted(params, tokens, jnp.asarray(ln))

        prefill_checked._jitted = jitted
        return prefill_checked
