"""The Falcon-H1 decoder family: in every layer a Mamba-2 mixer and
grouped-query attention read the same normalised input and are summed
inside one residual, then a SwiGLU MLP; RMSNorm, rotary positions, the
muP multipliers of the published ``config.json``.

    x0 = E[token] * embedding_multiplier
    h  = RMSNorm(x)
    x  = x + ssm_out_multiplier * Mixer(h)
           + attention_out_multiplier * Attn(attention_in_multiplier * h)
    x  = x + MLP(RMSNorm(x))
    logits = (RMSNorm(x_last) @ W_head^T) * lm_head_multiplier

ONE block function (:func:`_block`) serves the three query shapes the
system has: a whole sequence without a cache (:meth:`FalconH1LM.apply`),
a right-padded prompt block that fills fresh cache rows (the batched
prefill) and one token a row against the pooled cache (the sampling
decode step). A serving carry holds, per layer ``i`` and slot, K/V rows
``k{i}`` / ``v{i}`` ``(n_slots, max_len, kv_heads*head_dim)`` like every
family's, and beside them the mixer's state: ``ssm{i}`` ``(n_slots,
heads, head_dim, d_state)`` float32 and ``conv{i}`` ``(n_slots, d_conv -
1, conv_dim)``, the last inputs of the depthwise convolution.

The family serves through ``ServingEngine``'s default path only
(:class:`FalconH1Serving`); it does not train (``_ensure_params`` makes
no gradient buffers: at the published widths they do not fit a chip).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

from bigdl_tpu.models.decoder_ops import rms_norm as _rms_norm
from bigdl_tpu.models.decoder_ops import rope as _rope
from bigdl_tpu.models.decoder_ops import swiglu
from bigdl_tpu.nn.module import AbstractModule


class FalconH1Config(NamedTuple):
    """The published keys the layer's equations read, under their
    published names."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_d_ssm: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_n_groups: int
    mamba_d_conv: int
    mamba_chunk_size: int
    rms_norm_eps: float
    rope_theta: float
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: tuple
    mlp_multipliers: tuple

    @classmethod
    def from_dict(cls, config: dict) -> "FalconH1Config":
        if config["mamba_d_ssm"] != \
                config["mamba_n_heads"] * config["mamba_d_head"]:
            raise ValueError("mamba_d_ssm must be mamba_n_heads x "
                             "mamba_d_head")
        if config["mamba_n_heads"] % config["mamba_n_groups"] or \
                config["num_attention_heads"] % config["num_key_value_heads"]:
            raise ValueError("heads must divide into their groups")
        for flag, want in (("mamba_rms_norm", True), ("mamba_conv_bias", True),
                           ("mamba_norm_before_gate", False),
                           ("attention_bias", False), ("mlp_bias", False),
                           ("mamba_proj_bias", False),
                           ("projectors_bias", False),
                           ("tie_word_embeddings", False),
                           ("rope_scaling", None),
                           ("attn_layer_indices", None)):
            if config.get(flag, want) != want:
                raise ValueError(f"{flag}={config[flag]!r} is not "
                                 f"implemented (only {want!r})")
        return cls(**{k: (tuple(config[k]) if isinstance(config[k], list)
                          else config[k]) for k in cls._fields})

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        # gate z, then [x, B, C] (the convolution's channels), then dt
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads


# ------------------------------------------------------------ the layer


def _attention(cfg, p, u, qpos, valid, cache, decode):
    """Grouped-query attention of the block's input ``u`` (B, T, H).
    Without a cache, or into fresh cache rows, the block attends over
    its own keys under the causal mask (keys beyond a row's length are
    never under it for a real query); ``decode`` writes one key a row at
    ``qpos`` and attends over the row's cache ``0..qpos``."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.decode_attention import decode_attention

    B, T, _ = u.shape
    nq, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    q = (u @ p["wq"]).reshape(B, T, nq, d)
    k = ((u @ p["wk"]) * cfg.key_multiplier).astype(u.dtype)
    k = _rope(k.reshape(B, T, nkv, d), qpos, cfg.rope_theta)
    q = _rope(q, qpos, cfg.rope_theta)
    v = u @ p["wv"]                                   # (B, T, nkv*d)
    k = k.reshape(B, T, nkv * d)
    scale = d ** -0.5
    if decode:
        rows = jnp.arange(B)
        on = valid[:, 0]
        wpos = jnp.clip(qpos[:, 0], 0, cache["k"].shape[1] - 1)
        # an inactive row writes its OLD value back: bitwise untouched
        k_wr = jnp.where(on[:, None], k[:, 0].astype(cache["k"].dtype),
                         cache["k"][rows, wpos])
        v_wr = jnp.where(on[:, None], v[:, 0].astype(cache["v"].dtype),
                         cache["v"][rows, wpos])
        kc = cache["k"].at[rows, wpos].set(k_wr)
        vc = cache["v"].at[rows, wpos].set(v_wr)
        ctx = decode_attention(q[:, 0], kc, vc, wpos, scale=scale,
                               out_dtype=u.dtype, active=on)
        ctx = ctx.reshape(B, 1, nq * d)
        return ctx @ p["wo"], {"k": kc, "v": vc}
    qg = (q * scale).astype(u.dtype).reshape(B, T, nkv, nq // nkv, d)
    s = jnp.einsum("btgqd,bsgd->bgqts", qg, k.reshape(B, T, nkv, d),
                   preferred_element_type=jnp.float32)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bgqts,bsgd->btgqd", pr.astype(u.dtype),
                     v.reshape(B, T, nkv, d),
                     preferred_element_type=jnp.float32)
    out = ctx.astype(u.dtype).reshape(B, T, nq * d) @ p["wo"]
    if cache is None:
        return out, None
    # fresh rows: the block's keys land at 0..T-1, zeros beyond a
    # row's length (never read: the row's pos is its length)
    keep = valid[:, :, None]
    kc = cache["k"].at[:, :T].set(
        jnp.where(keep, k, 0).astype(cache["k"].dtype))
    vc = cache["v"].at[:, :T].set(
        jnp.where(keep, v, 0).astype(cache["v"].dtype))
    return out, {"k": kc, "v": vc}


def _scan_chunked(cfg, x, Bm, Cm, dt, A):
    """The selective scan from a ZERO state in chunks of
    ``mamba_chunk_size``: within a chunk the quadratic form, between
    chunks the carried state. ``x`` (B, T, G, hg, d), ``Bm`` / ``Cm``
    (B, T, G, N), ``dt`` (B, T, G, hg) float32 (0 where the recurrence
    must stand still), ``A`` (G, hg). Returns ``y`` (B, T, G, hg, d)
    float32 and the state after the last position (B, G, hg, d, N)."""
    import jax.numpy as jnp
    from jax import lax

    B, T, G, hg, d = x.shape
    N = Bm.shape[-1]
    Q = min(cfg.mamba_chunk_size, T)
    pad = -T % Q
    if pad:
        # dt = 0 there: decay 1, nothing added
        x, Bm, Cm, dt = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] *
                                 (a.ndim - 2)) for a in (x, Bm, Cm, dt))
    n_chunks = (T + pad) // Q

    def chunks(a):          # (B, T, ...) -> (n_chunks, B, Q, ...)
        return jnp.moveaxis(a.reshape(B, n_chunks, Q, *a.shape[2:]), 1, 0)

    tri = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]     # s >= u

    def body(S, c):
        xc, Bc, Cc, dtc = c
        a = jnp.moveaxis(dtc * A, 1, -1)             # (B, G, hg, Q), <= 0
        cum = jnp.cumsum(a, axis=-1)
        dtq = jnp.moveaxis(dtc, 1, -1)               # (B, G, hg, Q)
        # within the chunk:
        #   y_s += sum_{u<=s} (C_s.B_u) e^{cum_s-cum_u} dt_u x_u
        cb = jnp.einsum("bsgn,bugn->bgsu", Cc, Bc,
                        preferred_element_type=jnp.float32)
        decay = jnp.exp(jnp.where(tri, cum[..., :, None] - cum[..., None, :],
                                  -jnp.inf))
        w = cb[:, :, None] * decay * dtq[..., None, :]         # B,G,hg,s,u
        y = jnp.einsum("bghsu,bughd->bsghd", w.astype(xc.dtype), xc,
                       preferred_element_type=jnp.float32)
        # from the carried state: y_s += e^{cum_s} C_s . S
        y = y + jnp.einsum("bsgn,bghdn->bsghd", Cc.astype(jnp.float32), S) \
            * jnp.moveaxis(jnp.exp(cum), -1, 1)[..., None]
        # the state after the chunk
        tail = jnp.exp(cum[..., -1:] - cum) * dtq              # B,G,hg,u
        xw = (xc.astype(jnp.float32)
              * jnp.moveaxis(tail, -1, 1)[..., None]).astype(xc.dtype)
        S = S * jnp.exp(cum[..., -1])[..., None, None] + jnp.einsum(
            "bughd,bugn->bghdn", xw, Bc, preferred_element_type=jnp.float32)
        return S, y

    S0 = jnp.zeros((B, G, hg, d, N), jnp.float32)
    S, y = lax.scan(body, S0, tuple(chunks(a) for a in (x, Bm, Cm, dt)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, T + pad, G, hg, d)
    return y[:, :T], S


def _mixer(cfg, p, h, valid, cache, decode):
    """The Mamba-2 mixer of the block's input ``h`` (B, T, H). From a
    cache it continues (``decode``: the convolution's window and the
    scan state of each row) or starts fresh rows whose state it leaves
    as after each row's LAST REAL token: ``dt`` is 0 where ``valid`` is
    false, so beyond a row's length the decay is 1 and nothing is
    added, and the window kept is the row's last ``d_conv - 1`` real
    inputs."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.ssm_decode import ssm_decode

    B, T, _ = h.shape
    G, nh, d, N = cfg.mamba_n_groups, cfg.mamba_n_heads, cfg.mamba_d_head, \
        cfg.mamba_d_state
    hg, d_ssm, K = nh // G, cfg.mamba_d_ssm, cfg.mamba_d_conv
    m = cfg.ssm_multipliers
    proj = (h * cfg.ssm_in_multiplier).astype(h.dtype) @ p["in_proj"]
    z = proj[..., :d_ssm] * m[0]
    # the convolution's input: [x, B, C], each under its own multiplier
    mult = jnp.concatenate([jnp.full((d_ssm,), m[1], jnp.float32),
                            jnp.full((G * N,), m[2], jnp.float32),
                            jnp.full((G * N,), m[3], jnp.float32)])
    xbc = (proj[..., d_ssm:d_ssm + cfg.conv_dim] * mult).astype(h.dtype)
    dt_raw = proj[..., d_ssm + cfg.conv_dim:].astype(jnp.float32) * m[4]

    window = cache["conv"] if decode else \
        jnp.zeros((B, K - 1, cfg.conv_dim), h.dtype)
    ext = jnp.concatenate([window.astype(h.dtype), xbc], axis=1)
    w = p["conv_w"].astype(jnp.float32)                        # (K, C)
    conv = p["conv_b"].astype(jnp.float32) + sum(
        ext[:, j:j + T].astype(jnp.float32) * w[j] for j in range(K))
    xbc_c = jax.nn.silu(conv).astype(h.dtype)
    x = xbc_c[..., :d_ssm].reshape(B, T, G, hg, d)
    Bm = xbc_c[..., d_ssm:d_ssm + G * N].reshape(B, T, G, N)
    Cm = xbc_c[..., d_ssm + G * N:].reshape(B, T, G, N)

    dt = jax.nn.softplus(dt_raw + p["dt_bias"].astype(jnp.float32))
    dt = jnp.where(valid[..., None], dt, 0.0).reshape(B, T, G, hg)
    A = -jnp.exp(p["A_log"].astype(jnp.float32)).reshape(G, hg)
    if decode:
        # the decoding rows' state, read once and written once in place
        # (on a TPU; ops/ssm_decode.py); the others keep theirs bitwise
        y, S = ssm_decode(x[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0], A,
                          cache["ssm"], valid[:, 0])
        y = y[:, None]
    else:
        y, S = _scan_chunked(cfg, x, Bm, Cm, dt, A)
    y = y + p["D"].astype(jnp.float32).reshape(G, hg)[..., None] \
        * x.astype(jnp.float32)
    # gated group RMSNorm (norm AFTER the gate), one group a scan group
    y = y.reshape(B, T, G, hg * d) \
        * jax.nn.silu(z.astype(jnp.float32)).reshape(B, T, G, hg * d)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg.rms_norm_eps)
    y = (y.reshape(B, T, d_ssm) * p["norm"].astype(jnp.float32))
    out = y.astype(h.dtype) @ p["out_proj"]
    if cache is None:
        return out, None
    n_real = jnp.sum(valid, axis=1)
    S = S.reshape(B, nh, d, N)
    # the last K-1 real inputs: ext[n_real : n_real + K-1] (a row of
    # length 0 reads its old window back)
    idx = n_real[:, None] + jnp.arange(K - 1)[None, :]
    window = jnp.take_along_axis(ext, idx[:, :, None], axis=1)
    return out, {"ssm": S, "conv": window.astype(cache["conv"].dtype)}


def _mlp(cfg, p, u):
    return swiglu(u, p, cfg.mlp_multipliers[0]) * cfg.mlp_multipliers[1]


def _block(cfg, p, x, qpos, valid, cache=None, decode=False):
    """One layer for every query shape. ``x`` (B, T, H); ``qpos`` (B, T)
    absolute positions; ``valid`` (B, T) marks real tokens (a prefix of
    each row). ``cache`` None: no state is read or kept. ``cache`` with
    ``decode`` False: fresh rows are filled (K/V at 0..T-1, the mixer's
    state after each row's last real token). ``decode``: T = 1, every
    row continues from its cache at ``qpos``; rows where ``valid`` is
    false leave every leaf bitwise untouched."""
    import jax

    h = _rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
    with jax.named_scope("mixer"):
        mix, c_mix = _mixer(cfg, p["mixer"], h, valid, cache, decode)
    with jax.named_scope("attention"):
        u = (h * cfg.attention_in_multiplier).astype(h.dtype)
        att, c_att = _attention(cfg, p["attn"], u, qpos, valid, cache, decode)
    x = x + (mix * cfg.ssm_out_multiplier
             + att * cfg.attention_out_multiplier).astype(x.dtype)
    with jax.named_scope("mlp"):
        x = x + _mlp(cfg, p["mlp"],
                     _rms_norm(x, p["pre_ff_norm"], cfg.rms_norm_eps)
                     ).astype(x.dtype)
    return x, None if cache is None else {**c_att, **c_mix}


#: the per-layer leaves of a serving carry, beside ``pos``
CACHE_KINDS = ("k", "v", "ssm", "conv")


def _layers(cfg, params, tokens0, qpos, valid, carry=None, decode=False,
            dtype=None):
    """Embedding and every block; returns the hidden states before the
    final norm and the carry with every layer's leaves replaced."""
    import jax.numpy as jnp

    x = jnp.take(params["embed"], jnp.clip(tokens0, 0, cfg.vocab_size - 1),
                 axis=0) * cfg.embedding_multiplier
    x = x.astype(dtype or params["embed"].dtype)
    new_carry = None if carry is None else dict(carry)
    for i, lp in enumerate(params["layers"]):
        cache = None if carry is None else \
            {kind: carry[f"{kind}{i}"] for kind in CACHE_KINDS}
        x, cache = _block(cfg, lp, x, qpos, valid, cache, decode)
        if cache is not None:
            for kind in CACHE_KINDS:
                new_carry[f"{kind}{i}"] = cache[kind]
    return x, new_carry


def _logits(cfg, params, x):
    import jax.numpy as jnp

    xf = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum("...h,vh->...v", xf, params["head"],
                      preferred_element_type=jnp.float32) \
        * cfg.lm_head_multiplier


# ------------------------------------------------------------ the model


class FalconH1LM(AbstractModule):
    """Falcon-H1 decoder over 1-based token ids ``(B, T)`` -> logits
    ``(B, T, vocab)``, built from the published ``config.json`` keys.

    ``max_len`` is the cache window a ``ServingEngine`` over this model
    reserves per slot (rotary positions need no table). ``param_dtype``
    is the dtype the parameters are CREATED in, layer by layer: bfloat16
    as published for the chip, float32 for CPU tests. Initialisation:
    matrices normal std 0.02, norm weights 1, ``A_log = log(1..heads)``,
    ``D = 1``, ``dt_bias = 1``, the depthwise convolution uniform
    +-1/sqrt(d_conv) (its constructor's default)."""

    def __init__(self, config: dict, max_len: int = 1024,
                 param_dtype="float32") -> None:
        super().__init__()
        import jax.numpy as jnp

        self.config = FalconH1Config.from_dict(config)
        self.max_len = int(max_len)
        self.param_dtype = jnp.dtype(param_dtype).name
        self._serving: Optional[FalconH1Serving] = None

    def _init_layer(self, key):
        import jax
        import jax.numpy as jnp

        cfg, dt = self.config, jnp.dtype(self.param_dtype)
        H, F = cfg.hidden_size, cfg.intermediate_size
        nq, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        keys = iter(jax.random.split(key, 12))

        def normal(*shape):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * 0.02).astype(dt)

        def conv(*shape):
            bound = cfg.mamba_d_conv ** -0.5
            return jax.random.uniform(next(keys), shape, jnp.float32,
                                      -bound, bound).astype(dt)

        heads = jnp.arange(1, cfg.mamba_n_heads + 1, dtype=jnp.float32)
        return {
            "input_norm": jnp.ones((H,), dt),
            "mixer": {
                "in_proj": normal(H, cfg.in_proj_dim),
                "conv_w": conv(cfg.mamba_d_conv, cfg.conv_dim),
                "conv_b": conv(cfg.conv_dim),
                "A_log": jnp.log(heads).astype(dt),
                "D": jnp.ones((cfg.mamba_n_heads,), dt),
                "dt_bias": jnp.ones((cfg.mamba_n_heads,), dt),
                "norm": jnp.ones((cfg.mamba_d_ssm,), dt),
                "out_proj": normal(cfg.mamba_d_ssm, H),
            },
            "attn": {"wq": normal(H, nq * d), "wk": normal(H, nkv * d),
                     "wv": normal(H, nkv * d), "wo": normal(nq * d, H)},
            "pre_ff_norm": jnp.ones((H,), dt),
            "mlp": {"gate": normal(H, F), "up": normal(H, F),
                    "down": normal(F, H)},
        }

    def init_params(self, rng):
        import jax
        import jax.numpy as jnp

        cfg, dt = self.config, jnp.dtype(self.param_dtype)
        keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
        # one compiled initialiser, called once a layer: every leaf is
        # made in the parameter dtype, never as a float32 tree
        init_layer = jax.jit(self._init_layer)

        @jax.jit
        def table(key):
            return (jax.random.normal(
                key, (cfg.vocab_size, cfg.hidden_size), jnp.float32)
                * 0.02).astype(dt)

        return {"embed": table(keys[0]),
                "layers": [init_layer(k) for k in keys[2:]],
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
        x, _ = _layers(self.config, params, tokens0, qpos,
                       jnp.ones((B, T), bool))
        return _logits(self.config, params, x), state

    def serving_family(self) -> "FalconH1Serving":
        if self._serving is None:
            self._serving = FalconH1Serving(self)
        return self._serving


# ------------------------------------------------- the serving programs


class FalconH1Serving:
    """What ``ServingEngine`` asks of a model's family
    (``serving/family.py``): the cache window and vocabulary, the
    parameters in the serving dtype, the pooled sampling decode step
    with its ``init_carry``, the batched prefill step, and the engine
    options the family refuses. The programs are built once per compute
    dtype and shared by every engine over the model."""

    #: engine option -> why this family cannot take it yet
    refuses = {
        "prefix_cache": "a truncated prefix hit is invalid for recurrent "
                        "state",
        "speculative": "there is no verify step that rolls recurrent "
                       "state back",
        "adapters": "the block has no adapter sites",
        "kv_dtype": "the int8 K/V layout is not written by this family",
        "mesh": "recurrent state has no sharding rules yet",
        "parallelism": "recurrent state has no sharding rules yet",
        "admission": "only batched admission fills recurrent state "
                     "(no chunked or per-request prefill)",
        "tier": "the host tier's payload codec knows K/V leaves only",
    }

    def __init__(self, model: FalconH1LM) -> None:
        self.model = model
        self.max_len = model.max_len
        self.vocab = model.config.vocab_size
        self._built: Dict[tuple, object] = {}

    def _dtype(self, compute_dtype):
        import jax.numpy as jnp

        return jnp.dtype(compute_dtype or self.model.param_dtype)

    def params(self, compute_dtype=None):
        """The model's own tree where it already is in the serving
        dtype (no second copy); a cast copy otherwise."""
        import jax

        self.model._ensure_params()
        dt = self._dtype(compute_dtype)
        if dt.name == self.model.param_dtype:
            return self.model.params
        return jax.tree_util.tree_map(lambda a: a.astype(dt),
                                      self.model.params)

    def init_carry(self, compute_dtype=None):
        import jax.numpy as jnp

        cfg, dt = self.model.config, self._dtype(compute_dtype)
        kv = cfg.num_key_value_heads * cfg.head_dim

        def init_carry(n_slots: int):
            carry = {"pos": jnp.zeros((n_slots,), jnp.int32)}
            for i in range(cfg.num_hidden_layers):
                carry[f"k{i}"] = jnp.zeros((n_slots, self.max_len, kv), dt)
                carry[f"v{i}"] = jnp.zeros((n_slots, self.max_len, kv), dt)
                carry[f"ssm{i}"] = jnp.zeros(
                    (n_slots, cfg.mamba_n_heads, cfg.mamba_d_head,
                     cfg.mamba_d_state), jnp.float32)
                carry[f"conv{i}"] = jnp.zeros(
                    (n_slots, cfg.mamba_d_conv - 1, cfg.conv_dim), dt)
            carry["rng"] = jnp.zeros((n_slots, 2), jnp.uint32)
            carry["tok_counts"] = jnp.zeros((n_slots, self.vocab), jnp.int32)
            carry["prompt_mask"] = jnp.zeros((n_slots, self.vocab), bool)
            return carry

        return init_carry

    def decode_step(self, compute_dtype=None, **variant):
        """``(step, init_carry)``: ``step(params, tokens, active, carry,
        knobs) -> (token, chosen_logp, carry)``, the contract of
        ``make_batch_decode_step(sampling=True)``: one token a row, the
        carry donated, inactive rows bitwise untouched in every leaf."""
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
            x, new_carry = _layers(cfg, params, tokens[:, None],
                                   pos[:, None], active[:, None], carry,
                                   decode=True, dtype=dt)
            logp = jax.nn.log_softmax(_logits(cfg, params, x[:, 0]), axis=-1)
            new_carry["pos"] = pos + active.astype(jnp.int32)
            tok, chosen, new_keys, new_counts = sample_rows(
                logp, carry["rng"], knobs, carry["tok_counts"],
                carry["prompt_mask"], active)
            new_carry["rng"] = jnp.where(active[:, None], new_keys,
                                         carry["rng"])
            new_carry["tok_counts"] = jnp.where(
                active[:, None], new_counts, carry["tok_counts"])
            return tok, chosen, new_carry

        return jax.jit(sample_step, donate_argnums=(3,))

    def batch_prefill_step(self, compute_dtype=None, **variant):
        """``prefill(params, tokens, lengths, carry) -> (logprobs_last,
        carry)``, the contract of ``make_batch_prefill_step`` for FRESH
        rows: ``tokens`` (B, L) right-padded, ``lengths`` (B,); row r's
        K/V land at ``0..lengths[r]-1``, its scan state and convolution
        window are those after its last real token, its ``pos`` is its
        length; a row of length 0 is ballast and leaves zeros. The carry
        handed in gives the shapes (the engine's shared zero carry) and
        is not continued from."""
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

        def prefill(params, tokens, lengths, carry):
            B, L = tokens.shape
            qpos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
            valid = qpos < lengths[:, None]
            x, new_carry = _layers(cfg, params, tokens, qpos, valid, carry,
                                   dtype=dt)
            last = jnp.clip(lengths - 1, 0, L - 1)
            logits = _logits(cfg, params, x[jnp.arange(B), last])
            new_carry["pos"] = lengths.astype(carry["pos"].dtype)
            return jax.nn.log_softmax(logits, axis=-1), new_carry

        jitted = jax.jit(prefill)

        def prefill_checked(params, tokens, lengths, carry):
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
                if carry["pos"].shape[0] != tokens.shape[0]:
                    raise ValueError(
                        f"carry has {carry['pos'].shape[0]} rows but tokens "
                        f"has {tokens.shape[0]}")
                sp.note(rows=int(np.count_nonzero(ln)))
                if (ln < 0).any() or (ln > tokens.shape[1]).any() \
                        or tokens.shape[1] > max_len:
                    raise ValueError(
                        f"lengths must lie in 0..L={tokens.shape[1]} <= "
                        f"max_len {max_len} (got {ln.tolist()})")
                return jitted(params, tokens, jnp.asarray(ln), carry)

        prefill_checked._jitted = jitted
        return prefill_checked
