"""What the decoder families that prefill whole waves and route experts
share ABOVE their block function (``models/afmoe.py``,
``models/glm_moe_lite.py``): the model object (parameters created in
the stated dtype layer by layer, the whole-sequence forward) and the
serving family's two programs (the batched prefill that makes its fresh
cache rows inside the program, the pooled sampling decode step that
returns the expert layers' token counts after the carry). A family
brings its configuration, its layer's initialiser, its ``_layers``
function and the leaves its cache keeps a layer; nothing here knows an
attention.
"""

from __future__ import annotations

from typing import Dict

from bigdl_tpu.models.decoder_ops import rms_norm
from bigdl_tpu.nn.module import AbstractModule


def final_logits(cfg, params, x):
    """The final RMSNorm and the untied head, float32 logits."""
    import jax.numpy as jnp

    return jnp.einsum("...h,vh->...v",
                      rms_norm(x, params["final_norm"], cfg.rms_norm_eps),
                      params["head"], preferred_element_type=jnp.float32)


class DecoderLM(AbstractModule):
    """A decoder over 1-based token ids ``(B, T)`` -> logits ``(B, T,
    vocab)``, built from a published ``config.json``'s keys.

    A family sets ``config_class`` (``from_dict(config)``; the result
    has ``vocab_size``, ``hidden_size``, ``num_hidden_layers``,
    ``rms_norm_eps`` and ``is_dense(i)``), ``serving_class``,
    ``layers`` (a ``staticmethod``: ``layers(cfg, params, tokens0, qpos, valid, carry=None,
    fresh_lens=None, dtype=None) -> (hidden states before the final
    norm, new cache leaves, expert counts or None)``) and
    ``_init_layer(key, dense)``.

    ``max_len`` is the cache window a ``ServingEngine`` over this model
    reserves per slot (positions need no table). ``param_dtype`` is the
    dtype the parameters are CREATED in, layer by layer."""

    config_class = None
    serving_class = None
    layers = None

    def __init__(self, config: dict, max_len: int = 1024,
                 param_dtype="float32") -> None:
        super().__init__()
        import jax.numpy as jnp

        self.config = self.config_class.from_dict(config)
        self.max_len = int(max_len)
        self.param_dtype = jnp.dtype(param_dtype).name
        self._serving = None

    def _initialisers(self, key, n_experts: int):
        """``(normal, stack, mlp)`` for one layer's leaves, drawing from
        ``key`` in the order they are called: ``normal(*shape)`` a
        matrix of std 0.02 in the parameter dtype, ``stack(*shape)``
        ``n_experts`` of them, ``mlp(width, make=normal)`` a SwiGLU's
        three."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        dt, H = jnp.dtype(self.param_dtype), self.config.hidden_size
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
                jax.random.split(next(keys), n_experts))

        def mlp(width, make=normal):
            return {"gate": make(H, width), "up": make(H, width),
                    "down": make(width, H)}

        return normal, stack, mlp

    def _init_layer(self, key, dense: bool):
        raise NotImplementedError

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
        x, _, _ = self.layers(self.config, params, tokens0, qpos,
                              jnp.ones((B, T), bool))
        return final_logits(self.config, params, x), state

    def serving_family(self):
        if self._serving is None:
            self._serving = self.serving_class(self)
        return self._serving


class DecoderServing:
    """What ``ServingEngine`` asks of a model's family
    (``serving/family.py``). The programs are built once per compute
    dtype and shared by every engine over the model. A family sets
    ``refuses`` and :meth:`leaf_shapes`.

    ``prefill_token_bound``: a wave of such a family is at most so many
    tokens (rows x bucket), so its rows follow its bucket, and its
    prefill makes its fresh cache rows inside the program (it is handed
    no carry). ``decode_step``'s program returns, after the carry, the
    expert layers' token counts ``(n_expert_layers, held)`` of the
    ACTIVE rows, which the engine reads back at the decode fence."""

    #: engine option -> why this family cannot take it yet
    refuses: Dict[str, str] = {}

    #: rows x bucket of one prefill wave (16 rows up to 1,024 ... 1 at
    #: 16,384): what a wave's activations and its experts' grouped
    #: product are sized for
    prefill_token_bound = 16384

    def __init__(self, model: DecoderLM) -> None:
        self.model = model
        self.max_len = model.max_len
        self.vocab = model.config.vocab_size
        self._built: Dict[tuple, object] = {}

    def _dtype(self, compute_dtype):
        import jax.numpy as jnp

        return jnp.dtype(compute_dtype or self.model.param_dtype)

    def leaf_shapes(self, i: int) -> Dict[str, tuple]:
        """The cache leaves layer ``i`` keeps a slot: leaf name (``k``,
        ``v``) -> ``(positions, columns)``."""
        raise NotImplementedError

    def params(self, compute_dtype=None):
        """The model's own tree where it already is in the serving
        dtype (no second copy); a cast copy otherwise (the router's
        ``bias`` stays the float32 buffer it is)."""
        import jax

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

        def init_carry(n_slots: int):
            carry = {"pos": jnp.zeros((n_slots,), jnp.int32)}
            for i in range(cfg.num_hidden_layers):
                for name, shape in self.leaf_shapes(i).items():
                    carry[f"{name}{i}"] = jnp.zeros((n_slots,) + shape, dt)
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
        layers = self.model.layers

        def sample_step(params, tokens, active, carry, knobs):
            from bigdl_tpu.serving.sampling import sample_rows

            pos = carry["pos"]
            x, leaves, counts = layers(
                cfg, params, tokens[:, None], pos[:, None], active[:, None],
                carry, dtype=dt)
            logp = jax.nn.log_softmax(
                final_logits(cfg, params, x[:, 0]), axis=-1)
            tok, chosen, new_keys, new_counts = sample_rows(
                logp, carry["rng"], knobs, carry["tok_counts"],
                carry["prompt_mask"], active)
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
        cache leaf ``min(L, len_i)`` positions long: row r's entries at
        ``0..lengths[r]-1``, or in a ring shorter than the bucket its
        last ``len_i`` positions at ``p % len_i``; zeros beyond a row's
        length. ``carry`` is not read (None)."""
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
        layers = self.model.layers
        # a layer's leaves share one length
        fresh_lens = [next(iter(self.leaf_shapes(i).values()))[0]
                      for i in range(cfg.num_hidden_layers)]

        def prefill(params, tokens, lengths):
            B, L = tokens.shape
            qpos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
            x, rows, _ = layers(cfg, params, tokens, qpos,
                                qpos < lengths[:, None],
                                fresh_lens=fresh_lens, dtype=dt)
            last = jnp.clip(lengths - 1, 0, L - 1)
            logits = final_logits(cfg, params, x[jnp.arange(B), last])
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
