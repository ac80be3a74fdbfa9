"""The one seam between ``ServingEngine`` and a model's family.

The engine serves whatever hands it these five things; it names no
model class anywhere else:

* ``max_len`` / ``vocab`` — the cache window a slot reserves and the
  token range;
* ``params(compute_dtype)`` — the parameter tree in the serving dtype,
  passed to every program as a runtime argument;
* ``decode_step(compute_dtype, mesh=, kv_quant=, adapter=) -> (step,
  init_carry)`` — the pooled sampling decode program and the carry
  layout it steps;
* ``batch_prefill_step(compute_dtype, mesh=, carry_sampling=, kv_quant=,
  adapter=)`` — the masked multi-row prefill that fills fresh rows of
  such a carry (the variants are the engine options a family may
  refuse: a family that refuses them all is only ever asked for the
  default);
* ``refuses`` — engine option -> why the family cannot take it; the
  engine raises a ``ValueError`` naming the option at construction;
* ``prefill_token_bound`` (optional; absent for a family whose waves
  are ``n_slots`` rows whatever the bucket) — the most tokens (rows x
  bucket) one prefill wave may hold: the rows of a wave then follow its
  bucket (``AdmissionController.wave_rows``). Such a family's prefill
  makes its fresh cache rows inside the program and is handed no
  carry; its K/V leaves may come back shorter than the pool's (the
  bucket's columns), and the pool's scatter writes what it is given.

A family's decode step may return more after ``(token, chosen_logp,
carry)``: device values the engine reads back at the SAME decode fence
and hands to ``ServingMetrics`` (a routed-expert family's per-expert
token counts, ``on_expert_counts``).

A model brings its family as ``model.serving_family()``
(``models/falcon_h1.py``, ``models/afmoe.py``). A ``Sequential`` ``TransformerLM`` has no
such method and gets :class:`SequentialLMFamily`, which delegates to
the step factories of ``models/transformer.py`` and refuses nothing.
"""

from __future__ import annotations


class SequentialLMFamily:
    """``TransformerLM`` (``LookupTable / PositionEmbedding / blocks /
    LayerNorm / Linear``) through ``models/transformer.py``'s cached
    step factories; every engine option is available."""

    refuses: dict = {}

    def __init__(self, model) -> None:
        self.model = model
        self.max_len = model.modules[1].max_len
        self.vocab = model.modules[0].n_index

    def params(self, compute_dtype=None):
        from bigdl_tpu.models.transformer import serving_params

        return serving_params(self.model, compute_dtype)

    def decode_step(self, compute_dtype=None, **variant):
        from bigdl_tpu.models.transformer import get_batch_decode_step

        return get_batch_decode_step(self.model, compute_dtype,
                                     sampling=True, **variant)

    def batch_prefill_step(self, compute_dtype=None, **variant):
        from bigdl_tpu.models.transformer import get_batch_prefill_step

        return get_batch_prefill_step(self.model, compute_dtype, **variant)

    def prefill_step(self, compute_dtype=None, **variant):
        """The B=1 prefill of ``admission="per_request"``."""
        from bigdl_tpu.models.transformer import get_prefill_step

        return get_prefill_step(self.model, compute_dtype, **variant)


def family_of(model):
    """The model's own family, or the ``Sequential`` LM's."""
    own = getattr(model, "serving_family", None)
    return own() if own is not None else SequentialLMFamily(model)


def check_options(family, **asked) -> None:
    """Raise for the first engine option that was asked for (a true
    value) and that the family refuses."""
    for option, value in asked.items():
        if value and option in family.refuses:
            raise ValueError(
                f"{option}={value!r} is not supported by "
                f"{type(family.model).__name__}: {family.refuses[option]}")
