"""Per-row sampled decoding for the serving plane.

The engine's decode step consumes pooled ``(N, vocab)`` log-probs; until
this subsystem it took a bare argmax — greedy only, no real generation
workload (chat, creative text, best-of-n). This module makes sampling a
first-class, SHAPE-STABLE part of the pooled step:

* :class:`SamplingParams` — one request's knobs (temperature, top-k,
  top-p, repetition/presence/frequency penalties, seed, max/min tokens,
  stop token ids, stop sequences). Immutable, validated, greedy by
  default (``temperature=0``).
* :func:`sample_rows` — the pure, jit-traceable per-row sampler. Every
  knob arrives as a BATCHED per-row array, so ONE compiled program
  covers every parameter mix in the pool: greedy and sampled rows, any
  temperature/top-k/top-p combination, penalties on or off — changing a
  request's knobs never recompiles (the values ride as runtime data,
  the shapes never change). ``temperature == 0`` rows degrade EXACTLY
  to argmax (all default knobs are arithmetic no-ops: ``* 1.0`` and
  ``- 0.0`` are bitwise identities in IEEE754), which is what lets the
  engine run every request — greedy included — through the one sampled
  step while preserving token-for-token greedy parity.
* RNG lanes — each pool row carries its own ``jax.random`` key as one
  row of an ``(n_slots, 2)`` uint32 array inside the KV carry. A step
  splits every row's key (``new, sub = split(key)``) and draws from
  ``sub``; rows never read each other's lanes, so a request's token
  stream depends only on (its seed, its step index) — the same stream
  whether the request runs alone, batched with arbitrary neighbors, or
  readmitted into a different slot after eviction
  (:func:`lane_key` is THE one seed → key rule, shared by the engine
  and ``generate()``).

The sampler runs fused inside the decode step
(:func:`bigdl_tpu.models.transformer.make_batch_decode_step` with
``sampling=True``): the ``(N, vocab)`` distribution is reduced to a
per-row token + chosen-token log-prob ON DEVICE, preserving the
one-small-host-readback-per-step property. ``generate()`` uses the same
:func:`sample_rows` (jitted standalone via :func:`get_sampler`) so the
serving and ad-hoc paths share one sampling implementation.

Stop-sequence matching is intentionally NOT here: sequences are
variable-length host data; the engine matches them on host against the
token stream (``serving/engine.py``), where it is O(stop length) per
step. Only the MIN-TOKENS ban (suppressing eos/stop tokens before
``min_tokens``) is device-side, via a fixed-width ``(N, MAX_BAN_IDS)``
banned-id array — fixed width keeps the program shape-stable.

Knob semantics (order of application, matching common practice):

1. repetition penalty (``> 1`` discourages): log-probs of tokens seen
   in the row's PROMPT or generated output are multiplied by the
   penalty (log-probs are ``<= 0``, so multiplying pushes them down);
2. presence penalty (``> 0`` discourages): subtracted once from every
   token already GENERATED by the row;
3. frequency penalty (``> 0`` discourages): subtracted per occurrence
   in the row's generated output;
4. min-tokens ban: banned ids forced to ``-inf`` while the row's host
   flag is up; then the CONSTRAINT mask (``serving/constrain.py``) —
   when the knob dict carries a per-row ``(N, vocab)`` bool ``allow``
   array, disallowed tokens are forced to ``-inf`` the same way (an
   all-True row is the identity, so unconstrained rows are untouched
   and the knob dicts of pre-constraint callers — which simply lack
   the key — trace the exact pre-constraint program);
5. temperature scaling, then top-k (ties at the k-th value are kept,
   like ``lax.top_k``-threshold filtering), then top-p over the
   top-k-filtered distribution (the top token is always kept;
   ``top_p == 1`` is no restriction). Both reduce to ONE threshold
   value a row, and the selection that finds it is as wide as the
   row's knobs need (:func:`wide_rows`): a row with ``0 < top_k <=
   K_CAP`` takes it from its ``K_CAP`` largest values
   (:func:`largest_values`), and the ``(N, V)`` sort runs only in
   a step where a DECODING row asks a nucleus with no ``top_k`` or a
   ``top_k`` over the cap. With ``top_p == 1`` the threshold is the
   same float as the sort's k-th value, so the tokens are bit-equal
   to the whole sort's; with ``top_p < 1`` on a capped row the
   softmax's normaliser is summed over the unsorted row and not the
   sorted one, so a token can differ only where a cumulative mass
   lies within rounding of ``top_p``;
6. the draw: Gumbel argmax via ``jax.random.categorical`` for
   ``temperature > 0`` rows, plain argmax for ``temperature == 0``.

The returned per-token log-prob is the RAW model log-prob of the chosen
token (pre-penalty, pre-temperature — the OpenAI-logprobs convention),
so greedy and sampled rows report comparable numbers.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

#: Fixed width of the device-side banned-id row (eos + stop token ids
#: subject to the min-tokens ban). Fixed so the compiled step's shape
#: never depends on a request's stop-set size.
MAX_BAN_IDS = 8

#: The widest ``top_k`` whose cut :func:`sample_rows` takes from the
#: row's largest values without sorting the vocabulary. Fixed, so that
#: the one compiled program is the same whatever the rows ask; 50 is the
#: common default of serving front ends, and a request over the cap is
#: served by the whole sort.
K_CAP = 128


def wide_rows(temperature, top_k, top_p):
    """THE rule for which rows need the whole-vocabulary sort, from the
    knobs alone: ``(filters, wide)`` bool arrays. A row ``filters``
    when it samples under a ``top_k`` or a nucleus; it is ``wide`` when
    it filters and its ``top_k`` does not bound the selection by
    :data:`K_CAP` (a nucleus with no ``top_k``, or a ``top_k`` over
    the cap). Operators only, so ``numpy`` rows (the engine's host
    counter ``serving/sampler_wide``) and traced ``jax.numpy`` rows
    (:func:`sample_rows`) evaluate the same expression."""
    filters = (temperature > 0) & ((top_k > 0) | (top_p < 1))
    narrow = (top_k > 0) & (top_k <= K_CAP)
    return filters, filters & ~narrow


def _as_tuple_of_ints(x) -> Tuple[int, ...]:
    return tuple(int(t) for t in x)


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs (1-based token ids, like ``submit()``).

    The default is GREEDY (``temperature=0``) with every other knob a
    no-op — a default-constructed ``SamplingParams`` reproduces the
    pre-sampling engine token for token.

    ``seed=None`` asks the engine to derive a lane from its own base
    seed and the request id (fresh randomness per request); an explicit
    ``seed`` pins the lane — the SAME seed always yields the SAME token
    stream for the same prompt/knobs/weights, regardless of batching,
    slot placement, or eviction/readmission (see :func:`lane_key`).

    ``max_tokens`` (when set) overrides ``submit()``'s
    ``max_new_tokens``; ``min_tokens`` suppresses eos / stop-token /
    stop-sequence termination (and bans eos + stop TOKEN ids on device)
    until that many tokens exist. ``stop_token_ids`` end the request
    like an extra per-request eos set (the token is emitted, then the
    row finishes with reason ``"stop"``); ``stop_sequences`` are
    matched on HOST against the TAIL of the generated output at each
    step. Note the min-tokens asymmetry: banned stop TOKENS simply
    cannot be emitted early and still fire later, but a stop SEQUENCE
    that completes strictly inside the pre-``min_tokens`` window is not
    retro-matched afterwards (tail matching only — the standard stop-
    string behavior); the row runs on until another stop or ``length``.
    """

    temperature: float = 0.0
    top_k: int = 0                      # 0 = no top-k restriction
    top_p: float = 1.0                  # 1.0 = no nucleus restriction
    repetition_penalty: float = 1.0     # 1.0 = off; > 1 discourages
    presence_penalty: float = 0.0       # 0.0 = off; > 0 discourages
    frequency_penalty: float = 0.0      # 0.0 = off; > 0 discourages
    seed: Optional[int] = None          # None = engine-derived lane
    max_tokens: Optional[int] = None    # None = submit()'s max_new_tokens
    min_tokens: int = 0
    stop_token_ids: Tuple[int, ...] = ()            # 1-based ids
    stop_sequences: Tuple[Tuple[int, ...], ...] = ()  # 1-based id runs

    def __post_init__(self):
        # canonicalize list inputs to hashable tuples (frozen dataclass)
        object.__setattr__(self, "stop_token_ids",
                           _as_tuple_of_ints(self.stop_token_ids))
        object.__setattr__(self, "stop_sequences", tuple(
            _as_tuple_of_ints(s) for s in self.stop_sequences))
        self.validate()

    def validate(self) -> None:
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(
                f"top_p must lie in (0, 1], got {self.top_p}")
        if self.repetition_penalty <= 0:
            raise ValueError(
                f"repetition_penalty must be > 0, got "
                f"{self.repetition_penalty}")
        if self.min_tokens < 0:
            raise ValueError(
                f"min_tokens must be >= 0, got {self.min_tokens}")
        if self.max_tokens is not None and self.max_tokens <= 0:
            raise ValueError(
                f"max_tokens must be positive, got {self.max_tokens}")
        if any(t <= 0 for t in self.stop_token_ids):
            raise ValueError(
                f"stop_token_ids are 1-based positive ids, got "
                f"{self.stop_token_ids}")
        if any(not s for s in self.stop_sequences) or any(
                t <= 0 for s in self.stop_sequences for t in s):
            raise ValueError(
                "stop_sequences must be non-empty runs of 1-based "
                f"positive ids, got {self.stop_sequences}")
        if len(self.stop_token_ids) > MAX_BAN_IDS - 1:
            raise ValueError(
                f"at most {MAX_BAN_IDS - 1} stop_token_ids (the device "
                f"ban row is {MAX_BAN_IDS} wide incl. eos), got "
                f"{len(self.stop_token_ids)}")

    @property
    def is_greedy(self) -> bool:
        return self.temperature <= 0.0

    @classmethod
    def greedy(cls) -> "SamplingParams":
        return cls()


def lane_key(seed: int):
    """THE seed → RNG-lane rule: ``jax.random.PRNGKey(seed)``. One
    definition shared by the engine (slot lanes) and ``generate()``
    so a fixed seed is bit-reproducible across both paths."""
    import jax

    return jax.random.PRNGKey(int(seed))


_ADVANCE_LANE: list = []


def advance_lane(key, n: int):
    """The lane state after ``n`` draws: :func:`sample_rows` advances a
    row's key as ``key <- split(key)[0]`` once per draw (the verify
    step advances by exactly the emitted count), so the state is a pure
    function of (seed, draws so far) — recomputable on host. This is
    what makes EVICTION of an in-flight row loss-free even when its
    device-side lane is gone (preemption and fault recovery both
    readmit with ``advance_lane(lane_key(seed-rule), len(output))`` and
    the stream continues draw-for-draw; see ``ServingEngine``). One
    jitted ``fori_loop`` with ``n`` as a traced scalar — a single tiny
    program, never a per-n retrace."""
    import jax
    import jax.numpy as jnp

    if not _ADVANCE_LANE:
        def _adv(key, n):
            return jax.lax.fori_loop(
                0, n, lambda _, k: jax.random.split(k)[0], key)

        _ADVANCE_LANE.append(jax.jit(_adv))
    return _ADVANCE_LANE[0](key, jnp.int32(n))


def make_knob_rows(n_slots: int, vocab: Optional[int] = None):
    """Host-side per-slot knob arrays in their no-op (greedy) state —
    the engine mutates rows at admission and passes the dict (as jnp
    arrays) to the sampled decode step every step. All shapes are fixed
    by ``n_slots``, so the step never recompiles on knob changes.

    ``vocab`` adds the constrained-decoding ``allow`` mask — ``(n_slots,
    vocab)`` bool, all-True (= unconstrained) by default; the engine
    rewrites a row from its constraint cursor each emitted token
    (``serving/constrain.py``). Callers without constrained decoding
    omit ``vocab`` and their knob dict (and compiled program) is
    byte-for-byte the pre-constraint one — ``sample_rows`` checks for
    the key structurally."""
    import numpy as np

    knobs = {
        "temperature": np.zeros((n_slots,), np.float32),
        "top_k": np.zeros((n_slots,), np.int32),
        "top_p": np.ones((n_slots,), np.float32),
        "repetition_penalty": np.ones((n_slots,), np.float32),
        "presence_penalty": np.zeros((n_slots,), np.float32),
        "frequency_penalty": np.zeros((n_slots,), np.float32),
        "ban": np.zeros((n_slots,), bool),
        "ban_ids": np.full((n_slots, MAX_BAN_IDS), -1, np.int32),
    }
    if vocab is not None:
        knobs["allow"] = np.ones((n_slots, vocab), bool)
    return knobs


def knob_partition_specs(data_axis: str = "data"):
    """``PartitionSpec`` dict mirroring :func:`make_knob_rows`: every
    knob is a per-SLOT array, so each shards its leading row axis over
    the mesh's data axis (``ban_ids``'s trailing id width and
    ``allow``'s trailing vocab axis ride along replicated — specs carry
    no trailing ``None`` dims on purpose, the stable-hash rule of
    ``transformer.serving_carry_specs``). Used by the sharded serving
    plane for shard_map in_specs and host-side knob placement alike.
    Includes the ``allow`` key: the sharded engines always carry the
    constraint mask (all-True when unconstrained), so the mesh steps'
    in_specs match their knob dicts structurally."""
    from jax.sharding import PartitionSpec as P

    return {k: P(data_axis) for k in make_knob_rows(1, vocab=1)}


def knob_row_values(params: "SamplingParams", eos_id: int):
    """(scalar knob dict, 0-based banned-id row) for one request — what
    the engine writes into its slot arrays at admission. ``eos_id`` is
    the request's 1-based eos (or -1): it joins the stop-token ids in
    the min-tokens ban row."""
    import numpy as np

    ban_row = np.full((MAX_BAN_IDS,), -1, np.int32)
    ids = [eos_id] if eos_id > 0 else []
    ids += [t for t in params.stop_token_ids if t != eos_id]
    for j, t in enumerate(ids[:MAX_BAN_IDS]):
        ban_row[j] = t - 1                       # 0-based on device
    return {
        "temperature": np.float32(params.temperature),
        "top_k": np.int32(params.top_k),
        "top_p": np.float32(params.top_p),
        "repetition_penalty": np.float32(params.repetition_penalty),
        "presence_penalty": np.float32(params.presence_penalty),
        "frequency_penalty": np.float32(params.frequency_penalty),
        "ban": bool(params.min_tokens > 0 and ids),
    }, ban_row


def largest_values(x, k: int):
    """``(N, k)``: each row's ``k`` largest values, descending: the
    values of ``lax.top_k(x, k)`` exactly, found in two stages. (On the
    chip ``top_k`` of a whole row is no shortcut: alone it costs in
    proportion to ``k x V``, 0.76 ms for 128 of 32 x 50,257 against the
    sort's 1.88, and inside a decode program XLA compiles it to the
    whole sort; PERF.md section 6, PR 35.) The row is folded into
    ``fold`` rows over ``ceil(V / fold)`` columns. Every one of the
    ``k`` largest lies in a column whose maximum ranks in the first
    ``k``: were it in another, ``k`` column maxima, so ``k`` other
    elements, would be at least as large, and it would tie the k-th
    value. So the ``k`` largest of those columns' ``fold x k`` elements
    are the row's. Ties cannot matter: only values are returned. The
    two selections are over ``V / fold`` and ``fold x k`` elements,
    least together near ``fold = sqrt(V / k)``."""
    import jax
    import jax.numpy as jnp

    n, v = x.shape
    fold = 2 ** round(math.log2(v / k) / 2) if v > k else 1
    cols = -(-v // fold)
    if cols <= k:
        return jax.lax.top_k(x, k)[0]
    folded = jnp.pad(x, ((0, 0), (0, fold * cols - v)),
                     constant_values=-jnp.inf).reshape(n, fold, cols)
    _, held = jax.lax.top_k(folded.max(axis=1), k)        # (N, k) columns
    cand = jnp.take_along_axis(folded, held[:, None, :], axis=2)
    return jax.lax.top_k(cand.reshape(n, fold * k), k)[0]


def row_thresholds(ls, temperature, top_k, top_p, active=None):
    """``(N, 1)``: the value below which top-k and top-p mask a row of
    the temperature-scaled ``(N, V)`` log-probs ``ls`` (``-inf`` for a
    row that does not filter). The selection is as wide as the row's
    knobs need, by :func:`wide_rows`:

    * a narrow row finds it among its ``K_CAP`` largest values
      (:func:`largest_values`: the sorted row's head). The k-th of them
      is the sort's k-th value itself, so with ``top_p == 1`` the
      threshold is the same float as the whole sort's. Its nucleus runs
      inside those candidates, normalised by a sum over the UNSORTED row
      (exact also when ties at the k-th value run past the cap: they
      carry that value, and the mask is by value), so with ``top_p < 1``
      only the order of that sum differs from the sort's, and the
      threshold can differ only where a cumulative mass lies within
      rounding of ``top_p``;
    * the sort, its softmax and its cumulative sum over ``(N, V)`` run
      under a ``lax.cond``, in a step where a row of ``active`` (the
      rows that decode; ``None``: all) is wide, and only wide rows take
      their threshold from it: a row's arithmetic follows its own
      knobs, never its neighbours'.
    """
    import jax
    import jax.numpy as jnp

    N, V = ls.shape
    filters, wide = wide_rows(temperature, top_k, top_p)
    top_p = top_p[:, None]
    cap = min(K_CAP, V)
    vals = largest_values(ls, cap)                # (N, cap) descending
    kth = jnp.take_along_axis(
        vals, (jnp.clip(top_k, 1, cap) - 1)[:, None], axis=-1)    # (N,1)
    in_k = vals >= kth
    z = jnp.sum(jnp.where(ls >= kth, jnp.exp(ls - vals[:, :1]), 0.0),
                axis=-1, keepdims=True)           # one pass, no sort
    ps = jnp.where(in_k, jnp.exp(vals - vals[:, :1]), 0.0) / z
    # keep a token while the mass BEFORE it is < top_p (top token always
    # kept: its "mass before" is 0)
    keep = in_k & ((jnp.cumsum(ps, axis=-1) - ps) < top_p)
    cut = jnp.min(jnp.where(keep, vals, jnp.inf), axis=-1, keepdims=True)
    thr = jnp.where(top_p < 1.0, jnp.maximum(kth, cut), kth)
    thr = jnp.where(filters[:, None], thr, -jnp.inf)

    def sorted_thresholds(ls):
        sl = -jnp.sort(-ls, axis=-1)              # (N, V) descending
        k_eff = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
        kth = jnp.take_along_axis(sl, (k_eff - 1)[:, None], axis=-1)
        slm = jnp.where(sl < kth, -1e30, sl)      # top-k in sorted space
        ps = jax.nn.softmax(slm, axis=-1)
        keep = (jnp.cumsum(ps, axis=-1) - ps) < top_p
        cut = jnp.min(jnp.where(keep, slm, jnp.inf), axis=-1)[:, None]
        return jnp.where(top_p < 1.0, jnp.maximum(kth, cut), kth)

    decoding = wide if active is None else wide & active
    thr_wide = jax.lax.cond(
        jnp.any(decoding), sorted_thresholds,
        lambda ls: jnp.full((N, 1), -jnp.inf, ls.dtype), ls)
    return jnp.where(wide[:, None], thr_wide, thr)


def sample_rows(logp, keys, knobs, counts, prompt_mask, active=None):
    """The per-row sampler (pure; traced inside the decode step and
    jitted standalone by :func:`get_sampler`).

    * ``logp`` — ``(N, V)`` fp32 model log-probs;
    * ``keys`` — ``(N, 2)`` uint32, one ``jax.random`` key per row;
    * ``knobs`` — the :func:`make_knob_rows` dict as ``(N,)`` /
      ``(N, MAX_BAN_IDS)`` arrays (plus the ``(N, V)`` bool ``allow``
      constraint mask when the caller runs constrained decoding);
    * ``counts`` — ``(N, V)`` int32 per-row generated-token counts;
    * ``prompt_mask`` — ``(N, V)`` bool, tokens present in the row's
      prompt (repetition penalty covers prompt + output);
    * ``active`` — ``(N,)`` bool, the rows that decode this step
      (``None``: all). It decides only WHETHER the step sorts: a freed
      slot keeps its last occupant's knobs, and must not ask for it.

    Returns ``(tokens (N,) int32, chosen_logp (N,) fp32, new_keys,
    new_counts)`` — ``new_counts`` includes the sampled token; the
    CALLER masks inactive rows (the step's ``active`` convention).
    Every row's draw reads only its own key/knobs/state, so per-row
    results are independent of batch composition.

    Top-k and top-p come to one threshold a row
    (:func:`row_thresholds`): with ``top_p == 1`` the result is
    bit-equal to a whole sort's, with ``top_p < 1`` on a row whose
    ``top_k`` is at most :data:`K_CAP` a token can differ from it only
    where a cumulative mass lies within rounding of ``top_p``.
    """
    import jax
    import jax.numpy as jnp

    N, V = logp.shape
    rows = jnp.arange(N)
    l = logp
    # 1..3: penalties (defaults are exact no-ops: *1.0, -0.0)
    seen = (counts > 0) | prompt_mask
    l = jnp.where(seen, l * knobs["repetition_penalty"][:, None], l)
    l = l - knobs["presence_penalty"][:, None] * (counts > 0)
    l = l - knobs["frequency_penalty"][:, None] * counts
    # 4: min-tokens ban (fixed-width id rows; -1 pads never match)
    ban_hit = (jnp.arange(V)[None, None, :]
               == knobs["ban_ids"][:, :, None]).any(axis=1)
    l = jnp.where(knobs["ban"][:, None] & ban_hit, -1e30, l)
    # constraint mask (serving/constrain.py): structural — knob dicts
    # without the key trace the exact pre-constraint program, and an
    # all-True row is the identity
    allow = knobs.get("allow")
    if allow is not None:
        l = jnp.where(allow, l, -1e30)
    greedy_tok = jnp.argmax(l, axis=-1)
    # 5: temperature, then top-k / top-p as one threshold a row
    temp = knobs["temperature"]
    ls = l / jnp.maximum(temp, 1e-6)[:, None]
    thr = row_thresholds(ls, temp, knobs["top_k"], knobs["top_p"], active)
    ls = jnp.where(ls < thr, -1e30, ls)
    # 6: the draw — per-row key split + Gumbel argmax; vmap keeps each
    # row's stream a pure function of its own key (bitwise equal to a
    # B=1 call with that key, which is the reproducibility contract)
    split = jax.vmap(jax.random.split)(keys)      # (N, 2, 2)
    new_keys, subs = split[:, 0], split[:, 1]
    sampled = jax.vmap(jax.random.categorical)(subs, ls)
    tok = jnp.where(temp > 0.0, sampled, greedy_tok).astype(jnp.int32)
    chosen_logp = logp[rows, tok]                 # RAW model log-prob
    new_counts = counts.at[rows, tok].add(1)
    return tok, chosen_logp, new_keys, new_counts


_SAMPLER_CACHE: list = []


def get_sampler():
    """``sample_rows`` under one shared ``jax.jit`` — the non-serving
    path's sampler (``generate()``); the engine's copy is traced fused
    inside the decode step instead. One jit wrapper whose internal
    cache re-traces per ``(N, V)`` shape."""
    import jax

    if not _SAMPLER_CACHE:
        _SAMPLER_CACHE.append(jax.jit(sample_rows))
    return _SAMPLER_CACHE[0]


def match_stop_sequences(output: Sequence[int],
                         stop_sequences) -> Optional[Tuple[int, ...]]:
    """Host-side stop-sequence check: the matched sequence if ``output``
    (1-based ids) ends with any of ``stop_sequences``, else None."""
    n = len(output)
    for seq in stop_sequences:
        k = len(seq)
        if k and n >= k and tuple(output[n - k:]) == tuple(seq):
            return tuple(seq)
    return None
