"""Batched, length-bucketed admission for the serving engine.

PR 1 admitted requests ONE AT A TIME: each admission ran a private B=1
``make_prefill_step`` call, and every novel prompt length triggered a
fresh XLA trace MID-ADMISSION, stalling all in-flight rows for the
compile (the docs/serving.md operational caveat). The reference's core
scheduling lesson (SoCC'19: schedule work onto fixed, pre-compiled
executors instead of spawning per-job state) applies to prompt ingestion
just as much as to decode — and MLPerf-scale TPU practice shows bounding
the compiled-program set is what keeps admission latency flat under
ragged traffic.

:class:`AdmissionController` turns admission into a pooled,
shape-stable pipeline:

* waiting requests are grouped into POWER-OF-TWO length buckets
  (clamped at ``max_len``) — a bounded bucket set, so the set of
  compiled prefill programs is bounded by ``O(log max_len)`` buckets
  regardless of how many distinct prompt lengths traffic brings;
* each bucket prefills in ONE :func:`make_batch_prefill_step` call over
  a ``(B, L_bucket)`` right-padded token block with a per-row
  ``lengths`` vector. The row count B is FIXED (``prefill_rows``,
  default ``n_slots`` — an admission round never has more rows to
  fill; unfilled rows are zero-length ballast), so the
  compiled-program set is exactly ONE
  program per length bucket no matter how arrival timing groups the
  requests — admission never compiles mid-flight after the buckets are
  warm. (Ballast rows cost padding FLOPs; on the MXU a small fixed B
  is the cheap side of that trade, and shape stability is the point —
  it is also what keeps a future SHARDED prefill program reusable.)
  A family may bound a wave in TOKENS instead (``prefill_token_bound``,
  ``serving/family.py``): its rows then follow the bucket
  (:meth:`AdmissionController.wave_rows`: the largest power of two
  with ``rows x L`` within the bound, at most ``n_slots``), still ONE
  program a bucket, and more arrivals than rows prefill in chunks;
* every produced row is scattered into its :class:`KVPool` slot through
  the existing donated scatter (``write_prefill(..., row=j)``);
* with a :class:`bigdl_tpu.serving.prefix_cache.PrefixCache` attached,
  each prompt first takes the longest-cached-prefix path: a FULL hit
  clones the cached carry straight into the pool (zero prefill work), a
  PARTIAL hit clones it and prefills only the suffix (the batch
  prefill's nonzero per-row start offsets), and finished prefills are
  inserted back so later requests hit.

The zero input carries (one per row bucket) are built once and reused
for every admission — jax arrays are immutable, so sharing them is free
(the same trick as the engine's old ``_zero_carry1``, per shape).

On a SHARDED engine (``serving/sharded.py``) this controller runs
unchanged: ``pool.alloc()`` is the balanced cross-shard allocator, and
every ``write_prefill(..., row=j)`` routes the prefilled row to the
slot's OWNING shard through the pool's mesh-pinned scatter
(slot → (shard, row) is ``pool.slot_shard``) — admission never needs to
know the mesh exists, which is what keeps the bucketed prefill programs
reusable across mesh shapes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from bigdl_tpu.serving.prefix_cache import PrefixCache


@dataclass(frozen=True)
class Degrade:
    """A request's graceful-degradation knobs, applied AT ADMISSION when
    the engine is under pressure (queue depth ≥ the engine's
    ``degrade_at`` — see ``ServingEngine``): ``max_new_tokens`` caps the
    request's token budget (never raises it), ``draft_tokens`` replaces
    its speculative budget (``0`` disables speculation for the request —
    on a loaded engine the draft dispatches are pure added latency for
    everyone else in the batch). Both are per-row RUNTIME data of the
    already-compiled programs, so degrading traffic never recompiles —
    the same shape-stability rule every serving knob follows. ``None``
    fields leave the request untouched; a request with no ``degrade``
    attached is never degraded.

    The clamp is REVERTIBLE (PR 19): the engine's one degrade writer
    records the request's original limits, and when pressure drops
    while the row still WAITS (the static ``degrade_at`` path, or the
    autopilot's ``restore_waiting`` actuator) the originals come back
    — a burst's degrade must not outlive the burst."""

    max_new_tokens: Optional[int] = None
    draft_tokens: Optional[int] = None

    def __post_init__(self):
        if self.max_new_tokens is not None and self.max_new_tokens <= 0:
            raise ValueError(
                f"max_new_tokens must be positive, got "
                f"{self.max_new_tokens}")
        if self.draft_tokens is not None and self.draft_tokens < 0:
            raise ValueError(
                f"draft_tokens must be >= 0, got {self.draft_tokens}")


def bucket_len(n: int, cap: int) -> int:
    """The power-of-two length bucket for ``n`` tokens, clamped to
    ``cap`` (= max_len): 1, 2, 4, ... cap. Bucketing bounds the set of
    compiled prefill programs; the clamp keeps the block no wider than
    the cache (pad columns beyond a row's length are masked anyway)."""
    if n <= 0:
        raise ValueError(f"need a positive length, got {n}")
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap)


class AdmissionController:
    """Groups admissions into bucketed batch-prefill calls (see module
    docstring). Owned by :class:`ServingEngine`; reads the engine's
    pool/scheduler/metrics and its cached batch-prefill step."""

    def __init__(self, engine, prefix_cache: Optional[PrefixCache] = None,
                 prefill_rows: int = 0) -> None:
        # engine is the owning ServingEngine (pool, scheduler, metrics,
        # params, jitted steps); the controller is its admission policy,
        # split out so the pieces stay independently testable
        self.engine = engine
        self.prefix_cache = prefix_cache
        # FIXED batch-prefill row count (module docstring): one compiled
        # shape per length bucket, independent of arrival grouping (an
        # admission round never has more than n_slots rows to fill)
        self.prefill_rows = int(prefill_rows) or engine.pool.n_slots
        # a family's bound on a wave in tokens (wave_rows), or None
        self.token_bound = getattr(engine._family, "prefill_token_bound",
                                   None)
        # ONE shared fresh zero carry, built lazily and reused for every
        # admission (prefill never donates its carry and jax arrays are
        # immutable, so sharing the zero input is free)
        self._zero_carry_cache: Optional[dict] = None
        # (B, L) shapes routed through THIS controller — the bounded
        # compiled-program set this subsystem exists to enforce. The
        # serving/prefill_bucket_compiles counter instead counts shapes
        # new to the SHARED jitted step (cached per model/dtype), so a
        # second engine over a warm model reports zero compiles.
        self.traced_shapes: set = set()

    # -- streaming hooks (overridden by ChunkedAdmissionController) --------

    def pump(self) -> None:
        """Per-super-step streaming hook: batched admission does all
        its prefill work inside :meth:`admit`, so this is a no-op —
        the chunked controller (``serving/chunked.py``) overrides it to
        feed one budget of prompt chunks before the decode step."""

    def drop(self, slot: int) -> None:
        """Forget any per-slot streaming state (no-op here; the chunked
        controller drops the slot's chunk plan). Called by the engine
        whenever a slot is torn down mid-admission (cancel, fault
        eviction, preemption)."""

    # -- helpers -----------------------------------------------------------

    def _bind_next(self, partial: bool = False):
        """THE admission prologue, shared by the batched and chunked
        controllers so the loss-free-readmission invariants have one
        spelling: allocate a slot, bind the best waiting request
        (``partial=True`` binds mid-prefill — chunked), and handle the
        two zero-ingestion fast paths — an empty prefill list (1-token
        prompts start decoding at pos 0) and a PREEMPTED row's
        byte-exact ``resume_carry`` scatter. Returns ``(slot, req,
        pf)`` with ``pf`` None when the row needs no prompt
        ingestion."""
        eng = self.engine
        slot = eng.pool.alloc()
        assert slot is not None                # admissible() checked
        req = eng._bind(slot, partial=partial)
        # the last fed token is the first decode input — exactly
        # generate()'s convention, so outputs match token-for-token.
        # Called BEFORE the resume check on purpose: its side effects
        # (req.next_token, the degrade knob) are required on the
        # restored path too, even though pf itself goes unused there
        pf = eng._admitted_prefill_tokens(req)
        payload = eng._resume_payload(req)
        if payload is not None:
            # byte-exact resume: the stashed/spilled row_state payload
            # (preemption stash, host tier, or disaggregated handoff)
            # restores whole — KV + scales + lanes + mirrors + draft —
            # and the slot skips _configure_slot's device reseeding
            eng.pool.restore_row(slot, payload)
            req.resume_carry = None
            eng._restored.add(slot)
            return slot, req, None
        if not pf:
            eng.pool.set_pos(slot, 0)
            return slot, req, None
        return slot, req, pf

    def wave_rows(self, L: int) -> int:
        """Rows of the prefill wave of bucket ``L``: ``prefill_rows``,
        or under a family's token bound the largest power of two with
        ``rows x L`` within it (at least one row, at most
        ``prefill_rows``)."""
        if self.token_bound is None:
            return self.prefill_rows
        rows = 1
        while rows * 2 * L <= self.token_bound:
            rows *= 2
        return min(rows, self.prefill_rows)

    def _zero_carry(self) -> Optional[dict]:
        """The shared fresh carry a wave's prefill is handed; None for
        a family under a token bound, whose prefill makes its fresh rows
        inside the program (its waves have no one row count to share a
        carry for)."""
        if self.token_bound is not None:
            return None
        if self._zero_carry_cache is None:
            self._zero_carry_cache = self.engine._pool_init(self.prefill_rows)
        return self._zero_carry_cache

    def _note_shape(self, B: int, L: int) -> None:
        self.traced_shapes.add((B, L))
        fn = self.engine._batch_prefill_fn
        seen = getattr(fn, "_traced_shapes", None)
        if seen is None:
            seen = fn._traced_shapes = set()
        if (B, L) not in seen:
            seen.add((B, L))
            self.engine.metrics.on_bucket_compile()

    @staticmethod
    def _carry_row(carry: dict, row: int) -> dict:
        """Row ``row`` of a multi-row carry as a B=1 carry (a device
        slice per leaf — what PrefixCache stores)."""
        return {k: v[row:row + 1] for k, v in carry.items()}

    # -- the admission pipeline --------------------------------------------

    def admit(self, n: int) -> None:
        """Admit ``n`` scheduler-approved requests: allocate slots,
        route each prompt through the prefix cache, then prefill the
        misses bucket-by-bucket.

        Admission covers READMISSION too: a preempted or fault-evicted
        request re-enters here with its emitted tokens in
        ``req.output``, so its "prompt" for prefill purposes is
        ``prompt + output`` (``eng._admitted_prefill_tokens``) — the
        replay contract that makes eviction loss-free. A PREEMPTED row
        carries its stashed KV slice (``req.resume_carry``) and
        scatters it straight back (zero prefill work, byte-exact);
        fault-evicted rows replay through the normal prefill pipeline
        (their carry was never trusted). A prefill dispatch that FAULTS
        (injected or real — serving/faults.py) requeues exactly its own
        rows and frees their slots; other buckets in the round admit
        normally."""
        from bigdl_tpu.serving.faults import FaultError

        eng = self.engine
        groups: Dict[int, List[Tuple]] = {}    # L_bucket -> (req, slot, pf)
        for _ in range(n):
            slot, req, pf = self._bind_next()
            if pf is None:
                continue
            if self.prefix_cache is not None:
                try:
                    if self._try_prefix(slot, req, pf):
                        continue
                except FaultError:
                    eng._recover_admission([(slot, req)])
                    continue
            groups.setdefault(bucket_len(len(pf), eng.max_len),
                              []).append((req, slot, pf))
        for L in sorted(groups):
            rows = groups[L]
            # a bucket larger than the row block prefills in chunks
            B = self.wave_rows(L)
            for lo in range(0, len(rows), B):
                chunk = rows[lo:lo + B]
                try:
                    self._prefill_bucket(L, chunk)
                except FaultError:
                    eng._recover_admission(
                        [(slot, req) for req, slot, _ in chunk])

    def _try_prefix(self, slot: int, req, pf: List[int]) -> bool:
        """The prefix-cache path: full hit → clone into the pool;
        partial hit → clone + prefill only the suffix. Returns False on
        a miss (the caller buckets the prompt normally). Lookups and
        inserts are NAMESPACED by the request's adapter id — K/V
        computed under one tenant's factors must never splice into
        another tenant's row (null-adapter traffic keeps today's shared
        namespace and hit rate)."""
        import jax.numpy as jnp
        import numpy as np

        eng = self.engine
        carry, matched, lease = self.prefix_cache.acquire(
            pf, adapter_id=req.adapter_id)
        eng.metrics.on_prefix_lookup(matched, len(pf))
        if matched == 0:
            return False
        try:
            if matched == len(pf):             # full hit: zero prefill work
                eng.pool.write_prefill(slot, carry, len(pf))
                return True
            S = len(pf) - matched
            L = bucket_len(S, eng.max_len)
            toks = np.zeros((1, L), np.int32)
            toks[0, :S] = pf[matched:]
            self._note_shape(1, L)
            # the cached carry's pos IS the start offset: the batch
            # prefill continues over the cached prefix, writing only
            # positions matched..len(pf)-1. NO completion fence (and no
            # phase timer — it would measure the launch, the ASY305
            # lie; the prefill step's own ``prefill.launch`` span is
            # named for exactly that and feeds no series): the suffix
            # prefill overlaps the decode step under async dispatch,
            # and the step's decode fence absorbs its completion
            # (docs/async_readiness.md cashed-in entry).
            _, out = eng._dispatch(
                "prefill", eng._batch_prefill_fn, eng.params,
                jnp.asarray(toks), np.asarray([S], np.int32), carry,
                *eng._prefill_adapter_args([req.adapter_id]))
            eng.metrics.on_prefill_batch(1, 1)
            eng.pool.write_prefill(slot, out, len(pf))
            self.prefix_cache.insert(pf, out, adapter_id=req.adapter_id)
            return True
        finally:
            self.prefix_cache.release(lease)

    def _prefill_bucket(self, L: int, rows: List[Tuple]) -> None:
        """ONE masked multi-row prefill for every miss in an L-bucket,
        then per-row scatter into the pool."""
        import jax.numpy as jnp
        import numpy as np

        eng = self.engine
        k = len(rows)
        B = self.wave_rows(L)
        toks = np.zeros((B, L), np.int32)
        lengths = np.zeros((B,), np.int32)     # pad rows stay ballast (0)
        aids = np.zeros((B,), np.int32)        # pad rows: null adapter
        for j, (req, _, pf) in enumerate(rows):
            toks[j, :len(pf)] = pf
            lengths[j] = len(pf)
            aids[j] = req.adapter_id
        self._note_shape(B, L)
        # NO completion fence, no phase timer: the bucket prefill is
        # the work async dispatch-ahead overlaps with the decode step —
        # the step's decode fence absorbs its completion, and a timer
        # here would measure only the launch (the ASY305 lie): the
        # prefill step's own ``prefill.launch`` span says so in its
        # name and feeds no series — the wave's device time is the
        # jit_prefill program in the trace. The PR 12 worksheet marked
        # this site deletable (docs/async_readiness.md).
        _, out = eng._dispatch("prefill", eng._batch_prefill_fn,
                               eng.params, jnp.asarray(toks), lengths,
                               self._zero_carry(),
                               *eng._prefill_adapter_args(aids))
        eng.metrics.on_prefill_batch(k, B)
        for j, (req, slot, pf) in enumerate(rows):
            eng.pool.write_prefill(slot, out, len(pf), row=j)
            if self.prefix_cache is not None:
                self.prefix_cache.insert(pf, self._carry_row(out, j),
                                         adapter_id=req.adapter_id)
