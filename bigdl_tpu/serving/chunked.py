"""Chunked-prefill streaming admission: overlap prompt ingestion with
decode.

Batched admission (``serving/admission.py``) bounded the COMPILE cost of
ragged prompt ingestion, but its wall cost still lands in one lump: the
whole admission wave prefills between two decode steps, so a burst of
long-prompt arrivals stalls every in-flight decode row for the full
prefill of the bucket. That is the classic chunked-prefill problem, and
the fix is the MLPerf-TPU-pod playbook (arXiv:1909.09756, PAPERS.md)
applied to admission: keep the one compiled decode program busy and
stream the prompt work in underneath it, a bounded slice at a time.

The machinery already exists. :func:`make_batch_prefill_step` takes
per-row START OFFSETS from ``carry['pos']`` — a suffix continuation,
which IS a prefill chunk. So :class:`ChunkedAdmissionController`
(``ServingEngine(admission="chunked")``) admits a request by binding it
to a KV slot immediately (scheduler state PARTIAL — slot-owning but not
yet decoding) and then, each engine super-step, feeds at most
``chunk_budget`` prompt tokens of chunk prefills BEFORE the decode step
runs for the rows already streaming. A row whose last chunk lands is
``activate()``-d into the running set and decodes from the next step.

Contracts (all pinned by tests/test_serving_chunked.py):

* **token identity** — chunked output is token-identical to
  ``admission="batched"`` (greedy test-pinned; fixed-seed sampled
  streams replay draw-for-draw, including evict/readmit and
  preemption). Per-row streams are independent and each chunk's query
  attends over the SAME ``max_len`` cache window the one-shot prefill
  reduces over — chunking changes when K/V bytes are written, not what
  any position computes — so this is the same float-round-off contract
  every admission mode already meets. (int8 KV: the grow-only scale
  merge reaches the same FINAL scale — max over chunk amaxes = amax
  over the prompt — but early chunks quantized under a smaller interim
  scale requantize on growth, bounded by half a quantum; same honest
  scoping as the speculative int8 note in docs/serving.md.)
* **bounded compiles** — chunk calls are ``(1, L)`` bucket shapes with
  ``L`` riding the existing power-of-two set (capped by the budget's
  bucket), the same shapes the prefix-cache suffix path traces. The
  decode path adds ZERO compiles: PARTIAL rows simply aren't in
  ``running``, and activation is host bookkeeping.
* **bounded stalls** — each super-step spends at most ``chunk_budget``
  prompt tokens (one chunk may finish exactly at the budget; the next
  waits), so the decode-stall gap is bounded by one chunk + one decode
  step instead of one admission wave (``serving/decode_gap_s``;
  ``serving_bench --scenario chunked`` asserts the p99 shrinks on a
  bursty long-prompt trace).
* **composition** — priority scheduling (PARTIAL rows are never
  preemption victims: they progress every step and their replay cost
  is pure loss), prefix cache (a cached prefix writes straight into
  the slot and its tokens SKIP the chunk plan entirely), fault
  recovery (a chunk dispatch that faults evicts exactly its row, which
  replays its chunks at readmission; a decode-step fault never touches
  PARTIAL rows — they keep their progress), speculative decoding (the
  draft cache ingests at activation, like any admission), and the
  sharded plane (chunks route to the owning shard through the pool's
  mesh-pinned scatter, same as batched rows).

Progress lives in the POOL (``KVPool.chunk_done`` / ``chunk_target``,
host mirrors of the device ``pos``), reset with the slot like the int8
scales — the pump never reads the device back mid-stream.

Cost honesty: a chunk call reads the slot's row (``pool.read_row``) and
scatters it back (``write_prefill``) — two full-row copies per chunk on
top of the prefill itself, and per-call dispatch overhead batched
admission amortizes over the bucket. Chunked admission spends MORE total
prefill wall time to bound the per-step stall; it is a latency shaper,
not a throughput optimization (the bench reports both sides).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from bigdl_tpu.serving.admission import AdmissionController, bucket_len
from bigdl_tpu.serving.scheduler import Request


class ChunkedAdmissionController(AdmissionController):
    """Streaming admission: bind slots immediately, feed prompts in
    ``chunk_budget``-bounded chunks between decode steps (module
    docstring). Owned by :class:`ServingEngine` under
    ``admission="chunked"``; shares the batched controller's bucket
    ledger, prefix cache plumbing, and the engine's one cached
    batch-prefill step."""

    def __init__(self, engine, chunk_budget: int = 32,
                 prefix_cache=None) -> None:
        super().__init__(engine, prefix_cache=prefix_cache)
        if int(chunk_budget) < 1:
            raise ValueError(
                f"chunk_budget must be >= 1, got {chunk_budget}")
        # read FRESH each pump(), so the autopilot's declared actuator
        # (ActuatorBus.set_chunk_budget — the ONE sanctioned writer
        # outside this __init__; SRV208 flags any other) retunes the
        # budget between steps without touching compiled programs
        self.chunk_budget = int(chunk_budget)
        # slot -> (request, full fed-token list); admission order decides
        # pump order (earliest-admitted row completes first — the TTFT-
        # fair choice, and the one that matches batched admission's
        # effective ordering)
        self._plans: Dict[int, Tuple[Request, List[int]]] = {}
        self._order: List[int] = []

    # -- admission: bind now, stream later ----------------------------------

    def admit(self, n: int) -> None:
        """Admit ``n`` scheduler-approved requests as PARTIAL rows with
        chunk plans. Rows that need no streaming — empty prefill,
        byte-exact preemption resume, or a FULL prefix-cache hit —
        activate immediately (they are exactly as ready as a batched
        admission would have made them)."""
        eng = self.engine
        for _ in range(n):
            # the shared admission prologue (AdmissionController.
            # _bind_next): empty prefills and byte-exact preemption
            # resumes come back with pf=None — nothing to stream
            slot, req, pf = self._bind_next(partial=True)
            if pf is None:
                eng.scheduler.activate(slot)
                continue
            done = 0
            if self.prefix_cache is not None:
                done = self._prefix_head(slot, req, pf)
            if done >= len(pf):                # full hit: zero chunks
                eng.scheduler.activate(slot)
                continue
            eng.pool.begin_chunks(slot, done, len(pf))
            self._plans[slot] = (req, pf)
            self._order.append(slot)

    def _prefix_head(self, slot: int, req, pf: List[int]) -> int:
        """Prefix-cache head write: the longest cached prefix lands in
        the slot in one scatter and its tokens SKIP the chunk plan —
        returns the matched length (0 on a miss). Unlike the batched
        path, the remaining suffix is NOT prefilled here; it becomes
        the chunk plan. Namespaced by the request's adapter id, like
        every prefix-cache touch."""
        eng = self.engine
        carry, matched, lease = self.prefix_cache.acquire(
            pf, adapter_id=req.adapter_id)
        eng.metrics.on_prefix_lookup(matched, len(pf))
        if matched == 0:
            return 0
        try:
            # no phase timer: the head write is a device scatter whose
            # completion the step's decode fence absorbs, like every
            # un-fenced prefill (the prefill_s phase is gone — PR 15)
            eng.pool.write_prefill(slot, carry, matched)
        finally:
            self.prefix_cache.release(lease)
        return matched

    # -- the pump: one budget of chunks per super-step -----------------------

    def pump(self) -> None:
        """Feed at most ``chunk_budget`` prompt tokens of chunk
        prefills, earliest-admitted row first, then hand control back
        so the decode step runs. The first chunk always fits (chunk
        width is capped by the budget); a later chunk that would
        overflow the remaining budget waits for the next super-step.
        Rows whose last chunk lands are activated into the running set
        (and inserted into the prefix cache, like a completed batched
        prefill). A chunk dispatch that faults evicts exactly its own
        row for loss-free replay; other rows keep streaming."""
        from bigdl_tpu.serving.faults import FaultError

        if not self._plans:
            return
        eng = self.engine
        budget, spent, full = self.chunk_budget, 0, False
        for slot in list(self._order):
            if slot not in self._plans:
                continue                       # dropped mid-round
            req, pf = self._plans[slot]
            while slot in self._plans:
                done = int(eng.pool.chunk_done[slot])
                if done >= len(pf):
                    self._plans.pop(slot, None)
                    eng.scheduler.activate(slot)
                    break
                if full:
                    break
                n = min(budget, len(pf) - done)
                if spent and spent + n > budget:
                    full = True
                    break
                try:
                    self._feed_chunk(slot, req, pf, done, n)
                except FaultError:
                    # evicts this row only (drops its plan via the
                    # engine's recovery hook); the round continues
                    eng._recover_admission([(slot, req)])
                    break
                spent += n
                if spent >= budget:
                    full = True                # completion check still runs
            if full:
                break
        self._order = [s for s in self._order if s in self._plans]
        eng.metrics.on_partial_rows(len(self._plans))

    def _feed_chunk(self, slot: int, req, pf: List[int], done: int,
                    n: int) -> None:
        """ONE suffix-continuation prefill of ``pf[done:done+n]`` for a
        slot: the slot's current row is the input carry (its ``pos`` is
        the start offset), the chunk lands through the donated scatter,
        and the completed prompt is shared into the prefix cache."""
        import jax.numpy as jnp
        import numpy as np

        eng = self.engine
        L = bucket_len(n, eng.max_len)
        toks = np.zeros((1, L), np.int32)
        toks[0, :n] = pf[done:done + n]
        row = eng.pool.read_row(slot)          # pos[0] == done
        self._note_shape(1, L)
        # NO completion fence, no phase timer: the chunk prefill now
        # dispatches and RETURNS — it overlaps the decode step (the
        # very overlap chunked admission exists to create) and the
        # step's decode fence absorbs its completion. A timer here
        # would measure only the launch (the ASY305 lie — the prefill
        # step's own ``prefill.launch`` span is named for it and feeds
        # no series); the PR 12 worksheet marked this site deletable
        # (docs/async_readiness.md).
        _, out = eng._dispatch("prefill", eng._batch_prefill_fn,
                               eng.params, jnp.asarray(toks),
                               np.asarray([n], np.int32), row,
                               *eng._prefill_adapter_args(
                                   [req.adapter_id]))
        eng.metrics.on_prefill_batch(1, 1)
        eng.pool.write_prefill(slot, out, done + n)
        if done + n == len(pf) and self.prefix_cache is not None:
            self.prefix_cache.insert(pf, out, adapter_id=req.adapter_id)
        eng.metrics.on_chunk(n)

    # -- teardown hooks (cancel / fault / preempt paths) --------------------

    def drop(self, slot: int) -> None:
        """Forget a slot's chunk plan AND its pump-order position
        (cancellation, fault eviction — the engine frees the slot,
        which resets the pool's progress fields). The order entry must
        go with the plan: a freed slot's next occupant would otherwise
        inherit this row's queue position and stream ahead of
        earlier-admitted rows. Idempotent; a readmitted request replans
        from its replay stream."""
        self._plans.pop(slot, None)
        if slot in self._order:
            self._order.remove(slot)

    @property
    def partial_slots(self) -> List[int]:
        """Slots currently mid-stream, in pump order (introspection)."""
        return [s for s in self._order if s in self._plans]
