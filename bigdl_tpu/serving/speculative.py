"""Speculative decoding: draft-and-verify under the one-program
discipline.

Decode emits one token per model invocation, so per-request latency is
bound by SEQUENTIAL target-model steps no matter how well the engine
batches across requests. Speculative decoding breaks that bound the way
this repo breaks every serving bound — by restructuring the driver loop
around what the hardware does well (the BigDL thesis, arXiv:1804.05839)
and hiding per-step host/launch latency behind larger device steps (the
MLPerf-TPU-pod playbook, arXiv:1909.09756):

* a small DRAFT model proposes ``k`` tokens per row each super-step
  (``k + 1`` chained invocations of the existing per-row batched decode
  step — cheap, the draft is small);
* the TARGET model scores all proposed positions in ONE batched verify
  step (:func:`bigdl_tpu.models.transformer.make_batch_verify_step` —
  structurally the masked multi-row prefill: per-row start offsets
  already express "continue this row's suffix", so the verify program
  is shape-stable);
* each row advances by however many draws the target confirms —
  between 1 (all drafts rejected; exactly the plain decode step) and
  ``k + 1`` (all accepted plus the bonus draw) tokens per super-step.

The serving invariants carry over wholesale:

* **one compiled program** — per-row draft length is runtime data of
  the fixed-width ``(n_slots, k + 1)`` verify program. Mixed
  speculative/normal traffic (per-request ``draft_tokens=0`` rows,
  budget-capped rows, min-tokens-banned rows) adds ZERO target-side
  compiles: the speculative engine runs one verify program where the
  baseline runs one decode program (pinned by
  tests/test_serving_speculative.py via tests/compile_guards.py);
* **greedy parity** — temperature-0 rows verify by argmax agreement,
  so greedy speculative output is token-identical to the baseline
  engine and ``generate()`` (test-pinned, like sampling's
  temperature=0 contract);
* **seed replay** — verification draws ride the per-slot RNG lanes
  from ``serving/sampling.py``: the verify step splits each row's lane
  once per chunk position IN ORDER and advances it by exactly the
  emitted count, so a fixed-seed sampled request produces the SAME
  stream as the non-speculative engine, across eviction/readmission,
  batching, and admission modes. The draft only decides how many of
  those draws land per step — never their values — which also means a
  WRONG or weak draft degrades throughput, not correctness. That
  draft-independence is exact on the int8 cache too: the verify
  step's chunk attention reads FLOAT chunk K/V with the grow-only
  scale merge + quantized scatter deferred until acceptance is known,
  merging over ACCEPTED columns only — a rejected draft can touch
  neither a row's (slot, head) scales nor its stored bytes (pinned by
  the garbage-draft parity tests in tests/test_serving_speculative.py
  and tests/test_serving_kv_quant.py).
  (Acceptance is sampled-token agreement, deliberately traded against
  Leviathan-style distribution-matching rejection sampling, whose
  draft-dependent randomness consumption cannot replay the baseline
  stream; see ``make_batch_verify_step``'s docstring.)

KV bookkeeping: the draft's pooled KV carry rides alongside the
target's in the one :class:`~bigdl_tpu.serving.kv_pool.KVPool`
(``attach_draft`` — same slot ids, same allocator, freed together).
Rejected drafts need no cache rewrite on EITHER side: both caches
wrote the whole chunk, and the accepted-prefix rollback is pointer
arithmetic — ``pos`` advances by the emitted count only, leaving
rejected positions as stale bytes behind the per-row causal mask (the
same masking that makes recycled slots safe). The draft loop runs
``k + 1`` iterations (not ``k``) so the k-th draft's K/V lands too and
a fully-accepted chunk leaves no hole in the draft cache.

    from bigdl_tpu.serving import ServingEngine, SpeculativeConfig

    eng = ServingEngine(lm, n_slots=8,
                        speculative=SpeculativeConfig(draft_lm, k=4))
    rid = eng.submit([3, 7, 2], max_new_tokens=64)
    eng.submit([9, 9], max_new_tokens=8, draft_tokens=0)  # normal row
    outs = eng.drain()
    eng.metrics.summary()["serving/accept_rate"]    # drafts confirmed
    eng.metrics.summary()["serving/tokens_per_step"]  # > 1 when drafts land
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import numpy as np

from bigdl_tpu.serving.admission import bucket_len
from bigdl_tpu.serving.fences import fence, fence_wait


@dataclass(frozen=True)
class SpeculativeConfig:
    """Speculative-decoding knobs for :class:`ServingEngine`.

    ``draft`` is the proposer: a TransformerLM-shaped model over the
    SAME vocabulary as the target (its ids are fed to the target
    verbatim) with ``max_len`` at least the target's (its cache tracks
    the same positions). ``k`` is the drafts proposed per super-step —
    the verify chunk width is ``k + 1`` and tokens-per-step ranges over
    ``1..k+1``. Per-request ``submit(..., draft_tokens=)`` can lower
    (never raise) the budget per row at runtime."""

    draft: Any
    k: int = 4

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(
                f"k must be >= 1 (draft tokens per super-step), got "
                f"{self.k} — a k=0 engine is the plain ServingEngine")


class Speculator:
    """The engine's speculative plane: owns the draft model's serving
    state (params, decode/prefill steps, pooled carry attachment) and
    the draft→verify→emit super-step. Built by
    :class:`~bigdl_tpu.serving.engine.ServingEngine` when its
    ``speculative=`` knob is set; reads the engine's pool/scheduler/
    metrics/knobs the way :class:`AdmissionController` does."""

    def __init__(self, engine, config, mesh=None,
                 kv_quant: bool = False) -> None:
        import jax

        from bigdl_tpu.models.transformer import (
            get_batch_decode_step, get_batch_prefill_step,
            get_batch_verify_step, serving_params,
        )

        if not isinstance(config, SpeculativeConfig):
            # accept a bare draft model for the common case
            config = SpeculativeConfig(draft=config)
        self.engine = engine
        self.config = config
        self.k = int(config.k)
        self.width = self.k + 1
        draft = config.draft
        draft._ensure_params()
        tgt_vocab = engine.model.modules[0].n_index
        if draft.modules[0].n_index != tgt_vocab:
            raise ValueError(
                f"draft vocab {draft.modules[0].n_index} != target vocab "
                f"{tgt_vocab} — draft proposals are target token ids")
        self.draft_max_len = draft.modules[1].max_len
        if self.draft_max_len < engine.max_len:
            raise ValueError(
                f"draft max_len {self.draft_max_len} < target max_len "
                f"{engine.max_len} — the draft cache tracks the same "
                "positions as the target's")
        self.draft = draft
        dtype = engine.compute_dtype
        # ONE target-side program: the fixed-width verify step is the
        # speculative engine's decode step (a length-1 row IS plain
        # decode); its init_carry is the decode carry, so the pool is
        # layout-identical to a non-speculative engine's
        # the target scores each row under that row's ADAPTER (the
        # engine threads per-slot ids + the bank into the dispatch);
        # drafts are pinned to the null adapter — submit() rejects
        # adapted requests unless draft_tokens=0 — so the draft plane
        # below stays adapter-free by construction
        self.verify_fn, self.pool_init = get_batch_verify_step(
            engine.model, dtype, width=self.width, mesh=mesh,
            kv_quant=kv_quant, adapter=engine._adapter_spec)
        # draft plane: weights REPLICATED (a model small enough to
        # draft with is small enough to replicate — on a mesh XLA
        # partitions the per-row step over the carry's slot sharding,
        # so the step takes the plane's mesh with NO model axis, for
        # its pooled attention), plain float cache, greedy proposals
        self._draft_step_fn, self._draft_init = get_batch_decode_step(
            draft, dtype, mesh=engine.mesh, model_axis=None)
        self._draft_prefill_fn = get_batch_prefill_step(draft, dtype)
        self._draft_params = jax.device_put(serving_params(draft, dtype))
        # shared fresh B=1 carry for draft prefills (immutable, reused)
        self._zero_draft1 = self._draft_init(1)

    # -- pool wiring --------------------------------------------------------

    def attach_pool(self, pool) -> None:
        plane = self.engine._plane
        pool.attach_draft(
            self._draft_init,
            specs=None if plane is None
            else plane.draft_carry_specs(self.draft))

    # -- admission ----------------------------------------------------------

    def prefill_draft(self, slot: int, req) -> None:
        """Ingest an admitted request's fed stream (prompt + any tokens
        emitted before a preemption/fault eviction) into the DRAFT
        cache — called from the engine's slot configuration, so every
        admission path (batched, per_request, prefix-cache hits,
        loss-free readmission) feeds the draft the same way. Bucketed
        masked B=1 prefill: the compiled draft-prefill set stays
        bounded by the power-of-two buckets, no matter how many
        distinct prompt lengths traffic brings. (No draft-side prefix
        cache or preemption stash: draft prefill is cheap and a stale
        draft cache could only cost acceptance, never correctness —
        but the bookkeeping would be real.)"""
        import jax.numpy as jnp

        eng = self.engine
        prompt0 = [t - 1 for t in req.prompt] + \
                  [t - 1 for t in req.output]
        pf = prompt0[:-1]
        if not pf:
            eng.pool.set_draft_pos(slot, 0)
            return
        L = bucket_len(len(pf), self.draft_max_len)
        toks = np.zeros((1, L), np.int32)
        toks[0, :len(pf)] = pf
        # routed through the engine's fault hook like every other
        # serving dispatch (SRV201): an un-routed draft prefill would
        # silently escape fault injection and retry accounting — a
        # raised FaultError propagates to the caller (_configure_slot's
        # callers recover the row like any admission-side fault).
        # NO completion fence, no phase timer: the draft prefill
        # overlaps the decode step under async dispatch and the super-
        # step's verify fence absorbs its completion (the PR 12
        # worksheet's deletable entry — docs/async_readiness.md).
        _, dc = eng._dispatch(
            "prefill", self._draft_prefill_fn,
            self._draft_params, jnp.asarray(toks),
            np.asarray([len(pf)], np.int32), self._zero_draft1)
        eng.pool.write_draft_prefill(slot, dc, len(pf))

    # -- the super-step ------------------------------------------------------

    def _draft_budget(self, slot: int, req) -> int:
        """Row r's draft count this super-step — runtime data, never a
        recompile. Capped by the engine ``k``, the per-request
        ``draft_tokens`` hint, the remaining token budget (a chunk must
        not overshoot ``max_new_tokens`` — that would desync the RNG
        lane from the baseline stream), and forced to 0 while the row's
        min-tokens ban is up (the ban is per-STEP host state; a chunk
        must not cross its flip). Constrained rows
        (``serving/constrain.py``) are likewise forced to 0: the allow
        mask is a function of the emitted PREFIX, so every chunk
        position after the first would verify against a stale mask."""
        k = self.k if req.draft_tokens is None \
            else min(int(req.draft_tokens), self.k)
        # the autopilot's engine-wide ceiling (ActuatorBus.
        # set_draft_cap): when the windowed accept rate says drafts
        # are dying at verify, the cap cuts spend for EVERY row —
        # per-row hints still apply below it, and None means the
        # configured k. Runtime data, never a recompile.
        cap = getattr(self.engine, "draft_cap", None)
        if cap is not None:
            k = min(k, int(cap))
        if self.engine._knobs["ban"][slot]:
            k = 0
        if slot in self.engine._constraints:
            k = 0
        rem = req.max_new_tokens - len(req.output)
        return max(0, min(k, rem - 1))

    def _chunk_unhealthy(self, nxt, lps, nem, lengths, active):
        """Garbage verdict on a verify step's host-read outputs — the
        chunked twin of ``ServingEngine._step_unhealthy``: active rows
        must report an emit count in ``1..lengths[r]`` and finite
        log-probs / in-range tokens over their emitted columns. None =
        healthy."""
        if not active.any():
            return None
        a_nem = nem[active]
        if (a_nem < 1).any() or (a_nem > lengths[active]).any():
            return "garbage"
        emit = np.arange(nxt.shape[1])[None, :] < nem[:, None]
        emit &= active[:, None]
        if (not np.isfinite(lps[emit]).all() or (nxt[emit] < 0).any()
                or (nxt[emit] >= self.engine._vocab).any()):
            return "garbage"
        return None

    def step(self, running, had_running: bool) -> Dict[int, int]:
        """One draft-and-verify super-step over every active row:
        propose (``k + 1`` draft-decode dispatches), verify (ONE target
        dispatch), roll the draft cache back to the accepted prefix,
        then account the emitted tokens host-side exactly like the
        baseline per-token loop (same finish rules, truncating a chunk
        at its first stop condition). Returns ``{req_id: last emitted
        1-based token}`` — multi-token emissions land in
        ``Request.output``; the dict mirrors the baseline ``step()``
        shape for callers that only poll liveness. ``had_running`` is
        the engine's decode-gap anchor (rows were in flight before this
        step's admission): a healthy super-step leaves its gap sample
        itself, beside the rows it verified, and its verify launch and
        fence carry the dispatch's ``seq`` like a plain decode's.

        Resilience: both dispatch sites route through the engine's
        fault hook (``draft``/``verify`` — serving/faults.py). A raised
        dispatch, garbage verify outputs (non-finite log-probs,
        out-of-range tokens or emit counts), or a super-step exceeding
        the watchdog budget discards the step and evicts every
        implicated row for loss-free replay — both pooled carries are
        first re-pointed at their latest VALID buffers (earlier
        dispatches in the step donated the old ones), then the rows'
        bytes die with their freed slots."""
        import jax.numpy as jnp

        from bigdl_tpu.serving.faults import FaultError

        eng = self.engine
        t_start = eng._clock()
        N = eng.pool.n_slots
        tokens = np.zeros((N,), np.int32)
        active = np.zeros((N,), bool)
        k_r = np.zeros((N,), np.int32)
        n_sampled = 0
        for slot, req in list(running.items()):
            if slot not in eng._configured:
                try:
                    eng._configure_slot(slot, req)
                except FaultError:
                    # the draft-prefill dispatch inside slot
                    # configuration faulted: evict exactly this row for
                    # loss-free replay, keep the rest of the super-step
                    eng._recover_admission([(slot, req)])
                    continue
            tokens[slot] = req.next_token
            active[slot] = True
            k_r[slot] = self._draft_budget(slot, req)
            n_sampled += not req.sampling.is_greedy
        if not active.any():
            return {}
        if eng._knobs_device is None:
            eng._knobs_device = {k: eng._place_rows(jnp.asarray(v))
                                 for k, v in eng._knobs.items()}
        knobs = eng._knobs_device

        # propose: kmax+1 chained draft steps, kmax = the step's LARGEST
        # per-row budget (host data — every dispatch reuses the one
        # compiled draft program; an all-normal/banned step pays one
        # dispatch, not k+1). Iteration j is active for row r while
        # j <= k_r[r], so short-budget rows mask out and row r's last
        # iteration writes its k_r-th draft's K/V — a fully-accepted
        # chunk leaves no hole. Chunk columns past kmax are zero pad
        # the fixed-width verify program never reads (lengths <= kmax+1)
        t0 = eng._clock()
        u = eng._place_rows(jnp.asarray(tokens))
        dcarry = eng.pool.draft_carry
        kmax = int(k_r[active].max()) if active.any() else 0
        drafts = []
        try:
            for j in range(kmax + 1):
                act_j = eng._place_rows(jnp.asarray(active & (k_r >= j)))
                logp_d, dcarry = eng._dispatch(
                    "draft", self._draft_step_fn,
                    self._draft_params, u, act_j, dcarry)
                u = jnp.argmax(logp_d, axis=-1).astype(jnp.int32)
                if j < self.k:
                    drafts.append(u)
        except FaultError:
            # earlier iterations donated the pooled draft carry; keep
            # the latest VALID buffers before evicting the rows
            eng.pool.draft_carry = dcarry
            eng._recover_step(running, "fail")
            return {}
        while len(drafts) < self.k:
            drafts.append(eng._place_rows(jnp.zeros((N,), jnp.int32)))
        # completion fence pinning the draft timer: u is the chain's
        # last output, so waiting on it waits on every draft dispatch —
        # no copy, and the drafts themselves STAY on device for the
        # verify step (the async-friendly half of the super-step)
        fence_wait("draft", u)
        eng.metrics.add_phase("draft", eng._clock() - t0)

        # verify: ONE fixed-width target dispatch for the whole fleet
        lengths = np.where(active, k_r + 1, 0).astype(np.int32)
        vtoks = eng._place_rows(jnp.concatenate(
            [jnp.asarray(tokens)[:, None]] + [d[:, None] for d in drafts],
            axis=1))
        seq, waves = eng._next_dispatch()
        n_rows = int(active.sum())
        t0 = eng._clock()
        try:
            with eng.metrics.span("decode.launch", seq=seq, rows=n_rows,
                                  chained=0, waves=waves):
                vt, vlp, n_emit, carry = eng._dispatch(
                    "verify", self.verify_fn,
                    eng.params, vtoks,
                    eng._place_rows(jnp.asarray(lengths)),
                    eng.pool.carry, knobs, *eng._adapter_args())
        except FaultError:
            eng.pool.draft_carry = dcarry     # target carry never donated
            eng._recover_step(running, "fail")
            return {}
        eng.pool.carry = carry
        # ONE batched fence readback for the whole verify result —
        # tokens, log-probs, emit counts cross to host together
        # (serving/fences.py) instead of as three separate syncs. The
        # verify site stays an IMMEDIATE consumer (window depth
        # structurally 0 — fences.DELAYED_CONSUMER_SITES): next
        # super-step's draft budgets are a host decision made from
        # THIS readback, so there is nothing to dispatch ahead of it.
        # The span's bracket is the fenced-wait sample — the blocked
        # half of the host_step split (metrics.DEVICE_PHASES)
        with eng.metrics.span("fence", phase="fence_wait", seq=seq):
            nxt, lps, nem = fence("verify", vt, vlp, n_emit)
        eng.metrics.add_phase("decode_step", eng._clock() - t0)
        bad = self._chunk_unhealthy(nxt, lps, nem, lengths, active)
        if bad is None and eng._timed_out(eng._clock() - t_start):
            bad = "timeout"
        if bad is not None:
            # outputs discarded; both carries keep valid buffers and
            # every implicated row is evicted, so the suspect bytes die
            # with the freed slots
            eng.pool.draft_carry = dcarry
            eng._recover_step(running, bad)
            return {}
        eng._warm = True                   # arms the watchdog timeout

        # draft rollback: the loop advanced active rows by k_r+1; keep
        # the accepted prefix + the emission that will be re-fed (pure
        # pointer arithmetic — stale chunk bytes sit behind the mask)
        act_dev = eng._place_rows(jnp.asarray(active))
        dcarry = dict(dcarry)
        dcarry["pos"] = jnp.where(
            act_dev,
            dcarry["pos"] - (eng._place_rows(jnp.asarray(k_r)) + 1)
            + n_emit,
            dcarry["pos"])
        eng.pool.draft_carry = dcarry

        eng.metrics.on_step(
            eng.scheduler.queue_depth, eng.pool.occupancy(), n_rows,
            kv_used_share=eng._kv_used_share(
                eng._resident_positions(running)))
        eng.metrics.on_sample_rows(n_sampled, len(running) - n_sampled,
                                   eng._sampler_wide(active))

        # emission: the baseline per-token accounting, applied to each
        # chunk token IN ORDER and truncated at the first stop — a stop
        # mid-chunk discards the tail exactly as the baseline engine
        # would never have sampled it (the row is evicted; its
        # over-advanced lane/counts die with the slot)
        emitted: Dict[int, int] = {}
        n_landed = 0          # chunk tokens that actually reached outputs
        now = eng._clock()
        for slot, req in list(running.items()):
            m = int(nem[slot])
            reason = None
            for j in range(m):
                # the engine's shared per-token accounting
                # (_account_token): append + emitted + first-token
                # latency + finish verdict — one spelling for the
                # decode window's delayed consumer and this loop
                reason = eng._account_token(
                    slot, req, int(nxt[slot, j]),
                    float(lps[slot, j]), now, emitted)
                n_landed += 1
                if reason is not None:
                    break
            if reason is not None:
                eng._finish_row(req, reason, now)
            else:
                req.next_token = int(nxt[slot, m - 1])
                eng._maybe_flip_ban(slot, req)
                eng._advance_constraint(slot, req)
        # accounted AFTER truncation: accepted = landed minus the one
        # non-draft draw per row, so accept_rate/tokens_per_step report
        # what the engine actually emitted, not what the verify step
        # confirmed before a mid-chunk stop discarded the tail
        eng.metrics.on_spec_step(int(k_r[active].sum()),
                                 n_landed - n_rows, n_rows)
        # the super-step's gap sample, beside what it verified
        eng._note_decode_gap(had_running, n_rows, waves, False)
        return emitted
