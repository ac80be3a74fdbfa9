"""Serving observability counters.

Layered on :class:`bigdl_tpu.optim.metrics.Metrics` (the reference's
``Metrics.scala`` analog, already exercised by the observability suite)
so serving counters ride the same set/add/mean surface the training plane
uses — a ``TrainSummary``-style consumer can read either.

Counters (all under the ``serving/`` prefix in the backing Metrics):

* ``queue_depth``       — sampled every engine step
* ``slot_occupancy``    — used/total slots, sampled every engine step
* ``batch_active``      — active rows per decode step
* ``ttft_s``            — per-request time-to-first-token (submit →
  first GENERATED token on host; includes queueing + prefill)
* ``latency_s``         — per-request submit → finish
* ``tokens_out``        — generated tokens per request (recorded at
  finish; sum = total tokens served)
* ``decode_step_s``     — the fenced decode/verify dispatch window
  (prefill dispatches are no longer completion-fenced — they overlap
  the decode step and their device time lands inside this window; the
  former ``prefill_s``/``draft_prefill_s`` phase timers went with the
  fences, see docs/async_readiness.md — a prefill's HOST side is the
  ``prefill.launch`` span below, its device time is the ``jit_prefill``
  program in the trace)
* ``cancelled``         — requests cancelled while WAITING
* ``queue_wait_s``      — per slot binding, the engine clock at the
  binding minus ``submit_time`` (re-admissions included): what a request
  waited for a slot, apart from the prefill that follows
* ``kv_used_share``     — one sample a decode step: resident K/V
  positions (sum of ``pos`` over in-use slots, from host state — no
  readback) over the ``n_slots x max_len`` the pool reserves. This and
  the next three are computed when the step is DISPATCHED, for the rows
  it decodes at the positions the program reads, and ride the in-flight
  entry to its read-back (``ServingEngine._host_state``)
* ``state_in_use_bytes`` — one sample a decode step, only where the
  model's family keeps per-slot state beside K/V (a recurrent scan
  state, a convolution window): in-use slots x
  ``state_bytes_per_slot``, host state, no readback. A slot holds all
  of its state whatever its position
* ``kv_held_bytes``     — one sample a decode step: bytes of K/V the
  step's rows really hold, per row and layer ``min(pos, len_i)``
  positions (a sliding-window layer's ring holds at most its window),
  host state, no readback; ``kv_used_share`` keeps its meaning (``pos``
  over ``n_slots x max_len``)
* ``kv_fetched_bytes``  — one sample a decode step: bytes of K/V the
  decode program's attention FETCHES for the step's rows, per row and
  K/V leaf the whole kernel blocks up to its position
  (``KVPool.kv_fetched_bytes``). COMPUTED from shapes and host state,
  not measured: what the Pallas kernel fetches on a TPU (off it the
  whole window is read); not sampled by a speculative engine, whose
  verify step reads the whole window
* ``expert_pairs`` / ``experts_hit`` / ``expert_load_max`` — per decode
  step of a family with routed experts, from the per-expert token
  counts its program returns with the tokens (read back at the SAME
  decode fence): (token, expert) pairs this chip's experts computed,
  summed over the expert layers; held experts with at least one token,
  summed over layers; the busiest held expert's tokens
* ``fence_wait_s``      — the time the host was BLOCKED in the step's
  one fence readback (span ``fence``; the ``DEVICE_PHASES`` half of the
  ``host_step_s`` split)
* ``admit_host_s``      — HOST time of admission (span ``admit``: the
  scheduler, slot binding, prefill and scatter LAUNCHES, the chunk
  pump), recorded only for steps that bound >= 1 request. Launch time by
  design: the prefill's device time is in the trace

Spans (:meth:`ServingMetrics.span`; the closed vocabulary is
``fences.SPAN_NAMES``). Each is a ``jax.profiler.TraceAnnotation`` named
``serving.<name>`` on the dispatching thread's line of a running profile
— near free otherwise — and the ones with a series above also record
it, from the same bracket, on the engine's clock:

    serving.step (step=)
      serving.admit (rids=)            -> admit_host_s
        serving.prefill.launch (rows=, padded=, bucket=)
        serving.pool.write
      serving.decode.build             (also holds serving.pool.write:
                                        write_sampling at configuration)
      serving.decode.launch (seq=, rows=, chained=, waves=)
      serving.consume (seq=; kv_held=, kv_fetched=, experts_hit= noted
        serving.fence (seq=)            once read back) -> fence_wait_s
                                       (the delayed consumer; a finished
                                        row's slot reset is a
                                        serving.pool.write in it)

``seq`` numbers the engine's decode (or verify) dispatches: the launch,
the fence and the consume of ONE dispatch carry one number, the fence a
step after the launch under the default window. ``rows`` is what the
dispatch decodes, ``chained`` whether it was launched on the in-flight
token, ``waves`` the prefill launches (``on_prefill_batch`` calls: a
batched wave, a prefix suffix, a chunk) since the dispatch before it;
the notes on ``consume`` are the dispatch's ``kv_held_bytes`` /
``kv_fetched_bytes`` samples and the held experts that got a token.
``benchmark/step_join.py`` joins them to the program's execution on the
device through the runtime's ``run_id``.

The profile ``stop_trace`` writes is the span record; there is no
in-memory span log. ``benchmark/span_reduce.py`` puts every device idle
gap down to the innermost of these covering it.

Chunked-admission counters (``serving/chunked.py``):

* ``chunks`` / ``chunk_tokens`` — chunk-prefill calls fed by the pump
  and the prompt tokens they carried (sums = total chunk traffic;
  ``chunk_tokens``/``chunks`` mean = effective chunk width)
* ``partial_rows``     — mid-prefill PARTIAL rows, sampled per pump
* ``decode_gap_s``     — wall gap between consecutive decode (or
  verify) read-backs while rows were in flight across the gap: the
  DECODE-STALL signal chunked admission exists to shrink (a batched
  admission burst shows up as one huge gap; chunked bounds it by the
  chunk budget). ``decode_gap_percentiles()`` summarizes;
  ``summary()`` reports the p99
* ``step_rows`` / ``step_waves`` / ``step_chained`` — beside every
  ``decode_gap_s`` sample, from the same hook (``on_decode_gap``), so
  the four are equal in length and aligned sample for sample: the rows
  the dispatch just read back decoded, the prefill launches since the
  dispatch before it, and whether it was chained. ``decode_gap_s``
  where ``step_chained`` is 1, ``step_waves`` 0 and the NEXT sample's
  ``step_waves`` 0 too is the plain step; where ``step_waves`` > 0,
  less that, it is the device side of the stall one admission imposes
  on every running row (the host side, the wave's launch, lands on the
  sample before, the dispatch then in flight); weighted by
  ``step_rows`` it is the gap between tokens the users see
* ``host_step_s``      — per-super-step HOST time: step wall minus the
  fenced device phase windows (decode/verify dispatch, draft chain)
  timed inside it — the Python the device pipeline waits on between
  dispatches, i.e. the async dispatch-ahead refactor's before-number
  (``host_step_percentiles()``; ``summary()`` reports p50/p99)

Feasibility admission control (``ServingEngine(deadline_feasibility=
True)``):

* ``infeasible``       — waiting requests deadline-dropped because the
  running ``decode_step_s`` median says they cannot finish inside their
  deadline (each also counts as shed + deadline_missed; the EDF-with-
  admission-control step beyond dropping only already-expired work)

Batched-admission counters (``serving/admission.py``):

* ``prefill_batch``     — true rows per batched prefill call (mean =
  admission batching factor; count = number of prefill calls)
* ``prefill_batch_padded`` — padded rows per call (bucketing overhead)
* ``prefill_bucket_compiles`` — novel (B, L) prefill shapes traced
  (sum = the bounded compiled-program count the bucket scheme enforces)
* ``prefix_lookups`` / ``prefix_hits`` / ``prefix_hit_tokens`` —
  prefix-cache traffic; ``summary()`` derives ``prefix_hit_rate``

Sampling counters (``serving/sampling.py``):

* ``rows_sampled`` / ``rows_greedy`` — active rows per decode step that
  drew from a sampled distribution (temperature > 0) vs took argmax;
  ``summary()`` derives ``sampled_row_frac``
* ``sampler_wide``        — 1.0 for a decode step in which a running
  row is wide by ``sampling.wide_rows`` (a nucleus with no ``top_k``,
  or a ``top_k`` over ``sampling.K_CAP``), so that the step's sampler
  sorted the whole vocabulary, else 0.0; the mean in ``summary()`` is
  the share of decode steps that sorted
* ``mean_logprob``        — per-request mean chosen-token raw model
  log-prob (recorded at finish; a cheap generation-quality signal)

Speculative-decoding counters (``serving/speculative.py``):

* ``draft_tokens`` / ``accepted_tokens`` — drafts proposed per
  super-step vs landed in request outputs (verify-confirmed and not
  discarded by a mid-chunk stop); ``summary()`` derives
  ``accept_rate`` (accepted/drafted)
* ``spec_rows``          — active rows per super-step (row-steps);
  ``summary()`` derives ``tokens_per_step`` ((accepted + rows)/rows —
  emitted tokens per row per target invocation, 1.0 = plain decode)
* ``draft_s``            — draft-chain phase timing (the verify
  dispatch lands in ``decode_step_s``; the draft PREFILL is un-fenced
  and overlaps the step like every prefill)

Sharded-plane counters (``serving/sharded.py``):

* ``mesh_data_shards`` / ``mesh_model_shards`` — the engine's mesh
  shape (set once at construction; 1/1 for an unsharded engine)
* ``shard_occupancy_min`` / ``shard_occupancy_max`` — per-shard slot
  occupancy extremes, sampled every engine step
* ``shard_imbalance`` — cross-shard admission imbalance in ROWS
  (max − min allocated slots across shards; 0 = perfectly balanced —
  the balanced allocator keeps it ≤ 1 under drain-style traffic)

Resilience counters (``serving/scheduler.py`` + ``serving/faults.py``):

* ``preempted``         — RUNNING rows evicted loss-free by priority
  preemption (their streams resume byte-identically at readmission)
* ``shed``              — requests load-shed without running: queue-full
  rejections at submit plus deadline-drops of expired waiting requests
* ``deadline_missed``   — deadline-dropped requests plus FINISHED
  requests that completed after their deadline
* ``retries``           — row evictions by fault recovery (a failed /
  garbage / timed-out step evicts its rows and replays them)
* ``recovered_rows``    — retried requests that went on to FINISH
  successfully (the loss-free-recovery success count)
* ``degraded``          — requests whose ``degrade`` knob was applied
  at admission under pressure
* ``finished_in_slo``   — finished requests that met their deadline
  (no-deadline requests count as met); ``summary()`` derives
  ``goodput`` = finished_in_slo / submitted — the overload bench's
  headline (``serving_bench --scenario slo``)

Disaggregated-plane counters (``serving/disagg.py`` — recorded on the
front end's metrics; each pool's engine keeps its own full set):

* ``handoffs``         — prefill→decode KV-row handoffs (sum)
* ``transfer_bytes``   — serialized payload bytes per handoff (sum =
  total wire traffic; ``summary()`` derives
  ``transfer_bytes_per_handoff``)
* ``transfer_s``       — per-handoff transfer wall (pack + send +
  deliver on the in-process path); ``transfer_percentiles()``
  summarizes, ``summary()`` reports the p99
* ``prefill_occupancy`` / ``decode_occupancy`` — per-step pool slot
  occupancies (one decode sample per pool per step) — the
  pool-sizing signal

Pool-lifecycle counters (``serving/health.py`` + the failover /
autoscaler machinery in ``serving/disagg.py``):

* ``pool_deaths``       — decode pools classified DEAD (missed
  heartbeats, consecutive transfer failures, or an operator
  ``kill_pool``)
* ``failovers``         — completed pool failovers (one per death
  that had rows to reconstruct or a state to retire)
* ``failover_s``        — wall time of each failover (detect →
  every stranded row re-routed); ``failover_percentiles()``
  summarizes, ``summary()`` reports p50/p99
* ``migrated_rows``     — rows moved pool-to-pool LOSS-FREE via a
  ``row_state`` payload (graceful drain, wire re-routes, and
  stash-current failover rows)
* ``replayed_rows``     — rows reconstructed by byte-identical
  prefill replay of ``prompt + emitted`` (failover of rows whose
  handoff stash was stale — the PR 8 recovery contract lifted to
  pool scope)
* ``transfer_timeouts`` — sends past the configured
  ``send_timeout_s`` (treated as failed-unconfirmed and resent;
  the receiver deduplicates)
* ``autoscale_up`` / ``autoscale_down`` — standby-pool activations /
  drain-and-retire actions by the occupancy autoscaler

KV-format counters (``serving/kv_pool.py`` — set once at construction):

* ``kv_bits``            — bits per stored K/V element (32/16/8)
* ``kv_bytes_per_slot``  — one slot's KV footprint in bytes (int8
  payload + per-(slot, head) scales on the quantized path)
* ``state_bytes_per_slot`` — one slot's other per-slot state in bytes
  (``KVPool.state_bytes_per_slot``; 0 for a family that keeps none)
* ``kv_position_bytes``  — bytes ONE cached position holds over all K/V
  leaves of a slot, as stored (``KVPool.kv_position_bytes``: K and V of
  every layer, or a latent family's one padded row a layer); a constant,
  set at construction and repeated with every step's sample, so that a
  reader of a window's slice of the series finds it
* ``kv_slots_per_gib``   — derived effective capacity: concurrent
  slots per GiB of HBM at this format (the int8 path's ~2x headline)
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from bigdl_tpu.optim.metrics import Metrics, Span
from bigdl_tpu.serving.fences import SPAN_NAMES


def span(name: str, clock=None, record=None, key=None, **ids) -> Span:
    """A span of the closed serving vocabulary (``fences.SPAN_NAMES``),
    ``serving.<name>`` in the profile. Bare, as the KV pool uses it, it
    is a profile event only; :meth:`ServingMetrics.span` adds the phase
    series."""
    if name not in SPAN_NAMES:
        raise ValueError(
            f"unknown span {name!r} — add it to fences.SPAN_NAMES "
            f"first; known: {sorted(SPAN_NAMES)}")
    return Span(f"serving.{name}", clock, record, key, ids)


class ServingMetrics:
    """Queue/latency/throughput counters for :class:`ServingEngine`."""

    #: THE closed finish-reason vocabulary. Every string a request can
    #: finish with has a per-reason counter (``serving/finish_<reason>``
    #: via :meth:`on_finish_reason`), so dashboards/goodput math can
    #: never silently miss a disposition class. Adding a reason means
    #: adding it HERE first — the static analyzer's SRV205 rule reads
    #: this frozenset (cross-module) and flags any reason string the
    #: serving plane uses that is not in it.
    FINISH_REASONS = frozenset({
        "eos",         # the request's private eos token appeared
        "stop",        # stop-token / stop-sequence hit
        "length",      # max_new_tokens reached
        "shed",        # queue-full backpressure at submit
        "deadline",    # expired while WAITING (deadline-drop)
        "infeasible",  # feasibility admission control drop
        "error",       # fault-recovery retry budget exhausted
        "cancelled",   # caller cancel() — state-carried, so
                       # Request.finish_reason stays None for these
    })

    def __init__(self, backing: Optional[Metrics] = None) -> None:
        from collections import deque

        self.metrics = backing if backing is not None else Metrics()
        self._t_start: Optional[float] = None
        self._t_last: Optional[float] = None
        # bounded recent-decode-step window for the feasibility
        # estimator: the full-history sample list grows forever and
        # _admit consults the estimate EVERY step, so the estimator
        # must be O(window), not O(lifetime) — and a recent window
        # also tracks drift (load changes, thermal throttling) where
        # a lifetime median would lag
        self._step_window: "deque" = deque(maxlen=512)
        # the draft-phase twin: on speculative engines the k+1 draft
        # dispatches per super-step land in the "draft" phase, not in
        # "decode_step" (which times only the verify dispatch) — a
        # service-time estimate that ignored them would understate
        # true per-token wall by the whole draft share
        self._draft_window: "deque" = deque(maxlen=512)
        # running sums of the speculative counters: the estimator needs
        # lifetime accepted/rows every step, and re-summing the backing
        # Metrics sample lists would be O(lifetime) per call
        self._spec_acc = 0.0
        self._spec_rows = 0.0
        # running sum of the DEVICE phase windows (decode/verify
        # dispatch, draft chain): the engine's per-step
        # host-vs-device split subtracts this across a step
        # (serving/host_step_s — the async refactor's before-number),
        # plus the decode/verify SAMPLE COUNT so the engine can pair
        # one host_step sample with every decode_step sample — on
        # recovery paths too — without re-summing the backing lists
        self._device_s = 0.0
        self._n_decode_steps = 0
        # set_kv_format's constant, repeated beside every step's
        # kv_held_bytes sample
        self._kv_position_bytes: Optional[int] = None
        # lifetime count of prefill launches (on_prefill_batch calls):
        # the engine differences it across two decode dispatches to
        # say how many waves were launched in between
        self._n_prefill_launches = 0

    # -- engine hooks ------------------------------------------------------

    def on_submit(self) -> None:
        self.metrics.add("serving/submitted", 1.0)

    def on_queue_wait(self, seconds: float) -> None:
        """One slot binding: the engine clock at the binding minus the
        request's ``submit_time`` — re-admissions (preempted or
        fault-evicted rows seated again) count again."""
        self.metrics.add("serving/queue_wait_s", float(seconds))

    def on_step(self, queue_depth: int, occupancy: float,
                batch_active: int, kv_used_share: float,
                state_in_use_bytes: Optional[int] = None,
                kv_held_bytes: Optional[int] = None,
                kv_fetched_bytes: Optional[int] = None) -> None:
        # a declared CLOCK_SITES unit (serving/faults.py): the serve-
        # duration anchor timestamps (_t_start/_t_last span the whole
        # serve for summary()'s wall number) deliberately read the raw
        # wall clock — they are observability, never a lockstep
        # decision. Everything decision-bearing runs on the engine
        # clock; MH403 pins any NEW raw read to this vocabulary.
        now = time.perf_counter()
        if self._t_start is None:
            self._t_start = now
        self._t_last = now
        self.metrics.add("serving/queue_depth", float(queue_depth))
        self.metrics.add("serving/slot_occupancy", float(occupancy))
        self.metrics.add("serving/batch_active", float(batch_active))
        self.metrics.add("serving/kv_used_share", float(kv_used_share))
        if state_in_use_bytes is not None:
            self.metrics.add("serving/state_in_use_bytes",
                             float(state_in_use_bytes))
        if kv_held_bytes is not None:
            self.metrics.add("serving/kv_held_bytes", float(kv_held_bytes))
            if self._kv_position_bytes is not None:
                self.metrics.add("serving/kv_position_bytes",
                                 float(self._kv_position_bytes))
        if kv_fetched_bytes is not None:
            self.metrics.add("serving/kv_fetched_bytes",
                             float(kv_fetched_bytes))

    def on_expert_counts(self, counts) -> int:
        """One decode step's ``(n_expert_layers, held)`` token counts,
        as the fence read them back. Returns the held experts that got
        a token (the ``experts_hit`` sample)."""
        hit = int((counts > 0).sum())
        self.metrics.add("serving/expert_pairs", float(counts.sum()))
        self.metrics.add("serving/experts_hit", float(hit))
        self.metrics.add("serving/expert_load_max", float(counts.max()))
        return hit

    def on_first_token(self, ttft_s: float) -> None:
        self.metrics.add("serving/ttft_s", float(ttft_s))

    def on_finish(self, latency_s: float, n_tokens: int,
                  mean_logprob: Optional[float] = None,
                  met_deadline: Optional[bool] = None) -> None:
        self.metrics.add("serving/finished", 1.0)
        self.metrics.add("serving/latency_s", float(latency_s))
        self.metrics.add("serving/tokens_out", float(n_tokens))
        if mean_logprob is not None:
            self.metrics.add("serving/mean_logprob", float(mean_logprob))
        if met_deadline is not None:
            if met_deadline:
                self.metrics.add("serving/finished_in_slo", 1.0)
            else:
                self.metrics.add("serving/deadline_missed", 1.0)

    # -- resilience hooks (scheduler preemption + fault recovery) ----------

    def on_finish_reason(self, reason: str) -> None:
        """Per-reason disposition counter (``serving/finish_<reason>``),
        recorded for EVERY request leaving the engine — finished,
        shed, deadline-dropped, or errored out. The vocabulary is
        closed (:data:`FINISH_REASONS`): an unknown reason raises here
        rather than minting an unaccounted counter name, and SRV205
        catches the same drift statically before it ever runs."""
        if reason not in self.FINISH_REASONS:
            raise ValueError(
                f"unknown finish_reason {reason!r} — add it to "
                f"ServingMetrics.FINISH_REASONS (and a counter "
                f"consumer) first; known: {sorted(self.FINISH_REASONS)}")
        self.metrics.add(f"serving/finish_{reason}", 1.0)

    def on_preempt(self) -> None:
        """A RUNNING row evicted loss-free to make room for a
        higher-priority request."""
        self.metrics.add("serving/preempted", 1.0)

    def on_shed(self, deadline: bool = False) -> None:
        """A request load-shed without ever running: queue-full
        rejection at submit, or (``deadline=True``) a deadline-drop of
        an expired waiting request — the latter also counts as a
        deadline miss."""
        self.metrics.add("serving/shed", 1.0)
        if deadline:
            self.metrics.add("serving/deadline_missed", 1.0)

    def on_retry(self) -> None:
        """One row evicted by fault recovery (step failure, garbage
        outputs, or a watchdog timeout) and requeued for replay."""
        self.metrics.add("serving/retries", 1.0)

    def on_recovered(self) -> None:
        """A previously fault-evicted request FINISHED successfully —
        the recovery path's success counter."""
        self.metrics.add("serving/recovered_rows", 1.0)

    def on_degrade(self) -> None:
        """A request's ``degrade`` knob applied at admission under
        queue pressure."""
        self.metrics.add("serving/degraded", 1.0)

    def on_degrade_restored(self) -> None:
        """A still-WAITING degraded request got its recorded original
        limits back after pressure dropped (the revertible-Degrade
        contract: a burst's clamp must not outlive the burst)."""
        self.metrics.add("serving/degrade_restored", 1.0)

    def on_actuation(self, actuator: str) -> None:
        """One autopilot bus actuation (``serving/autopilot.py``):
        counted in total and per actuator, so a flapping controller is
        visible on the metrics plane, not just in the bus log."""
        self.metrics.add("serving/actuations", 1.0)
        self.metrics.add(f"serving/actuation_{actuator}", 1.0)

    def on_decode_dispatch(self, chained: bool) -> None:
        """Per plain decode dispatch: 1.0 when it was CHAINED on the
        in-flight dispatch's device token (launched before the previous
        step was read back), 0.0 when the window was empty or had to be
        flushed and the token rows were rebuilt on the host. The mean is
        the share of decode steps the dispatch-ahead window hid the
        host behind (``serving/decode_chained``)."""
        self.metrics.add("serving/decode_chained", float(chained))

    def on_sample_rows(self, n_sampled: int, n_greedy: int,
                       wide: bool) -> None:
        """Per decode step: how many active rows drew from a sampled
        distribution (temperature > 0) vs took the argmax, and whether
        one of them made the step sort the whole vocabulary."""
        self.metrics.add("serving/rows_sampled", float(n_sampled))
        self.metrics.add("serving/rows_greedy", float(n_greedy))
        self.metrics.add("serving/sampler_wide", float(wide))

    def on_spec_step(self, n_drafted: int, n_accepted: int,
                     n_rows: int) -> None:
        """Per speculative super-step (``serving/speculative.py``):
        draft tokens proposed across active rows, how many LANDED in
        request outputs (confirmed by the verify step AND not discarded
        by a mid-chunk stop truncation), and the active row count
        (row-steps). Every row also emits one non-draft draw per step,
        so emitted tokens = accepted + rows; ``summary()`` derives
        ``accept_rate`` = accepted/drafted and ``tokens_per_step`` =
        emitted/rows (the per-row speedup denominator — 1.0 is the
        plain decode floor)."""
        self.metrics.add("serving/draft_tokens", float(n_drafted))
        self.metrics.add("serving/accepted_tokens", float(n_accepted))
        self.metrics.add("serving/spec_rows", float(n_rows))
        self._spec_acc += float(n_accepted)
        self._spec_rows += float(n_rows)

    def on_cancel(self) -> None:
        self.metrics.add("serving/cancelled", 1.0)

    def set_mesh_shape(self, data_shards: int, model_shards: int) -> None:
        """Record the engine's mesh shape (once, at construction)."""
        self.metrics.set("serving/mesh_data_shards", float(data_shards))
        self.metrics.set("serving/mesh_model_shards", float(model_shards))

    def set_kv_format(self, kv_dtype: str, bytes_per_slot: int,
                      state_bytes_per_slot: int = 0,
                      position_bytes: Optional[int] = None) -> None:
        """Record the pooled cache's storage format (once, at
        construction): bits per stored K/V element, the per-slot KV
        footprint in bytes (int8 payload + dequant scales, or the float
        cache), and the derived effective capacity — concurrent slots
        one GiB of HBM holds at this format. The capacity number is the
        kv_quant headline: int8 runs ~2x the fp16-cache slots."""
        bits = {"fp32": 32.0, "bf16": 16.0, "int8": 8.0}.get(kv_dtype, 0.0)
        self.metrics.set("serving/kv_bits", bits)
        self.metrics.set("serving/kv_bytes_per_slot", float(bytes_per_slot))
        self.metrics.set("serving/state_bytes_per_slot",
                         float(state_bytes_per_slot))
        self._kv_position_bytes = position_bytes
        if position_bytes is not None:
            self.metrics.set("serving/kv_position_bytes",
                             float(position_bytes))
        self.metrics.set("serving/kv_slots_per_gib",
                         float((1 << 30) // max(int(bytes_per_slot), 1)))

    def on_shard_slots(self, used_per_shard, rows_per_shard: int) -> None:
        """Per-shard occupancy + cross-shard admission imbalance
        (max−min allocated rows), sampled per engine step on sharded
        pools."""
        if not used_per_shard or not rows_per_shard:
            return
        lo, hi = min(used_per_shard), max(used_per_shard)
        self.metrics.add("serving/shard_occupancy_min", lo / rows_per_shard)
        self.metrics.add("serving/shard_occupancy_max", hi / rows_per_shard)
        self.metrics.add("serving/shard_imbalance", float(hi - lo))

    # -- chunked admission + feasibility hooks -----------------------------

    def on_chunk(self, n_tokens: int) -> None:
        """One chunk-prefill call fed by the streaming-admission pump,
        carrying ``n_tokens`` true prompt tokens."""
        self.metrics.add("serving/chunks", 1.0)
        self.metrics.add("serving/chunk_tokens", float(n_tokens))

    def on_partial_rows(self, n: int) -> None:
        """Mid-prefill PARTIAL rows after one pump pass."""
        self.metrics.add("serving/partial_rows", float(n))

    def on_decode_gap(self, gap_s: float, rows: int, waves: int,
                      chained: bool) -> None:
        """Wall gap between consecutive decode read-backs while rows
        stayed in flight — the decode-stall sample (admission work in
        the gap is what stretches it) — with what the dispatch just
        read back was: the rows it decoded, the prefill launches since
        the decode dispatch before it, and whether it was chained.
        THE one place the four series gain a sample, so they stay
        equal in length and aligned sample for sample."""
        self.metrics.add("serving/decode_gap_s", float(gap_s))
        self.metrics.add("serving/step_rows", float(rows))
        self.metrics.add("serving/step_waves", float(waves))
        self.metrics.add("serving/step_chained", float(chained))

    def on_infeasible(self) -> None:
        """A waiting request dropped by feasibility admission control:
        the service-time estimate says it cannot finish in time."""
        self.metrics.add("serving/infeasible", 1.0)

    # -- disaggregated-plane hooks (serving/disagg.py) ---------------------

    def on_handoff(self, n_bytes: int, seconds: float) -> None:
        """One prefill→decode KV-row handoff: the serialized payload's
        size on the wire and the transfer wall (pack + send on the
        sending clock; the in-process engine's sample covers the full
        pack→deliver path). ``summary()`` derives the per-handoff byte
        mean and the transfer_s p99."""
        self.metrics.add("serving/handoffs", 1.0)
        self.metrics.add("serving/transfer_bytes", float(n_bytes))
        self.metrics.add("serving/transfer_s", float(seconds))

    def on_pool_occupancy(self, prefill_occ: float, decode_occs) -> None:
        """Per-front-end-step pool occupancies: the prefill pool's
        slot usage and each decode pool's (one sample per pool per
        step). A prefill pool pinned at 1.0 while decode pools idle
        says the split is prefill-bound — resize the pools, not the
        engine (the interference signal disaggregation turns into a
        CAPACITY signal)."""
        self.metrics.add("serving/prefill_occupancy", float(prefill_occ))
        for occ in decode_occs:
            self.metrics.add("serving/decode_occupancy", float(occ))

    def transfer_percentiles(self, qs=(50, 90, 99)) -> Dict[str, float]:
        """Percentiles of the per-handoff transfer wall (seconds)."""
        return self._pctl("transfer_s", qs)

    # -- pool-lifecycle hooks (serving/health.py + disagg failover) --------

    def on_pool_death(self) -> None:
        """A decode pool classified DEAD (heartbeat silence,
        consecutive transfer failures, or an operator kill)."""
        self.metrics.add("serving/pool_deaths", 1.0)

    def on_failover(self, n_migrated: int, n_replayed: int,
                    seconds: float) -> None:
        """One completed pool failover: rows reconstructed loss-free
        from a current ``row_state`` payload (wire re-routes + stash
        restores) vs by prefill replay of ``prompt + emitted``, and
        the detect→done wall time."""
        self.metrics.add("serving/failovers", 1.0)
        if n_migrated:
            self.metrics.add("serving/migrated_rows", float(n_migrated))
        if n_replayed:
            self.metrics.add("serving/replayed_rows", float(n_replayed))
        self.metrics.add("serving/failover_s", float(seconds))

    def on_migrated(self, n_rows: int) -> None:
        """Rows moved pool-to-pool loss-free via the ``row_state``
        handoff payload (graceful drain)."""
        if n_rows:
            self.metrics.add("serving/migrated_rows", float(n_rows))

    def on_transfer_timeout(self) -> None:
        """A handoff send exceeded ``send_timeout_s`` on the engine
        clock: delivery unconfirmed, the request resends (the
        receiver deduplicates by request id)."""
        self.metrics.add("serving/transfer_timeouts", 1.0)

    def on_autoscale(self, direction: str) -> None:
        """One autoscaler action: ``"up"`` (standby pool activated)
        or ``"down"`` (cold pool drained and retired)."""
        if direction not in ("up", "down"):
            raise ValueError(
                f"autoscale direction must be 'up' or 'down', "
                f"got {direction!r}")
        self.metrics.add(f"serving/autoscale_{direction}", 1.0)

    def failover_percentiles(self, qs=(50, 90, 99)) -> Dict[str, float]:
        """Percentiles of the per-failover wall time (seconds)."""
        return self._pctl("failover_s", qs)

    # -- host KV tier hooks (serving/kv_tier.py) ---------------------------

    def on_spill(self, n_bytes: int) -> None:
        """One row/prefix entry written into the host tier (packed
        through the ``row_state``/``pack_payload`` codec). ``summary()``
        surfaces the count and total bytes as sums and derives the
        per-spill byte mean."""
        self.metrics.add("serving/spills", 1.0)
        self.metrics.add("serving/spill_bytes", float(n_bytes))

    def on_fetch(self, n_bytes: int, seconds: float) -> None:
        """One tier entry read back (row readmission or prefix
        promotion): the blob size and the host-side unpack wall.
        ``summary()`` derives the fetch_s p99 — the number to hold
        against the re-prefill wall it replaces."""
        self.metrics.add("serving/fetches", 1.0)
        self.metrics.add("serving/fetch_bytes", float(n_bytes))
        self.metrics.add("serving/fetch_s", float(seconds))

    def on_tier_bytes(self, n_bytes: int) -> None:
        """Resident tier footprint (a gauge, not a counter): the bytes
        currently held against ``host_budget_bytes``."""
        self.metrics.set("serving/tier_bytes", float(n_bytes))

    def on_tier_evict(self) -> None:
        """A tier entry evicted by the byte budget (LRU): the copy is
        gone — a row readmission downgrades to prefill replay, a
        prefix lookup to a miss. Loss-free either way; this counter
        rising is the 'raise host_budget_bytes' signal."""
        self.metrics.add("serving/tier_evictions", 1.0)

    def on_resume_without_prefill(self) -> None:
        """A mid-stream row (tokens already emitted) re-seated from a
        stashed/spilled ``row_state`` payload instead of replaying
        prefill — the capacity win the tier exists for."""
        self.metrics.add("serving/resumed_without_prefill", 1.0)

    def fetch_percentiles(self, qs=(50, 90, 99)) -> Dict[str, float]:
        """Percentiles of the per-fetch host wall (seconds)."""
        return self._pctl("fetch_s", qs)

    def decode_step_estimate(self, n: int = 64) -> Optional[float]:
        """MEDIAN of the last ``n`` decode-step samples (seconds), or
        None before the first decode step — the per-step service-time
        estimate feasibility admission control builds on. Median, not
        mean: the engine's first dispatch carries the one-time XLA
        compile (multi-second at LM scale — the same cold-start
        outlier the watchdog's arming grace exists for) and
        fault-injected stalls are outliers too; a mean polluted by
        either would spuriously shed early traffic as infeasible. A
        bounded RECENT window (the :meth:`window` discipline), not
        full history: _admit consults this every engine step, so the
        cost must stay O(window) for the engine's whole lifetime — and
        a whole-run median goes stale across traffic phases (a warm
        lull's fast steps would understate a burst's service time and
        admit guaranteed misses)."""
        if not self._step_window:
            return None
        return self._window_stats(
            list(self._step_window)[-int(n):])["p50"]

    def service_time_estimate(self) -> Optional[float]:
        """Estimated seconds per EMITTED TOKEN — what feasibility
        admission control multiplies a request's remaining tokens by.
        Per super-step wall = the decode-step median PLUS the draft-
        phase median (zero on plain engines; on speculative engines
        "decode_step" times only the verify dispatch, and skipping the
        k+1 draft dispatches would understate service time and admit
        guaranteed misses), divided by the measured tokens-per-step
        (1.0 plain; a speculative engine emits 1..k+1 tokens per
        super-step, and dividing by the lifetime rate keeps the
        estimate from overstating service time by up to (k+1)x and
        shedding requests that would have met their deadline — the
        lifetime rate lags a mid-flight Degrade(draft_tokens=0) shift,
        an accepted coarseness)."""
        est = self.decode_step_estimate()
        if est is None:
            return None
        if self._draft_window:
            est += self._window_stats(
                list(self._draft_window)[-64:])["p50"]
        # running sums, not Metrics.get (which re-sums the full
        # per-step sample lists — O(lifetime) on a hot path)
        if self._spec_rows:
            est /= (self._spec_acc + self._spec_rows) / self._spec_rows
        return est

    def decode_gap_percentiles(self, qs=(50, 90, 99)) -> Dict[str, float]:
        """Percentiles of the decode-stall samples (seconds)."""
        return self._pctl("decode_gap_s", qs)

    def on_prefill_batch(self, n_rows: int, n_padded: int) -> None:
        self.metrics.add("serving/prefill_batch", float(n_rows))
        self.metrics.add("serving/prefill_batch_padded", float(n_padded))
        self._n_prefill_launches += 1

    @property
    def prefill_launch_count(self) -> int:
        """Lifetime count of prefill launches — a batched wave, a
        prefix suffix, a chunk: every ``on_prefill_batch`` call."""
        return self._n_prefill_launches

    def on_bucket_compile(self) -> None:
        self.metrics.add("serving/prefill_bucket_compiles", 1.0)

    def on_prefix_lookup(self, matched_tokens: int, total_tokens: int) -> None:
        self.metrics.add("serving/prefix_lookups", 1.0)
        if matched_tokens > 0:
            self.metrics.add("serving/prefix_hits", 1.0)
            self.metrics.add("serving/prefix_hit_tokens",
                             float(matched_tokens))

    #: phases during which the host is genuinely BLOCKED on device
    #: completion — everything else a step spends is host Python
    #: (scheduling, admission bookkeeping, per-token accounting).
    #: The prefill/draft_prefill phases left this set when their
    #: completion fences were deleted (the PR 12 worksheet's cashed-in
    #: "deletable" entries). The dispatch-ahead refactor (PR 20) moved
    #: ``decode_step`` out too: under a window the dispatch→consume
    #: elapsed OVERLAPS host work on other in-flight steps, so summing
    #: it as "device" would double-count against the step wall and the
    #: host_step residue would lie at W>0. What remains is exactly the
    #: blocked time: ``fence_wait`` (the bracket around each fence
    #: readback — the delayed consumer's actual stall) and ``draft``
    #: (the chain's completion pin). ``decode_step`` samples still
    #: land (the service-time estimator and the step windows read
    #: them); they just stop feeding ``device_seconds``.
    DEVICE_PHASES = frozenset({"fence_wait", "draft"})

    def span(self, name: str, phase: Optional[str] = None, **ids) -> Span:
        """``with metrics.span("fence", phase="fence_wait"):`` — one
        ``serving.<name>`` event in a running profile and, with
        ``phase``, one :meth:`add_phase` sample of the bracket's
        duration on the backing Metrics' clock (the engine's): the
        series and the profile bracket the same code, and the
        ``DEVICE_PHASES`` / host_step bookkeeping is ``add_phase``'s
        own."""
        return span(name, self.metrics.clock, self.add_phase, phase, **ids)

    def add_phase(self, name: str, seconds: float,
                  service_s: Optional[float] = None) -> None:
        """``service_s`` (decode_step only) is what the service-time
        estimator takes in place of ``seconds``: under the dispatch-
        ahead window the dispatch-to-fence bracket of a chained
        dispatch includes its wait behind the previous program, which
        is no part of what a token costs."""
        self.metrics.add(f"serving/{name}_s", float(seconds))
        if name == "decode_step":
            self._step_window.append(
                float(seconds if service_s is None else service_s))
            self._n_decode_steps += 1
        elif name == "draft":
            self._draft_window.append(float(seconds))
        if name in self.DEVICE_PHASES:
            self._device_s += float(seconds)

    @property
    def device_seconds(self) -> float:
        """Lifetime sum of the device phase windows (the fenced
        dispatch timings) — the engine snapshots this around a step to
        derive ``serving/host_step_s``."""
        return self._device_s

    @property
    def decode_step_count(self) -> int:
        """Lifetime count of decode/verify dispatch samples — the
        engine pairs exactly one ``host_step_s`` sample with each (a
        recovered step's discarded outputs still cost real host time),
        so the split series stay comparable sample for sample."""
        return self._n_decode_steps

    def host_step_percentiles(self, qs=(50, 90, 99)) -> Dict[str, float]:
        """Percentiles of the per-step host-side time (seconds) — the
        Python the device pipeline waits on between dispatches."""
        return self._pctl("host_step_s", qs)

    # -- derived views -----------------------------------------------------

    def _values(self, name: str) -> List[float]:
        return self.metrics.values(f"serving/{name}")

    def tokens_per_sec(self) -> float:
        """Aggregate generated-token throughput over the engine's active
        window (first step → last step)."""
        total, _ = self.metrics.get("serving/tokens_out")
        if self._t_start is None or self._t_last is None \
                or self._t_last <= self._t_start:
            return 0.0
        return total / (self._t_last - self._t_start)

    def _pctl(self, name: str, qs) -> Dict[str, float]:
        """Percentiles of one counter's raw samples (0.0 when empty)."""
        import numpy as np

        vals = self._values(name)
        if not vals:
            return {f"p{q}": 0.0 for q in qs}
        arr = np.asarray(vals)
        return {f"p{q}": float(np.percentile(arr, q)) for q in qs}

    @staticmethod
    def _window_stats(vals) -> Dict[str, float]:
        """mean/p50/p99 over one bounded sample window — the shared
        math behind :meth:`window` and the feasibility estimators."""
        import numpy as np

        arr = np.asarray(vals, dtype=float)
        return {"n": int(arr.size),
                "mean": float(arr.mean()),
                "p50": float(np.percentile(arr, 50)),
                "p99": float(np.percentile(arr, 99))}

    def window(self, name: str, n: int) -> Optional[Dict[str, float]]:
        """Rolling-window view of one serving counter: mean/p50/p99
        (plus the actual sample count ``n``) over the LAST ``n``
        samples of ``serving/<name>`` — the bounded-recency signal the
        autopilot's controllers read. A whole-run percentile goes
        stale across traffic phases (an hour of lull poisons the
        burst's p99 for the rest of the run); a window follows the
        phase. None before the first sample, so controllers never act
        on a guess."""
        if n < 1:
            raise ValueError(f"window size must be >= 1, got {n}")
        vals = self._values(name)
        if not vals:
            return None
        return self._window_stats(vals[-int(n):])

    def ttft_percentiles(self, qs=(50, 90, 99)) -> Dict[str, float]:
        return self._pctl("ttft_s", qs)

    def summary(self) -> Dict[str, float]:
        """Means of every serving counter plus derived throughput/TTFT
        percentiles — one flat dict for logging/asserting."""
        out = {k: v for k, v in self.metrics.summary().items()
               if k.startswith("serving/")}
        out["serving/tokens_per_sec"] = self.tokens_per_sec()
        n_look, _ = self.metrics.get("serving/prefix_lookups")
        if n_look:
            n_hit, _ = self.metrics.get("serving/prefix_hits")
            out["serving/prefix_hit_rate"] = n_hit / n_look
        n_s, _ = self.metrics.get("serving/rows_sampled")
        n_g, _ = self.metrics.get("serving/rows_greedy")
        if n_s + n_g > 0:
            out["serving/sampled_row_frac"] = n_s / (n_s + n_g)
        # count-like resilience counters surface as SUMS (the backing
        # Metrics means each add-series; "preempted 0.97 mean" is
        # useless where "preempted 13 rows" is the operational number)
        for name in ("preempted", "shed", "deadline_missed", "retries",
                     "recovered_rows", "degraded", "degrade_restored",
                     "actuations", "finished_in_slo",
                     "infeasible", "chunks", "chunk_tokens",
                     "handoffs", "transfer_bytes",
                     "pool_deaths", "failovers", "migrated_rows",
                     "replayed_rows", "transfer_timeouts",
                     "autoscale_up", "autoscale_down",
                     "spills", "fetches", "spill_bytes", "fetch_bytes",
                     "tier_evictions", "resumed_without_prefill",
                     *(f"finish_{r}" for r in sorted(self.FINISH_REASONS))):
            total, n = self.metrics.get(f"serving/{name}")
            if n:
                out[f"serving/{name}"] = total
        n_sub, _ = self.metrics.get("serving/submitted")
        if n_sub:
            n_slo, _ = self.metrics.get("serving/finished_in_slo")
            # goodput: requests that finished USEFULLY (met their
            # deadline; no-deadline finishes count as met, error
            # finishes never do) over everything submitted —
            # shed/dropped/late/errored all count against it
            out["serving/goodput"] = n_slo / n_sub
        n_draft, _ = self.metrics.get("serving/draft_tokens")
        n_acc, _ = self.metrics.get("serving/accepted_tokens")
        n_rows, _ = self.metrics.get("serving/spec_rows")
        if n_draft:
            out["serving/accept_rate"] = n_acc / n_draft
        if n_rows:
            out["serving/tokens_per_step"] = (n_acc + n_rows) / n_rows
        _, n_gap = self.metrics.get("serving/decode_gap_s")
        if n_gap:
            out["serving/decode_gap_p99_s"] = \
                self.decode_gap_percentiles()["p99"]
        n_hand, n_hand_n = self.metrics.get("serving/handoffs")
        if n_hand_n:
            nb, _ = self.metrics.get("serving/transfer_bytes")
            out["serving/transfer_bytes_per_handoff"] = nb / n_hand
            out["serving/transfer_p99_s"] = \
                self.transfer_percentiles()["p99"]
        n_sp, n_sp_n = self.metrics.get("serving/spills")
        if n_sp_n:
            sb, _ = self.metrics.get("serving/spill_bytes")
            out["serving/spill_bytes_per_row"] = sb / n_sp
        _, n_fe = self.metrics.get("serving/fetch_s")
        if n_fe:
            out["serving/fetch_p99_s"] = self.fetch_percentiles()["p99"]
        _, n_fo = self.metrics.get("serving/failover_s")
        if n_fo:
            fp = self.failover_percentiles()
            out["serving/failover_p50_s"] = fp["p50"]
            out["serving/failover_p99_s"] = fp["p99"]
        _, n_host = self.metrics.get("serving/host_step_s")
        if n_host:
            hp = self.host_step_percentiles()
            out["serving/host_step_p50_s"] = hp["p50"]
            out["serving/host_step_p99_s"] = hp["p99"]
        for k, v in self.ttft_percentiles().items():
            out[f"serving/ttft_{k}_s"] = v
        return out
