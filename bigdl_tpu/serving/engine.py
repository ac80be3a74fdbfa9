"""Continuous-batching serving engine for :func:`TransformerLM` and any
other model family that brings its decode and prefill programs through
``serving/family.py`` (``models/falcon_h1.py``: recurrent state beside
K/V in the same pool; it refuses, by name at construction, the prefix
cache, speculation, adapters, int8 K/V, a mesh, chunked and per-request
admission and the host tier).

``generate()`` runs one request per call with a private KV carry and
pays the full weight-read bandwidth per token for a single row.
:class:`ServingEngine` instead serves MANY independent requests from one
pooled cache (:class:`bigdl_tpu.serving.kv_pool.KVPool`) stepped by ONE
compiled per-row-position decode program
(:func:`bigdl_tpu.models.transformer.make_batch_decode_step`):

* requests are ``submit()``-ed at any time and queue FIFO;
* before every decode step the scheduler admits waiting requests into
  free slots (continuous batching: admission happens MID-FLIGHT,
  between decode steps of the requests already running). The DEFAULT
  admission path (``admission="batched"``) groups the admitted prompts
  into power-of-two length buckets and ingests each bucket in ONE
  masked multi-row :func:`make_batch_prefill_step` call, row-scattering
  every result into the pooled cache — ragged prompt lengths share a
  BOUNDED set of compiled prefill programs instead of compiling per
  novel length mid-admission (see ``serving/admission.py``).
  ``admission="per_request"`` keeps PR 1's one-at-a-time B=1
  :func:`make_prefill_step` path (the parity baseline), and
  ``admission="chunked"`` STREAMS prompts in as budget-bounded
  suffix-continuation chunks interleaved with decode so long-prompt
  bursts never stall in-flight rows (``serving/chunked.py``);
* an optional :class:`bigdl_tpu.serving.prefix_cache.PrefixCache`
  (``prefix_cache=True`` or an instance) reuses prefilled K/V across
  requests sharing a token prefix — a full hit clones cached state
  straight into the pool, a partial hit prefills only the suffix;
* every ``step()`` decodes one token for ALL active rows at once —
  decode is weight-read-bound, so a batched step costs roughly what a
  single-row step costs and aggregate tokens/sec scales with occupancy
  (measured in benchmarks/serving_bench.py);
* rows are evicted at EOS or ``max_new_tokens`` and their slot returns
  to the free list for the next admission.

Decoding is SAMPLED per row (``bigdl_tpu.serving.sampling``): every
request carries its own :class:`~bigdl_tpu.serving.sampling.
SamplingParams` (temperature, top-k/top-p, penalties, seed, stop sets)
and its own ``jax.random`` lane in the pooled carry, and ONE compiled
step samples all rows at once — the knobs are per-row runtime arrays,
so greedy and sampled rows mix freely in a batch and changing knobs
never recompiles. The default params are greedy (``temperature=0``
degrades exactly to argmax inside the same program), and the pooled
step computes the same math as the single-request step, so default
engine outputs match per-request ``generate(..., temperature=0)`` token
for token — pinned by tests/test_serving.py for plain and bf16-serving
params; a fixed-seed sampled request reproduces its stream across
batching, slot placement, and eviction/readmission (pinned by
tests/test_serving_sampling.py). (The pooled and single-request steps
are numerically equal only to float round-off — different batch shapes
can reorder XLA reductions — so a checkpoint whose top-2 logprobs tie
within ~1e-5 could in principle break a tie differently; the parity
tests pin the realistic case, not a bitwise guarantee.) Stop-SEQUENCE
matching runs on host against each row's token tail; stop TOKEN ids
(incl. the per-request ``eos_id``) evict the row the step they appear,
with ``min_tokens`` banning them on device until the floor is met.

The jitted step/prefill functions come from the per-(model, dtype) step
cache (``get_batch_decode_step`` / ``get_prefill_step``), so several
engines over one model — or an engine plus ad-hoc ``generate()`` calls —
share compilations; prompt-length buckets re-trace once each inside the
cached prefill's own jit cache.
"""

from __future__ import annotations

import contextlib
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np

from bigdl_tpu.serving.faults import (
    FaultError, WatchdogConfig, default_clock,
)
from bigdl_tpu.serving.fences import fence
from bigdl_tpu.serving.kv_pool import KVPool
from bigdl_tpu.serving.metrics import ServingMetrics
from bigdl_tpu.serving.sampling import (
    SamplingParams, advance_lane, knob_row_values, make_knob_rows,
    match_stop_sequences, wide_rows,
)
from bigdl_tpu.serving.scheduler import (
    FINISHED, SHED, WAITING, Request, Scheduler,
)


# THE depth of the dispatch-ahead window every engine runs at when the
# caller passes nothing: ONE decode program stays in flight behind the
# host (launch step k+1 on step k's device token, then read step k
# back). Chosen on the chip (PERF.md section 6, PR 31): depth 2 read no
# better than 1 in either serving cell — the host's whole per-step cost
# is a quarter of the shorter decode program, and a deeper window only
# adds discarded overshoot steps at every finish.
DISPATCH_AHEAD = 1


class _InFlight:
    """One dispatched-but-not-yet-fenced decode step in the engine's
    dispatch-ahead window: the device token/logprob handles the delayed
    consumer will read back through the decode fence, plus the host
    facts frozen
    at dispatch time that its bookkeeping needs (the row snapshot, the
    pre-dispatch clock read the watchdog's elapsed is measured from,
    and whether rows were already in flight — the decode-gap
    anchor), and what identifies and describes the dispatch on every
    span and series it causes: its number, whether it was chained, the
    prefill launches since the dispatch before it, and the host-state
    samples of the rows it reads (``on_step``'s, taken at dispatch)."""

    __slots__ = ("tok", "chosen", "active", "active_dev", "rows", "t0",
                 "had_running", "extra", "seq", "chained", "waves", "host")

    def __init__(self, tok, chosen, active, active_dev, rows, t0,
                 had_running, extra, seq, chained, waves, host):
        self.tok = tok                  # device handle: next 0-based ids
        self.chosen = chosen            # device handle: chosen logprobs
        # device handles a family's step returns after the carry (an
        # expert family's per-expert token counts): read back at the
        # SAME fence as the tokens
        self.extra = extra
        self.active = active            # host bool mask at dispatch
        self.active_dev = active_dev    # the mask's PLACED device twin
        self.rows = rows                # {slot: Request} at dispatch
        self.t0 = t0                    # clock at dispatch (pre-launch)
        self.had_running = had_running  # decode-gap anchor flag
        self.seq = seq                  # the dispatch's number
        self.chained = chained          # launched on the in-flight token
        self.waves = waves              # prefill launches since seq - 1
        self.host = host                # _host_state() at dispatch


class ServingEngine:
    """Continuous-batching per-row-sampled decoder over a pooled KV cache.

    ``n_slots`` is the fixed decode capacity (concurrent requests);
    ``compute_dtype`` is the serving precision knob (weights + KV cache,
    e.g. ``jnp.bfloat16`` — scores and log-softmax stay fp32);
    ``policy`` is the admission policy (``"prefill_priority"`` = admit
    into freed rows before every step, ``"fifo"`` = refill only after
    the running batch drains, ``"priority"`` = continuous refill in
    (priority, deadline, arrival) order with loss-free preemption —
    see ``serving.scheduler`` and the resilience notes below);
    ``admission`` picks the prompt-ingestion pipeline: ``"batched"``
    (default — bucketed multi-row masked prefill, bounded compile set),
    ``"chunked"`` (streaming admission — requests bind a KV slot
    immediately and their prompts stream in as suffix-continuation
    chunks of at most ``chunk_budget`` tokens per step, interleaved
    with decode so an arrival burst never stalls in-flight rows for a
    whole admission wave; token-identical to batched, zero extra
    decode compiles — ``serving/chunked.py``),
    or ``"per_request"`` (PR 1's B=1-per-admission baseline);
    ``chunk_budget`` is the chunked pump's per-step prompt-token budget
    (default 32; only valid with ``admission="chunked"``);
    ``deadline_feasibility`` turns on feasibility ADMISSION CONTROL:
    waiting requests whose remaining DECLARED token budget
    (``max_new_tokens`` less what is already emitted — the pessimistic
    bound; a request that would stop early at EOS under a generous cap
    is shed conservatively, so deadline-carrying callers should set
    honest caps) cannot fit inside their
    deadline at the measured per-token service rate (the
    ``decode_step_s`` median over the measured tokens-per-step — so
    speculative engines' multi-token super-steps don't overstate
    service time) are dropped at
    admission with ``finish_reason="infeasible"`` (counted as shed +
    deadline-missed) instead of burning decode steps on a guaranteed
    SLO miss — the EDF-with-admission-control step beyond dropping
    only already-expired work;
    ``prefix_cache`` enables shared-prefix K/V reuse under batched
    admission: ``True`` for a default-capacity
    :class:`~bigdl_tpu.serving.prefix_cache.PrefixCache`, or pass a
    configured instance (``None`` = off);
    ``keep_finished`` bounds the finished-request ledger: only the N
    most recently finished requests stay retrievable via ``result()``
    (older ones are evicted oldest-first), so a long-lived engine under
    heavy traffic doesn't grow without bound. ``None`` keeps everything
    (then ``pop_result()`` is the caller's eviction lever);
    ``seed`` is the engine's base RNG seed: requests whose
    ``SamplingParams.seed`` is None draw from a lane folded from this
    base and their request id (fresh per request); an explicit
    per-request seed pins the lane regardless of the engine seed;
    ``mesh``/``parallelism`` swap in the SHARDED serving plane
    (``serving/sharded.py``): pass a ``jax.sharding.Mesh`` with
    ``data``/``model`` axes, or a ``{"data": N, "model": M}`` dict to
    build one from the host's devices. Slot rows shard over ``data``
    (token-identical to the unsharded engine — same per-row math, SPMD-
    partitioned), attention heads + MLP hidden over ``model``
    (Megatron two-psums-per-block under ``compat.shard_map``; equal to
    round-off). Still ONE compiled decode program per engine;
    ``kv_dtype="int8"`` stores the pooled K/V caches as per-(slot,
    head)-scaled int8 — half the KV bytes per slot, so an HBM budget
    holds ~2x the concurrent slots — with dequantization fused into the
    attention read (the Pallas pooled decode kernel on TPU, its jnp
    reference on CPU; ``ops/decode_attention.py``). Greedy outputs are
    parity-pinned against the float-KV engine and quantization adds
    ZERO decode compiles (tests/test_serving_kv_quant.py); default
    (None) follows ``compute_dtype``;
    ``speculative`` turns on DRAFT-AND-VERIFY decoding
    (``serving/speculative.py``): pass a
    :class:`~bigdl_tpu.serving.speculative.SpeculativeConfig` (or a
    bare draft model) and every step becomes a super-step — a small
    draft proposes up to ``k`` tokens per row, ONE fixed-width batched
    verify program (structurally the masked multi-row prefill) scores
    them all, and each row advances by the confirmed count (1..k+1
    tokens per step). Greedy output stays token-identical to the plain
    engine, fixed-seed sampled streams replay exactly (verification
    draws ride the per-slot RNG lanes), per-row draft budgets are
    runtime data of the one program (``submit(..., draft_tokens=0)``
    rows run as plain decode), and the draft's KV carry rides the same
    pool slots (tests/test_serving_speculative.py).

    RESILIENCE knobs (docs/serving.md "Operating under faults and
    overload"; all host-side or per-row runtime data — none of them
    adds a compiled program):

    * ``policy="priority"`` orders the queue by (priority DESC,
      deadline ASC, arrival) and enables loss-free PREEMPTION
      (``preemption=False`` disables it): when waiting requests
      outrank the lowest-priority running row and no slot is free,
      that row is evicted — its KV slice stashed on the request (and
      shared into the prefix cache when one is attached) — and
      readmitted later byte-identically (RNG lanes are request-keyed
      and recomputable, penalty counts rebuild from the emitted
      tokens);
    * ``max_queue`` bounds the waiting BACKLOG (queue depth beyond
      what the pool's free slots will absorb at the next admission —
      an idle engine with free capacity never sheds): a ``submit()``
      arriving past the bound is SHED — it lands in the finished
      ledger with ``finish_reason="shed"`` and empty output instead
      of raising (backpressure the caller can observe per request).
      WAITING requests whose ``deadline_s`` expires before admission
      are deadline-dropped the same way
      (``finish_reason="deadline"``);
    * ``degrade_at`` is the pressure threshold (queue depth at
      admission) beyond which a request's ``submit(...,
      degrade=Degrade(...))`` knobs apply — capping
      ``max_new_tokens`` and/or disabling speculation for that
      request (graceful degradation instead of shedding);
    * ``watchdog`` (a :class:`~bigdl_tpu.serving.faults.
      WatchdogConfig`) bounds step time and per-request retries: a
      decode/verify dispatch that raises, returns non-finite or
      out-of-range outputs, or exceeds ``step_timeout_s`` on the
      engine's clock is treated as FAILED — its outputs are
      discarded, its rows evicted and replayed from the prompt +
      emitted tokens (byte-identical streams, pinned by
      tests/test_serving_faults.py) — and a request evicted more than
      ``max_retries`` times finishes with ``finish_reason="error"``
      so a persistent fault fails requests instead of wedging the
      engine;
    * ``faults`` (a :class:`~bigdl_tpu.serving.faults.FaultInjector`)
      deterministically injects step failures / garbage outputs /
      stalls / admission errors at the engine's dispatch sites — the
      test harness for all of the above; ``clock`` swaps the engine's
      time source (a :class:`~bigdl_tpu.serving.faults.VirtualClock`
      lets deadline and stall tests run without sleeping);
    * ``autopilot`` (a :class:`~bigdl_tpu.serving.autopilot.Autopilot`)
      closes the control loop: sampled once at the end of every
      ``step()`` on the engine clock, it drives ``chunk_budget``,
      per-class ``Degrade`` apply/restore, and the speculative draft
      cap from windowed metrics through the declared actuator bus,
      folds the measured service-time estimate into the priority
      key, and preempts FOR deadlines (a short-deadline feasible
      waiter evicts the longest-slack running row rather than miss).
      Every actuation is host bookkeeping over per-row runtime data —
      the compiled-program set is untouched.

    ``dispatch_ahead`` is the depth W of the DISPATCH-AHEAD window
    (docs/serving.md "Dispatch-ahead decode"), by default
    :data:`DISPATCH_AHEAD` = 1: a plain decode step LAUNCHES program
    k+1 on program k's device token and only then reads program k
    back, so the host's per-step work hides behind the device. The
    token of program k is therefore returned by the ``step()`` that
    launched k+1 — the first ``step()`` after an admission or any
    other flush can return ``{}`` while rows are running — and a row
    that finishes has been stepped once more by the program in
    flight (that token is thrown away and counted nowhere). Anything
    the in-flight program assumed changing (admission, a finish, an
    eviction, a ban flip, a constraint, a preemption spill, a fault)
    flushes the window first; streams are byte-identical at every W
    (tests/test_serving_async.py, tests/test_falcon_h1.py). ``0`` is
    the classic dispatch-fence-bookkeep step, kept as the tests'
    oracle. A speculative engine keeps its verify fence inline and
    its window empty whatever the depth.
    """

    def __init__(self, model, n_slots: int = 8, compute_dtype=None,
                 policy: str = "prefill_priority",
                 metrics: Optional[ServingMetrics] = None,
                 admission: str = "batched",
                 chunk_budget: Optional[int] = None,
                 deadline_feasibility: bool = False,
                 prefix_cache=None,
                 keep_finished: Optional[int] = None,
                 seed: int = 0,
                 mesh=None, parallelism=None,
                 kv_dtype: Optional[str] = None,
                 speculative=None,
                 clock=None,
                 max_queue: Optional[int] = None,
                 degrade_at: Optional[int] = None,
                 preemption: Optional[bool] = None,
                 watchdog: Optional[WatchdogConfig] = None,
                 faults=None,
                 adapters=None,
                 tier=None,
                 autopilot=None,
                 dispatch_ahead: int = DISPATCH_AHEAD) -> None:
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.serving.admission import AdmissionController
        from bigdl_tpu.serving.family import check_options, family_of
        from bigdl_tpu.serving.prefix_cache import PrefixCache

        if admission not in ("batched", "per_request", "chunked"):
            raise ValueError(
                f"unknown admission mode {admission!r} "
                "(one of 'batched', 'per_request', 'chunked')")
        # THE seam to the model's family (serving/family.py): its
        # programs, its carry layout, and the options it refuses
        family = self._family = family_of(model)
        check_options(
            family, prefix_cache=prefix_cache, speculative=speculative,
            adapters=adapters, mesh=mesh, parallelism=parallelism,
            tier=tier, kv_dtype=kv_dtype if kv_dtype == "int8" else None,
            admission=admission if admission != "batched" else None)
        if chunk_budget is not None:
            if admission != "chunked":
                raise ValueError(
                    "chunk_budget requires admission='chunked' — it is "
                    "the streaming pump's per-step token budget")
            if int(chunk_budget) < 1:
                raise ValueError(
                    f"chunk_budget must be >= 1, got {chunk_budget}")
        if keep_finished is not None and keep_finished < 0:
            raise ValueError(
                f"keep_finished must be >= 0 or None, got {keep_finished}")
        if max_queue is not None and max_queue < 0:
            raise ValueError(
                f"max_queue must be >= 0 or None, got {max_queue}")
        if degrade_at is not None and degrade_at < 0:
            raise ValueError(
                f"degrade_at must be >= 0 or None, got {degrade_at}")
        if int(dispatch_ahead) < 0:
            raise ValueError(
                f"dispatch_ahead must be >= 0, got {dispatch_ahead} "
                f"(default {DISPATCH_AHEAD}: W = keep up to W decode "
                "dispatches in flight behind the fence; 0 = consume each "
                "decode readback in the step that launched it)")
        if preemption and policy != "priority":
            raise ValueError(
                "preemption=True requires policy='priority' — victim "
                "selection is a priority-order decision")
        # multi-tenant LoRA (serving/lora.py): an AdapterBank makes the
        # compiled steps gather per-row low-rank factors by the rows'
        # adapter ids — runtime data, one program for mixed traffic.
        # The per-request B=1 prefill path predates the batched row
        # convention the adapter arguments ride, so it stays base-only.
        if adapters is not None and admission == "per_request":
            raise ValueError(
                "adapters require admission='batched' or 'chunked' — "
                "the per-request prefill has no adapter arguments")
        self.adapters = adapters
        self._adapter_spec = None if adapters is None else adapters.spec
        # device-side bank cache, invalidated by the bank's version
        # counter (alloc/free mutate the host arrays; steady-state
        # decode reuses the placed arrays)
        self._bank_device = None
        self._bank_version = None
        # resilience wiring: the engine's ONE time source (a
        # VirtualClock here lets deadline/stall tests move time without
        # sleeping), the step watchdog, and the optional deterministic
        # fault injector the dispatch sites consult
        self._clock = clock if clock is not None else default_clock
        self.watchdog = watchdog if watchdog is not None \
            else WatchdogConfig()
        self._faults = faults
        self.max_queue = max_queue
        self.degrade_at = degrade_at
        # preemption defaults ON for the priority policy (it is the
        # policy's point), and is meaningless elsewhere
        self.preemption = (policy == "priority") if preemption is None \
            else bool(preemption)
        model._ensure_params()
        self.model = model
        self.max_len = family.max_len
        self._vocab = family.vocab               # step-health token range
        self.compute_dtype = compute_dtype
        # KV storage format: None follows compute_dtype (the status quo);
        # "int8" switches the pooled cache to the quantized layout
        # (per-(slot, head)-scaled int8 — half the KV bytes, double the
        # slots at equal HBM; see docs/serving.md "Quantized KV cache").
        # Spelling out "fp32"/"bf16" is allowed but must AGREE with
        # compute_dtype — the float cache always stores the serving
        # dtype, and a silent disagreement would misreport capacity.
        # normalize the dtype spelling: compute_dtype may arrive as the
        # jnp type, a np.dtype, or a string ("bfloat16") — all serve
        # identically, so all must classify identically here. The name
        # must match KVPool's stored-dtype mapping for EVERY float
        # dtype (fp16 engines serve fine and their default must keep
        # constructing), not just the two canonical serving formats —
        # so uncanonical dtypes keep their numpy name ("float16").
        stored = jnp.zeros((), compute_dtype or jnp.float32).dtype.name
        float_kv = {"float32": "fp32", "bfloat16": "bf16"}.get(stored,
                                                               stored)
        if kv_dtype is None:
            kv_dtype = float_kv
        elif kv_dtype not in ("fp32", "bf16", "int8"):
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r} "
                "(one of 'fp32', 'bf16', 'int8')")
        if kv_dtype != "int8" and kv_dtype != float_kv:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} conflicts with "
                f"compute_dtype={compute_dtype!r} (the float KV cache "
                f"stores the serving dtype, {float_kv!r} here) — pick "
                "kv_dtype='int8' or drop the knob")
        self.kv_dtype = kv_dtype
        kv_quant = kv_dtype == "int8"
        # the sharded serving plane (serving/sharded.py): a mesh or a
        # {"data": N, "model": M} parallelism dict swaps the pooled
        # tensors onto a device mesh — slot rows shard over "data"
        # (token-identical: pure SPMD partitioning of the same per-row
        # math), weights/KV-heads over "model" (Megatron layout under
        # compat.shard_map). None/None is the stock single-device plane.
        if mesh is not None or parallelism is not None:
            from bigdl_tpu.serving.sharded import ShardPlane

            self._plane = ShardPlane(mesh=mesh, parallelism=parallelism)
            self.mesh = self._plane.mesh
        else:
            self._plane = None
            self.mesh = None
        # weights as resident device buffers in the serving dtype
        # (runtime arguments — never baked into the compiled programs);
        # tensor-parallel planes pre-shard them over the model axis
        sp = family.params(compute_dtype)
        self.params = (jax.device_put(sp) if self._plane is None
                       else self._plane.place_params(model, sp))
        # the SAMPLED pooled step is the only decode program: greedy
        # requests are temperature=0 rows of the same compiled step, so
        # greedy-only and mixed traffic share one program (pinned by the
        # compile-count guards in tests/test_serving_sampling.py and
        # tests/test_serving_sharded.py). A SPECULATIVE engine swaps in
        # the fixed-width batched VERIFY step instead (serving/
        # speculative.py) — still exactly one target-side program, with
        # per-row draft lengths as runtime data (length-1 rows ARE plain
        # decode), and a layout-identical pooled carry.
        tp = self._plane is not None and self._plane.tensor_parallel
        if speculative is None:
            self._spec = None
            # the decode step takes the mesh of a data-only plane
            # too: its attention kernel has to know the rows' axis
            self._step_fn, pool_init = family.decode_step(
                compute_dtype, mesh=self.mesh, kv_quant=kv_quant,
                adapter=self._adapter_spec)
        else:
            from bigdl_tpu.serving.speculative import Speculator

            self._spec = Speculator(self, speculative,
                                    mesh=self.mesh if tp else None,
                                    kv_quant=kv_quant)
            self._step_fn = None
            pool_init = self._spec.pool_init
        self._pool_init = pool_init
        self.pool = (KVPool(pool_init, n_slots, kv_dtype=kv_dtype,
                            max_len=family.max_len)
                     if self._plane is None
                     else self._plane.make_pool(model, pool_init, n_slots,
                                                kv_quant=kv_quant,
                                                kv_dtype=kv_dtype))
        if self._spec is not None:
            # the draft model's pooled carry rides the same slots
            self._spec.attach_pool(self.pool)
        # host spill tier (serving/kv_tier.py): True builds a default
        # MemBlockStore-backed TieredKVStore; an instance is shared
        # as-is (the disaggregated plane passes ONE tier to every
        # pool); None keeps the legacy in-memory stash semantics
        # (resume_carry blobs). With a tier, preemption spills rows to
        # host RAM under its byte budget, readmission fetches them
        # back currency-checked, and the scheduler's victim selection
        # goes cold-first (LRU over last-decoded step).
        if tier is True:
            from bigdl_tpu.serving.kv_tier import TieredKVStore

            tier = TieredKVStore()
        self.tier = tier or None
        self.scheduler = Scheduler(policy, tier=self.tier)
        self.metrics = metrics if metrics is not None else ServingMetrics()
        # spans time on the engine's ONE clock, whoever built the metrics
        self.metrics.metrics.clock = self._clock
        # step counter and the request ids the current step bound: the
        # serving.step / serving.admit spans' arguments
        self._n_steps = 0
        self._bound: List[int] = []
        # decode (or verify) dispatch counter, the ``seq`` every span a
        # dispatch causes carries, and the prefill launch count as of
        # the newest dispatch (see _next_dispatch)
        self._n_dispatches = 0
        self._launches_seen = 0
        if self.tier is not None:
            self.tier.attach_metrics(self.metrics, clock=self._clock)
        if self._plane is not None:
            self.metrics.set_mesh_shape(self._plane.data_shards,
                                        self._plane.model_shards)
        # KV-format observability: bytes one slot owns + the derived
        # effective-capacity number (slots a GiB of HBM would hold)
        self.metrics.set_kv_format(kv_dtype, self.pool.kv_bytes_per_slot,
                                   self.pool.state_bytes_per_slot,
                                   self.pool.kv_position_bytes)
        self.admission = admission
        self.keep_finished = keep_finished
        self.seed = int(seed)
        # host-side per-slot knob rows (greedy no-op state) + which
        # slots have been configured for their current occupant
        # the allow mask (constrained decoding — serving/constrain.py)
        # always rides: an all-True row is the sampler identity, and
        # carrying it unconditionally keeps the knob dict's structure
        # one shape for plain / constrained / sharded engines alike
        self._knobs = make_knob_rows(n_slots, vocab=self._vocab)
        # live constraint cursors by slot (host-side; rebuilt from
        # (request.constraint, request.output) at every (re)admission —
        # never checkpointed, the replay rule constrain.py states)
        self._constraints: Dict[int, object] = {}
        self._ban_base = np.zeros((n_slots,), bool)
        self._configured: set = set()
        # slots whose occupant arrived as a FULL row_state payload
        # (preemption resume or a disaggregated handoff): their RNG
        # lane / penalty counts / draft cache were restored verbatim,
        # so _configure_slot sets knobs only and skips the device
        # reseeding. Torn down with _configured everywhere a slot is.
        self._restored: set = set()
        # device-side knob cache: knobs only change at admission or a
        # min-tokens ban flip, so the steady-state decode loop reuses
        # the same device arrays instead of re-uploading every step
        self._knobs_device = None
        # dispatch-ahead window (docs/serving.md "Dispatch-ahead
        # decode"): up to ``dispatch_ahead`` decode dispatches stay in
        # flight BEHIND the one being consumed, each chained on the
        # previous dispatch's device token handle, so the decode-fence
        # readback of step N overlaps the device work of steps
        # N+1..N+W. The deque holds _InFlight entries oldest-first; the
        # delayed consumer (_consume_window) pops them. The default is
        # DISPATCH_AHEAD (one program in flight: launch k+1, then
        # consume k); W=0 keeps the deque depth at zero across step()
        # calls — dispatch-then-consume within one step, the pre-window
        # engine and the W-sweep tests' oracle.
        self.dispatch_ahead = int(dispatch_ahead)
        self._window: deque = deque()
        # engine-clock time the newest consumed entry's fence returned:
        # an entry chained behind it had the device only from then on
        # (see _consume_window's service sample)
        self._last_fence_t = float("-inf")
        # watchdog cold-start grace: the step timeout arms only after
        # one healthy step has completed (see _timed_out)
        self._warm = False
        # feasibility admission control (EDF-with-admission-control):
        # when on, _admit deadline-drops WAITING requests the running
        # decode_step_s median says cannot finish inside their deadline —
        # not just those already expired (finish_reason="infeasible")
        self.deadline_feasibility = bool(deadline_feasibility)
        # decode-stall bookkeeping: wall time of the last completed
        # decode/verify dispatch, None while no rows are in flight —
        # the gap between consecutive dispatches over a live batch is
        # the stall signal chunked admission bounds (serving/
        # decode_gap_s)
        self._last_decode_end: Optional[float] = None
        if admission in ("batched", "chunked"):
            # the tensor-parallel prefill shares the mesh (and must name
            # the sampling carry leaves in its shard_map specs); data-
            # only planes keep the stock prefill — its output rows
            # reshard into the sharded pool through the scatter
            self._batch_prefill_fn = family.batch_prefill_step(
                compute_dtype, mesh=self.mesh if tp else None,
                carry_sampling=tp, kv_quant=kv_quant,
                adapter=self._adapter_spec)
            # True -> default cache, False/None -> off, else an instance
            self.prefix_cache = (PrefixCache() if prefix_cache is True
                                 else (prefix_cache or None))
            # tier-backed prefix spill: capacity evictions demote to
            # the host tier and lookups promote back (kv_tier.py); an
            # explicitly pre-wired cache keeps its own tier
            if (self.tier is not None and self.prefix_cache is not None
                    and self.prefix_cache.tier is None):
                self.prefix_cache.tier = self.tier
            if admission == "chunked":
                from bigdl_tpu.serving.chunked import (
                    ChunkedAdmissionController,
                )

                self.admitter = ChunkedAdmissionController(
                    self, chunk_budget=chunk_budget or 32,
                    prefix_cache=self.prefix_cache)
            else:
                self.admitter = AdmissionController(
                    self, prefix_cache=self.prefix_cache)
        else:
            if prefix_cache:
                raise ValueError(
                    "prefix_cache requires admission='batched' or "
                    "'chunked' (the per-request prefill cannot continue "
                    "from a cached carry)")
            self.prefix_cache = None
            self.admitter = None
            self._prefill_fn = family.prefill_step(compute_dtype,
                                                   kv_quant=kv_quant)
            # ONE fresh B=1 carry for prefill, built once and reused for
            # every admission (prefill returns a new carry; jax arrays
            # are immutable, so sharing the zero input is free — at 137M
            # scale a per-admission rebuild would be ~12 MB of pure
            # allocation churn). pool_init's carry layout is
            # make_decode_step's, so n_slots=1 IS the single-request
            # carry.
            self._zero_carry1 = pool_init(1)
        self._next_id = 0
        self._finished: Dict[int, Request] = {}
        # the SLO autopilot (serving/autopilot.py): an engine-wide
        # ceiling on the speculative draft count (runtime data the
        # super-step's _draft_budget reads — never a recompile), and
        # the closed control loop itself, sampled once at the end of
        # every step() on the engine clock. attach() binds the
        # actuator bus to this engine and folds the measured
        # service-time estimate into the scheduler's priority key.
        self.draft_cap: Optional[int] = None
        self.autopilot = autopilot or None
        if self.autopilot is not None:
            self.autopilot.attach(self)

    # -- request surface ---------------------------------------------------

    def submit(self, prompt_ids: Sequence[int], max_new_tokens: int = 32,
               eos_id: int = -1, sampling: Optional[SamplingParams] = None,
               draft_tokens: Optional[int] = None, priority: int = 0,
               deadline_s: Optional[float] = None, degrade=None,
               adapter_id: int = 0, constraint=None) -> int:
        """Queue one generation request (1-based prompt ids, like
        ``generate()``); returns its request id. Raises if the request
        could ever overflow the cache (same ``max_len`` guard as
        ``generate()``).

        ``eos_id`` is the request's PRIVATE eos (1-based; -1 = none) —
        different requests in the same batch may stop on different
        tokens; it joins ``sampling.stop_token_ids`` in the min-tokens
        device ban. ``sampling`` carries the request's
        :class:`~bigdl_tpu.serving.sampling.SamplingParams` (None =
        greedy defaults, the pre-sampling engine behavior);
        ``sampling.max_tokens`` (when set) overrides
        ``max_new_tokens``; ``draft_tokens`` is the request's
        speculative-decoding budget HINT (None = the engine's configured
        draft count, 0 = plain decode for this request, n = at most n
        drafts per super-step, clamped to the engine's ``k``; ignored
        by non-speculative engines, so traces stay portable across
        engine configs).

        Resilience knobs (ignored semantically outside their engine
        configs, so traces stay portable): ``priority`` orders the
        queue and selects preemption victims under ``policy=
        "priority"`` (higher admits first); ``deadline_s`` is the
        request's completion SLO in seconds after submit (expired
        WAITING requests are dropped with ``finish_reason="deadline"``,
        late finishes count against ``serving/goodput``); ``degrade``
        is a :class:`~bigdl_tpu.serving.admission.Degrade` applied at
        admission when the engine is under pressure. When the engine's
        ``max_queue`` is set and the waiting BACKLOG (queue depth minus
        free slots) has reached it, the request is SHED instead of
        queued: it lands in the finished ledger with
        ``finish_reason="shed"`` and empty output — still returns the
        request id, so callers observe backpressure per request rather
        than as an exception.

        Multi-tenant knobs: ``adapter_id`` selects the request's LoRA
        adapter in the engine's :class:`~bigdl_tpu.serving.lora.
        AdapterBank` (0 = the null adapter ≡ base model; nonzero ids
        must be live in the bank, and the engine RETAINS the slot for
        the request's lifetime so a tenant unload cannot recycle
        factors under an in-flight row). On a SPECULATIVE engine a
        nonzero ``adapter_id`` requires ``draft_tokens=0``: the draft
        model carries no adapter factors, and scoring base-model drafts
        against an adapted target would silently corrupt accept-rate
        accounting — pinned by tests/test_serving_lora.py.
        ``constraint`` is an optional
        :class:`~bigdl_tpu.serving.constrain.TokenDFA`: the engine
        advances its cursor per emitted token and masks the row's
        sampler to the tokens the automaton allows (the per-row
        ``allow`` knob); constrained rows on a speculative engine run
        with draft budget 0 (the mask is per-position — a multi-token
        super-step would verify against a stale mask)."""
        prompt = [int(t) for t in prompt_ids]
        if not prompt:
            raise ValueError("need a non-empty prompt")
        if draft_tokens is not None and int(draft_tokens) < 0:
            raise ValueError(
                f"draft_tokens must be >= 0 or None, got {draft_tokens}")
        adapter_id = int(adapter_id)
        if adapter_id:
            if self.adapters is None:
                raise ValueError(
                    f"adapter_id={adapter_id} but this engine has no "
                    "AdapterBank (pass adapters= at construction)")
            if not self.adapters.is_live(adapter_id):
                raise ValueError(
                    f"adapter id {adapter_id} is not allocated in the "
                    "bank (alloc() it first, or use 0 = base model)")
            if self._spec is not None and (draft_tokens is None
                                           or int(draft_tokens) > 0):
                raise ValueError(
                    "adapted requests on a speculative engine must "
                    "submit draft_tokens=0 — drafts are pinned to the "
                    "null adapter, and a base-model draft chain under "
                    "an adapted target would corrupt accept-rate "
                    "accounting")
        if constraint is not None and not hasattr(constraint, "cursor"):
            raise ValueError(
                "constraint must be a TokenDFA-like object with a "
                ".cursor(prefix) method (serving/constrain.py)")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be positive or None, got {deadline_s}")
        # SamplingParams validates on construction (frozen dataclass)
        sp = sampling if sampling is not None else SamplingParams()
        if sp.max_tokens is not None:
            max_new_tokens = sp.max_tokens
        if max_new_tokens <= 0:
            raise ValueError(
                f"max_new_tokens must be positive, got {max_new_tokens}")
        if len(prompt) - 1 + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the model's max_len "
                f"{self.max_len} — the cache position would silently "
                "clamp (same guard as generate())")
        # every validation precedes the submitted counter and the shed
        # decision: an invalid call must raise the same way loaded or
        # idle, and must never skew serving/submitted (goodput's
        # denominator)
        rid = self._next_id
        self._next_id += 1
        req = Request(
            req_id=rid, prompt=prompt, max_new_tokens=int(max_new_tokens),
            eos_id=int(eos_id), sampling=sp,
            draft_tokens=None if draft_tokens is None else int(draft_tokens),
            priority=int(priority),
            deadline_s=None if deadline_s is None else float(deadline_s),
            degrade=degrade,
            adapter_id=adapter_id, constraint=constraint,
            submit_time=self._clock())
        # hold the adapter slot for the request's lifetime (released at
        # every terminal disposition: finish, shed, cancel)
        if adapter_id:
            self.adapters.retain(adapter_id)
        self.metrics.on_submit()
        # admission backpressure: a bounded queue sheds at the door —
        # the cheapest place to reject work is before any of it runs.
        # The bound is on the BACKLOG (waiting beyond what the pool's
        # free slots will absorb at the next admission), so an idle
        # engine with free capacity never sheds — max_queue=0 means
        # "serve up to capacity, queue nothing", not "serve nothing".
        if self.max_queue is not None \
                and (self.scheduler.queue_depth - self.pool.free_slots
                     >= self.max_queue):
            self._shed(req, "shed")
            return rid
        self.scheduler.submit(req)
        return rid

    def result(self, req_id: int) -> Optional[np.ndarray]:
        """Generated 1-based ids for a FINISHED request, else None
        (also None once evicted by ``keep_finished``/``pop_result``)."""
        req = self._finished.get(req_id)
        return None if req is None else np.asarray(req.output, np.int32)

    def pop_result(self, req_id: int) -> Optional[np.ndarray]:
        """Like :meth:`result` but RELEASES the request's ledger entry —
        the memory-bounding consumption pattern for long-lived engines
        (take each output exactly once; see ``keep_finished`` for the
        automatic alternative)."""
        req = self._finished.pop(req_id, None)
        return None if req is None else np.asarray(req.output, np.int32)

    def logprobs(self, req_id: int) -> Optional[np.ndarray]:
        """Chosen-token raw model log-probs for a FINISHED request (one
        per output token), else None — the logprobs twin of
        :meth:`result`."""
        req = self._finished.get(req_id)
        return None if req is None else np.asarray(req.logprobs, np.float32)

    def cancel(self, req_id: int) -> bool:
        """Cancel a WAITING or RUNNING request. A waiting request is
        dequeued and never occupies a slot; a RUNNING request's slot is
        freed immediately — target AND draft caches alike (``pool.free``
        resets both position counters), mid-speculative-chunk included —
        and no token is ever emitted for it again (the next step simply
        has no such row). Either way the request lands in the finished
        ledger with state 'cancelled', keeping whatever output it had
        already emitted. Returns False (no-op) for requests already
        finished or unknown."""
        req = self.scheduler.cancel(req_id)
        if req is None:
            req = self.scheduler.cancel_running(req_id)
            if req is None:
                return False
            slot, req.slot = req.slot, None
            self.pool.free(slot)
            self._configured.discard(slot)
            self._restored.discard(slot)
            self._constraints.pop(slot, None)
            if self.admitter is not None:
                self.admitter.drop(slot)       # mid-prefill chunk plan
        # WAITING cancellations drop their stashed payload too: a
        # preempted/handed-off row cancelled before readmission must
        # not pin its KV slices in the finished ledger forever (the
        # same teardown contract _shed follows)
        req.resume_carry = None
        self._drop_tier_row(req)
        self._release_adapter(req)
        self.metrics.on_cancel()
        # cancellation is a disposition too: without this bucket the
        # finish_<reason> counters would not sum to every request's
        # fate (the accounting contract docs/serving.md states)
        self.metrics.on_finish_reason("cancelled")
        self._finished[req_id] = req
        self._evict_finished()
        return True

    def request(self, req_id: int) -> Optional[Request]:
        return self._finished.get(req_id)

    # -- the serving loop --------------------------------------------------

    def _evict_finished(self) -> None:
        # dict preserves insertion order = finish order → oldest-first
        if self.keep_finished is None:
            return
        while len(self._finished) > self.keep_finished:
            self._finished.pop(next(iter(self._finished)))

    def _place_rows(self, x):
        """Commit a per-slot array to the plane's mesh (identity on the
        single-device plane). Every slot-axis array the step consumes
        goes through here so its sharding matches the pooled carry —
        mismatched placements would recompile or silently gather."""
        return x if self._plane is None else self._plane.place_rows(x)

    def _admit_and_pump(self) -> None:
        """One super-step's admission — :meth:`_admit`, then the chunk
        pump — under the ``admit`` span. Its series
        (``serving/admit_host_s``) is HOST time, the launches of the
        prefills and scatters and never their device time (that is in
        the trace), and gets a sample only where a request was bound."""
        self._bound.clear()
        with self.metrics.span("admit", phase="admit_host") as sp:
            self._admit()
            if self.admitter is not None:
                self.admitter.pump()
            if self._bound:
                sp.note(rids=" ".join(map(str, self._bound)))
            else:
                sp.drop()

    def _admit(self) -> None:
        import jax.numpy as jnp

        now = self._clock()
        # deadline-drop: an expired WAITING request can only miss its
        # SLO — spending decode steps on it starves requests that can
        # still make theirs
        for req in self.scheduler.pop_expired(now):
            self._shed(req, "deadline")
        # the static degrade path's REVERT half: when the queue has
        # drained back below the pressure threshold, still-WAITING
        # degraded rows (preempted/fault-evicted under the burst) get
        # their recorded original limits back — a burst's clamp must
        # not outlive the burst (the autopilot's bus drives the same
        # restore from its own controller when attached)
        if (self.degrade_at is not None
                and self.scheduler.queue_depth < self.degrade_at):
            for req in self.scheduler.iter_waiting():
                self._restore_degrade(req)
        # feasibility admission control: with a measured per-token
        # service-time estimate in hand, a request whose DECLARED
        # budget (max_new_tokens — the only bound available before the
        # model runs; EOS-early traffic under a generous cap is shed
        # conservatively, so deadline callers should set honest caps)
        # cannot fit inside its deadline even decoding uncontended
        # from this instant is dropped at the door instead of spending
        # steps proving the miss. The
        # estimate is the running decode_step_s MEDIAN (robust to the
        # cold-compile first step and stall outliers) divided by the
        # measured tokens-per-step, so a speculative engine's
        # multi-token super-steps don't overstate service time and
        # shed requests that would have made it. Before the first
        # decode step there is no estimate and nothing is dropped —
        # feasibility control never guesses.
        if self.deadline_feasibility:
            est = self.metrics.service_time_estimate()
            if est is not None:
                def _infeasible(req: Request) -> bool:
                    dl = req.deadline_time
                    if dl is None:
                        return False
                    # price the budget the request would ACTUALLY get:
                    # under pressure _maybe_degrade will cap
                    # max_new_tokens at admission, and shedding on the
                    # un-degraded budget would drop requests the cap
                    # makes feasible (mirrors _maybe_degrade's
                    # first-admission condition)
                    cap = req.max_new_tokens
                    if (req.degrade is not None and not req.degraded
                            and not req.output
                            and self.degrade_at is not None
                            and self.scheduler.queue_depth
                            >= self.degrade_at
                            and req.degrade.max_new_tokens is not None):
                        cap = min(cap, int(req.degrade.max_new_tokens))
                    rem = cap - len(req.output)
                    return now + est * rem > dl

                for req in self.scheduler.pop_waiting(_infeasible):
                    self.metrics.on_infeasible()
                    self._shed(req, "infeasible")
        # loss-free preemption (priority policy): evict lowest-priority
        # running rows while strictly-higher-priority requests wait
        # without a free slot — each eviction stashes the row's KV for
        # byte-exact resumption, so this trades latency across classes
        # without ever trading correctness
        if self.preemption:
            while True:
                victim = self.scheduler.lowest_running()
                if victim is None:
                    break
                demand = self.scheduler.waiting_higher_than(victim.priority)
                if demand <= self.pool.free_slots:
                    break
                if self._window:
                    # a preemption spill snapshots the victim's DEVICE
                    # row state — with dispatches in flight the device
                    # KV is up to W positions AHEAD of the host's
                    # emitted prefix, so a mid-window spill would
                    # resume the row desynchronized. Flush first (only
                    # when a preemption is actually due — the window
                    # stays hot otherwise), then re-select: the flush
                    # may have finished the victim or freed its slot.
                    self._drain_window({})
                    continue
                self._preempt_row(victim)
            # deadline-aware preemption (autopilot): evict long-slack
            # running rows so short-deadline FEASIBLE waiters seat
            # before their would-miss point — within or below class,
            # where the static loop above only trades across classes.
            # Loss-free like every preemption: latency reorders,
            # tokens never do.
            if self.autopilot is not None:
                victims = list(self.autopilot.deadline_victims(self, now))
                if victims and self._window:
                    # same mid-window spill hazard; re-select after
                    # the flush for the same reasons as above
                    self._drain_window({})
                    victims = list(
                        self.autopilot.deadline_victims(self, now))
                for victim in victims:
                    self._preempt_row(victim)
        n = self.scheduler.admissible(self.pool.free_slots)
        if not n:
            return
        if self.tier is not None:
            # batch the host->host fetches for the rows about to seat
            # BEFORE admission touches the device, so tier latency
            # never lands inside the decode gap (the fetch itself is
            # host-side; only restore_row uploads, same as the legacy
            # stash path)
            self.tier.prefetch(self.scheduler.peek_waiting(n))
        if self.admitter is not None:
            # batched admission: bucketed multi-row masked prefill with
            # optional shared-prefix reuse (serving/admission.py)
            self.admitter.admit(n)
            self._note_shard_balance()
            return
        for _ in range(n):
            slot = self.pool.alloc()
            assert slot is not None          # admissible() checked
            req = self._bind(slot)
            # the last fed token is the first decode input — exactly
            # generate()'s convention, so outputs match token-for-token
            # (called before the resume check: next_token/degrade are
            # needed on the restored path too)
            pf = self._admitted_prefill_tokens(req)
            payload = self._resume_payload(req)
            if payload is not None:
                # byte-exact resume: the stashed/spilled row_state
                # payload (KV + scales + lanes + mirrors + draft)
                # restores whole — _configure_slot then sets knobs only
                self.pool.restore_row(slot, payload)
                req.resume_carry = None
                self._restored.add(slot)
                continue
            if not pf:
                self.pool.set_pos(slot, 0)
                continue
            ptoks = jnp.asarray([pf], jnp.int32)
            try:
                _, pc = self._dispatch("prefill", self._prefill_fn,
                                       self.params, ptoks,
                                       self._zero_carry1)
            except FaultError:
                self._recover_admission([(slot, req)])
                continue
            # NO completion fence: the prefill dispatch is exactly the
            # work async dispatch-ahead overlaps with the decode step —
            # the step's one decode fence absorbs its completion, and
            # the per-phase prefill timer went with the wait (a timer
            # here would measure the launch — the ASY305 lie; the
            # prefill step's own ``prefill.launch`` span is named for
            # exactly that, and feeds no series). The PR 12 worksheet
            # marked this site deletable (docs/async_readiness.md).
            self.pool.write_prefill(slot, pc, len(pf))
        self._note_shard_balance()

    # -- resilience: shedding, degradation, preemption, recovery -----------

    def _release_adapter(self, req: Request) -> None:
        """Drop the adapter-slot reference :meth:`submit` took — called
        from every terminal disposition exactly once (finish ledger,
        shed, cancel), so a freed tenant's slot recycles only after its
        last in-flight request is gone."""
        if (req.adapter_id and self.adapters is not None
                and not getattr(req, "_adapter_released", False)):
            req._adapter_released = True   # terminal paths run once
            self.adapters.free(req.adapter_id)

    def _shed(self, req: Request, reason: str) -> None:
        """Load-shed a request WITHOUT running it (queue-full submit,
        waiting-deadline expiry, or a feasibility drop): ledgered with
        ``finish_reason`` set and empty output — observable
        backpressure, never an exception. Deadline expiry and
        feasibility drops both count as deadline misses (either way
        the SLO was not going to be met)."""
        self._release_adapter(req)
        req.state = SHED
        req.finish_reason = reason
        # a PREEMPTED request re-entering the queue carries its stashed
        # KV row; shedding it must drop that stash (n_layers*2 max_len
        # device slices) or the finished ledger pins it forever — the
        # same teardown contract cancel() follows
        req.resume_carry = None
        self._drop_tier_row(req)
        req.finish_time = self._clock()
        self._finished[req.req_id] = req
        self._evict_finished()
        self.metrics.on_finish_reason(reason)
        self.metrics.on_shed(deadline=(reason in ("deadline",
                                                  "infeasible")))

    def _maybe_degrade(self, req: Request) -> None:
        """Apply the request's ``degrade`` knob at FIRST admission when
        the waiting queue is at or past ``degrade_at`` — pure host-side
        bookkeeping (the caps become per-row runtime data)."""
        if (req.degrade is None or req.degraded or req.output
                or self.degrade_at is None
                or self.scheduler.queue_depth < self.degrade_at):
            return
        self._apply_degrade(req)

    def _apply_degrade(self, req: Request) -> bool:
        """The ONE degrade writer (a declared ACTUATION_SITES unit —
        serving/autopilot.py): clamp the request to its submitted
        ``Degrade`` knobs, RECORDING the originals on the request so
        the clamp is revertible while the row still waits. Both the
        static ``degrade_at`` path (via ``_maybe_degrade``) and the
        autopilot's per-class pressure loop land here. False when
        there is nothing to do (no knob, or already degraded)."""
        d = req.degrade
        if d is None or req.degraded:
            return False
        req._pre_degrade = (req.max_new_tokens, req.draft_tokens)
        if d.max_new_tokens is not None:
            req.max_new_tokens = min(req.max_new_tokens,
                                     int(d.max_new_tokens))
        if d.draft_tokens is not None:
            req.draft_tokens = int(d.draft_tokens)
        req.degraded = True
        self.metrics.on_degrade()
        return True

    def _restore_degrade(self, req: Request) -> bool:
        """Revert ``_apply_degrade`` for a still-WAITING row (a
        declared ACTUATION_SITES unit): put the recorded original
        ``max_new_tokens``/``draft_tokens`` back and clear the degraded
        mark, so the knob can re-apply if pressure returns. Only
        WAITING rows restore — a seated row's budget was already
        priced into its admission (feasibility, chunk planning), and a
        preempted-then-requeued row IS waiting, which is exactly the
        regression this fixes: before PR 19 a row degraded at a
        queue-depth spike kept its clamp forever, burst or no burst.
        False when the row is not a restorable degraded waiter."""
        if (not req.degraded or req._pre_degrade is None
                or req.state != WAITING):
            return False
        mnt, dt = req._pre_degrade
        # never clamp BELOW what already streamed out (a preempted
        # row's emitted tokens are immutable history)
        req.max_new_tokens = max(int(mnt), len(req.output))
        req.draft_tokens = dt
        req._pre_degrade = None
        req.degraded = False
        self.metrics.on_degrade_restored()
        return True

    def _bind(self, slot: int, partial: bool = False) -> Request:
        """THE slot binding, shared by every admission path: pop the
        best waiting request into ``slot`` and record what it waited
        for one (``serving/queue_wait_s``, on the engine clock from
        ``submit_time`` — a re-admitted row counts again)."""
        req = self.scheduler.admit(slot, partial=partial)
        self.metrics.on_queue_wait(self._clock() - req.submit_time)
        self._bound.append(req.req_id)
        return req

    def _resident_positions(self, rows, ahead: int = 0) -> List[int]:
        """K/V positions resident once the decode dispatch about to
        launch has run, a row each, from host state alone (no
        readback): a decoding row of ``rows`` holds its prompt and
        everything emitted (the token it feeds included, once this
        program has written it), ``ahead`` more where that many
        dispatches of the same rows are in flight and not yet consumed;
        then the mid-prefill rows, each what the chunk pump has landed."""
        return [len(r.prompt) + len(r.output) + ahead
                for r in rows.values()] \
            + [int(self.pool.chunk_done[slot])
               for slot in self.scheduler.partial]

    def _kv_used_share(self, resident: List[int]) -> float:
        """Those positions over the ``n_slots x max_len`` the pool
        reserves."""
        return sum(resident) / (self.pool.n_slots * self.pool.max_len)

    def _host_state(self, rows, ahead: int) -> dict:
        """``on_step``'s host-state samples for ONE plain decode
        dispatch, taken when it is launched, over the rows it decodes
        as the PROGRAM reads them (:meth:`_resident_positions`).

        * ``kv_used_share``: :meth:`_kv_used_share`;
        * ``kv_held_bytes``: the same positions per row and layer as
          ``min(pos, len_i)`` (a ring holds at most its window);
        * ``kv_fetched_bytes``: what the program's attention fetches: a
          row feeds its last token at position ``held - 1`` and attends
          up to it (a mid-prefill row does not decode: none). Not taken
          by a speculative engine's super-step, whose verify program
          reads the whole window;
        * ``state_in_use_bytes``: the per-slot ``state`` leaves of the
          in-use slots (each holds all of its own), None for a family
          that keeps none."""
        pool = self.pool
        resident = self._resident_positions(rows, ahead)
        per_slot = pool.state_bytes_per_slot
        return dict(
            kv_used_share=self._kv_used_share(resident),
            kv_held_bytes=sum(map(pool.kv_held_bytes, resident)),
            kv_fetched_bytes=pool.kv_fetched_bytes(
                [held - 1 for held in resident[:len(rows)]]),
            state_in_use_bytes=per_slot * pool.used_slots
            if per_slot else None)

    def _next_dispatch(self):
        """Number the decode (or verify) dispatch about to launch:
        ``(seq, waves)``, ``waves`` the prefill launches (a batched
        wave, a prefix suffix, a chunk) since the dispatch before it."""
        self._n_dispatches += 1
        launches = self.metrics.prefill_launch_count
        waves, self._launches_seen = \
            launches - self._launches_seen, launches
        return self._n_dispatches, waves

    def _admitted_prefill_tokens(self, req: Request) -> List[int]:
        """0-based tokens whose K/V must be resident before ``req``
        decodes: the original prompt plus everything already emitted —
        empty output for fresh requests, the REPLAY source for
        preempted/fault-evicted rows (the stream is its own lineage:
        re-prefilling ``prompt + output`` reconstructs exactly the
        cache state the evicted row had). Sets ``req.next_token`` to
        the last fed token and applies the degrade knob under
        pressure; returns everything before it (the prefill list)."""
        self._maybe_degrade(req)
        fed0 = [t - 1 for t in req.prompt] + [t - 1 for t in req.output]
        req.next_token = fed0[-1]
        return fed0[:-1]

    def _spill_or_carry(self, req: Request, payload: Optional[dict]) -> None:
        """Park a row's ``row_state`` payload for later readmission:
        into the host tier when one backs this engine (packed host
        bytes under the tier's budget — THE unified stash path), else
        on ``req.resume_carry`` (the legacy in-memory stash of device
        slices). One spelling for preemption, the disagg transfer
        requeue, and handoff staging."""
        if payload is None:
            return
        if self.tier is not None:
            self.tier.put_row(req, payload)
        else:
            req.resume_carry = payload

    def _resume_payload(self, req: Request) -> Optional[dict]:
        """The byte-exact resume source for a (re)admitted request: its
        in-memory stash if one rode the request (tier-less engines),
        else a currency-checked fetch from the host tier. None -> no
        resident copy: the row replays via prefill of ``prompt +
        output`` (the PR 8 contract — a budget-evicted tier entry
        downgrades to replay, never to corruption). Mid-stream resumes
        count ``serving/resumed_without_prefill``."""
        payload = req.resume_carry
        if payload is None and self.tier is not None:
            payload = self.tier.fetch_row(req)
        if payload is not None and req.output:
            self.metrics.on_resume_without_prefill()
        return payload

    def _drop_tier_row(self, req: Request) -> None:
        """Tier-side twin of ``req.resume_carry = None``: every
        terminal (or carry-distrusting) disposition drops the
        request's spilled row eagerly, so the host tier never pins a
        dead row's bytes — the fix for the old disagg wart where a
        finished row's stash lingered until a later hygiene sweep."""
        if self.tier is not None:
            self.tier.drop_row(req.req_id)

    def _dispatch(self, site: str, fn, *args):
        """Every serving-path device dispatch routes through here so
        the optional :class:`~bigdl_tpu.serving.faults.FaultInjector`
        can fail, corrupt, or stall it deterministically — a no-op
        passthrough without one."""
        if self._faults is None:
            return fn(*args)
        return self._faults.call(site, fn, *args)

    def row_state(self, slot: int) -> Dict:
        """``pool.row_state(slot)`` for a SLOT-HOLDING row — THE way a
        row's device state leaves this engine (preemption spill, the
        disaggregated handoff, a pool drain). With decode dispatches
        in flight the device row is up to W tokens AHEAD of the
        request's emitted prefix, and a payload taken then resumes
        desynchronized (a token lost from the stream), so the window
        must be empty here. The reader cannot flush on the caller's
        behalf: a flush may FINISH the row or free its slot, so callers
        flush (``_drain_window`` inside a step, ``flush_window()``
        outside one) and THEN choose their rows."""
        assert not self._window, (
            f"row_state({slot}) with {len(self._window)} decode "
            "dispatch(es) in flight — flush the window first")
        return self.pool.row_state(slot)

    def _preempt_row(self, victim: Request) -> None:
        """Loss-free preemption of one RUNNING row: stash its FULL
        ``pool.row_state`` payload (KV + int8 scales + RNG lane +
        penalty counts + chunk mirrors + draft slice — restored
        bitwise at readmission through ``restore_row``, the same
        serialization the disaggregated handoff speaks) — into the
        host tier when one is attached (packed bytes under the tier
        budget, HBM freed outright), else on the request, share its
        carry into the prefix cache when one is attached (any request
        on the same prefix benefits), then free the slot and requeue
        the request at its ORIGINAL arrival key — preemption reorders
        latency, never tokens."""
        slot = victim.slot
        payload = self.row_state(slot)
        if len(victim.prompt) + len(victim.output) > 1:
            self._spill_or_carry(victim, payload)
            if self.prefix_cache is not None:
                fed0 = [t - 1 for t in victim.prompt] + \
                       [t - 1 for t in victim.output]
                # namespaced by the victim's adapter: its K/V was
                # computed under those factors and must never serve a
                # prefix hit for another tenant
                self.prefix_cache.insert(fed0[:-1], payload["carry"],
                                         adapter_id=victim.adapter_id)
        victim.preemptions += 1
        self.scheduler.requeue(victim)            # running -> waiting
        self.pool.free(slot)
        self._configured.discard(slot)
        self._restored.discard(slot)
        self._constraints.pop(slot, None)
        self.metrics.on_preempt()

    def _recover_rows(self, rows, now: float) -> None:
        """Fault-recovery disposition for evicted rows: requeue each
        request for loss-free replay (its carry is never trusted — the
        stream replays via prefill of ``prompt + output``), or fail it
        out with ``finish_reason='error'`` once past the watchdog's
        per-request retry budget. Either way the engine keeps making
        progress — a persistent fault fails requests, not the engine."""
        for slot, req in rows:
            self._configured.discard(slot)
            self._restored.discard(slot)
            self._constraints.pop(slot, None)
            if self.admitter is not None:
                self.admitter.drop(slot)       # mid-prefill chunk plan
            req.retries += 1
            req.resume_carry = None
            # recovery never trusts a stashed copy either: a faulted
            # step may postdate the spill, so the tier row is dropped
            # and the request replays from prompt + output
            self._drop_tier_row(req)
            mr = self.watchdog.max_retries
            if mr is not None and req.retries > mr:
                self._finish_row(req, "error", now)   # frees the slot
            else:
                self.scheduler.requeue(req)           # running -> waiting
                self.pool.free(slot)
                self.metrics.on_retry()

    def _recover_admission(self, rows) -> None:
        """An admission-side prefill dispatch faulted: evict exactly
        its rows (slots freed, requests requeued or failed out); other
        buckets in the same admission round proceed normally."""
        self._recover_rows(rows, self._clock())

    def _recover_step(self, running, kind: str) -> None:
        """A decode/verify step failed (raised dispatch, garbage
        outputs, watchdog timeout): discard the step's outputs and
        evict EVERY implicated row — a whole-batch dispatch fault
        cannot be attributed to one row — for loss-free replay."""
        self._recover_rows(list(running.items()), self._clock())

    def _step_unhealthy(self, nxt, lps, active) -> Optional[str]:
        """Garbage verdict on a decode step's host-read outputs:
        non-finite chosen log-probs or out-of-range tokens on active
        rows (the NaN-logits / corrupted-readback failure shape).
        None = healthy."""
        if active.any():
            a_tok, a_lp = nxt[active], lps[active]
            if (not np.isfinite(a_lp).all() or (a_tok < 0).any()
                    or (a_tok >= self._vocab).any()):
                return "garbage"
        return None

    def _timed_out(self, elapsed: float) -> bool:
        """Watchdog timeout verdict. The timeout arms only after the
        engine's FIRST healthy step: a cold engine's first dispatch
        carries the one-time XLA compile (multi-second at LM scale on a
        real clock), and evicting the whole batch for a healthy-but-
        compiling device would burn every request's retry budget at
        startup. A stall missed during that grace window is only a slow
        CORRECT step — its outputs are valid, so accepting them costs
        latency, never correctness."""
        to = self.watchdog.step_timeout_s
        return to is not None and self._warm and elapsed > to

    def _note_shard_balance(self) -> None:
        """Post-admission shard-balance sample (sharded pools only):
        per-shard occupancy extremes + the max−min admission imbalance
        the balanced allocator is supposed to keep ≤ 1."""
        if self.pool.n_shards > 1:
            self.metrics.on_shard_slots(self.pool.used_per_shard(),
                                        self.pool.rows_per_shard)

    def _lane_key(self, req: Request):
        """The request's RNG-lane key: an explicit ``SamplingParams.seed``
        pins the lane (``sampling.lane_key`` — the rule ``generate()``
        shares), else a fresh lane folded from the engine seed and the
        request id. Either way the lane is a function of the REQUEST,
        never the slot, so readmission into any slot replays the same
        stream."""
        import jax

        from bigdl_tpu.serving.sampling import lane_key

        sp = req.sampling
        if sp.seed is not None:
            return lane_key(sp.seed)
        return jax.random.fold_in(lane_key(self.seed), req.req_id)

    def _configure_slot(self, slot: int, req: Request) -> None:
        """Thread one admitted request's SamplingParams into its slot:
        knob rows on host, RNG lane + penalty state on device. For a
        READMITTED request (preempted or fault-evicted mid-stream —
        ``req.output`` non-empty) the state resumes where it left off:
        the lane fast-forwards by one split per emitted draw
        (:func:`~bigdl_tpu.serving.sampling.advance_lane` — the lane
        after n draws is a pure function of the request seed), penalty
        counts rebuild from the emitted tokens, and the min-tokens ban
        reflects the CURRENT output length, not the fresh-request
        default. That host-side reconstruction is the whole loss-free
        eviction contract's second half (the KV half is prefill
        replay/the stashed row). A slot RESTORED from a full
        ``row_state`` payload (preemption resume, disaggregated
        handoff) skips the device half entirely: its lane, counts, and
        draft cache arrived verbatim with the payload — byte-identical
        to what the rebuild would write, without the device traffic —
        and only the host knob rows are (re)built here."""
        sp = req.sampling
        scal, ban_row = knob_row_values(sp, req.eos_id)
        for k, v in scal.items():
            self._knobs[k][slot] = v
        self._knobs["ban_ids"][slot] = ban_row
        self._ban_base[slot] = self._knobs["ban"][slot]
        if self._ban_base[slot] and req.output:
            # resumed mid-stream: the ban may already have lifted
            self._knobs["ban"][slot] = len(req.output) < sp.min_tokens
        # the slot's adapter id (runtime data of the compiled steps;
        # already set for restored rows — the payload carried it — but
        # rewriting the same value is harmless and covers every path)
        self.pool.adapter_ids[slot] = req.adapter_id
        # constraint cursor: rebuilt from (constraint, emitted prefix)
        # — THE replay rule; a recycled slot's stale mask is always
        # overwritten (all-True for unconstrained occupants)
        self._constraints.pop(slot, None)
        if req.constraint is not None:
            cur = req.constraint.cursor(req.output)
            self._constraints[slot] = cur
            cur.mask_row(self._vocab, out=self._knobs["allow"][slot])
        else:
            self._knobs["allow"][slot][:] = True
        self._knobs_device = None                # re-upload next step
        if slot in self._restored:
            self._restored.discard(slot)
            self._configured.add(slot)
            return
        key = self._lane_key(req)
        if req.output:
            key = advance_lane(key, len(req.output))
        self.pool.write_sampling(slot, key, req.prompt,
                                 output_ids=req.output)
        if self._spec is not None:
            # the draft cache ingests the fed stream alongside the
            # target's (every admission path configures through here)
            self._spec.prefill_draft(slot, req)
        self._configured.add(slot)

    def _finish_check(self, req: Request) -> Optional[str]:
        """Stop/length decision for the token JUST appended to
        ``req.output`` — THE one copy of the per-token finish rule
        (the decode loop and the speculative chunk emission both apply
        it, token by token, so multi-token super-steps stop exactly
        where the baseline would)."""
        sp = req.sampling
        n_out = len(req.output)
        tok1 = req.output[-1]
        if n_out >= sp.min_tokens:
            if req.eos_id > 0 and tok1 == req.eos_id:
                return "eos"
            if (tok1 in sp.stop_token_ids
                    or match_stop_sequences(req.output, sp.stop_sequences)):
                return "stop"
        if n_out >= req.max_new_tokens:
            return "length"
        return None

    def _finish_row(self, req: Request, reason: str, now: float) -> None:
        """Evict a finished request: free its slot, then the shared
        ledger tail (:meth:`_ledger_finish`)."""
        freed = self.scheduler.finish(req, now)
        self.pool.free(freed)
        self._configured.discard(freed)
        self._restored.discard(freed)
        self._constraints.pop(freed, None)
        self._ledger_finish(req, reason, now)

    def _ledger_finish(self, req: Request, reason: str,
                       now: float) -> None:
        """THE finish-ledger tail — reason counter, finished ledger,
        latency/logprob/SLO accounting (plus the recovery-success
        counter for requests that survived an eviction). One spelling
        shared by :meth:`_finish_row` (slot-holding rows) and slotless
        terminations (the disaggregated plane's transfer-retry
        error-out), so a new finish-time counter can never cover one
        path and miss the other."""
        self._release_adapter(req)
        req.finish_reason = reason
        req.resume_carry = None
        self._drop_tier_row(req)
        req.state = FINISHED
        req.finish_time = now
        self._finished[req.req_id] = req
        self._evict_finished()
        self.metrics.on_finish_reason(reason)
        if reason == "error":
            met = None          # neither goodput nor a deadline miss
        else:
            dl = req.deadline_time
            met = dl is None or now <= dl
            if req.retries > 0:
                self.metrics.on_recovered()
        self.metrics.on_finish(
            now - req.submit_time, len(req.output),
            mean_logprob=(float(np.mean(req.logprobs))
                          if req.logprobs else None),
            met_deadline=met)

    def _sampler_wide(self, slots) -> bool:
        """Whether a decode step of these slots sorts the vocabulary:
        the device's own rule (``sampling.wide_rows``) over the knob
        rows the step reads, for ``serving/sampler_wide``."""
        k = self._knobs
        _, wide = wide_rows(k["temperature"][slots], k["top_k"][slots],
                            k["top_p"][slots])
        return bool(wide.any())

    def _maybe_flip_ban(self, slot: int, req: Request) -> None:
        """min-tokens ban lifts the step the floor is met — a runtime
        VALUE change, never a recompile."""
        if self._ban_base[slot]:
            ban = len(req.output) < req.sampling.min_tokens
            if ban != self._knobs["ban"][slot]:
                self._knobs["ban"][slot] = ban
                self._knobs_device = None

    def _advance_constraint(self, slot: int, req: Request) -> None:
        """Advance a constrained row's automaton over the token JUST
        emitted and rewrite its allow-mask row — a runtime VALUE
        change, never a recompile (the constrained twin of
        :meth:`_maybe_flip_ban`; no-op for unconstrained rows)."""
        cur = self._constraints.get(slot)
        if cur is None:
            return
        cur.advance(req.output[-1])
        cur.mask_row(self._vocab, out=self._knobs["allow"][slot])
        self._knobs_device = None

    def _bank_device_arrays(self):
        """The adapter bank as placed device arrays, cached against the
        bank's version counter (tenant alloc/free re-uploads; the
        steady-state decode loop reuses). Tensor-parallel planes pin
        the Megatron bank sharding (``adapter_bank_specs``)."""
        if (self._bank_device is None
                or self._bank_version != self.adapters.version):
            import jax

            bank = self.adapters.device_arrays()
            if self._plane is not None and self._plane.tensor_parallel:
                from bigdl_tpu.models.transformer import adapter_bank_specs
                from bigdl_tpu.serving.sharded import named_sharding

                specs = adapter_bank_specs(self.model)
                bank = jax.device_put(
                    bank, {k: named_sharding(self.mesh, specs[k])
                           for k in bank})
            self._bank_device = bank
            self._bank_version = self.adapters.version
        return self._bank_device

    def _adapter_args(self):
        """The decode/verify dispatch's trailing adapter arguments:
        ``()`` without a bank, else ``(per-slot adapter ids, bank)`` —
        the ids re-upload each step like the token/active rows (tiny),
        the bank rides the version-keyed cache."""
        if self.adapters is None:
            return ()
        import jax.numpy as jnp

        ids = self._place_rows(jnp.asarray(self.pool.adapter_ids))
        return (ids, self._bank_device_arrays())

    def _prefill_adapter_args(self, row_adapter_ids):
        """The batched-prefill dispatch's trailing adapter arguments
        for one bucket: ``()`` without a bank, else ``(per-ROW ids,
        bank)`` — prefill rows are bucket rows, not pool slots, so the
        admission paths pass the bucket's own id list."""
        if self.adapters is None:
            return ()
        import jax.numpy as jnp

        return (jnp.asarray(np.asarray(row_adapter_ids, np.int32)),
                self._bank_device_arrays())

    def _note_host_step(self, t_begin: float, device_before: float,
                        n_samples: int = 1) -> None:
        """Record the per-super-step TRUE-HOST residue: the step's wall
        time minus the fenced-wait windows timed inside it (the
        ``DEVICE_PHASES`` accumulator — the time the host spent BLOCKED
        on a fence readback or the draft chain's completion pin). What
        remains is the Python the device waits on between dispatches —
        the number the dispatch-ahead window exists to shrink
        (``serving/host_step_s``; percentiles in ``summary()``),
        measured on the engine's clock like every other serving timer.

        ``n_samples`` keeps the host_step_s and decode_step_s series
        comparable sample-for-sample when one super-step consumed
        SEVERAL window entries (a flush): the residue lands once and
        the remaining samples are recorded as zeros — the flush's host
        cost is real but belongs to one wall-clock step."""
        dev = self.metrics.device_seconds - device_before
        self.metrics.add_phase(
            "host_step", max(0.0, (self._clock() - t_begin) - dev))
        for _ in range(max(0, int(n_samples) - 1)):
            self.metrics.add_phase("host_step", 0.0)

    def _note_decode_gap(self, had_running: bool, rows: int, waves: int,
                         chained: bool) -> None:
        """Record the wall gap between consecutive decode (or verify)
        dispatch completions while rows stayed in flight across it —
        the decode-stall sample — beside what the dispatch just read
        back was (its rows, the prefill launches before it, chained or
        not). Admission work between the two dispatches (a batched
        prefill wave, a chunk budget) is exactly what stretches the
        gap, which is the phenomenon ``serving_bench --scenario
        chunked`` measures."""
        now = self._clock()
        if had_running and self._last_decode_end is not None:
            self.metrics.on_decode_gap(now - self._last_decode_end,
                                       rows, waves, chained)
        self._last_decode_end = now

    def step(self) -> Dict[int, int]:
        """Admit waiting requests (CHUNKED admission then pumps at most
        ``chunk_budget`` prompt tokens of streaming prefill —
        ``serving/chunked.py``), then decode for every active row: ONE
        token per row on the plain engine, up to ``k + 1`` on a
        speculative engine (draft-and-verify super-step —
        ``serving/speculative.py``). Returns ``{req_id: 1-based token}``
        READ BACK this step: with a decode program in flight behind the
        host (the default) those are the tokens of the program the
        PREVIOUS step launched (the LAST token per request when a
        super-step, or a flush at depth > 1, lands several; empty when
        the engine is idle, every slot-holding row is still mid-
        prefill, or this step only launched — the first after an
        admission or any other flush of the window)."""
        return self._step_impl()

    @contextlib.contextmanager
    def _paired_host_step(self):
        """The step's host/device split, as a context INSIDE the
        ``step`` span (so that ``step()``'s own frame outlasts the span
        by a call, not by this bookkeeping)."""
        t_step = self._clock()
        dev0 = self.metrics.device_seconds
        ndec0 = self.metrics.decode_step_count
        try:
            yield
        finally:
            # exactly one host/device split sample per decode/verify
            # dispatch sample — recovery paths included (a recovered
            # step's discarded outputs still cost real host time), so
            # the host_step_s and decode_step_s series stay comparable
            # sample for sample. A step that consumed several window
            # entries (a flush) pads with zero samples to keep the pair
            # count aligned; a step that consumed none (filling the
            # window) records nothing — its host cost lands with the
            # step that eventually fences it.
            n_new = self.metrics.decode_step_count - ndec0
            if n_new > 0:
                self._note_host_step(t_step, dev0, n_samples=n_new)
            # the SLO autopilot's ONE control sample per super-step —
            # after the step's metrics landed, idle steps included
            # (pressure relief mostly happens in lulls)
            if self.autopilot is not None:
                self.autopilot.sample(self)

    def _account_token(self, slot: int, req: Request, tok0: int,
                       lp: float, now: float,
                       emitted: Dict[int, int]) -> Optional[str]:
        """Host bookkeeping for ONE emitted token (0-based ``tok0``
        with chosen log-prob ``lp``): append to the request's stream,
        record it in ``emitted``, stamp the first-token latency, and
        return the finish verdict (:meth:`_finish_check` — None =
        still generating). Shared by the decode window's delayed
        consumer and the speculative super-step's emission loop so the
        two planes cannot drift on per-token accounting."""
        tok1 = tok0 + 1                      # back to 1-based ids
        req.output.append(tok1)
        req.logprobs.append(lp)
        emitted[req.req_id] = tok1
        if req.first_token_time is None:
            req.first_token_time = now
            self.metrics.on_first_token(now - req.submit_time)
        return self._finish_check(req)

    def _window_open(self, running) -> bool:
        """May this step EXTEND the dispatch-ahead window — chain a new
        decode dispatch on the newest in-flight dispatch's device token
        handle without fencing anything first? Only when nothing the
        in-flight dispatches assumed has changed: same rows in the same
        slots, knobs still the cached device arrays (no ban flip /
        constraint rewrite invalidated them), no row whose knobs COULD
        change mid-window (an armed min-tokens ban lifts on a consume;
        a constrained row rewrites its allow mask every token). Any
        mismatch answers False and the caller flushes the window
        through the delayed consumer before dispatching classically."""
        if not self._window or self.dispatch_ahead < 1:
            return False
        if self._knobs_device is None:
            return False
        prev = self._window[-1]
        if len(prev.rows) != len(running):
            return False
        for slot, req in prev.rows.items():
            if running.get(slot) is not req:
                return False
            if slot not in self._configured:
                return False
            if slot in self._constraints:
                return False
            if self._ban_base[slot] and self._knobs["ban"][slot]:
                return False
        return True

    def _consume_window(self, emitted: Dict[int, int]) -> bool:
        """THE delayed consumer: fence the OLDEST in-flight decode
        dispatch and run its batched host bookkeeping (health verdict,
        watchdog, metrics, per-token accounting, finish checks). Rows
        that left ``running`` since the dispatch (finished or evicted
        out from under the window) have their readback values
        discarded — per-row independence makes the overshoot
        harmless. Returns False when the entry was unhealthy: its
        outputs are discarded, every implicated row is evicted for
        loss-free replay, and the REST of the window is discarded too
        (every newer dispatch chained through the poisoned carry)."""
        entry = self._window.popleft()
        # ONE batched fence readback per dispatch (THE declared
        # delayed-consumer site — fences.DELAYED_CONSUMER_SITES; the
        # (N, V) distribution never crosses to host, only token ids +
        # chosen log-probs do). The span's bracket is the fenced-wait
        # sample: the time the host was genuinely BLOCKED here, the
        # DEVICE_PHASES half of the host_step split.
        with self.metrics.span("consume", seq=entry.seq) as consume:
            with self.metrics.span("fence", phase="fence_wait",
                                   seq=entry.seq):
                nxt, lps, *extra = fence("decode", entry.tok, entry.chosen,
                                         *entry.extra)
            now = self._clock()
            # the load this dispatch read, as of its launch, for a
            # reader of the profile (free when none runs)
            consume.note(kv_held=entry.host["kv_held_bytes"],
                         kv_fetched=entry.host["kv_fetched_bytes"])
            # the watchdog's elapsed spans dispatch → readback landed; at
            # W>0 that window covers host work on other in-flight steps
            # too, and a stall fault's clock advance at dispatch time is
            # inside it either way, so step_timeout_s keeps firing
            elapsed = now - entry.t0
            # the estimator's sample is the part of that bracket in which
            # this dispatch HAD the device: a chained dispatch sat queued
            # behind the previous program until ITS fence returned, and
            # counting the wait would price a token at up to W + 1 steps
            # (feasibility admission and deadline preemption would then
            # shed and spare what they should not). An entry dispatched
            # after a flush (and every entry at W=0) started after the
            # last fence, so its sample is the whole bracket, as before.
            self.metrics.add_phase(
                "decode_step", elapsed,
                service_s=now - max(entry.t0, self._last_fence_t))
            self._last_fence_t = now
            running = self.scheduler.running
            rows = {slot: req for slot, req in entry.rows.items()
                    if running.get(slot) is req}
            bad = self._step_unhealthy(nxt, lps, entry.active)
            if bad is None and self._timed_out(elapsed):
                bad = "timeout"
            if bad is not None:
                # outputs discarded; the pooled carry was committed at each
                # dispatch only so the pool keeps valid (post-donation)
                # buffers — every implicated row is evicted, so its bytes
                # die with the slot. Newer in-flight dispatches chained
                # through the poisoned carry: discard them unfenced. No gap
                # sample either: a discarded step served no tokens, and the
                # evicted batch anchors no future gap
                self._window.clear()
                self._recover_step(rows, bad)
                self._last_decode_end = None
                return False
            self._warm = True                  # arms the watchdog timeout
            # HEALTHY steps only: the decode-stall histogram measures gaps
            # between dispatches that actually served the batch
            self._note_decode_gap(entry.had_running, len(entry.rows),
                                  entry.waves, entry.chained)
            # recency stamps feed the tier's cold-first victim selection:
            # a row decoded this step is never the LRU preemption victim
            self.scheduler.note_decoded(list(rows))
            # what is counted is what is KEPT: a row that finished one
            # consume earlier was stepped once more by this dispatch and
            # its token is thrown away (the overshoot), so it is no
            # emitted token of ``serving/batch_active`` and no sampled
            # row; an entry none of whose rows still runs served nothing
            # and leaves no step sample at all. The host-state samples
            # are the entry's own, taken at its dispatch: what the
            # program read, not what runs one dispatch later
            if rows:
                n_sampled = sum(not req.sampling.is_greedy
                                for req in rows.values())
                self.metrics.on_step(
                    self.scheduler.queue_depth, self.pool.occupancy(),
                    len(rows), **entry.host)
                if extra:
                    consume.note(
                        experts_hit=self.metrics.on_expert_counts(extra[0]))
                self.metrics.on_sample_rows(
                    n_sampled, len(rows) - n_sampled,
                    self._sampler_wide(list(rows)))
            for slot, req in list(rows.items()):
                tok0 = int(nxt[slot])
                reason = self._account_token(slot, req, tok0,
                                             float(lps[slot]), now, emitted)
                if reason is not None:
                    self._finish_row(req, reason, now)
                else:
                    req.next_token = tok0
                    self._maybe_flip_ban(slot, req)
                    self._advance_constraint(slot, req)
            return True

    def _drain_window(self, emitted: Dict[int, int]) -> bool:
        """Flush every in-flight dispatch through the delayed consumer,
        oldest first. Returns False when a flushed entry was unhealthy
        (the consumer then discarded the rest of the window itself)."""
        while self._window:
            if not self._consume_window(emitted):
                return False
        return True

    def flush_window(self) -> None:
        """Flush every in-flight dispatch through the delayed consumer
        OUTSIDE a step() — drain()'s teardown and the disaggregated
        front end's — with the host/device split pairing intact: the
        flush records one host_step_s sample per consumed entry, so
        the host_step_s and decode_step_s series stay comparable
        sample for sample no matter who drove the flush."""
        if not self._window:
            return
        t0 = self._clock()
        dev0 = self.metrics.device_seconds
        ndec0 = self.metrics.decode_step_count
        self._drain_window({})
        n_new = self.metrics.decode_step_count - ndec0
        if n_new > 0:
            self._note_host_step(t0, dev0, n_samples=n_new)

    def _step_impl(self) -> Dict[int, int]:
        import jax.numpy as jnp

        # the span wraps the BODY, not the call: on the profile's python3
        # line this function's own frame is then the longer of the two,
        # and a device idle gap is put down to the span, a name that
        # survives an edit, not to a file-and-line frame
        self._n_steps += 1
        with self.metrics.span("step", step=self._n_steps), \
                self._paired_host_step():
            emitted: Dict[int, int] = {}
            had_running = bool(self.scheduler.running)
            self._admit_and_pump()
            running = self.scheduler.running
            if not running:
                # nothing to dispatch: flush any leftover in-flight work
                # first (rows that finished out from under the window —
                # the consumer's row filter discards their readbacks),
                # then report idle. No decode dispatch this step: a gap
                # measured across an empty batch would be idle time, not
                # a stall
                self._drain_window(emitted)
                self._last_decode_end = None
                return emitted
            if self._spec is not None:
                slots = list(running)
                out = self._spec.step(running, had_running)
                # a healthy super-step emits for every running row (and
                # has left its gap sample); an empty dict here means the
                # step faulted and recovery evicted the batch — no
                # dispatch completed, so there is no gap sample and no
                # live batch to anchor the next one
                if out:
                    self.scheduler.note_decoded(slots)
                else:
                    self._last_decode_end = None
                return out
            chained = self._window_open(running)
            if chained:
                # STEADY-STATE window extension: nothing the in-flight
                # dispatches assumed changed, so the next dispatch chains
                # directly on the newest dispatch's device token handle —
                # exactly the value its delayed consumer will set
                # req.next_token to — and reuses its placed active mask.
                # No host→device token upload, no fence, no readback: the
                # device stays fed while step N-W's readback is in flight.
                prev = self._window[-1]
                tokens_dev = prev.tok
                active = prev.active
                active_dev = prev.active_dev
                rows = dict(prev.rows)
            else:
                # the window's assumptions broke (admission, finish, evict,
                # knob change) or it is empty: flush everything in flight
                # through the delayed consumer, then dispatch classically
                # from host-built token rows
                if not self._drain_window(emitted):
                    # a flushed entry was unhealthy — recovery evicted the
                    # batch and discarded the window; nothing to dispatch
                    return emitted
                running = self.scheduler.running   # a flush may finish rows
                if not running:
                    self._last_decode_end = None
                    return emitted
                # host-built token rows, slot configuration (the sampling
                # lanes' pool writes) and their uploads
                with self.metrics.span("decode.build"):
                    N = self.pool.n_slots
                    tokens = np.zeros((N,), np.int32)
                    active = np.zeros((N,), bool)
                    for slot, req in list(running.items()):
                        if slot not in self._configured:
                            try:
                                self._configure_slot(slot, req)
                            except FaultError:
                                # slot configuration dispatches device work
                                # (the speculative draft prefill) — a fault
                                # there evicts exactly this row for loss-free
                                # replay; the rest of the batch decodes
                                # without it
                                self._recover_admission([(slot, req)])
                                continue
                        tokens[slot] = req.next_token
                        active[slot] = True
                    if not active.any():
                        self._last_decode_end = None
                        return emitted
                    tokens_dev = self._place_rows(jnp.asarray(tokens))
                    active_dev = self._place_rows(jnp.asarray(active))
                    rows = {slot: req for slot, req in running.items()
                            if active[slot]}
            seq, waves = self._next_dispatch()
            host = self._host_state(rows, ahead=len(self._window))
            t0 = self._clock()
            try:
                # the LAUNCH of the knob upload and the decode dispatch; the
                # program's device time is the trace's (and, fenced, t0's)
                with self.metrics.span("decode.launch", seq=seq,
                                       rows=len(rows), chained=int(chained),
                                       waves=waves):
                    if self._knobs_device is None:
                        self._knobs_device = {
                            k: self._place_rows(jnp.asarray(v))
                            for k, v in self._knobs.items()}
                    knobs = self._knobs_device
                    tok, chosen, carry, *extra = self._dispatch(
                        "decode", self._step_fn,
                        self.params, tokens_dev, active_dev,
                        self.pool.carry, knobs, *self._adapter_args())
            except FaultError:
                # the dispatch failed BEFORE running: the pooled carry was
                # never donated and stays valid. Everything already in the
                # window was dispatched BEFORE the fault and is healthy —
                # flush it through the delayed consumer (its tokens are
                # real), THEN evict + replay whatever rows remain (no gap
                # sample for the failed dispatch: nothing dispatched, and
                # the evicted batch anchors no future gap)
                self._drain_window(emitted)
                self._recover_step(self.scheduler.running, "fail")
                self._last_decode_end = None
                return emitted
            self.pool.carry = carry
            self.metrics.on_decode_dispatch(chained)
            # the (N, V) distribution never crosses to host — sampling is
            # fused into the step; only token ids + chosen log-probs will,
            # through ONE batched fence readback at this entry's DELAYED
            # consumption (_consume_window — THE declared delayed-consumer
            # site, serving/fences.py). t0 rides the entry so the
            # watchdog's elapsed covers the device work, not the launch
            self._window.append(_InFlight(tok, chosen, active, active_dev,
                                          rows, t0, had_running, extra,
                                          seq, chained, waves, host))
            # delayed consumer: fence the oldest entry once the window
            # exceeds its DECLARED depth knob (fences.WINDOW_KNOBS —
            # ASY308 rejects any other bound). dispatch_ahead=0 consumes
            # the entry just appended: dispatch-then-fence within one
            # step, byte-for-byte the pre-window engine
            while len(self._window) > self.dispatch_ahead:
                if not self._consume_window(emitted):
                    break
            if self._window and not self.scheduler.running:
                # the last rows finished at that consume: what is still
                # in flight is overshoot for rows that are gone. Read it
                # back here, inside the step, so that no device handle
                # outlives the batch (a caller that polls idle() and
                # never calls drain() would keep it for ever)
                self._drain_window(emitted)
            return emitted

    def drain(self) -> Dict[int, np.ndarray]:
        """Step until every submitted request has finished; returns
        ``{req_id: generated 1-based ids}`` for all RETAINED finished
        requests (all of them unless ``keep_finished``/``pop_result``
        evicted some)."""
        while not self.scheduler.idle():
            self.step()
        # the last consume can finish every row while NEWER dispatches
        # are still in flight (their readbacks belong to finished rows
        # — pure overshoot): flush them so no device handle outlives
        # the drain. The consumer's row filter discards every token.
        self.flush_window()
        return {rid: np.asarray(r.output, np.int32)
                for rid, r in self._finished.items()
                if r.state == FINISHED}

    # -- introspection -----------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return self.scheduler.queue_depth

    @property
    def active(self) -> int:
        return self.scheduler.active

    def idle(self) -> bool:
        return self.scheduler.idle()
