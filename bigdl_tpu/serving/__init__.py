"""bigdl_tpu.serving — continuous-batching inference engine.

The serving layer between the model zoo and the parallel stack: many
independent generation requests share ONE pooled, slot-indexed KV cache
and ONE compiled per-row decode program, with FIFO admission into rows
freed mid-flight (continuous batching). The engine serves any model
FAMILY that brings its programs through ``family.py``: ``TransformerLM``
by delegation to ``models/transformer.py``, and ``models/falcon_h1.py``
(a Mamba-2 mixer beside grouped-query attention in every layer), whose
carry holds per-slot state leaves beside ``k{i}``/``v{i}`` — the
float32 scan state ``ssm{i}`` and the convolution window ``conv{i}`` —
that the pool scatters at admission, zeroes on ``free()``, carries
through ``row_state``/``restore_row`` and counts in
``state_bytes_per_slot``. That family REFUSES, with a ``ValueError`` at
construction that names the option: ``prefix_cache``, ``speculative``,
``adapters``, ``kv_dtype="int8"``, ``mesh``/``parallelism``,
``admission="chunked"``/``"per_request"`` and ``tier`` (so also
``DisaggregatedEngine``, which is always tiered). Decoding is sampled PER ROW
(``sampling.py``): each request's ``SamplingParams`` (temperature,
top-k/top-p, penalties, seed, stop sets) ride as per-row runtime arrays
of the one compiled step — greedy and sampled requests mix freely with
zero recompiles, and per-row RNG lanes make a fixed seed bit-stable
across batching and slot readmission. Admission itself is batched and
shape-stable: ragged prompts prefill together through a bounded set of
power-of-two length buckets (``admission.py``), optionally reusing
shared-prefix K/V from a ref-counted radix cache (``prefix_cache.py``);
``admission="chunked"`` streams prompts in as bounded suffix-
continuation chunks interleaved with decode, so an arrival burst never
stalls in-flight rows for a whole admission wave (``chunked.py``).
The plane is OPERABLE under faults and overload (``scheduler.py`` +
``faults.py``): priority classes with per-request deadlines and
loss-free preemption (evicted rows resume byte-identically), bounded-
queue admission backpressure with shed/deadline-drop/degrade policies,
and a step watchdog + deterministic fault injector whose
retry-with-evict recovery replays failed, garbage, or stalled steps
without ever wedging the engine. Past one host loop, ``disagg.py``
splits the plane into a PREFILL POOL and DECODE POOLS with serialized
KV-row handoff between them (``KVPool.row_state``/``restore_row`` —
the same byte-exact payload the preemption stash speaks; in-process
queue or ``block_store`` transfer backends), token-identical to the
monolithic engine at zero extra compiles per pool — and ``health.py``
makes each POOL a failure domain: heartbeat/transfer-failure health
classification, decode-pool failover that reconstructs every stranded
row loss-free-or-replayed with token-identical streams, graceful
``drain_pool`` migration, backoff-hardened transfer retries, and an
occupancy autoscaler with hysteresis. The plane is MULTI-TENANT
(``lora.py`` + ``constrain.py``): a pooled per-row LoRA adapter bank
lets every request carry its own adapter id as runtime data of the one
compiled step (id 0 = the base model, mixed traffic recompiles
nothing), and per-row token-mask constrained decoding rides the same
knob arrays — both replay byte-identically through preemption,
handoff, and failover. And capacity scales past HBM (``kv_tier.py``):
a :class:`TieredKVStore` backs any engine with a budgeted host-RAM
spill tier — the BigDL paper's BlockManager storage level mirrored
below HBM — so cold KV rows spill as packed ``row_state`` bytes and
resume WITHOUT re-prefill, evicted warm prefixes demote/promote
through the same tier, and the preemption stash, disagg handoff
staging, and failover copies become one store with one byte budget.
See ``docs/serving.md``.

    from bigdl_tpu.serving import SamplingParams, ServingEngine

    eng = ServingEngine(lm, n_slots=8, compute_dtype=jnp.bfloat16,
                        prefix_cache=True)
    rid = eng.submit([3, 7, 2], max_new_tokens=32, eos_id=5,
                     sampling=SamplingParams(temperature=0.8,
                                             top_k=50, seed=42))
    outputs = eng.drain()            # {rid: 1-based token ids}
    print(eng.logprobs(rid))         # chosen-token model log-probs
    print(eng.metrics.summary())     # TTFT percentiles, tokens/sec, ...
"""

from bigdl_tpu.serving.admission import (
    AdmissionController, Degrade, bucket_len,
)
from bigdl_tpu.serving.autopilot import (
    ACTUATION_SITES, ActuatorBus, Autopilot, AutopilotConfig, Controller,
)
from bigdl_tpu.serving.chunked import ChunkedAdmissionController
from bigdl_tpu.serving.constrain import (
    ConstraintCursor, ConstraintError, TokenDFA, fixed_sequence,
    from_token_sets,
)
from bigdl_tpu.serving.disagg import (
    BlockStoreTransfer, DecodeWorker, DisaggregatedEngine,
    InProcessTransfer, KVTransfer, PrefillWorker, ROW_PAYLOAD_KEYS,
    pack_payload, payload_header, unpack_payload,
)
from bigdl_tpu.serving.health import (
    AutoscalerConfig, HealthConfig, OccupancyAutoscaler, PoolHealth,
    TransferRetryConfig,
)
from bigdl_tpu.serving.engine import ServingEngine
from bigdl_tpu.serving.faults import (
    FaultError, FaultInjector, SteppingClock, VirtualClock, WatchdogConfig,
)
from bigdl_tpu.serving.fences import FENCE_SITES, fence, fence_wait
from bigdl_tpu.serving.kv_pool import KVPool
from bigdl_tpu.serving.kv_tier import TieredKVStore
from bigdl_tpu.serving.lora import AdapterBank, AdapterSpec
from bigdl_tpu.serving.metrics import ServingMetrics
from bigdl_tpu.serving.prefix_cache import PrefixCache
from bigdl_tpu.serving.sampling import SamplingParams
from bigdl_tpu.serving.scheduler import Request, Scheduler
from bigdl_tpu.serving.sharded import (
    ShardedEngine, ShardedKVPool, make_mesh,
)
from bigdl_tpu.serving.speculative import SpeculativeConfig

__all__ = ["ServingEngine", "KVPool", "ServingMetrics", "Request",
           "Scheduler", "AdmissionController",
           "ChunkedAdmissionController", "PrefixCache",
           "SamplingParams", "SpeculativeConfig", "bucket_len",
           "ShardedEngine", "ShardedKVPool", "make_mesh",
           "Degrade", "FaultError",
           "FaultInjector", "VirtualClock", "WatchdogConfig",
           "FENCE_SITES", "fence", "fence_wait",
           "DisaggregatedEngine", "PrefillWorker", "DecodeWorker",
           "KVTransfer", "InProcessTransfer", "BlockStoreTransfer",
           "ROW_PAYLOAD_KEYS", "pack_payload", "payload_header",
           "unpack_payload", "HealthConfig", "PoolHealth",
           "TransferRetryConfig", "AutoscalerConfig",
           "OccupancyAutoscaler", "AdapterBank", "AdapterSpec",
           "TokenDFA", "ConstraintCursor", "ConstraintError",
           "fixed_sequence", "from_token_sets", "TieredKVStore",
           "ACTUATION_SITES", "ActuatorBus", "Autopilot",
           "AutopilotConfig", "Controller", "SteppingClock"]
