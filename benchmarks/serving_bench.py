"""Continuous-batching serving throughput under mixed arrivals, plus the
batched-admission scenario (``--scenario admission``): ragged prompt
lengths + a shared system prefix, comparing PR 1's per-request admission
(B=1 prefill per request, one XLA trace per NOVEL prompt length,
mid-admission) against the batched admission subsystem (bucketed masked
multi-row prefill + prefix cache — bounded compiled-program set). The
admission scenario deliberately runs COLD: the compile stall on novel
lengths IS the phenomenon under study.

``--scenario sharded`` exercises the sharded serving plane
(``serving/sharded.py``) on an EMULATED device mesh (CPU host split
into virtual devices via ``XLA_FLAGS=--xla_force_host_platform_
device_count``): the same mixed greedy/sampled trace through the
single-device engine and a slot-data-parallel engine, asserting
token-identical outputs and ONE compiled decode program on either
path, and reporting per-step wall time + cross-shard admission
imbalance. On a CPU host the decode step is compute-bound and the
virtual devices share one socket, so the sharded per-step time is the
PARTITIONING OVERHEAD (scatter/gather glue) rather than a speedup —
on real hardware each shard owns its rows' weight reads and the step
scales with the mesh (the decode_bench batching numbers, per shard).

``--scenario kv_quant`` exercises the quantized KV serving path
(``kv_dtype="int8"``: per-(slot, head)-scaled int8 pooled K/V with the
dequant fused into the pooled decode-attention read —
``ops/decode_attention.py``): the same greedy trace through a float-KV
engine and an int8-KV engine at EQUAL slot counts (identical compile
counts — quantization is a storage-format choice, never a recompile —
plus per-request greedy agreement, reported honestly: near-uniform
untrained-model logits flip a few near-tie rollouts at ANY sub-fp32
cache precision, see run_kv_quant), then through an int8 engine sized
to the SAME simulated HBM budget (the headline: ~2x the concurrent
slots of a bf16 cache, ~4x fp32, with bitwise-identical outputs
ASSERTED across the slot-count change). On a CPU host the decode step
is compute-bound so equal-slot tokens/sec shows the quantize/dequant
epilogue cost rather than the bandwidth win; the capacity ratio is
hardware-independent (bytes are bytes).

``--scenario speculative`` exercises draft-and-verify decoding
(``serving/speculative.py``): one mixed speculative/normal trace
(greedy spec rows, ``draft_tokens=0`` normal rows, fixed-seed sampled
rows) through the plain engine and a speculative engine — asserting
equal target-side compile counts (ONE verify program vs ONE decode
program; per-row draft length is runtime data) and byte-identical
greedy outputs, and reporting accept rate + tokens-per-step (the
hardware-independent speedup bound; the bench drafts with a
weight-tied copy of the target since untrained independent drafts
accept ~nothing — see run_speculative's docstring).

``--scenario chunked`` exercises chunked streaming admission
(``serving/chunked.py``, ``admission="chunked"``): short-prompt steady
rows already mid-decode when a burst of long prompts lands all at once,
replayed through batched and chunked admission with both paths fully
warm — asserting token-identical outputs, EQUAL compile counts (one
decode program each, equally many prefill programs, zero programs
compiled inside the timed pass), and that the steady rows'
DECODE-STALL p99 (their inter-token gap while the burst ingests)
shrinks under chunked admission, whose pump spends at most
``chunk_budget`` prompt tokens per step instead of one whole admission
wave. Total wall time is HIGHER chunked (per-chunk dispatch + scatter
overhead, reported) — the scenario measures a latency shaper, not a
throughput win.

``--scenario disagg`` exercises the disaggregated serving plane
(``serving/disagg.py``): the same mixed greedy/sampled trace through
the monolithic engine and a prefill-pool → decode-pools split with
in-process KV-row handoff — asserting token-identical outputs and
EQUAL compile counts per pool (the pools ride the shared per-(model,
dtype) step caches; the timed passes compile nothing), and reporting
decode-gap p99 on each path plus the per-handoff transfer bytes and
latency percentiles. On one CPU host the split shows handoff OVERHEAD
(both pools share the socket); the interference win is per-pool
hardware, priced analytically by pod_projection's disagg rows.

``--scenario failover`` exercises POOL-LEVEL fault tolerance
(``serving/health.py``): a decode pool is KILLED mid-stream at several
fault seeds (each seed varies the victim, the kill step, and the
sampling lanes) and the scenario ASSERTS token-identical outputs vs
the monolithic engine for every affected row plus zero new compiles
on the surviving pool, reporting failover latency p50/p99 and the
migrated/replayed row split. A second section runs the occupancy
autoscaler (1 active + 1 standby pool) through a bursty
submit-drain-idle cycle and asserts it is FLAP-FREE: at most one
activation per burst, at most one drain-and-retire per lull, streams
still identical.

``--scenario sampling`` exercises the per-row sampling subsystem
(``serving/sampling.py``): mixed greedy/sampled traffic (distinct
temperature/top-k/top-p/penalty mixes, fixed seeds) against an
all-greedy baseline on the same prompts — asserting ZERO extra
decode-program compiles (every knob mix is runtime data of ONE compiled
sampled step), greedy rows unperturbed by sampled neighbors, and
reporting the fused epilogue's tokens/sec overhead.

``--scenario sampler`` times :func:`~bigdl_tpu.serving.sampling.
sample_rows` ALONE (one jitted call, no model) at the serving cells'
``(n_slots, vocab)`` shapes under three knob mixes: all greedy, half
``temperature 0.8, top_k 50`` (the cells' traffic), and that mix with
one nucleus-only row (``top_p 0.9``, no ``top_k``: the row that makes
the step sort the vocabulary). What the sampling epilogue costs a
decode step, and what one wide row adds to it.

The mixed-arrival question decode_bench.py leaves open: decode_bench
measures a FIXED batch decoded in lockstep, but production traffic is
independent requests arriving at staggered times with different
prompt/output lengths. The default scenario replays one such trace two
ways:

* **sequential** — requests served one at a time in arrival order with
  the per-call KV-cached path (``get_decode_step``/``get_prefill_step``,
  jit-warm, i.e. the strongest fair baseline for ``generate()``-style
  serving: later requests queue behind earlier ones);
* **engine** — the same trace through ``bigdl_tpu.serving.ServingEngine``
  (pooled paged KV cache + continuous batching: arrivals are admitted
  into freed slots mid-flight and every step decodes all active rows).

Both paths are greedy and produce IDENTICAL tokens (pinned by
tests/test_serving.py); the bench isolates the scheduling/batching win.
Reports aggregate tokens/sec (first arrival → last finish) and
time-to-first-token percentiles (arrival → first generated token, i.e.
queueing + prefill + first step). Prints ONE JSON line.

``--scenario async`` sweeps the dispatch-ahead window (``dispatch_ahead``
W in {0, 1, 2, 4}) over the default mixed trace's prompts, asserting
byte-identical streams and equal compile counts at every W and that
``host_frac`` drops at W >= 1 — the measured before/after row for the
delayed-consumer decode refactor (docs/async_readiness.md).

Scale note: decode is weight-read-bound on an accelerator, so a pooled
step costs ~a single-row step and the win approaches slot occupancy
(decode_bench measured 137M bf16 at 1,740 tok/s B=1 vs 7,438 B=8 on
v5e — 4.3x from batching alone). On a CPU host the step is COMPUTE-
bound (an N-row step costs ~N/2.5 single-row steps), so the default
config is sized small enough that batching + dispatch amortization
still shows the scheduling win end-to-end; use ``--model 137m --variant
bf16`` on real hardware.

    python -m benchmarks.serving_bench
    ... --model tiny --requests 12 --slots 12 --stagger_ms 10  # defaults
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

MODELS = {
    # CPU-friendly configs + the decode_bench flagship for TPU runs
    "tiny": dict(vocab=512, hidden=128, layers=2, heads=4, max_len=128),
    "small": dict(vocab=2048, hidden=256, layers=4, heads=8, max_len=256),
    "137m": dict(vocab=32768, hidden=768, layers=12, heads=12, max_len=512),
}


def build(name: str, variant: str):
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.utils.random_gen import RNG

    cfg = MODELS[name]
    RNG.set_seed(17)
    lm = TransformerLM(cfg["vocab"], hidden_size=cfg["hidden"],
                       n_heads=cfg["heads"], n_layers=cfg["layers"],
                       max_len=cfg["max_len"], output="logits")
    lm._ensure_params()
    lm.evaluate()
    dtype = {"fp32": None, "bf16": jnp.bfloat16}[variant]
    return lm, dtype, cfg


def make_trace(cfg, n_requests: int, gen_tokens: int, stagger_s: float,
               seed: int = 5):
    """(arrival_s, prompt 1-based ids, max_new) per request — prompt
    lengths cycle through a few buckets so both paths hit the same
    prefill compilation buckets."""
    rng = np.random.RandomState(seed)
    buckets = [5, 9, 17]
    trace = []
    for i in range(n_requests):
        plen = buckets[i % len(buckets)]
        prompt = rng.randint(1, cfg["vocab"] + 1, size=(plen,)).tolist()
        trace.append((i * stagger_s, prompt, gen_tokens))
    return trace


def _percentiles(vals, qs=(50, 90, 99)):
    arr = np.asarray(vals) if vals else np.zeros((1,))
    return {f"p{q}_ms": round(float(np.percentile(arr, q)) * 1e3, 2)
            for q in qs}


def run_sequential(lm, dtype, trace):
    """Arrival-ordered one-at-a-time serving on the warm per-call path."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import (
        get_decode_step, get_prefill_step, serving_params,
    )

    step, init_carry = get_decode_step(lm, dtype)
    prefill = get_prefill_step(lm, dtype)
    P = jax.device_put(serving_params(lm, dtype))
    ttfts, n_tokens = [], 0
    t0 = time.perf_counter()
    for arrival, prompt, n_new in trace:
        while time.perf_counter() - t0 < arrival:
            time.sleep(0.0005)
        t_arr = t0 + arrival
        carry = init_carry(1)
        p0 = [t - 1 for t in prompt]
        if len(p0) > 1:
            _, carry = prefill(P, jnp.asarray([p0[:-1]], jnp.int32), carry)
        tok = jnp.asarray([p0[-1]], jnp.int32)
        for i in range(n_new):
            logp, carry = step(P, tok, carry)
            nxt = int(jnp.argmax(logp[0]))
            if i == 0:
                ttfts.append(time.perf_counter() - t_arr)
            tok = jnp.asarray([nxt], jnp.int32)
            n_tokens += 1
    wall = time.perf_counter() - t0
    return {"tokens_per_sec": round(n_tokens / wall, 1),
            "wall_s": round(wall, 3), "tokens": n_tokens,
            "ttft": _percentiles(ttfts)}


def run_engine(lm, dtype, trace, n_slots: int, policy: str):
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                        policy=policy)
    pending = sorted(trace, key=lambda r: r[0])
    arrivals = {}                  # req_id -> scheduled arrival offset
    n_tokens, i = 0, 0
    t0 = time.perf_counter()
    while i < len(pending) or not eng.idle():
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i][0] <= now:
            arrival, prompt, n_new = pending[i]
            arrivals[eng.submit(prompt, max_new_tokens=n_new)] = arrival
            i += 1
        emitted = eng.step()
        n_tokens += len(emitted)
        if not emitted and i < len(pending):
            time.sleep(max(0.0, pending[i][0] - (time.perf_counter() - t0)))
    wall = time.perf_counter() - t0
    # TTFT from the SCHEDULED arrival (same clock start as the sequential
    # path — a submit() that had to wait out an in-flight decode step
    # charges that queueing delay to the engine, not to the trace)
    ttfts = [eng.request(rid).first_token_time - (t0 + arr)
             for rid, arr in arrivals.items()]
    # the per-step host-vs-device split: host_step_s is the Python the
    # device pipeline waits on between dispatches (scheduling,
    # admission bookkeeping, per-token accounting) — THE before-number
    # the async dispatch-ahead refactor will cite (docs/
    # async_readiness.md); host_frac is its share of the serve
    host_total, n_host = eng.metrics.metrics.get("serving/host_step_s")
    device_total = eng.metrics.device_seconds
    return {"tokens_per_sec": round(n_tokens / wall, 1),
            "wall_s": round(wall, 3), "tokens": n_tokens,
            "ttft": _percentiles(ttfts),
            "occupancy_mean": round(
                eng.metrics.metrics.mean("serving/slot_occupancy"), 3),
            "host_step": _percentiles(
                eng.metrics.metrics.values("serving/host_step_s"),
                qs=(50, 99)),
            "host_frac": round(
                host_total / max(host_total + device_total, 1e-9), 3)
            if n_host else 0.0}


def make_ragged_trace(cfg, n_requests: int, gen_tokens: int,
                      shared_frac: float = 0.5, prefix_len: int = 12,
                      seed: int = 7):
    """The admission-stress trace: EVERY prompt has a distinct length
    (the per-request path's worst case — one compile per length) and a
    ``shared_frac`` fraction open with one shared ``prefix_len``-token
    system prefix (the prefix cache's best case)."""
    rng = np.random.RandomState(seed)
    prefix = rng.randint(1, cfg["vocab"] + 1, size=(prefix_len,)).tolist()
    # distinct lengths while they fit; wrap once a prompt plus its
    # generation budget would overflow the model's max_len
    max_plen = max(cfg["max_len"] - gen_tokens + 1, 3)
    plens = [2 + i % (max_plen - 1) for i in range(n_requests)]
    eligible = [i for i in range(n_requests) if plens[i] > prefix_len + 1]
    shared = set(rng.choice(eligible,
                            size=int(len(eligible) * shared_frac),
                            replace=False).tolist()) if eligible else set()
    with_prefix, without = [], []
    for i in range(n_requests):
        plen = plens[i]
        if i in shared:
            prompt = prefix + rng.randint(
                1, cfg["vocab"] + 1, size=(plen - prefix_len,)).tolist()
            with_prefix.append((0.0, prompt, gen_tokens))
        else:
            prompt = rng.randint(1, cfg["vocab"] + 1, size=(plen,)).tolist()
            without.append((0.0, prompt, gen_tokens))
    # interleave SUBMIT order so shared-prefix prompts spread across
    # admission waves: within one wave every lookup precedes that
    # wave's inserts, so same-wave repeats can't hit — spreading them
    # is what exercises the cache-hit path
    trace, step = [], max(1, n_requests // (len(with_prefix) + 1))
    for j in range(n_requests):
        src = with_prefix if (j % step == step - 1 and with_prefix) \
            else (without or with_prefix)
        trace.append(src.pop(0))
    return trace


def run_admission_mode(lm, dtype, trace, n_slots: int, admission: str,
                       prefix_cache: bool):
    """One cold engine pass; reports admission-phase time and the
    compiled prefill-program count next to the usual aggregates."""
    from bigdl_tpu.serving import ServingEngine

    import jax

    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                        admission=admission, prefix_cache=prefix_cache)
    for _, prompt, n_new in trace:
        eng.submit(prompt, max_new_tokens=n_new)
    # admission cost is measured HERE, bench-side: the engine no longer
    # completion-fences its prefill dispatches (they overlap the decode
    # step — the PR 12 worksheet's cashed-in "deletable" entries, see
    # docs/async_readiness.md), so the per-phase serving/prefill_s
    # timer is gone by design. A cold-path bench may block freely
    # (reachability-exempt), so reproduce the OLD per-call semantics at
    # the bench level: wrap the engine's dispatch hook and bracket each
    # "prefill"-site dispatch with a completion wait. That times
    # exactly what the deleted phase timer timed — prefill traces +
    # dispatches, one window per CALL — which is what differentiates
    # the modes warm or cold (per-request pays one dispatch+sync per
    # request, batched one per bucket); timing whole admission waves
    # instead lets the mode-independent wave overhead dilute the ratio
    # to ~1 on a warm process.
    admission_s, n_prefill_calls = 0.0, 0
    orig_dispatch = eng._dispatch

    def _timed_dispatch(site, fn, *args):
        nonlocal admission_s, n_prefill_calls
        if site != "prefill":
            return orig_dispatch(site, fn, *args)
        t1 = time.perf_counter()
        out = orig_dispatch(site, fn, *args)
        jax.block_until_ready(out)
        admission_s += time.perf_counter() - t1
        n_prefill_calls += 1
        return out

    eng._dispatch = _timed_dispatch
    t0 = time.perf_counter()
    outs = eng.drain()
    wall = time.perf_counter() - t0
    if admission == "batched":
        programs = eng._batch_prefill_fn._jitted._cache_size()
    else:
        programs = eng._prefill_fn._jitted._cache_size()
    out = {"wall_s": round(wall, 3),
           "admission_s": round(admission_s, 3),
           "prefill_calls": n_prefill_calls,
           "prefill_programs": programs,
           "ttft": _percentiles([eng.request(rid).first_token_time
                                 - eng.request(rid).submit_time
                                 for rid in outs])}
    if prefix_cache:
        out["prefix_hit_rate"] = round(eng.prefix_cache.hit_rate(), 3)
        out["prefix_hit_tokens"] = eng.prefix_cache.hit_tokens
    return out, outs


def run_admission(model: str = "tiny", variant: str = "fp32",
                  n_requests: int = 20, gen_tokens: int = 4,
                  n_slots: int = 8, shared_frac: float = 0.5,
                  prefix_len: int = 12) -> dict:
    """Batched vs per-request ADMISSION on the ragged + shared-prefix
    trace. Decode is pre-warmed (both paths share the pooled step); the
    prefill paths start cold on purpose — bounding that compile set is
    the subsystem's reason to exist. ``n_slots < n_requests`` so
    admission happens in waves and later waves hit the prefix cache."""
    from bigdl_tpu.serving import ServingEngine, bucket_len

    lm, dtype, cfg = build(model, variant)
    trace = make_ragged_trace(cfg, n_requests, gen_tokens,
                              shared_frac, prefix_len)
    # warm ONLY the shared pooled decode step (1-token prompts touch no
    # prefill path), so the comparison isolates admission cost
    warm = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype)
    warm.submit([1], max_new_tokens=2)
    warm.drain()

    per_req, outs_p = run_admission_mode(lm, dtype, trace, n_slots,
                                         "per_request", False)
    batched, outs_b = run_admission_mode(lm, dtype, trace, n_slots,
                                         "batched", True)
    match = (sorted(outs_p) == sorted(outs_b)
             and all(np.array_equal(outs_p[k], outs_b[k])
                     for k in outs_p))
    distinct = {len(p) - 1 for _, p, _ in trace if len(p) > 1}
    buckets = {bucket_len(n, cfg["max_len"]) for n in distinct}
    return {
        "metric": "serving_admission_ragged_shared_prefix",
        "model": model, "variant": variant, "requests": n_requests,
        "gen_tokens": gen_tokens, "slots": n_slots,
        "shared_frac": shared_frac, "prefix_len": prefix_len,
        "distinct_prompt_lengths": len(distinct),
        "length_buckets": len(buckets),
        "outputs_match": match,
        "per_request": per_req, "batched": batched,
        "admission_speedup": round(
            per_req["admission_s"] / max(batched["admission_s"], 1e-9), 2),
        "wall_speedup": round(
            per_req["wall_s"] / max(batched["wall_s"], 1e-9), 2),
    }


def make_sampling_trace(cfg, n_requests: int, gen_tokens: int,
                        seed: int = 13):
    """Mixed greedy/sampled traffic: even requests are greedy (default
    params), odd requests cycle through distinct knob mixes
    (temperature/top-k/top-p/penalties, fixed per-request seeds) — the
    one-compiled-program-for-every-mix claim under test."""
    from bigdl_tpu.serving import SamplingParams

    rng = np.random.RandomState(seed)
    buckets = [5, 9, 17]
    mixes = [
        dict(temperature=0.7, top_k=20, seed=101),
        dict(temperature=1.0, top_p=0.95, repetition_penalty=1.2,
             seed=102),
        dict(temperature=1.3, top_k=50, top_p=0.8, presence_penalty=0.4,
             seed=103),
        dict(temperature=0.9, frequency_penalty=0.3, min_tokens=4,
             seed=104),
    ]
    trace = []
    for i in range(n_requests):
        plen = buckets[i % len(buckets)]
        prompt = rng.randint(1, cfg["vocab"] + 1, size=(plen,)).tolist()
        sp = SamplingParams(**mixes[(i // 2) % len(mixes)]) \
            if i % 2 else None
        trace.append((prompt, gen_tokens, sp))
    return trace


def _run_sampling_engine(lm, dtype, trace, n_slots: int, greedy: bool):
    """One drain()-to-empty pass; greedy=True strips every request's
    SamplingParams (the baseline same-prompts workload)."""
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype)
    rids = [eng.submit(p, max_new_tokens=n,
                       sampling=None if greedy else sp)
            for p, n, sp in trace]
    t0 = time.perf_counter()
    outs = eng.drain()
    wall = time.perf_counter() - t0
    n_tokens = int(sum(len(v) for v in outs.values()))
    return eng, rids, outs, {
        "tokens_per_sec": round(n_tokens / wall, 1),
        "wall_s": round(wall, 3), "tokens": n_tokens,
        "decode_programs": eng._step_fn._cache_size(),
    }


def run_sampling(model: str = "tiny", variant: str = "fp32",
                 n_requests: int = 16, gen_tokens: int = 32,
                 n_slots: int = 8) -> dict:
    """Mixed greedy/sampled serving vs an all-greedy baseline on the
    SAME prompts. The contract under test: (a) the mixed run adds ZERO
    decode-program compiles beyond the greedy baseline (knobs are
    runtime per-row arrays of one compiled sampled step), and (b) the
    greedy requests inside the mixed batch produce tokens identical to
    the greedy-only run (sampled neighbors don't perturb greedy rows).
    Reports the tokens/sec delta — the fused sampling epilogue's cost."""
    lm, dtype, cfg = build(model, variant)
    trace = make_sampling_trace(cfg, n_requests, gen_tokens)
    # warm the (model, dtype, n_slots) step + prefill buckets so both
    # timed passes are compile-free and the delta is pure epilogue math
    _run_sampling_engine(lm, dtype, [(p, 2, sp) for p, _, sp in trace],
                         n_slots, greedy=False)
    eng_g, rids_g, outs_g, greedy_stats = _run_sampling_engine(
        lm, dtype, trace, n_slots, greedy=True)
    eng_m, rids_m, outs_m, mixed_stats = _run_sampling_engine(
        lm, dtype, trace, n_slots, greedy=False)
    greedy_rows_match = all(
        np.array_equal(outs_g[rg], outs_m[rm])
        for (p, n, sp), rg, rm in zip(trace, rids_g, rids_m)
        if sp is None)
    s = eng_m.metrics.summary()
    return {
        "metric": "serving_mixed_sampling_tokens_per_sec",
        "model": model, "variant": variant, "requests": n_requests,
        "gen_tokens": gen_tokens, "slots": n_slots,
        "greedy": greedy_stats, "mixed": mixed_stats,
        "extra_decode_compiles": (mixed_stats["decode_programs"]
                                  - greedy_stats["decode_programs"]),
        "greedy_rows_match": bool(greedy_rows_match),
        "sampled_row_frac": round(s.get("serving/sampled_row_frac", 0.0),
                                  3),
        "mean_logprob": round(s.get("serving/mean_logprob", 0.0), 3),
        "sampling_overhead_pct": round(
            100.0 * (greedy_stats["tokens_per_sec"]
                     / max(mixed_stats["tokens_per_sec"], 1e-9) - 1.0),
            1),
    }


#: ``(n_slots, vocab)`` of the benchmark's serving cells (gpt2m-serve-chat,
#: glm47flash-serve-longctx, trinity-serve-mixed, falconh1-serve-reason)
SAMPLER_SHAPES = ((32, 50257), (32, 19360), (16, 25024), (32, 32640))


def _device_ms(fn, args, reps: int):
    """Mean device time of one call of the jitted ``fn``, from a
    profiler trace of ``reps`` calls; None where the trace holds no
    device plane (the CPU)."""
    import tempfile

    import jax

    from benchmark import trace_reduce

    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(reps):
                jax.block_until_ready(fn(*args))
        reduced = trace_reduce.reduce_dir(trace_dir)
    if reduced is None:
        return None
    (program,) = reduced["programs"].values()
    return program["mean_ms"]


def run_sampler(shapes=SAMPLER_SHAPES, reps: int = 10, sample_rows=None,
                seed: int = 13) -> dict:
    """Milliseconds of one jitted ``sample_rows`` call by shape and knob
    mix, every row active, after three warm calls: ``device_ms`` from a
    profiler trace of ``reps`` calls (on the chip a call's dispatch, 0.2
    ms, is longer than the program), ``wall_ms`` the median of ``reps``
    blocked calls. ``sample_rows`` defaults to the tree's; a caller
    comparing two trees hands in the other's."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.serving import sampling

    fn = jax.jit(sample_rows or sampling.sample_rows)
    rng = np.random.RandomState(seed)
    device_ms, wall_ms = {}, {}
    for n, v in shapes:
        logp = jax.nn.log_softmax(
            jnp.asarray(rng.randn(n, v).astype(np.float32) * 3.0), axis=-1)
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
        counts = jnp.zeros((n, v), jnp.int32)
        pmask = jnp.zeros((n, v), bool)
        active = jnp.ones((n,), bool)
        for mix in ("greedy", "half_top_k", "one_nucleus"):
            knobs = sampling.make_knob_rows(n, vocab=v)
            if mix != "greedy":
                knobs["temperature"][::2] = 0.8
                knobs["top_k"][::2] = 50
            if mix == "one_nucleus":
                knobs["temperature"][1] = 0.8
                knobs["top_p"][1] = 0.9
            knobs = {k: jnp.asarray(a) for k, a in knobs.items()}
            args = (logp, keys, knobs, counts, pmask, active)
            for _ in range(3):
                jax.block_until_ready(fn(*args))
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                times.append(time.perf_counter() - t0)
            wall_ms[f"{n}x{v}/{mix}"] = round(1e3 * float(np.median(times)), 4)
            ms = _device_ms(fn, args, reps)
            if ms is not None:
                device_ms[f"{n}x{v}/{mix}"] = round(ms, 4)
    dev = jax.devices()[0]
    return {"metric": "sample_rows_ms", "device": dev.device_kind,
            "platform": dev.platform, "reps": reps,
            "device_ms": device_ms, "wall_ms": wall_ms}


def make_spec_trace(cfg, n_requests: int, gen_tokens: int, seed: int = 23):
    """Mixed speculative/normal traffic for ``--scenario speculative``:
    half the requests are greedy speculative (the engine's default draft
    budget), a quarter are explicit NORMAL rows (``draft_tokens=0`` —
    plain decode inside the same batch), and a quarter are sampled with
    fixed per-request seeds. One trace exercises every per-row draft
    length the one verify program must cover."""
    from bigdl_tpu.serving import SamplingParams

    rng = np.random.RandomState(seed)
    buckets = [5, 9, 17]
    trace = []
    for i in range(n_requests):
        plen = buckets[i % len(buckets)]
        prompt = rng.randint(1, cfg["vocab"] + 1, size=(plen,)).tolist()
        if i % 4 == 3:
            sp, dt = SamplingParams(temperature=0.8, top_k=20,
                                    seed=200 + i), None
        elif i % 4 == 1:
            sp, dt = None, 0               # normal row in the spec batch
        else:
            sp, dt = None, None            # greedy speculative
        trace.append((prompt, gen_tokens, sp, dt))
    return trace


def _run_spec_engine(lm, draft, dtype, trace, n_slots: int, k: int):
    """One submit-all drain()-to-empty pass; ``draft=None`` is the plain
    (non-speculative) baseline engine on the same trace."""
    from bigdl_tpu.serving import ServingEngine, SpeculativeConfig

    eng = ServingEngine(
        lm, n_slots=n_slots, compute_dtype=dtype,
        speculative=None if draft is None
        else SpeculativeConfig(draft, k=k))
    rids = [eng.submit(p, max_new_tokens=n, sampling=sp, draft_tokens=dt)
            for p, n, sp, dt in trace]
    t0 = time.perf_counter()
    outs = eng.drain()
    wall = time.perf_counter() - t0
    n_tokens = int(sum(len(v) for v in outs.values()))
    # target-side program count: the one decode program (baseline) vs
    # the one verify program (speculative) — the equal-compiles claim
    step_fn = eng._step_fn if draft is None else eng._spec.verify_fn
    _, n_steps = eng.metrics.metrics.get("serving/queue_depth")
    return eng, rids, outs, {
        "tokens_per_sec": round(n_tokens / wall, 1),
        "wall_s": round(wall, 3), "tokens": n_tokens,
        "engine_steps": int(n_steps),
        "target_programs": step_fn._cache_size(),
    }


def run_speculative(model: str = "tiny", variant: str = "fp32",
                    n_requests: int = 16, gen_tokens: int = 24,
                    n_slots: int = 8, draft_k: int = 3) -> dict:
    """Speculative vs plain serving on one mixed spec/normal trace.

    The contracts under test: (a) the speculative engine runs ONE
    target-side program (the fixed-width verify step) where the
    baseline runs one decode program — per-row draft lengths, normal
    ``draft_tokens=0`` rows, and budget-capped rows are all runtime
    data, so the mixed trace adds ZERO compiles on either side; (b)
    greedy requests produce byte-identical outputs through either
    engine (verification is argmax agreement for temperature-0 rows);
    (c) tokens-per-step > 1 at the reported accept rate.

    Draft honesty note: these bench models are UNTRAINED, and an
    independently-initialized small draft proposes essentially
    uncorrelated tokens (accept rate ~0 — the machinery still emits the
    exact baseline stream, just one token per step). So the bench
    drafts with a same-seed WEIGHT-TIED copy of the target. Even tied,
    the untrained model's near-uniform logits leave argmax on a knife
    edge the chunked verify path and the single-token draft path break
    differently (different float summation order), so the measured
    accept rate sits mid-range (~0.4 on the default trace — sampled
    rows also accept at P(draw == argmax), which is low at temperature
    0.8) rather than near 1; a trained draft's real logit gaps push it
    toward its true agreement. tokens_per_step > 1 and the exact
    contracts are what this scenario pins; the engine's correctness is
    draft-independent either way (tests/test_serving_speculative.py).

    On a CPU host the target step is compute-bound, so the k+1 draft
    dispatches plus the S-wide verify cost MORE wall time than they
    save — tokens_per_sec here measures that overhead, not the win. On
    an accelerator decode is weight-read-bound and a verify step costs
    ~one decode step, so the win approaches tokens_per_step (the
    hardware-independent number this scenario reports)."""
    lm, dtype, cfg = build(model, variant)
    draft, _, _ = build(model, variant)        # same seed -> weight-tied
    trace = make_spec_trace(cfg, n_requests, gen_tokens)
    warm = [(p, 2, sp, dt) for p, _, sp, dt in trace[:4]]

    _run_spec_engine(lm, None, dtype, warm, n_slots, draft_k)
    eng_b, rids_b, outs_b, base_stats = _run_spec_engine(
        lm, None, dtype, trace, n_slots, draft_k)
    _run_spec_engine(lm, draft, dtype, warm, n_slots, draft_k)
    eng_s, rids_s, outs_s, spec_stats = _run_spec_engine(
        lm, draft, dtype, trace, n_slots, draft_k)

    greedy_match = all(
        np.array_equal(outs_b[rb], outs_s[rs])
        for (p, n, sp, dt), rb, rs in zip(trace, rids_b, rids_s)
        if sp is None)
    # the two CI-pinned contracts hold in any standalone run too (the
    # kv_quant scenario's convention): a green bench line IS the claim
    assert spec_stats["target_programs"] == base_stats["target_programs"], (
        f"speculative engine compiled {spec_stats['target_programs']} "
        f"target program(s) vs baseline {base_stats['target_programs']} — "
        "per-row draft lengths must stay runtime data")
    assert greedy_match, (
        "greedy speculative outputs diverged from the baseline engine")
    s = eng_s.metrics.summary()
    return {
        "metric": "serving_speculative_tokens_per_step",
        "model": model, "variant": variant, "requests": n_requests,
        "gen_tokens": gen_tokens, "slots": n_slots, "draft_k": draft_k,
        "baseline": base_stats, "speculative": spec_stats,
        "extra_target_compiles": (spec_stats["target_programs"]
                                  - base_stats["target_programs"]),
        "draft_programs": eng_s._spec._draft_step_fn._cache_size(),
        "greedy_outputs_match": bool(greedy_match),
        "accept_rate": round(s.get("serving/accept_rate", 0.0), 3),
        "tokens_per_step": round(s.get("serving/tokens_per_step", 0.0), 3),
        "step_ratio": round(base_stats["engine_steps"]
                            / max(spec_stats["engine_steps"], 1), 2),
    }


def make_slo_trace(cfg, n_requests: int, seed: int = 41,
                   hi_frac: float = 0.25, burst: int = 4,
                   burst_gap_s: float = 0.03, deadline_s: float = 2.0):
    """The overload trace for ``--scenario slo``: BURSTY arrivals
    (requests land in back-to-back clusters of ``burst`` separated by
    ``burst_gap_s`` — a Poisson-process caricature sharpened until the
    queue actually builds) with HEAVY-TAIL decode lengths (a geometric
    body plus a long tail: most requests want a few tokens, a few want
    many — the mix that makes FIFO head-of-line blocking hurt) and a
    ``hi_frac`` fraction of HIGH-PRIORITY interactive requests
    (priority 10, tight deadline) scattered through the low-priority
    bulk. Every request carries ``deadline_s`` so goodput-under-SLO is
    measurable on both engines. Returns ``(arrival_s, prompt, max_new,
    priority, deadline_s)`` tuples."""
    rng = np.random.RandomState(seed)
    plens = [3, 5, 9]
    trace = []
    for i in range(n_requests):
        arrival = (i // burst) * burst_gap_s
        plen = plens[i % len(plens)]
        prompt = rng.randint(1, cfg["vocab"] + 1, size=(plen,)).tolist()
        # heavy tail: geometric body, every 5th request from the tail
        n_new = int(min(4 + rng.geometric(0.35), 12))
        if i % 5 == 4:
            n_new = int(min(16 + rng.geometric(0.15), 40))
        hi = (i % max(2, int(round(1 / max(hi_frac, 1e-9)))) == 1)
        pri = 10 if hi else 0
        dl = deadline_s * (0.5 if hi else 1.5)
        trace.append((arrival, prompt, n_new, pri, dl))
    return trace


def _run_slo_engine(lm, dtype, trace, n_slots: int, policy: str,
                    max_queue):
    """Replay one timed SLO trace through an engine: submit each
    request at its scheduled arrival (host clock), honoring priorities
    and deadlines; report goodput + latency percentiles and the
    resilience counters."""
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                        policy=policy, max_queue=max_queue)
    pending = sorted(enumerate(trace), key=lambda r: r[1][0])
    rids = {}                 # trace index -> req id
    i = 0
    t0 = time.perf_counter()
    while i < len(pending) or not eng.idle():
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i][1][0] <= now:
            ti, (arr, prompt, n_new, pri, dl) = pending[i]
            rids[ti] = eng.submit(prompt, max_new_tokens=n_new,
                                  priority=pri, deadline_s=dl)
            i += 1
        emitted = eng.step()
        if not emitted and i < len(pending):
            time.sleep(max(0.0, pending[i][1][0]
                           - (time.perf_counter() - t0)))
    wall = time.perf_counter() - t0
    s = eng.metrics.summary()

    def _req_stats(indices):
        ttfts, itls = [], []
        for ti in indices:
            req = eng.request(rids[ti])
            if req is None or req.first_token_time is None:
                continue
            ttfts.append(req.first_token_time - req.submit_time)
            n = len(req.output)
            if req.finish_time is not None and n > 1:
                itls.append((req.finish_time - req.first_token_time)
                            / (n - 1))
        return ttfts, itls

    hi_idx = [ti for ti, r in enumerate(trace) if r[3] > 0]
    lo_idx = [ti for ti, r in enumerate(trace) if r[3] == 0]
    ttft_all, itl_all = _req_stats(range(len(trace)))
    ttft_hi, _ = _req_stats(hi_idx)
    ttft_lo, _ = _req_stats(lo_idx)
    return eng, {
        "wall_s": round(wall, 3),
        "goodput": round(s.get("serving/goodput", 0.0), 3),
        "finished_in_slo": s.get("serving/finished_in_slo", 0.0),
        "deadline_missed": s.get("serving/deadline_missed", 0.0),
        "preempted": s.get("serving/preempted", 0.0),
        "shed": s.get("serving/shed", 0.0),
        "retries": s.get("serving/retries", 0.0),
        "recovered_rows": s.get("serving/recovered_rows", 0.0),
        "ttft": _percentiles(ttft_all, qs=(50, 99)),
        "ttft_hi": _percentiles(ttft_hi, qs=(50, 99)),
        "ttft_lo": _percentiles(ttft_lo, qs=(50, 99)),
        "inter_token": _percentiles(itl_all, qs=(50, 99)),
    }


def run_slo(model: str = "tiny", variant: str = "fp32",
            n_requests: int = 32, n_slots: int = 4,
            max_queue: int = None) -> dict:
    """Overload serving under an SLO: ONE bursty heavy-tail trace with
    mixed priority classes and per-request deadlines, replayed through
    (a) the FIFO-ordered ``prefill_priority`` engine (priorities
    ignored — the baseline every PR before this one shipped) and (b)
    the ``priority`` engine (priority/EDF queue order + loss-free
    preemption: high-priority arrivals evict the lowest-priority
    running rows, whose streams resume byte-identically later).

    The contract under test (asserted, the kv_quant convention): with
    the pool saturated by low-priority heavy-tail work, priority
    preemption must cut HIGH-PRIORITY p99 TTFT vs FIFO on the same
    trace — an interactive request's wait drops from "a slot drains"
    to "one decode step". The cost surfaces honestly as low-priority
    TTFT/latency and the preempted count (each preemption also
    re-prefills the victim's emitted tokens at readmission). Goodput
    (finished-in-SLO / submitted) is the headline; p50/p99 TTFT per
    class and inter-token latency percentiles ride along."""
    lm, dtype, cfg = build(model, variant)
    trace = make_slo_trace(cfg, n_requests)
    # warm every prefill bucket + the pooled step so neither timed pass
    # pays a compile mid-trace
    warm = [(0.0, p, 2, 0, None) for _, p, _, _, _ in trace[:6]]
    _run_slo_engine(lm, dtype, warm, n_slots, "prefill_priority", None)

    eng_f, fifo = _run_slo_engine(lm, dtype, trace, n_slots,
                                  "prefill_priority", max_queue)
    eng_p, prio = _run_slo_engine(lm, dtype, trace, n_slots,
                                  "priority", max_queue)
    # the one-program discipline survives the resilience layer: the
    # priority engine ran the same single compiled decode program
    same_programs = (eng_p._step_fn._cache_size()
                     == eng_f._step_fn._cache_size())
    assert same_programs, (
        "the priority/preemption engine compiled extra decode programs "
        "— priorities and deadlines must stay host-side data")
    hi_gain = fifo["ttft_hi"]["p99_ms"] / max(prio["ttft_hi"]["p99_ms"],
                                              1e-9)
    assert hi_gain > 1.0, (
        f"priority preemption did not improve high-priority p99 TTFT "
        f"(fifo {fifo['ttft_hi']['p99_ms']} ms vs priority "
        f"{prio['ttft_hi']['p99_ms']} ms on the same trace)")
    return {
        "metric": "serving_slo_goodput_and_hi_p99_ttft",
        "model": model, "variant": variant, "requests": n_requests,
        "slots": n_slots, "max_queue": max_queue,
        "hi_requests": sum(1 for r in trace if r[3] > 0),
        "fifo": fifo, "priority": prio,
        "hi_p99_ttft_speedup": round(hi_gain, 2),
        "goodput_delta": round(prio["goodput"] - fifo["goodput"], 3),
        "same_decode_programs": bool(same_programs),
    }


def make_burst_trace(cfg, n_steady: int, n_burst: int, steady_gen: int,
                     burst_gen: int, burst_plen: int, seed: int = 31):
    """The decode-stall trace for ``--scenario chunked``: ``n_steady``
    SHORT-prompt interactive requests that will be mid-decode when a
    burst of ``n_burst`` LONG prompts (``burst_plen`` tokens each)
    lands all at once — the admission pattern that makes batched
    ingestion stall every in-flight row for the whole wave. Returns
    ``(steady, burst)`` request lists."""
    rng = np.random.RandomState(seed)
    steady = [(rng.randint(1, cfg["vocab"] + 1, size=(5,)).tolist(),
               steady_gen) for _ in range(n_steady)]
    burst = [(rng.randint(1, cfg["vocab"] + 1,
                          size=(burst_plen,)).tolist(), burst_gen)
             for _ in range(n_burst)]
    return steady, burst


def _run_burst_engine(lm, dtype, steady, burst, n_slots: int,
                      admission: str, chunk_budget, warm_steps: int = 5):
    """One burst replay: submit the steady rows, decode ``warm_steps``
    steps so they are genuinely in flight, drop the whole burst in at
    once, then step to drain — timestamping every step so the steady
    rows' inter-token gaps (the decode-stall signal) can be read off
    the emission log. Also snapshots the compiled-program counts around
    the run so the caller can assert the timed pass compiled NOTHING."""
    from bigdl_tpu.serving import ServingEngine

    kw = {} if chunk_budget is None else {"chunk_budget": chunk_budget}
    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                        admission=admission, **kw)
    programs0 = (eng._step_fn._cache_size()
                 + eng._batch_prefill_fn._jitted._cache_size())
    rids = [eng.submit(p, max_new_tokens=n) for p, n in steady]
    emit_log = []                       # (t, {req_id: token}) per step
    t0 = time.perf_counter()
    for _ in range(warm_steps):
        out = eng.step()
        emit_log.append((time.perf_counter(), out))
    for p, n in burst:
        eng.submit(p, max_new_tokens=n)
    while not eng.idle():
        out = eng.step()
        emit_log.append((time.perf_counter(), out))
    wall = time.perf_counter() - t0
    # per-steady-row inter-token gaps from the emission log: the stall
    # a batched admission wave causes is the max gap; chunked bounds it
    gaps = []
    for rid in rids:
        times = [t for t, out in emit_log if rid in out]
        gaps.extend(np.diff(times).tolist())
    programs1 = (eng._step_fn._cache_size()
                 + eng._batch_prefill_fn._jitted._cache_size())
    s = eng.metrics.summary()
    return eng, {
        "wall_s": round(wall, 3),
        "stall": _percentiles(gaps, qs=(50, 99)),
        "stall_max_ms": round(1e3 * max(gaps), 2) if gaps else 0.0,
        "decode_programs": eng._step_fn._cache_size(),
        "prefill_programs": eng._batch_prefill_fn._jitted._cache_size(),
        "programs_total": programs1,
        "compiled_in_run": programs1 - programs0,
        "chunks": s.get("serving/chunks", 0.0),
        "chunk_tokens": s.get("serving/chunk_tokens", 0.0),
        "decode_gap_p99_ms": round(
            1e3 * s.get("serving/decode_gap_p99_s", 0.0), 2),
    }


def run_chunked(model: str = "tiny", variant: str = "fp32",
                n_steady: int = 4, n_burst: int = 8,
                steady_gen: int = 40, burst_gen: int = 8,
                burst_plen: int = 96, n_slots: int = 12,
                chunk_budget: int = 32) -> dict:
    """Chunked streaming admission vs batched admission on one bursty
    long-prompt trace (the decode-stall scenario).

    The contracts under test (asserted — a green bench line IS the
    claim, the kv_quant convention): (a) outputs are token-identical
    across admission modes; (b) both modes run with EQUAL compile
    counts — the same ONE decode program each, equally many prefill
    programs (the trace is sized so both paths trace two prefill
    shapes: batched buckets (slots, 4)/(slots, 128), chunk buckets
    (1, 4)/(1, 32)), and ZERO programs compiled inside the timed pass
    (both engines are warmed on the trace's shapes first); (c) the
    steady rows' decode-stall p99 — the inter-token gap of requests
    already decoding when the burst lands — SHRINKS under chunked
    admission, because each super-step spends at most ``chunk_budget``
    prompt tokens before the next decode step instead of ingesting the
    whole wave.

    The cost surfaces honestly: chunked admission pays per-chunk
    dispatch overhead plus a read-row/scatter round-trip per chunk, so
    its total wall time is HIGHER — it is a latency shaper (bounded
    stalls for in-flight rows), not a throughput win. On a CPU host
    prefill is compute-bound so the stall contrast is, if anything,
    understated relative to an accelerator, where a (slots, 128)
    masked prefill wave costs many decode-steps' worth of wall time
    while a (1, 32) chunk hides inside one."""
    lm_b, dtype, cfg = build(model, variant)
    steady, burst = make_burst_trace(cfg, n_steady, n_burst, steady_gen,
                                     burst_gen, burst_plen)
    warm_s = [(p, 2) for p, _ in steady[:1]]
    warm_b = [(p, 2) for p, _ in burst[:2]]

    _run_burst_engine(lm_b, dtype, warm_s, warm_b, n_slots, "batched",
                      None, warm_steps=1)
    lm_c, _, _ = build(model, variant)          # same seed, own cache
    _run_burst_engine(lm_c, dtype, warm_s, warm_b, n_slots, "chunked",
                      chunk_budget, warm_steps=1)
    # the stall contrast is structural (one admission wave vs bounded
    # chunks), but each gap is ONE wall-clock sample — a host-scheduler
    # blip on the chunked run's worst gap can fake a regression, so the
    # timed passes retry once before the assert gets to fail
    for attempt in range(2):
        eng_b, batched = _run_burst_engine(lm_b, dtype, steady, burst,
                                           n_slots, "batched", None)
        eng_c, chunked = _run_burst_engine(lm_c, dtype, steady, burst,
                                           n_slots, "chunked",
                                           chunk_budget)
        if chunked["stall"]["p99_ms"] < batched["stall"]["p99_ms"]:
            break

    match = all(
        np.array_equal(eng_b.result(r), eng_c.result(r))
        for r in range(len(steady) + len(burst)))
    assert match, (
        "chunked admission outputs diverged from batched admission — "
        "chunk prefill must be the same math as the one-shot prefill")
    assert batched["compiled_in_run"] == 0 \
        and chunked["compiled_in_run"] == 0, (
            f"timed passes must be compile-free (batched "
            f"{batched['compiled_in_run']}, chunked "
            f"{chunked['compiled_in_run']} new programs)")
    assert chunked["decode_programs"] == batched["decode_programs"], (
        "chunked admission must add ZERO decode compiles — PARTIAL "
        "rows are host bookkeeping, never a program shape")
    # cross-mode program-count EQUALITY is a property of the trace
    # sizing, not of the subsystem: batched traces {(slots, 4),
    # (slots, 128)} while chunked traces one (1, L) bucket per distinct
    # chunk width — equal only when the budget splits the burst prompt
    # into chunks sharing one bucket (the default 32 does; 64 would
    # legally trace 64- and 32-buckets). Assert equality exactly when
    # the chunk plan predicts it; the measurement contract proper —
    # a compile-free timed pass at one decode program each — is
    # asserted unconditionally above.
    from bigdl_tpu.serving import bucket_len

    pf_burst, pf_steady = burst_plen - 1, 4
    widths = {bucket_len(pf_steady, cfg["max_len"])}
    rem = pf_burst
    while rem > 0:
        widths.add(bucket_len(min(chunk_budget, rem), cfg["max_len"]))
        rem -= min(chunk_budget, rem)
    if len(widths) == 2:
        assert chunked["programs_total"] == batched["programs_total"], (
            f"compile counts diverged: batched "
            f"{batched['programs_total']} vs chunked "
            f"{chunked['programs_total']} programs — this trace is "
            "sized for equality")
    assert chunked["stall"]["p99_ms"] < batched["stall"]["p99_ms"], (
        f"chunked admission did not shrink decode-stall p99 "
        f"(batched {batched['stall']['p99_ms']} ms vs chunked "
        f"{chunked['stall']['p99_ms']} ms)")
    return {
        "metric": "serving_chunked_decode_stall_p99_ms",
        "model": model, "variant": variant,
        "steady": n_steady, "burst": n_burst,
        "burst_prompt_len": burst_plen, "slots": n_slots,
        "chunk_budget": chunk_budget,
        "outputs_match": bool(match),
        "batched": batched, "chunked": chunked,
        "stall_p99_improvement": round(
            batched["stall"]["p99_ms"]
            / max(chunked["stall"]["p99_ms"], 1e-9), 2),
        "stall_max_improvement": round(
            batched["stall_max_ms"]
            / max(chunked["stall_max_ms"], 1e-9), 2),
        "wall_overhead_pct": round(
            100.0 * (chunked["wall_s"] / max(batched["wall_s"], 1e-9)
                     - 1.0), 1),
    }


def make_mixed_trace(cfg, n_requests: int, gen_tokens: int, seed: int = 13):
    """Mixed greedy/sampled submit-all-at-once trace for the sharded
    scenario (reuses the sampling scenario's knob mixes)."""
    return make_sampling_trace(cfg, n_requests, gen_tokens, seed=seed)


def _run_disagg_engine(lm, dtype, trace, n_slots: int,
                       decode_pools: int):
    """One drain()-to-empty pass through the disaggregated plane
    (in-process transfer): prefill pool + ``decode_pools`` decode pools
    at ``n_slots`` each, least-loaded routing."""
    from bigdl_tpu.serving import DisaggregatedEngine

    eng = DisaggregatedEngine(lm, prefill_slots=n_slots,
                              decode_slots=n_slots,
                              decode_pools=decode_pools,
                              compute_dtype=dtype)
    rids = [eng.submit(p, max_new_tokens=n, sampling=sp)
            for p, n, sp in trace]
    t0 = time.perf_counter()
    outs = eng.drain()
    wall = time.perf_counter() - t0
    n_tokens = int(sum(len(v) for v in outs.values()))
    s = eng.summary()
    tp = eng.metrics.transfer_percentiles(qs=(50, 99))
    gap_p99 = max((w.engine.metrics.decode_gap_percentiles()["p99"]
                   for w in eng.decoders), default=0.0)
    pe = eng.prefill.engine
    return eng, rids, outs, {
        "tokens_per_sec": round(n_tokens / wall, 1),
        "wall_s": round(wall, 3), "tokens": n_tokens,
        "decode_programs": eng.decoders[0].engine._step_fn._cache_size(),
        "prefill_programs":
            pe._batch_prefill_fn._jitted._cache_size(),
        "handoffs": s.get("serving/handoffs", 0.0),
        "transfer_bytes_per_handoff": round(
            s.get("serving/transfer_bytes_per_handoff", 0.0), 1),
        "transfer_ms": {"p50": round(1e3 * tp["p50"], 3),
                        "p99": round(1e3 * tp["p99"], 3)},
        "decode_gap_p99_ms": round(1e3 * gap_p99, 2),
        "prefill_occupancy": round(
            s.get("serving/prefill_occupancy", 0.0), 3),
        "decode_occupancy": round(
            s.get("serving/decode_occupancy", 0.0), 3),
    }


def run_disagg(model: str = "tiny", variant: str = "fp32",
               n_requests: int = 16, gen_tokens: int = 24,
               n_slots: int = 8, decode_pools: int = 2) -> dict:
    """Disaggregated (prefill pool → decode pools, in-process KV-row
    handoff) vs the monolithic engine on ONE mixed greedy/sampled
    trace.

    The contracts under test (asserted — a green bench line IS the
    claim, the kv_quant convention): (a) outputs are token-identical
    request for request — splitting admission and decode across pools
    changes where state lives, never what any row computes; (b) EQUAL
    compile counts per pool — both paths run after a shared warm pass,
    the timed passes compile NOTHING, and the decode pools run the
    SAME one decode program (the per-(model, dtype) step cache is
    process-wide) while the prefill pool runs the same bucketed
    prefill set.

    Reported, not asserted: the decode-stall p99 on each path (on one
    CPU host both pools share a socket, so the in-process split shows
    the HANDOFF overhead, not the interference win — the win is
    per-pool hardware, priced by pod_projection's disagg rows), the
    per-handoff transfer size and latency percentiles, and per-pool
    occupancies."""
    lm, dtype, cfg = build(model, variant)
    trace = make_mixed_trace(cfg, n_requests, gen_tokens)
    warm = [(p, 2, sp) for p, _, sp in trace]
    # one warm pass per path: traces every decode/prefill/scatter shape
    # both engines will touch, so the timed passes are compile-free
    _run_sampling_engine(lm, dtype, warm, n_slots, greedy=False)
    _run_disagg_engine(lm, dtype, warm, n_slots, decode_pools)

    def _programs(e):
        return (e._step_fn._cache_size()
                + e._batch_prefill_fn._jitted._cache_size())

    eng_m, rids_m, outs_m, mono = _run_sampling_engine(
        lm, dtype, trace, n_slots, greedy=False)
    programs_mid = _programs(eng_m)
    eng_d, rids_d, outs_d, disagg = _run_disagg_engine(
        lm, dtype, trace, n_slots, decode_pools)
    programs_end = _programs(eng_m)

    match = all(np.array_equal(outs_m[rm], outs_d[rd])
                for rm, rd in zip(rids_m, rids_d))
    assert match, (
        "disaggregated outputs diverged from the monolithic engine — "
        "the KV-row handoff must be byte-exact")
    assert programs_end == programs_mid, (
        f"the disaggregated pass compiled {programs_end - programs_mid} "
        "new program(s) — pools must ride the shared step caches")
    assert disagg["decode_programs"] == mono["decode_programs"], (
        "decode pools must run the monolithic engine's ONE compiled "
        "decode program")
    # decode-gap accounting: the monolithic engine interleaves
    # admission with decode (gaps include prefill waves); decode pools
    # only ever decode, so their gap samples bound the handoff +
    # scheduling overhead between consecutive dispatches
    mono_gap = round(
        1e3 * eng_m.metrics.decode_gap_percentiles()["p99"], 2)
    return {
        "metric": "serving_disagg_parity_and_transfer",
        "model": model, "variant": variant, "requests": n_requests,
        "gen_tokens": gen_tokens, "slots": n_slots,
        "decode_pools": decode_pools,
        "outputs_match": bool(match),
        "monolithic": dict(mono, decode_gap_p99_ms=mono_gap),
        "disagg": disagg,
        "throughput_overhead_pct": round(
            100.0 * (mono["tokens_per_sec"]
                     / max(disagg["tokens_per_sec"], 1e-9) - 1.0), 1),
    }


def run_failover(model: str = "tiny", variant: str = "fp32",
                 n_requests: int = 12, gen_tokens: int = 16,
                 n_slots: int = 6, decode_pools: int = 2,
                 seeds=(0, 1, 2)) -> dict:
    """Pool-death chaos + autoscaler cycle (``serving/health.py``).

    Section 1 — FAILOVER: the mixed greedy/sampled trace runs through
    the monolithic engine once, then through the disaggregated plane
    once per fault seed; each pass KILLS one decode pool mid-stream
    (the seed picks the victim, the kill step, and the trace).
    ASSERTED (a green line IS the claim): token-identical outputs
    request for request — rows the dead pool owned come back loss-free
    from the last-handoff stash or by byte-identical prefill replay of
    prompt + emitted — and ZERO new decode programs on the surviving
    pools. REPORTED: failover latency p50/p99 (detect → every stranded
    row re-routed, real wall clock) and the migrated/replayed split.

    Section 2 — AUTOSCALER: one active + one standby decode pool under
    a bursty submit→drain→idle cycle (two bursts). ASSERTED: streams
    still match the monolithic engine, and the controller is
    FLAP-FREE — at most one activation per burst and one
    drain-and-retire per lull (hysteresis: dead band + sustain window
    + cooldown; docs/serving.md has the math)."""
    from bigdl_tpu.serving import AutoscalerConfig, DisaggregatedEngine

    lm, dtype, cfg = build(model, variant)
    trace = make_mixed_trace(cfg, n_requests, gen_tokens)
    # warm both paths so the kill passes are compile-free and the
    # failover timer measures re-routing, not XLA
    warm = [(p, 2, sp) for p, _, sp in trace]
    _run_sampling_engine(lm, dtype, warm, n_slots, greedy=False)
    eng_m, rids_m, outs_m, mono = _run_sampling_engine(
        lm, dtype, trace, n_slots, greedy=False)

    fo_samples: list = []
    n_migrated = n_replayed = n_deaths = 0
    match = True
    for seed in seeds:
        # decode pools at HALF the slots: the kill then strands both
        # row kinds — seated rows (stash stale → prefill replay) and
        # queued rows (stash current → loss-free migration)
        d = DisaggregatedEngine(lm, prefill_slots=n_slots,
                                decode_slots=max(2, n_slots // 2),
                                decode_pools=decode_pools,
                                compute_dtype=dtype)
        rids_d = [d.submit(p, max_new_tokens=n, sampling=sp)
                  for p, n, sp in trace]
        for _ in range(1 + seed):
            d.step()
        victim = seed % decode_pools
        survivors = [w for j, w in enumerate(d.decoders) if j != victim]
        programs_before = [w.engine._step_fn._cache_size()
                           for w in survivors]
        d.kill_pool(victim)
        outs_d = d.drain()
        match &= all(np.array_equal(outs_m[rm], outs_d[rd])
                     for rm, rd in zip(rids_m, rids_d))
        assert match, (
            f"failover seed {seed}: outputs diverged through the pool "
            "death — stash restore / prefill replay must be byte-exact")
        after = [w.engine._step_fn._cache_size() for w in survivors]
        assert after == programs_before, (
            f"failover seed {seed}: survivors compiled "
            f"{sum(after) - sum(programs_before)} new decode "
            "program(s) — failover must reuse the shared step caches")
        s = d.summary()
        n_deaths += int(s.get("serving/pool_deaths", 0))
        n_migrated += int(s.get("serving/migrated_rows", 0))
        n_replayed += int(s.get("serving/replayed_rows", 0))
        fo_samples += d.metrics.metrics.values("serving/failover_s")

    fo = np.asarray(fo_samples) if fo_samples else np.zeros((1,))
    failover_ms = {"p50": round(1e3 * float(np.percentile(fo, 50)), 3),
                   "p99": round(1e3 * float(np.percentile(fo, 99)), 3)}

    # -- autoscaler cycle (bursty trace) ------------------------------------
    a = DisaggregatedEngine(
        lm, prefill_slots=n_slots, decode_slots=max(2, n_slots // 3),
        decode_pools=1, standby_pools=1, compute_dtype=dtype,
        autoscaler=AutoscalerConfig(high_water=0.9, low_water=0.3,
                                    sustain=2, cooldown=3))
    bursts = 2
    auto_match = True
    for b in range(bursts):
        rids_a = [a.submit(p, max_new_tokens=n, sampling=sp)
                  for p, n, sp in trace]
        outs_a = a.drain()
        auto_match &= all(np.array_equal(outs_m[rm], outs_a[ra])
                          for rm, ra in zip(rids_m, rids_a))
        for _ in range(12):               # the lull: cold pools retire
            a.step()
    sa = a.summary()
    ups = int(sa.get("serving/autoscale_up", 0))
    downs = int(sa.get("serving/autoscale_down", 0))
    flap_free = ups <= bursts and downs <= bursts and auto_match
    assert flap_free, (
        f"autoscaler flapped: {ups} up / {downs} down over {bursts} "
        "burst cycles (hysteresis must bound one action per swing)")

    return {
        "metric": "serving_failover_parity_and_latency",
        "model": model, "variant": variant, "requests": n_requests,
        "gen_tokens": gen_tokens, "slots": n_slots,
        "decode_pools": decode_pools, "fault_seeds": list(seeds),
        "outputs_match": bool(match),
        "pool_deaths": n_deaths,
        "failover_ms": failover_ms,
        "migrated_rows": n_migrated,
        "replayed_rows": n_replayed,
        "monolithic": mono,
        "autoscaler": {
            "bursts": bursts, "autoscale_up": ups,
            "autoscale_down": downs,
            "flap_free": bool(flap_free),
            "final_pool_states": a.pool_states(),
        },
    }


def _run_sharded_engine(lm, dtype, trace, n_slots: int, parallelism):
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                        parallelism=parallelism)
    rids = [eng.submit(p, max_new_tokens=n, sampling=sp)
            for p, n, sp in trace]
    # warm pass timing would hide admission; time the drain whole, then
    # read the per-step phase timer for the steady-state number
    t0 = time.perf_counter()
    outs = eng.drain()
    wall = time.perf_counter() - t0
    n_tokens = int(sum(len(v) for v in outs.values()))
    step_ms = 1e3 * eng.metrics.metrics.mean("serving/decode_step_s")
    return eng, rids, outs, {
        "tokens_per_sec": round(n_tokens / wall, 1),
        "wall_s": round(wall, 3), "tokens": n_tokens,
        "step_ms_mean": round(step_ms, 3),
        "decode_programs": eng._step_fn._cache_size(),
    }


def run_sharded(model: str = "tiny", variant: str = "fp32",
                n_requests: int = 12, gen_tokens: int = 16,
                n_slots: int = 8, data_shards: int = 8) -> dict:
    """Slot-data-parallel engine on a ``data_shards``-device mesh of
    the devices jax has (too few raises — to emulate them on a CPU box
    start python with ``JAX_PLATFORMS=cpu
    XLA_FLAGS=--xla_force_host_platform_device_count=8``) vs the
    single-device engine, SAME trace: asserts token
    identity, reports per-step wall time and shard balance. Two model
    builds with the same seed give each engine a private step cache, so
    ``decode_programs`` counts each engine's own compiles (the
    one-program-regardless-of-mesh-size claim)."""
    lm_a, dtype, cfg = build(model, variant)
    trace = make_mixed_trace(cfg, n_requests, gen_tokens)
    # warm both paths on a short prefix of the trace (compiles excluded
    # from the timed drains)
    warm = [(p, 2, sp) for p, _, sp in trace[:3]]
    _run_sharded_engine(lm_a, dtype, warm, n_slots, None)
    _, rids_s, outs_s, single = _run_sharded_engine(
        lm_a, dtype, trace, n_slots, None)
    lm_b, _, _ = build(model, variant)          # same seed, own cache
    _run_sharded_engine(lm_b, dtype, warm, n_slots,
                        {"data": data_shards})
    eng_m, rids_m, outs_m, meshed = _run_sharded_engine(
        lm_b, dtype, trace, n_slots, {"data": data_shards})
    match = all(np.array_equal(outs_s[a], outs_m[b])
                for a, b in zip(rids_s, rids_m))
    imb = eng_m.metrics.metrics.values("serving/shard_imbalance")
    return {
        "metric": "serving_sharded_step_ms",
        "model": model, "variant": variant, "requests": n_requests,
        "gen_tokens": gen_tokens, "slots": n_slots,
        "mesh": {"data": eng_m._plane.data_shards,
                 "model": eng_m._plane.model_shards},
        "outputs_match": bool(match),
        "single": single, "sharded": meshed,
        "shard_imbalance_max": max(imb) if imb else 0.0,
        "step_overhead_pct": round(
            100.0 * (meshed["step_ms_mean"]
                     / max(single["step_ms_mean"], 1e-9) - 1.0), 1),
    }


def _run_kv_engine(lm, dtype, trace, n_slots: int, kv_dtype):
    """One submit-all drain()-to-empty greedy pass at the given KV
    storage format; every engine gets its own freshly-built (same-seed)
    model so ``decode_programs`` counts that engine's compiles alone."""
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                        kv_dtype=kv_dtype)
    rids = [eng.submit(p, max_new_tokens=n) for _, p, n in trace]
    t0 = time.perf_counter()
    outs = eng.drain()
    wall = time.perf_counter() - t0
    n_tokens = int(sum(len(v) for v in outs.values()))
    return eng, rids, outs, {
        "kv_dtype": eng.kv_dtype, "slots": n_slots,
        "kv_bytes_per_slot": eng.pool.kv_bytes_per_slot,
        "tokens_per_sec": round(n_tokens / wall, 1),
        "wall_s": round(wall, 3), "tokens": n_tokens,
        "decode_programs": eng._step_fn._cache_size(),
    }


def run_kv_quant(model: str = "tiny", variant: str = "fp32",
                 n_requests: int = 16, gen_tokens: int = 24,
                 budget_slots: int = 16) -> dict:
    """Float-KV vs int8-KV serving, two comparisons off one greedy
    trace; each engine owns a same-seed model build (private
    jitted-step cache).

    (a) EQUAL slots, float vs int8 — identical compile counts
    (quantization is a storage format, never a program), tokens/sec
    delta = the quantize/dequant cost on this backend, and per-request
    greedy agreement reported as ``float_match_rows``. On an UNTRAINED
    bench model that fraction is a near-tie coin flip, not an accuracy
    metric: random-init logits are near-uniform, so top-2 argmax gaps
    sit within the ~0.5% cache-rounding noise of ANY sub-fp32 format
    and a few long rollouts flip per batch (bf16-cache-vs-fp32-cache
    flips the same way). The pinned accuracy contract — token-identical
    greedy decode on configs where gaps are real — lives in
    tests/test_serving_kv_quant.py.

    (b) EQUAL simulated HBM budget, int8 at ``budget_slots`` vs int8 at
    ~2x (bf16 baseline) / ~4x (fp32) the slots — the capacity headline.
    Outputs here must be IDENTICAL bitwise (asserted): pooled rows are
    independent, so packing 2x the concurrent requests into the same
    HBM budget changes no request's tokens — that invariance under
    load, not luck, is what lets a production deployment actually
    cash the halved bytes in as concurrency."""
    lm_f, dtype, cfg = build(model, variant)
    trace = make_trace(cfg, n_requests, gen_tokens, 0.0)
    warm = [(0.0, p, 2) for _, p, _ in trace[:3]]

    _run_kv_engine(lm_f, dtype, warm, budget_slots, None)
    eng_f, rids_f, outs_f, float_stats = _run_kv_engine(
        lm_f, dtype, trace, budget_slots, None)

    lm_q, _, _ = build(model, variant)
    _run_kv_engine(lm_q, dtype, warm, budget_slots, "int8")
    eng_q, rids_q, outs_q, int8_stats = _run_kv_engine(
        lm_q, dtype, trace, budget_slots, "int8")

    # equal simulated HBM budget: re-spend the float engine's KV bytes
    # on int8 slots (fresh same-seed model build — a different n_slots
    # is a different carry shape, so sharing lm_q's step cache would
    # make decode_programs read 2; a private cache keeps every engine's
    # count at the meaningful 1)
    budget_bytes = float_stats["kv_bytes_per_slot"] * budget_slots
    slots_at_budget = int(budget_bytes // int8_stats["kv_bytes_per_slot"])
    lm_c, _, _ = build(model, variant)
    _run_kv_engine(lm_c, dtype, warm, slots_at_budget, "int8")
    eng_c, rids_c, outs_c, cap_stats = _run_kv_engine(
        lm_c, dtype, trace, slots_at_budget, "int8")

    float_match = sum(np.array_equal(outs_f[a], outs_q[b])
                      for a, b in zip(rids_f, rids_q))
    match_cap = all(np.array_equal(outs_q[a], outs_c[b])
                    for a, b in zip(rids_q, rids_c))
    assert match_cap, (
        "int8 engine outputs changed with slot count — pooled rows must "
        "be independent of their neighbors")
    return {
        "metric": "serving_kv_quant_slots_at_budget_ratio",
        "model": model, "variant": variant, "requests": n_requests,
        "gen_tokens": gen_tokens,
        "hbm_budget_bytes": int(budget_bytes),
        "float_kv": float_stats, "int8_kv": int8_stats,
        "int8_kv_at_budget": cap_stats,
        "float_match_rows": f"{float_match}/{n_requests}",
        "outputs_match_at_budget": bool(match_cap),
        "extra_decode_compiles": (int8_stats["decode_programs"]
                                  - float_stats["decode_programs"]),
        "kv_bytes_ratio": round(float_stats["kv_bytes_per_slot"]
                                / int8_stats["kv_bytes_per_slot"], 2),
        "slots_at_budget_ratio": round(slots_at_budget / budget_slots, 2),
        "equal_slot_overhead_pct": round(
            100.0 * (float_stats["tokens_per_sec"]
                     / max(int8_stats["tokens_per_sec"], 1e-9) - 1.0), 1),
        "tokens_per_sec_at_budget_vs_float": round(
            cap_stats["tokens_per_sec"]
            / max(float_stats["tokens_per_sec"], 1e-9), 2),
    }


def run(model: str = "tiny", variant: str = "fp32", n_requests: int = 12,
        gen_tokens: int = 48, stagger_ms: float = 10.0, n_slots: int = 12,
        policy: str = "prefill_priority") -> dict:
    lm, dtype, cfg = build(model, variant)
    trace = make_trace(cfg, n_requests, gen_tokens, stagger_ms / 1e3)
    # jit warmup on a throwaway 2-request trace so neither timed path
    # pays compiles (every prompt bucket + the pooled step get traced)
    warm = [(0.0, p, 2) for _, p, _ in trace[:len(set(len(p) for _, p, _
                                                      in trace))]]
    run_sequential(lm, dtype, warm)
    run_engine(lm, dtype, warm, n_slots, policy)

    seq = run_sequential(lm, dtype, trace)
    eng = run_engine(lm, dtype, trace, n_slots, policy)
    return {
        "metric": "serving_mixed_arrival_tokens_per_sec",
        "model": model, "variant": variant, "requests": n_requests,
        "gen_tokens": gen_tokens, "stagger_ms": stagger_ms,
        "slots": n_slots, "policy": policy,
        "engine": eng, "sequential": seq,
        "speedup": round(eng["tokens_per_sec"]
                         / max(seq["tokens_per_sec"], 1e-9), 2),
    }


def make_multitenant_trace(cfg, n_requests: int, gen_tokens: int,
                           n_tenants: int, seed: int = 29):
    """Mixed multi-tenant traffic for ``--scenario multitenant``:
    adapter ids round-robin over {0 (base), 1..n_tenants}, every fourth
    request carries a fixed-sequence template constraint, and half the
    rows sample with fixed per-request seeds — one trace exercising the
    whole per-row knob surface of the one compiled step."""
    from bigdl_tpu.serving import SamplingParams, fixed_sequence

    rng = np.random.RandomState(seed)
    buckets = [5, 9, 17]
    trace = []
    for i in range(n_requests):
        plen = buckets[i % len(buckets)]
        prompt = rng.randint(1, cfg["vocab"] + 1, size=(plen,)).tolist()
        sp = SamplingParams(temperature=0.8, top_k=20, seed=300 + i) \
            if i % 2 else None
        aid = i % (n_tenants + 1)
        forced = rng.randint(1, cfg["vocab"] + 1, size=(3,)).tolist() \
            if i % 4 == 3 else None
        cons = None if forced is None else fixed_sequence(forced)
        trace.append((prompt, gen_tokens, sp, aid, cons, forced))
    return trace


def _run_multitenant_engine(lm, dtype, trace, n_slots, bank,
                            tenants_on: bool):
    """One drain()-to-empty pass on an adapter-enabled engine;
    ``tenants_on=False`` strips adapter ids and constraints (the
    base-only workload the mixed pass must not out-compile)."""
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                        adapters=bank, seed=5)
    rids = [eng.submit(p, max_new_tokens=n, sampling=sp,
                       adapter_id=aid if tenants_on else 0,
                       constraint=cons if tenants_on else None)
            for p, n, sp, aid, cons, _ in trace]
    t0 = time.perf_counter()
    outs = eng.drain()
    wall = time.perf_counter() - t0
    n_tokens = int(sum(len(v) for v in outs.values()))
    return eng, rids, outs, {
        "tokens_per_sec": round(n_tokens / wall, 1),
        "wall_s": round(wall, 3), "tokens": n_tokens,
        "decode_programs": eng._step_fn._cache_size(),
        "prefill_programs": eng._batch_prefill_fn._jitted._cache_size(),
    }


def run_multitenant(model: str = "tiny", variant: str = "fp32",
                    n_requests: int = 16, gen_tokens: int = 16,
                    n_slots: int = 8, n_tenants: int = 3) -> dict:
    """Multi-tenant serving (pooled LoRA bank + constrained decoding)
    vs base-only traffic on the SAME adapter-enabled engine.

    The contracts under test: (a) the mixed-tenant pass — base rows,
    ``n_tenants`` adapted tenants, and template-constrained rows in one
    batch — adds ZERO decode or prefill programs over the base-only
    pass (adapter ids and allow-masks are per-row runtime data of the
    one compiled step); (b) the null-adapter unconstrained rows inside
    the mixed batch are token-identical to a bank-less engine on the
    same prompts (the all-zero gather and all-True mask are exact
    identities); (c) every constrained row emits exactly its forced
    template prefix. Reports the tokens/sec delta — the gather +
    mask epilogue cost at this model size (on real accelerators the
    rank-r gather is noise against the dense matmuls; on the CPU host
    it is visible and reported honestly)."""
    from bigdl_tpu.serving import AdapterBank, ServingEngine

    lm, dtype, cfg = build(model, variant)
    bank = AdapterBank(lm, rank=4, n_slots=n_tenants + 1)
    for t in range(n_tenants):
        bank.alloc(bank.random_factors(seed=50 + t, amp=0.5))
    trace = make_multitenant_trace(cfg, n_requests, gen_tokens,
                                   n_tenants)

    # bank-less oracle for the null-adapter rows (and warm the shared
    # prefill buckets so the timed passes are compile-free)
    plain = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                          seed=5)
    rids_p = [plain.submit(p, max_new_tokens=n, sampling=sp)
              for p, n, sp, _, _, _ in trace]
    outs_p = plain.drain()

    _run_multitenant_engine(                 # warm the adapter programs
        lm, dtype, [(p, 2, sp, a, c, f) for p, _, sp, a, c, f in trace],
        n_slots, bank, tenants_on=True)
    eng_b, rids_b, outs_b, base_stats = _run_multitenant_engine(
        lm, dtype, trace, n_slots, bank, tenants_on=False)
    eng_m, rids_m, outs_m, mixed_stats = _run_multitenant_engine(
        lm, dtype, trace, n_slots, bank, tenants_on=True)

    null_rows_match = all(
        np.array_equal(outs_p[rp], outs_m[rm])
        for (p, n, sp, aid, cons, _), rp, rm
        in zip(trace, rids_p, rids_m)
        if aid == 0 and cons is None)
    constrained_ok = all(
        list(outs_m[rm])[:len(forced)] == forced
        for (_, _, _, _, cons, forced), rm in zip(trace, rids_m)
        if cons is not None)
    adapted_diverge = any(
        not np.array_equal(outs_p[rp], outs_m[rm])
        for (_, _, _, aid, cons, _), rp, rm
        in zip(trace, rids_p, rids_m)
        if aid != 0 and cons is None)
    return {
        "metric": "serving_multitenant_tokens_per_sec",
        "model": model, "variant": variant, "requests": n_requests,
        "gen_tokens": gen_tokens, "slots": n_slots,
        "tenants": n_tenants,
        "base_only": base_stats, "mixed": mixed_stats,
        "extra_decode_compiles": (mixed_stats["decode_programs"]
                                  - base_stats["decode_programs"]),
        "extra_prefill_compiles": (mixed_stats["prefill_programs"]
                                   - base_stats["prefill_programs"]),
        "null_rows_match": bool(null_rows_match),
        "constrained_ok": bool(constrained_ok),
        "adapted_rows_diverge": bool(adapted_diverge),
        "multitenant_overhead_pct": round(
            100.0 * (base_stats["tokens_per_sec"]
                     / max(mixed_stats["tokens_per_sec"], 1e-9) - 1.0),
            1),
    }


def make_tiered_trace(cfg, n_requests: int, gen_tokens: int,
                      seed: int = 31):
    """Two-wave priority traffic for ``--scenario tiered``: the first
    wave (low priority) fills every slot and decodes until the second
    wave (high priority) lands and preempts it — the preempted rows
    are exactly the spill/fetch traffic under test. Half the rows
    sample with fixed per-request seeds so byte-identity covers the
    RNG-lane restore, not just greedy argmax."""
    from bigdl_tpu.serving import SamplingParams

    rng = np.random.RandomState(seed)
    buckets = [5, 9, 17]
    trace = []
    for i in range(n_requests):
        plen = buckets[i % len(buckets)]
        prompt = rng.randint(1, cfg["vocab"] + 1, size=(plen,)).tolist()
        sp = SamplingParams(temperature=0.8, top_k=20, seed=400 + i) \
            if i % 2 else None
        trace.append((prompt, gen_tokens, sp))
    return trace


def _run_tiered_engine(lm, dtype, trace, n_slots, tier,
                       burst_after: int = 3):
    """One two-wave pass: the first ``n_slots`` requests enter at
    priority 0, decode ``burst_after`` steps, then the rest arrive at
    priority 5 (higher number outranks) and evict them. Returns the
    engine, submission-ordered outputs, and the timing/compile stats
    every configuration is compared on."""
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                        policy="priority", preemption=True, seed=5,
                        tier=tier)
    t0 = time.perf_counter()
    rids = [eng.submit(p, max_new_tokens=n, sampling=sp, priority=0)
            for p, n, sp in trace[:n_slots]]
    for _ in range(burst_after):
        eng.step()
    rids += [eng.submit(p, max_new_tokens=n, sampling=sp, priority=5)
             for p, n, sp in trace[n_slots:]]
    outs = eng.drain()
    wall = time.perf_counter() - t0
    n_tokens = int(sum(len(v) for v in outs.values()))
    return eng, [outs[r] for r in rids], {
        "tokens_per_sec": round(n_tokens / wall, 1),
        "wall_s": round(wall, 3), "tokens": n_tokens,
        "decode_programs": eng._step_fn._cache_size(),
        "prefill_programs": eng._batch_prefill_fn._jitted._cache_size(),
    }


def run_tiered(model: str = "tiny", variant: str = "fp32",
               n_requests: int = 12, gen_tokens: int = 16,
               n_slots: int = 4, host_budget_gb: float = 16.0) -> dict:
    """Tiered KV (host-RAM spill) vs the legacy in-memory stash vs a
    forced re-prefill baseline, on the same fixed "HBM budget" — a
    deliberately small slot count that a high-priority burst overflows.

    The contracts under test: (a) the tiered pass is BYTE-identical to
    the stash pass (greedy + fixed-seed sampled rows through a
    spill→fetch round trip); (b) evicted rows resume WITHOUT
    re-prefill (``serving/resumed_without_prefill`` > 0 — the resume
    shortcut, not a replay); (c) the tier adds ZERO compiled programs
    (spill/fetch is host machinery around the one decode step). The
    re-prefill baseline is the same engine with a starved tier budget
    (every spill evicted before readmission → the PR 8 replay path):
    still byte-identical, but every resume pays prefill again — the
    reported wall-clock gap is what host DRAM buys. Also reports
    spill/fetch p99 and the warm-prefix capacity ``host_budget_gb``
    buys at the measured packed-row size (HBM capacity ends at
    n_slots; tier capacity scales with DRAM)."""
    from bigdl_tpu.serving import TieredKVStore

    lm, dtype, cfg = build(model, variant)
    trace = make_tiered_trace(cfg, n_requests, gen_tokens)

    _run_tiered_engine(                      # warm the compile buckets
        lm, dtype, [(p, 2, sp) for p, _, sp in trace], n_slots, None,
        burst_after=1)
    eng_s, outs_s, stash_stats = _run_tiered_engine(
        lm, dtype, trace, n_slots, None)
    eng_t, outs_t, tier_stats = _run_tiered_engine(
        lm, dtype, trace, n_slots, TieredKVStore())
    eng_r, outs_r, replay_stats = _run_tiered_engine(
        lm, dtype, trace, n_slots, TieredKVStore(host_budget_bytes=1024))

    tiered_identical = all(
        np.array_equal(a, b) for a, b in zip(outs_s, outs_t))
    replay_identical = all(
        np.array_equal(a, b) for a, b in zip(outs_s, outs_r))
    s_t = eng_t.metrics.summary()
    assert tiered_identical, "tiered stream diverged from stash stream"
    assert s_t.get("serving/resumed_without_prefill", 0) > 0, \
        "no evicted row resumed from the tier without re-prefill"
    per_row = s_t["serving/spill_bytes"] / s_t["serving/spills"]
    fetch_pct = eng_t.metrics.fetch_percentiles()
    return {
        "metric": "serving_tiered_tokens_per_sec",
        "model": model, "variant": variant, "requests": n_requests,
        "gen_tokens": gen_tokens, "slots": n_slots,
        "stash": stash_stats, "tiered": tier_stats,
        "replay_baseline": replay_stats,
        "tiered_identical": bool(tiered_identical),
        "replay_identical": bool(replay_identical),
        "extra_decode_compiles": (tier_stats["decode_programs"]
                                  - stash_stats["decode_programs"]),
        "extra_prefill_compiles": (tier_stats["prefill_programs"]
                                   - stash_stats["prefill_programs"]),
        "spills": s_t["serving/spills"],
        "fetches": s_t["serving/fetches"],
        "resumed_without_prefill": s_t["serving/resumed_without_prefill"],
        "spill_bytes_per_row": round(per_row, 0),
        "fetch_p50_ms": round(fetch_pct["p50"] * 1e3, 3),
        "fetch_p99_ms": round(fetch_pct["p99"] * 1e3, 3),
        # what DRAM buys: prefix entries a host budget holds at the
        # measured packed-row size, vs the n_slots rows HBM holds
        "host_budget_gb": host_budget_gb,
        "warm_prefix_capacity": int(host_budget_gb * (1 << 30)
                                    // max(per_row, 1.0)),
        "resume_vs_reprefill_wall_pct": round(
            100.0 * (replay_stats["wall_s"]
                     / max(tier_stats["wall_s"], 1e-9) - 1.0), 1),
    }


# -- the workload zoo (--scenario autopilot) --------------------------------
#
# Composable arrival generators, each one production traffic shape the
# serving literature names: prefix-heavy interactive chat, long-context
# RAG, agentic many-short-turn tool loops, and a diurnal ramp (peak
# burst then off-peak trickle). Every generator emits ``(arrival_s,
# prompt, max_new, priority, deadline_s, degrade_to)`` rows and
# ``zoo_tenant_mix`` merges any set of them into one multi-tenant
# priority-mix trace — the closed-loop scenario's input, and (seeded)
# the autopilot test suite's.  Arrivals are VIRTUAL seconds: the
# replay runs on a SteppingClock, so the same seed gives the same
# goodput on every machine, every run.

def zoo_chat(cfg, rng, t0=0.0, n=8, gap_s=0.15, prefix_len=8,
             turn_len=4, gen=(4, 6), deadline_s=0.6, priority=10):
    """Prefix-heavy interactive chat: every turn opens with one shared
    system prefix (the prefix-cache shape), short user turns, short
    answers, TIGHT deadlines, high priority — the tenant class whose
    p99 the whole control loop is protecting."""
    prefix = rng.randint(1, cfg["vocab"] + 1, size=(prefix_len,)).tolist()
    return [(t0 + i * gap_s,
             prefix + rng.randint(1, cfg["vocab"] + 1,
                                  size=(turn_len,)).tolist(),
             int(rng.randint(gen[0], gen[1] + 1)), priority, deadline_s,
             None)
            for i in range(n)]


def zoo_rag(cfg, rng, t0=0.05, n=6, gap_s=0.08, ctx_len=24, gen=24,
            deadline_s=3.0):
    """Long-context RAG: fat retrieved-document prompts, long answers,
    GENEROUS deadlines, batch priority — the slot-hogging background
    class a deadline-aware preemptor trades latency from (loss-free:
    an evicted RAG row still makes its deadline)."""
    return [(t0 + i * gap_s,
             rng.randint(1, cfg["vocab"] + 1, size=(ctx_len,)).tolist(),
             gen, 0, deadline_s, None)
            for i in range(n)]


def zoo_agentic(cfg, rng, t0=0.3, loops=5, turns=2, loop_gap_s=0.16,
                turn_gap_s=0.02, turn_len=3, gen=3, deadline_s=0.35):
    """Agentic tool loops: many very short turns in quick succession,
    SAME priority class as the RAG bulk but knife-edge deadlines — the
    class only deadline-aware preemption can save (class-priority
    preemption sees equal classes and does nothing; a 3-token turn
    behind a 24-token RAG row misses by queueing alone)."""
    out = []
    for i in range(loops):
        for j in range(turns):
            out.append((t0 + i * loop_gap_s + j * turn_gap_s,
                        rng.randint(1, cfg["vocab"] + 1,
                                    size=(turn_len,)).tolist(),
                        gen, 0, deadline_s, None))
    return out


def zoo_diurnal(cfg, rng, t0=1.2, peak_n=14, peak_gap_s=0.03,
                off_n=3, off_gap_s=0.3, plen=5, gen=16,
                deadline_s=1.0, degrade_to=4):
    """Diurnal ramp: a peak-hour burst arriving faster than service
    (the queue genuinely builds — the degrade controller's moment:
    each row carries a ``Degrade`` fallback budget that makes its
    deadline feasible under load), then an off-peak trickle (pressure
    drops — the restore half's moment: late arrivals keep their FULL
    budget exactly because the loop reverts the clamp when the rush
    ends)."""
    peak = [(t0 + i * peak_gap_s,
             rng.randint(1, cfg["vocab"] + 1, size=(plen,)).tolist(),
             gen, 0, deadline_s, degrade_to)
            for i in range(peak_n)]
    t1 = t0 + peak_n * peak_gap_s + 0.6
    off = [(t1 + i * off_gap_s,
            rng.randint(1, cfg["vocab"] + 1, size=(plen,)).tolist(),
            gen, 0, deadline_s * 3, degrade_to)
           for i in range(off_n)]
    return peak + off


def zoo_tenant_mix(*segments):
    """Merge any set of generator outputs into one multi-tenant trace,
    sorted by arrival (ties by segment order — deterministic)."""
    out = []
    for seg in segments:
        out.extend(seg)
    return sorted(out, key=lambda r: r[0])


def make_zoo_trace(cfg, seed: int = 43):
    """THE seeded workload-zoo trace: chat + RAG + agentic + diurnal
    tenants mixed onto one arrival timeline (module comment above for
    why each shape is there). Calibrated against the SteppingClock's
    ~7-reads-per-step virtual step cost so each tenant's pathology
    actually bites at 4 slots: RAG rows long enough that slot turnover
    (~gen/slots steps) exceeds the agentic deadline — only a deadline-
    aware preemptor can seat those turns in time — and the diurnal
    peak arriving faster than service so the queue genuinely builds
    and the degrade path decides who makes the SLO."""
    rng = np.random.RandomState(seed)
    return zoo_tenant_mix(
        zoo_chat(cfg, rng, n=6, gap_s=0.35, deadline_s=0.45),
        zoo_rag(cfg, rng, n=8, gap_s=0.05, ctx_len=24, gen=48,
                deadline_s=4.0),
        zoo_agentic(cfg, rng, t0=0.3, loops=6, loop_gap_s=0.2,
                    deadline_s=0.16),
        zoo_diurnal(cfg, rng, t0=2.3, peak_n=20, peak_gap_s=0.025,
                    gen=16, deadline_s=0.55, degrade_to=4),
    )


def _run_zoo_engine(lm, dtype, trace, n_slots: int, tick_s: float = 0.002,
                    autopilot=None, degrade_at=None, chunk_budget=32,
                    policy: str = "priority"):
    """Replay one zoo trace in VIRTUAL time: the engine runs on a
    SteppingClock (every clock read advances ``tick_s``, so elapsed
    time per step is a fixed function of the code path — deterministic
    per trace, no sleeping), requests are submitted when the virtual
    clock reaches their arrival, and an idle engine jumps the clock to
    the next arrival. Returns the engine plus goodput / miss-rate /
    actuation stats and the per-request outputs for identity checks."""
    from bigdl_tpu.serving import Degrade, ServingEngine, SteppingClock

    clk = SteppingClock(tick_s)
    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                        policy=policy, admission="chunked",
                        chunk_budget=chunk_budget, clock=clk,
                        degrade_at=degrade_at, autopilot=autopilot)
    programs0 = (eng._step_fn._cache_size()
                 + eng._batch_prefill_fn._jitted._cache_size())
    order = sorted(range(len(trace)), key=lambda i: (trace[i][0], i))
    rids = {}
    i, steps = 0, 0
    while i < len(order) or not eng.idle():
        while i < len(order) and trace[order[i]][0] <= clk.t:
            ti = order[i]
            _, prompt, n_new, pri, dl, dg = trace[ti]
            rids[ti] = eng.submit(
                prompt, max_new_tokens=n_new, priority=pri,
                deadline_s=dl,
                degrade=(None if dg is None
                         else Degrade(max_new_tokens=dg)))
            i += 1
        if eng.idle() and i < len(order):
            clk.advance(max(0.0, trace[order[i]][0] - clk.t))
            continue
        eng.step()
        steps += 1
    programs1 = (eng._step_fn._cache_size()
                 + eng._batch_prefill_fn._jitted._cache_size())
    s = eng.metrics.summary()

    def _missed(req) -> bool:
        if req is None:
            return True
        if req.finish_reason not in (None, "length", "stop"):
            return True                     # shed / deadline / error
        return (req.deadline_time is not None
                and req.finish_time is not None
                and req.finish_time > req.deadline_time)

    hi = [ti for ti, r in enumerate(trace) if r[3] > 0]
    hi_missed = sum(1 for ti in hi if _missed(eng.request(rids[ti])))
    outs, clean = {}, set()
    for ti in range(len(trace)):
        req = eng.request(rids[ti])
        if req is not None:
            outs[ti] = np.asarray(req.output, np.int64)
            # a CLEAN row ran its stream to a normal finish with its
            # submitted budget intact — the byte-identity candidates;
            # degraded or deadline-dropped rows are prefix candidates
            # (their streams were cut short, not reordered)
            if req.finish_reason in ("length", "stop") \
                    and not req.degraded:
                clean.add(ti)
    ap = eng.autopilot
    return eng, outs, clean, {
        "virtual_s": round(clk.t, 3),
        "steps": steps,
        "goodput": round(s.get("serving/goodput", 0.0), 3),
        "finished_in_slo": s.get("serving/finished_in_slo", 0.0),
        "deadline_missed": s.get("serving/deadline_missed", 0.0),
        "hi_missed": hi_missed,
        "preempted": s.get("serving/preempted", 0.0),
        "degraded": s.get("serving/degraded", 0.0),
        "degrade_restored": s.get("serving/degrade_restored", 0.0),
        "actuations": (len(ap.bus.log) if ap is not None else 0),
        "programs_total": programs1,
        "compiled_in_run": programs1 - programs0,
    }


def run_autopilot(model: str = "tiny", variant: str = "fp32",
                  n_slots: int = 4, seed: int = 43,
                  tick_ms: float = 2.0) -> dict:
    """The closed loop vs every static knob config, one seeded zoo
    trace, virtual time (``--scenario autopilot``).

    ONE multi-tenant workload-zoo trace (chat + RAG + agentic +
    diurnal; ``make_zoo_trace``) replays through a STATIC sweep —
    chunk budget {low, high} x degrade threshold {off, on}, all on the
    priority/EDF engine — and through the closed loop
    (``ServingEngine(..., autopilot=Autopilot())``: least-laxity
    queue order, deadline-aware preemption, pressure-scaled Degrade
    with revert, hysteresis-debounced chunk budget). Everything runs
    on a SteppingClock, so every number here is a pure function of
    the seed.

    Asserted (the kv_quant convention — a green line IS the claim):
    the closed loop's goodput-under-SLO STRICTLY beats every static
    config on the same trace; the high-priority tenant's deadline-miss
    count does not regress vs the best static config; every pass
    compiles ZERO programs (the warm pass owns every bucket — an
    actuation is host bookkeeping, never a recompile) and ends at the
    SAME total program count; and each request that finished
    un-degraded in both the closed and the reference static pass
    emitted BYTE-IDENTICAL tokens (the loop reorders latency, never
    tokens; degraded rows are checked as prefixes)."""
    from bigdl_tpu.serving import Autopilot, AutopilotConfig

    lm, dtype, cfg = build(model, variant)
    trace = make_zoo_trace(cfg, seed)

    # warm EVERY compiled bucket the sweep can touch: all prompt-length
    # buckets at every chunk budget the sweep or the closed loop's
    # halving/doubling ladder can select, plus a long row so preempted
    # replays find their buckets warm too
    warm_prompts = sorted({len(p) for _, p, _, _, _, _ in trace}) + [40]
    for b in (8, 16, 32, 64):
        warm = [(j * 0.01, list(range(3, 3 + n)), 2, 0, None, None)
                for j, n in enumerate(warm_prompts)]
        _run_zoo_engine(lm, dtype, warm, n_slots, chunk_budget=b)

    def _autopilot():
        # preempt_margin_s absorbs the share of a virtual step the
        # service estimate cannot see (the estimate is the decode
        # DISPATCH median — one clock tick here — while a full
        # super-step costs ~7 reads of host bookkeeping around it):
        # a waiter whose slack is within the margin of one victim
        # completion preempts rather than gambling on the estimate
        return Autopilot(AutopilotConfig(
            queue_high=3.0, queue_low=1.0, sustain=2, cooldown=4,
            chunk_min=8, chunk_max=64, gap_target_s=0.05,
            preempt_margin_s=0.12))

    sweep = {
        "chunk8": dict(chunk_budget=8),
        "chunk64": dict(chunk_budget=64),
        "chunk32_degrade": dict(chunk_budget=32, degrade_at=4),
        "chunk8_degrade": dict(chunk_budget=8, degrade_at=4),
    }
    tick_s = tick_ms / 1e3
    statics = {}
    ref_eng = ref_outs = ref_clean = None
    for name, kw in sweep.items():
        eng_s, outs_s, clean_s, stats = _run_zoo_engine(
            lm, dtype, trace, n_slots, tick_s=tick_s, **kw)
        statics[name] = stats
        if name == "chunk32_degrade":
            ref_eng, ref_outs, ref_clean = eng_s, outs_s, clean_s
    eng_c, outs_c, clean_c, closed = _run_zoo_engine(
        lm, dtype, trace, n_slots, tick_s=tick_s,
        autopilot=_autopilot())

    for name, stats in statics.items():
        assert closed["goodput"] > stats["goodput"], (
            f"closed loop goodput {closed['goodput']} did not beat "
            f"static config {name} ({stats['goodput']}) on the same "
            f"seeded zoo trace")
        assert stats["compiled_in_run"] == 0, \
            f"static pass {name} compiled mid-trace (warmup gap)"
        assert stats["programs_total"] == closed["programs_total"], (
            f"program counts diverged: static {name} "
            f"{stats['programs_total']} vs closed "
            f"{closed['programs_total']} — an actuation recompiled")
    assert closed["compiled_in_run"] == 0, \
        "the closed loop compiled mid-trace — actuation must stay host data"
    best_hi = min(s["hi_missed"] for s in statics.values())
    assert closed["hi_missed"] <= best_hi, (
        f"closed loop hi-priority misses {closed['hi_missed']} regressed "
        f"vs best static {best_hi}")
    assert closed["actuations"] > 0, \
        "the closed loop never actuated — the scenario is vacuous"
    identical = prefix_ok = True
    n_identical = 0
    for ti, a in outs_c.items():
        b = ref_outs.get(ti)
        if b is None:
            continue
        if ti in clean_c and ti in ref_clean:
            identical = identical and np.array_equal(a, b)
            n_identical += 1
        else:
            # degraded or deadline-cut in at least one pass: the
            # shorter stream must be a PREFIX of the longer (greedy
            # rows: scheduling may cut a stream, never rewrite it)
            n = min(len(a), len(b))
            prefix_ok = prefix_ok and np.array_equal(a[:n], b[:n])
    assert n_identical > 0, "no request finished clean in both passes"
    assert identical, (
        "a clean request's stream diverged between the closed loop "
        "and the static engine — the loop must reorder latency, "
        "never tokens")
    assert prefix_ok, (
        "a degraded/deadline-cut request's stream is not a prefix of "
        "its counterpart")
    best_static = max(statics, key=lambda k: statics[k]["goodput"])
    return {
        "metric": "serving_autopilot_goodput_vs_static_sweep",
        "model": model, "variant": variant, "slots": n_slots,
        "seed": seed, "requests": len(trace),
        "hi_requests": sum(1 for r in trace if r[3] > 0),
        "tick_ms": tick_ms,
        "static": statics, "closed": closed,
        "best_static": best_static,
        "goodput_gain_vs_best": round(
            closed["goodput"] - statics[best_static]["goodput"], 3),
        "streams_identical": bool(identical),
        "zero_extra_compiles": True,
    }


def _run_window_engine(lm, dtype, trace, n_slots: int, window: int):
    """One drain()-to-empty pass at dispatch-ahead depth ``window`` —
    everything submitted up front so the sweep is decode-dominant and
    the streams are a pure function of the prompts (no arrival
    timing in the loop)."""
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                        dispatch_ahead=window)
    rids = [eng.submit(p, max_new_tokens=n) for _, p, n in trace]
    t0 = time.perf_counter()
    outs = eng.drain()
    wall = time.perf_counter() - t0
    n_tokens = int(sum(len(v) for v in outs.values()))
    host_total, n_host = eng.metrics.metrics.get("serving/host_step_s")
    device_total = eng.metrics.device_seconds
    s = eng.metrics.summary()
    return eng, [tuple(outs[r]) for r in rids], {
        "tokens_per_sec": round(n_tokens / wall, 1),
        "wall_s": round(wall, 3), "tokens": n_tokens,
        "host_frac": round(
            host_total / max(host_total + device_total, 1e-9), 3)
        if n_host else 0.0,
        "host_step_p99_ms": round(
            s.get("serving/host_step_p99_s", 0.0) * 1e3, 2),
        "decode_gap_p99_ms": round(
            s.get("serving/decode_gap_p99_s", 0.0) * 1e3, 2),
        "decode_programs": eng._step_fn._cache_size(),
    }


def run_async(model: str = "tiny", variant: str = "fp32",
              n_requests: int = 12, gen_tokens: int = 48,
              n_slots: int = 12, windows=(0, 1, 2, 4)) -> dict:
    """The dispatch-ahead W-sweep (``--scenario async``): the default
    mixed trace's prompts replayed drain-to-empty through fresh engines
    at ``dispatch_ahead`` W in {0, 1, 2, 4} — the measured row for the
    ROADMAP's "THE number this item drives down" (`host_frac`, born in
    docs/async_readiness.md, honestly inflated by PR 15's prefill-fence
    deletion, driven down here by consuming step N's decode readback
    only after step N+1..N+W have dispatched).

    Asserted (the autopilot convention — a green line IS the claim):
    every W emits BYTE-IDENTICAL token streams to W=0 (the window
    re-times the fence, it never reorders math); every pass ends at
    the SAME decode-program count (one warm pass owns every bucket —
    a window depth is a host-side deque bound, never a trace input);
    and `host_frac` at every W >= 1 is STRICTLY below W=0 (the
    true-host residue the delayed consumer pays per step is smaller:
    its readback lands on already-materialized buffers instead of
    stalling the freshly-enqueued dispatch). Reported per W:
    host_frac, host_step p99, decode-gap p99, tokens/sec."""
    lm, dtype, cfg = build(model, variant)
    trace = make_trace(cfg, n_requests, gen_tokens, stagger_s=0.0)
    # warm the (model, dtype, n_slots) decode step + prefill buckets at
    # the deepest window so every timed pass is compile-free and the
    # sweep deltas are pure fence-timing
    _run_window_engine(lm, dtype, [(a, p, 2) for a, p, _ in trace],
                       n_slots, window=max(windows))
    sweep = {}
    base_outs = None
    programs = set()
    for w in windows:
        eng, outs, stats = _run_window_engine(lm, dtype, trace,
                                              n_slots, w)
        if base_outs is None:
            base_outs = outs
        else:
            assert outs == base_outs, \
                f"W={w} diverged from the W=0 streams"
        assert not eng._window, \
            f"W={w}: drain() left {len(eng._window)} in-flight dispatches"
        programs.add(stats["decode_programs"])
        sweep[f"W{w}"] = stats
    assert len(programs) == 1, \
        f"decode-program counts diverged across the sweep: {programs}"
    base_frac = sweep[f"W{windows[0]}"]["host_frac"]
    deeper = [w for w in windows if w >= 1]
    assert all(sweep[f"W{w}"]["host_frac"] < base_frac for w in deeper), \
        "host_frac did not drop at W>=1: " + repr(
            {k: v["host_frac"] for k, v in sweep.items()})
    return {
        "metric": "serving_dispatch_ahead_sweep",
        "model": model, "variant": variant, "requests": n_requests,
        "gen_tokens": gen_tokens, "slots": n_slots,
        "windows": sweep,
        "streams_identical": True,
        "equal_decode_programs": True,
        "host_frac_drop_at_w1": round(
            base_frac - sweep["W1"]["host_frac"], 3) if 1 in windows
        else None,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="mixed",
                    choices=["mixed", "admission", "sampling", "sharded",
                             "kv_quant", "speculative", "slo", "chunked",
                             "disagg", "failover", "multitenant",
                             "tiered", "autopilot", "async", "sampler"])
    ap.add_argument("--model", default="tiny", choices=sorted(MODELS))
    ap.add_argument("--variant", default="fp32", choices=["fp32", "bf16"])
    # requests/gen_tokens/slots default per scenario: mixed 12/48/12,
    # admission 20/4/8 (admission wants waves — n_slots < n_requests
    # exercises the cache — and short decodes that keep admission
    # dominant)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--gen_tokens", type=int, default=None)
    ap.add_argument("--stagger_ms", type=float, default=10.0)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--policy", default="prefill_priority",
                    choices=["prefill_priority", "fifo"])
    ap.add_argument("--shared_frac", type=float, default=0.5)
    ap.add_argument("--prefix_len", type=int, default=12)
    ap.add_argument("--data_shards", type=int, default=8)
    ap.add_argument("--budget_slots", type=int, default=16,
                    help="kv_quant: slots the simulated HBM budget buys "
                         "at the FLOAT KV format (16 keeps the floor'd "
                         "int8 slot count above 1.9x even though the "
                         "per-slot scale rows eat ~0.1% of the budget)")
    ap.add_argument("--draft_k", type=int, default=3,
                    help="speculative: draft tokens per super-step "
                         "(verify chunk width = k + 1)")
    ap.add_argument("--max_queue", type=int, default=None,
                    help="slo: bound the waiting queue (arrivals beyond "
                         "it are shed with finish_reason='shed')")
    ap.add_argument("--chunk_budget", type=int, default=32,
                    help="chunked: prompt tokens the streaming pump may "
                         "spend per engine step before decode runs")
    ap.add_argument("--decode_pools", type=int, default=2,
                    help="disagg: decode pools fed by the one prefill "
                         "pool (in-process transfer)")
    ap.add_argument("--tenants", type=int, default=3,
                    help="multitenant: live LoRA adapters sharing the "
                         "pooled bank (plus the null adapter)")
    ap.add_argument("--host_budget_gb", type=float, default=16.0,
                    help="tiered: host DRAM budget the warm-prefix "
                         "capacity figure is quoted against")
    ap.add_argument("--zoo_seed", type=int, default=43,
                    help="autopilot: the workload-zoo trace seed (every "
                         "number in the scenario is a pure function of "
                         "it — virtual time, no wall clock)")
    ap.add_argument("--tick_ms", type=float, default=2.0,
                    help="autopilot: SteppingClock tick per clock read")
    ap.add_argument("--shapes", default=None,
                    help="sampler: comma-separated <rows>x<vocab> in "
                         "place of the serving cells' four shapes")
    args = ap.parse_args()

    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.scenario == "async":
        print(json.dumps(run_async(
            args.model, args.variant,
            n_requests=args.requests or 12,
            gen_tokens=args.gen_tokens or 48,
            n_slots=args.slots or 12)))
        return
    if args.scenario == "autopilot":
        print(json.dumps(run_autopilot(
            args.model, args.variant,
            n_slots=args.slots or 4, seed=args.zoo_seed,
            tick_ms=args.tick_ms)))
        return
    if args.scenario == "tiered":
        print(json.dumps(run_tiered(
            args.model, args.variant,
            n_requests=args.requests or 12,
            gen_tokens=args.gen_tokens or 16,
            n_slots=args.slots or 4,
            host_budget_gb=args.host_budget_gb)))
        return
    if args.scenario == "multitenant":
        print(json.dumps(run_multitenant(
            args.model, args.variant,
            n_requests=args.requests or 16,
            gen_tokens=args.gen_tokens or 16,
            n_slots=args.slots or 8, n_tenants=args.tenants)))
        return
    if args.scenario == "failover":
        print(json.dumps(run_failover(
            args.model, args.variant,
            n_requests=args.requests or 12,
            gen_tokens=args.gen_tokens or 16,
            n_slots=args.slots or 6,
            decode_pools=args.decode_pools)))
        return
    if args.scenario == "disagg":
        print(json.dumps(run_disagg(
            args.model, args.variant,
            n_requests=args.requests or 16,
            gen_tokens=args.gen_tokens or 24,
            n_slots=args.slots or 8,
            decode_pools=args.decode_pools)))
        return
    if args.scenario == "chunked":
        print(json.dumps(run_chunked(
            args.model, args.variant,
            n_slots=args.slots or 12,
            chunk_budget=args.chunk_budget)))
        return
    if args.scenario == "slo":
        print(json.dumps(run_slo(
            args.model, args.variant,
            n_requests=args.requests or 32,
            n_slots=args.slots or 4, max_queue=args.max_queue)))
        return
    if args.scenario == "speculative":
        print(json.dumps(run_speculative(
            args.model, args.variant,
            n_requests=args.requests or 16,
            gen_tokens=args.gen_tokens or 24,
            n_slots=args.slots or 8, draft_k=args.draft_k)))
        return
    if args.scenario == "kv_quant":
        print(json.dumps(run_kv_quant(
            args.model, args.variant,
            n_requests=args.requests or 16,
            gen_tokens=args.gen_tokens or 24,
            budget_slots=args.budget_slots)))
        return
    if args.scenario == "sharded":
        # must run before any jax computation initializes the backend
        print(json.dumps(run_sharded(
            args.model, args.variant,
            n_requests=args.requests or 12,
            gen_tokens=args.gen_tokens or 16,
            n_slots=args.slots or 8, data_shards=args.data_shards)))
        return
    if args.scenario == "sampling":
        print(json.dumps(run_sampling(
            args.model, args.variant,
            n_requests=args.requests or 16,
            gen_tokens=args.gen_tokens or 32,
            n_slots=args.slots or 8)))
        return
    if args.scenario == "sampler":
        shapes = [tuple(int(d) for d in sh.split("x"))
                  for sh in args.shapes.split(",")] if args.shapes \
            else SAMPLER_SHAPES
        print(json.dumps(run_sampler(shapes)))
        return
    if args.scenario == "admission":
        print(json.dumps(run_admission(
            args.model, args.variant,
            n_requests=args.requests or 20,
            gen_tokens=args.gen_tokens or 4,
            n_slots=args.slots or 8, shared_frac=args.shared_frac,
            prefix_len=args.prefix_len)))
        return
    print(json.dumps(run(args.model, args.variant, args.requests or 12,
                         args.gen_tokens or 48, args.stagger_ms,
                         args.slots or 12, args.policy)))


if __name__ == "__main__":
    main()
