"""Chunked-prefill streaming admission (bigdl_tpu/serving/chunked.py):
token-identical parity with batched admission and generate() (greedy
fp32+bf16 and fixed-seed sampled streams, across eviction/readmission),
mid-prefill fault replay / cancellation / preemption, prefix-cache
chunk skipping, the zero-extra-decode-compiles + bounded-chunk-shapes
guards, KV-pool chunk-progress lifecycle, feasibility admission
control, sharded DP parity, and the bench smoke."""

import numpy as np
import pytest

from tests.test_serving import _make_lm


def _ragged_reqs(rng, n=9, vocab=29, max_plen=30):
    """Mixed prompt lengths including a 1-token prompt and prompts much
    longer than any test chunk budget, so plans span 1..several
    chunks."""
    reqs = [([int(rng.randint(1, vocab + 1))], 4)]      # 1-token prompt
    for i in range(n - 1):
        plen = int(rng.randint(2, max_plen + 1))
        reqs.append((rng.randint(1, vocab + 1, size=(plen,)).tolist(),
                     int(rng.randint(3, 9))))
    return reqs


def _run_mode(lm, reqs, mode, dtype=None, n_slots=3, stagger=True, **kw):
    """One trace through an engine: optionally staggered submits so
    later requests land mid-flight (the readmission path), drain to
    empty, assert the free list healed."""
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=n_slots, compute_dtype=dtype,
                        admission=mode, **kw)
    if stagger:
        ids = [eng.submit(*r) for r in reqs[:n_slots]]
        eng.step(); eng.step()
        ids += [eng.submit(*r) for r in reqs[n_slots:]]
    else:
        ids = [eng.submit(*r) for r in reqs]
    res = eng.drain()
    assert eng.pool.free_slots == eng.pool.n_slots
    assert not eng.scheduler.partial
    return eng, [res[rid] for rid in ids]


# -- parity (THE acceptance contract) --------------------------------------

@pytest.mark.parametrize("dtype_name", ["fp32", "bf16"])
def test_chunked_parity_with_batched_and_generate(dtype_name, rng):
    """Ragged staggered trace (1-token prompts through prompts many
    chunks long, fewer slots than requests so rows recycle mid-flight):
    chunked admission must be token-for-token identical to batched
    admission AND sequential generate() — streaming changes WHEN
    prompts are ingested, never what any row computes."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import generate

    dtype = None if dtype_name == "fp32" else jnp.bfloat16
    lm = _make_lm()
    reqs = _ragged_reqs(rng)
    _, outs_b = _run_mode(lm, reqs, "batched", dtype)
    _, outs_c = _run_mode(lm, reqs, "chunked", dtype, chunk_budget=7)
    for j, (prompt, n_new) in enumerate(reqs):
        want = generate(lm, prompt, length=n_new, temperature=0.0,
                        compute_dtype=dtype)
        np.testing.assert_array_equal(
            outs_c[j], want,
            err_msg=f"req {j} prompt={prompt} dtype={dtype_name}")
        np.testing.assert_array_equal(outs_c[j], outs_b[j])


def test_chunked_sampled_seed_replay(rng):
    """Fixed-seed sampled requests replay draw-for-draw across
    admission modes — including rows evicted and readmitted mid-stream
    (more requests than slots) whose chunk plans replay prompt +
    emitted tokens."""
    from bigdl_tpu.serving import SamplingParams

    lm = _make_lm()
    reqs = []
    for i in range(8):
        plen = [1, 6, 19][i % 3]
        prompt = rng.randint(1, 30, size=(plen,)).tolist()
        sp = SamplingParams(temperature=0.9, top_k=12, seed=300 + i) \
            if i % 2 else None
        reqs.append((prompt, 6, -1, sp))
    _, outs_b = _run_mode(lm, reqs, "batched", n_slots=2)
    _, outs_c = _run_mode(lm, reqs, "chunked", n_slots=2, chunk_budget=5)
    for a, b in zip(outs_b, outs_c):
        np.testing.assert_array_equal(a, b)


def test_chunked_speculative_parity(rng):
    """Chunked admission composes with draft-and-verify: the draft
    cache ingests at activation like any admission, and greedy output
    stays identical to the batched baseline engine."""
    from bigdl_tpu.serving import SpeculativeConfig

    lm = _make_lm()
    draft = _make_lm()                    # same seed -> weight-tied
    reqs = _ragged_reqs(rng, n=6)
    _, outs_b = _run_mode(lm, reqs, "batched")
    _, outs_s = _run_mode(lm, reqs, "chunked", chunk_budget=6,
                          speculative=SpeculativeConfig(draft, k=3))
    for a, b in zip(outs_b, outs_s):
        np.testing.assert_array_equal(a, b)


# -- mid-prefill teardown paths --------------------------------------------

def test_chunked_fault_replay_byte_identical(rng):
    """Injected dispatch faults (step failures + admission faults that
    land mid-chunk-plan) must leave every stream byte-identical to the
    fault-free run: a faulted chunk evicts exactly its row, which
    replays its chunks at readmission."""
    from bigdl_tpu.serving import (
        FaultInjector, ServingEngine, WatchdogConfig,
    )

    lm = _make_lm()
    reqs = _ragged_reqs(rng, n=8)

    def run(faults=None):
        eng = ServingEngine(
            lm, n_slots=3, admission="chunked", chunk_budget=8,
            watchdog=WatchdogConfig(max_retries=None), faults=faults)
        ids = [eng.submit(*r) for r in reqs]
        res = eng.drain()
        assert eng.pool.free_slots == eng.pool.n_slots
        return eng, [res[r] for r in ids]

    _, clean = run()
    for seed in (1, 2, 3):
        inj = FaultInjector(seed=seed, p_fail=0.15, p_admit_fail=0.25)
        eng, faulty = run(inj)
        assert inj.counts["admit_fail"] > 0, (
            f"seed {seed} injected no admission faults — the mid-chunk "
            "replay path went unexercised")
        for a, b in zip(clean, faulty):
            np.testing.assert_array_equal(a, b)
        assert eng.metrics.summary()["serving/retries"] > 0


def test_chunked_cancel_partial_row_frees_everything(rng):
    """Cancelling a mid-prefill PARTIAL row drops its chunk plan, frees
    its slot, resets the pool's chunk-progress fields, and never emits
    a token for it — while other rows keep serving."""
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=2, admission="chunked",
                        chunk_budget=4)
    r0 = eng.submit(rng.randint(1, 30, size=(3,)).tolist(),
                    max_new_tokens=12)
    r1 = eng.submit(rng.randint(1, 30, size=(30,)).tolist(),
                    max_new_tokens=5)
    eng.step()                  # r1 admitted PARTIAL (budget went to r0)
    eng.step()                  # r1's first chunk fed
    assert eng.scheduler.partial, "expected a mid-prefill row"
    slot = next(iter(eng.scheduler.partial))
    assert eng.pool.chunk_target[slot] == 29
    assert 0 < eng.pool.chunk_done[slot] < 29
    assert eng.cancel(r1)
    assert eng.pool.chunk_done[slot] == 0
    assert eng.pool.chunk_target[slot] == 0
    # the pump-order entry goes with the plan: a recycled slot must not
    # inherit the cancelled row's queue position (it would stream ahead
    # of earlier-admitted rows)
    assert slot not in eng.admitter._plans
    assert slot not in eng.admitter._order
    eng.drain()
    assert eng.request(r1).state == "cancelled"
    assert eng.request(r1).output == []
    assert len(eng.result(r0)) == 12
    assert eng.pool.free_slots == 2


def test_chunked_preemption_composes(rng):
    """Priority preemption under chunked admission: a high-priority
    arrival evicts a RUNNING victim loss-free while other rows are
    mid-prefill; every stream still matches generate()."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=2, admission="chunked",
                        chunk_budget=8, policy="priority")
    reqs = [(rng.randint(1, 30, size=(n,)).tolist(), g)
            for n, g in ((5, 8), (17, 8), (9, 4))]
    ids = [eng.submit(p, max_new_tokens=g, priority=0)
           for p, g in reqs[:2]]
    for _ in range(4):
        eng.step()
    ids.append(eng.submit(reqs[2][0], max_new_tokens=reqs[2][1],
                          priority=10))
    res = eng.drain()
    assert eng.metrics.summary().get("serving/preempted", 0) >= 1
    for rid, (p, g) in zip(ids, reqs):
        np.testing.assert_array_equal(
            res[rid], generate(lm, p, length=g, temperature=0.0),
            err_msg=f"prompt={p}")


# -- prefix cache: cached prefixes skip whole chunks -----------------------

def test_chunked_prefix_cache_skips_chunks(rng):
    """A cached prefix writes into the slot in one scatter and its
    tokens never enter the chunk plan: the second wave of a shared
    long-prefix trace streams strictly fewer chunk tokens, and outputs
    still match generate()."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=2, admission="chunked",
                        chunk_budget=6, prefix_cache=True)
    sys_p = rng.randint(1, 30, size=(18,)).tolist()
    reqs = [(sys_p + rng.randint(1, 30, size=(3,)).tolist(), 5)
            for _ in range(4)]
    reqs.append((reqs[0][0], 5))                  # identical: full hit
    ids = [eng.submit(*r) for r in reqs]
    outs = eng.drain()
    for rid, (p, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            outs[rid], generate(lm, p, length=n, temperature=0.0),
            err_msg=f"prompt={p}")
    s = eng.metrics.summary()
    assert s["serving/prefix_hit_rate"] > 0
    # the first wave (2 slots, cold cache) streams two full 20-token
    # plans; every later admission hits the 18-token cached prefix and
    # chunks at most its few-token suffix — far below the no-cache
    # total of ~20 tokens per request
    assert s["serving/chunk_tokens"] < 20 * len(reqs) - 18


# -- compile guards ---------------------------------------------------------

def test_chunked_zero_extra_decode_compiles_and_bounded_chunks(rng):
    """Chunked admission adds ZERO decode programs (PARTIAL rows are
    host bookkeeping) and its chunk-prefill shapes are (1, L) buckets
    capped by the budget's bucket — bounded no matter how many distinct
    prompt lengths traffic brings."""
    from bigdl_tpu.serving import ServingEngine, bucket_len
    from tests.compile_guards import assert_compile_count

    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=4, admission="chunked",
                        chunk_budget=8)
    plens = list(range(2, 26))
    rng.shuffle(plens)
    for n in plens:
        eng.submit(rng.randint(1, 30, size=(n,)).tolist(),
                   max_new_tokens=3)
    eng.drain()
    assert_compile_count(eng._step_fn, 1, what="chunked decode")
    cap = bucket_len(eng.admitter.chunk_budget, eng.max_len)
    shapes = eng.admitter.traced_shapes
    assert all(B == 1 and L <= cap for B, L in shapes), shapes
    # bucketed: far fewer shapes than distinct chunk lengths
    assert len(shapes) <= 4
    n_before = len(shapes)
    # a second wave of the same lengths re-traces NOTHING
    for n in plens:
        eng.submit(rng.randint(1, 30, size=(n,)).tolist(),
                   max_new_tokens=3)
    eng.drain()
    assert len(eng.admitter.traced_shapes) == n_before
    assert_compile_count(eng._step_fn, 1, what="repeat lengths")


# -- KV-pool chunk-progress lifecycle (the recycled-slot pin) ---------------

def test_chunk_progress_resets_with_slot():
    """``chunk_done``/``chunk_target`` follow the recycled-slot
    contract the int8 scales set: ``free()`` resets both, so a new
    occupant never inherits its predecessor's progress; ``write_prefill``
    and ``set_pos`` keep ``chunk_done`` in lockstep with the device
    ``pos``."""
    from bigdl_tpu.models.transformer import get_batch_decode_step
    from bigdl_tpu.serving import KVPool

    lm = _make_lm()
    _, init = get_batch_decode_step(lm, sampling=True)
    pool = KVPool(init, 2)
    slot = pool.alloc()
    pool.begin_chunks(slot, 0, 20)
    assert pool.chunk_remaining(slot) == 20
    prefill_like = init(1)
    pool.write_prefill(slot, prefill_like, 7)
    assert pool.chunk_done[slot] == 7 and pool.chunk_remaining(slot) == 13
    pool.free(slot)
    assert pool.chunk_done[slot] == 0 and pool.chunk_target[slot] == 0
    assert pool.chunk_remaining(slot) == 0
    # a recycled slot starts from clean progress state
    slot2 = pool.alloc()
    assert pool.chunk_done[slot2] == 0 and pool.chunk_target[slot2] == 0
    pool.set_pos(slot2, 3)
    assert pool.chunk_done[slot2] == 3
    pool.free(slot2)
    with pytest.raises(ValueError, match="not allocated"):
        pool.begin_chunks(slot2, 0, 4)
    slot3 = pool.alloc()
    with pytest.raises(ValueError, match="chunk plan"):
        pool.begin_chunks(slot3, 5, 4)            # done > target
    with pytest.raises(ValueError, match="chunk plan"):
        pool.begin_chunks(slot3, 0, pool.max_len + 1)


# -- validation -------------------------------------------------------------

def test_chunked_knob_validation():
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    with pytest.raises(ValueError, match="chunk_budget"):
        ServingEngine(lm, admission="chunked", chunk_budget=0)
    with pytest.raises(ValueError, match="chunk_budget"):
        ServingEngine(lm, admission="batched", chunk_budget=8)
    with pytest.raises(ValueError, match="admission mode"):
        ServingEngine(lm, admission="streamed")
    # chunked + prefix cache is legal; per_request + prefix cache stays
    # rejected
    ServingEngine(lm, admission="chunked", prefix_cache=True)
    with pytest.raises(ValueError, match="prefix_cache"):
        ServingEngine(lm, admission="per_request", prefix_cache=True)


# -- feasibility admission control ------------------------------------------

def test_deadline_feasibility_drops_infeasible(rng):
    """With a measured decode-step estimate, a waiting request whose
    remaining tokens cannot fit inside its deadline is dropped at
    admission (finish_reason='infeasible', counted shed + deadline-
    missed) — while a feasible same-deadline request is served. Before
    any estimate exists nothing is dropped (feasibility never
    guesses)."""
    from bigdl_tpu.serving import ServingEngine, VirtualClock

    lm = _make_lm()
    clk = VirtualClock()
    eng = ServingEngine(lm, n_slots=2, admission="chunked",
                        deadline_feasibility=True, clock=clk)
    prompt = rng.randint(1, 30, size=(4,)).tolist()
    # no estimate yet: even an absurd deadline is admitted, not dropped
    r_warm = eng.submit(prompt, max_new_tokens=3, deadline_s=1e-9)
    eng.step()
    assert eng.request(r_warm) is None or \
        eng.request(r_warm).finish_reason != "infeasible"
    eng.drain()
    # seed a deterministic estimate: 0.1 s per decode step
    for _ in range(5):
        eng.metrics.add_phase("decode_step", 0.1)
    est = eng.metrics.decode_step_estimate()
    assert est is not None and est > 0.05
    r_bad = eng.submit(prompt, max_new_tokens=30, deadline_s=1.0)
    r_ok = eng.submit(prompt, max_new_tokens=30, deadline_s=1e6)
    eng.drain()
    bad = eng.request(r_bad)
    assert bad.finish_reason == "infeasible" and bad.output == []
    assert len(eng.result(r_ok)) == 30
    s = eng.metrics.summary()
    assert s["serving/infeasible"] == 1
    assert s["serving/shed"] >= 1 and s["serving/deadline_missed"] >= 1


def test_shed_preempted_request_drops_kv_stash(rng):
    """A PREEMPTED request carries its stashed KV row back to the
    queue; shedding it there (deadline/feasibility drop) must release
    the stash — the finished ledger must never pin per-row K/V device
    arrays (the cancel() teardown contract)."""
    from bigdl_tpu.serving import ServingEngine, VirtualClock

    lm = _make_lm()
    clk = VirtualClock()
    eng = ServingEngine(lm, n_slots=1, admission="chunked",
                        policy="priority", clock=clk)
    lo = eng.submit(rng.randint(1, 30, size=(5,)).tolist(),
                    max_new_tokens=8, priority=0, deadline_s=100.0)
    eng.step(); eng.step()
    hi = eng.submit(rng.randint(1, 30, size=(4,)).tolist(),
                    max_new_tokens=4, priority=10)
    eng.step()                              # preempts lo (stash taken)
    req_lo = eng.scheduler.waiting[0]
    assert req_lo.req_id == lo and req_lo.resume_carry is not None
    clk.advance(200.0)                      # lo's deadline expires
    eng.drain()
    assert eng.request(lo).finish_reason == "deadline"
    assert eng.request(lo).resume_carry is None
    assert len(eng.result(hi)) == 4


# -- decode-stall metric -----------------------------------------------------

def test_decode_gap_metric_records_stalls(rng):
    """The decode-gap samples exist exactly when rows stayed in flight
    across consecutive decode dispatches, and the summary reports a
    p99."""
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=2, admission="chunked",
                        chunk_budget=4)
    eng.submit(rng.randint(1, 30, size=(3,)).tolist(), max_new_tokens=8)
    eng.step()
    eng.submit(rng.randint(1, 30, size=(20,)).tolist(), max_new_tokens=3)
    eng.drain()
    s = eng.metrics.summary()
    assert s.get("serving/decode_gap_p99_s", 0.0) > 0.0
    gaps = eng.metrics.decode_gap_percentiles()
    assert gaps["p99"] >= gaps["p50"] >= 0.0


# -- sharded plane -----------------------------------------------------------

@pytest.mark.mesh
def test_chunked_sharded_dp_parity(rng):
    """Chunked admission on a slot-data-parallel mesh: chunks route to
    the owning shard through the pool's mesh-pinned scatter, outputs
    token-identical to the unsharded chunked engine."""
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    reqs = _ragged_reqs(rng, n=9)

    def run(**kw):
        eng = ServingEngine(lm, n_slots=4, admission="chunked",
                            chunk_budget=6, **kw)
        ids = [eng.submit(*r) for r in reqs]
        res = eng.drain()
        assert eng.pool.free_slots == 4
        return [res[r] for r in ids]

    plain = run()
    sharded = run(parallelism={"data": 4})
    for a, b in zip(plain, sharded):
        np.testing.assert_array_equal(a, b)


# -- bench smoke -------------------------------------------------------------

def test_chunked_bench_smoke():
    """The chunked scenario's contracts hold at smoke size: outputs
    match, equal compile counts, stall p99 shrinks (the in-bench
    asserts), and the report carries the chunk/stall metrics."""
    import benchmarks.serving_bench as sb

    out = sb.run_chunked(n_steady=2, n_burst=4, steady_gen=24,
                         burst_gen=4, burst_plen=64, n_slots=8,
                         chunk_budget=16)
    assert out["outputs_match"]
    assert out["chunked"]["decode_programs"] == \
        out["batched"]["decode_programs"]
    assert out["chunked"]["programs_total"] == \
        out["batched"]["programs_total"]
    assert out["stall_p99_improvement"] > 1.0
    assert out["chunked"]["chunks"] > 0
    assert out["chunked"]["decode_gap_p99_ms"] > 0
