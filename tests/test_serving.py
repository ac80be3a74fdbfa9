"""Continuous-batching serving engine (bigdl_tpu/serving/): output parity
with sequential generate(), eviction/readmission, KV-pool free-list
invariants, metrics plumbing, and the jitted-step cache."""

import numpy as np
import pytest

from tests.oracle import assert_close


def _make_lm(V=29, hidden=32, heads=4, layers=2, max_len=48, seed=9):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(seed)
    lm = TransformerLM(V, hidden_size=hidden, n_heads=heads,
                       n_layers=layers, max_len=max_len)
    lm._ensure_params()
    lm.evaluate()
    return lm


# -- KV pool ---------------------------------------------------------------

def test_kv_pool_free_list_invariants():
    """No slot aliasing while allocated, None when saturated, double-free
    and foreign-slot writes raise, and a full drain leaks nothing."""
    from bigdl_tpu.models.transformer import make_batch_decode_step
    from bigdl_tpu.serving import KVPool

    lm = _make_lm()
    _, init_carry = make_batch_decode_step(lm)
    pool = KVPool(init_carry, 4)
    assert pool.free_slots == 4 and pool.used_slots == 0

    slots = [pool.alloc() for _ in range(4)]
    assert sorted(slots) == [0, 1, 2, 3]          # every slot handed out once
    assert len(set(slots)) == 4                   # no aliasing
    assert pool.alloc() is None                   # saturated → None, no raise
    assert pool.occupancy() == 1.0

    pool.free(slots[1])
    s = pool.alloc()
    assert s == slots[1]                          # freed slot is reusable
    with pytest.raises(ValueError, match="not allocated"):
        pool.free(99)
    pool.free(s)
    with pytest.raises(ValueError, match="not allocated"):
        pool.free(s)                              # double free
    with pytest.raises(ValueError, match="not allocated"):
        pool.set_pos(s, 0)                        # foreign-slot write
    for x in (slots[0], slots[2], slots[3]):
        pool.free(x)
    assert pool.free_slots == 4 and pool.used_slots == 0   # no leak
    assert np.asarray(pool.carry["pos"]).tolist() == [0, 0, 0, 0]

    with pytest.raises(ValueError, match="n_slots"):
        KVPool(init_carry, 0)


def test_kv_pool_write_prefill_row_scatter():
    """A B=1 prefilled carry row-scatters into exactly the target slot:
    K/V rows 0..P-1 land there, pos becomes P, other slots untouched."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import (
        make_batch_decode_step, make_decode_step, make_prefill_step,
        serving_params,
    )
    from bigdl_tpu.serving import KVPool

    lm = _make_lm()
    _, init1 = make_decode_step(lm)
    _, initN = make_batch_decode_step(lm)
    prefill = make_prefill_step(lm)
    P = serving_params(lm, None)
    pool = KVPool(initN, 3)
    slot = pool.alloc()

    toks = np.array([[3, 7, 1, 4]], np.int32)
    _, pc = prefill(P, jnp.asarray(toks), init1(1))
    before = {k: np.asarray(v).copy() for k, v in pool.carry.items()}
    pool.write_prefill(slot, pc, 4)

    assert int(np.asarray(pool.carry["pos"])[slot]) == 4
    for i in range(pool.n_layers):
        got = np.asarray(pool.carry[f"k{i}"])
        assert_close(got[slot, :4], np.asarray(pc[f"k{i}"])[0, :4], atol=0)
        # other slots bitwise untouched
        others = [s for s in range(3) if s != slot]
        np.testing.assert_array_equal(got[others], before[f"k{i}"][others])
    with pytest.raises(ValueError, match="prompt_len"):
        pool.write_prefill(slot, pc, pool.max_len + 1)


# -- scheduler -------------------------------------------------------------

def test_scheduler_policies_and_lifecycle():
    from bigdl_tpu.serving.scheduler import Request, Scheduler

    with pytest.raises(ValueError, match="policy"):
        Scheduler("lifo")

    def req(i):
        return Request(req_id=i, prompt=[1, 2], max_new_tokens=4)

    cont = Scheduler("prefill_priority")
    cont.submit(req(0)); cont.submit(req(1)); cont.submit(req(2))
    assert cont.queue_depth == 3
    assert cont.admissible(free_slots=2) == 2
    a = cont.admit(0)
    assert a.req_id == 0 and a.state == "running"      # FIFO order
    # continuous batching: admission allowed while others run
    assert cont.admissible(free_slots=1) == 1

    fifo = Scheduler("fifo")
    fifo.submit(req(0)); fifo.submit(req(1))
    fifo.admit(0)
    # run-to-completion: no refill while the batch is non-empty
    assert fifo.admissible(free_slots=1) == 0
    r = fifo.running[0]
    fifo.finish(r, now=1.0)
    assert fifo.admissible(free_slots=2) == 1
    assert r.state == "finished" and r.finish_time == 1.0

    s = Scheduler()
    with pytest.raises(ValueError, match="non-empty"):
        s.submit(Request(req_id=9, prompt=[], max_new_tokens=4))
    with pytest.raises(ValueError, match="max_new_tokens"):
        s.submit(Request(req_id=9, prompt=[1], max_new_tokens=0))


# -- engine parity (THE serving contract) ----------------------------------

@pytest.mark.parametrize("dtype_name", ["fp32", "bf16"])
def test_engine_parity_with_sequential_generate(dtype_name, rng):
    """For a mixed-arrival trace (varying prompt lengths and output
    budgets, staggered submits, fewer slots than requests so rows are
    evicted and reused mid-flight), every request's engine output must be
    token-for-token identical to per-request sequential
    generate(temperature=0) — plain and bf16-serving params."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    dtype = None if dtype_name == "fp32" else jnp.bfloat16
    lm = _make_lm()
    reqs = []
    for i in range(7):
        plen = int(rng.randint(1, 6))
        prompt = rng.randint(1, 30, size=(plen,)).tolist()
        reqs.append((prompt, int(rng.randint(3, 12))))

    eng = ServingEngine(lm, n_slots=3, compute_dtype=dtype)
    ids = [eng.submit(*reqs[0]), eng.submit(*reqs[1])]
    eng.step(); eng.step()                       # mid-flight...
    ids += [eng.submit(*r) for r in reqs[2:5]]   # ...staggered arrivals
    eng.step()
    ids += [eng.submit(*r) for r in reqs[5:]]
    outs = eng.drain()

    for rid, (prompt, n_new) in zip(ids, reqs):
        want = generate(lm, prompt, length=n_new, temperature=0.0,
                        compute_dtype=dtype)
        np.testing.assert_array_equal(
            outs[rid], want,
            err_msg=f"req {rid} prompt={prompt} dtype={dtype_name}")
    # free-list invariant after drain: nothing leaked
    assert eng.pool.free_slots == eng.pool.n_slots


def test_engine_eos_eviction_and_slot_readmission():
    """A row must be evicted the step its EOS appears (output truncated
    there) and its slot immediately reusable by a waiting request."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm(seed=13)
    prompt = [3, 7]
    free_run = generate(lm, prompt, length=8, temperature=0.0)
    eos = int(free_run[3])                 # a token greedy WILL emit
    cut = int(np.where(free_run == eos)[0][0])   # its FIRST occurrence

    eng = ServingEngine(lm, n_slots=1)     # single slot forces queueing
    a = eng.submit(prompt, max_new_tokens=8, eos_id=eos)
    b = eng.submit([5], max_new_tokens=4)  # waits for a's slot
    outs = eng.drain()
    np.testing.assert_array_equal(outs[a], free_run[:cut + 1])  # cut AT eos
    assert eng.request(a).done_reason == "eos"
    assert eng.request(b).done_reason == "length"
    np.testing.assert_array_equal(
        outs[b], generate(lm, [5], length=4, temperature=0.0))
    assert eng.pool.free_slots == 1


def test_engine_fifo_policy_runs_to_completion():
    """policy="fifo" (static batching baseline): same outputs, but no
    admission while the running batch is non-empty."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm(seed=17)
    eng = ServingEngine(lm, n_slots=2, policy="fifo")
    ids = [eng.submit([3, 4], max_new_tokens=3),
           eng.submit([5], max_new_tokens=5),
           eng.submit([7, 2], max_new_tokens=4)]
    eng.step()
    assert eng.active == 2 and eng.queue_depth == 1
    # the first request finishes at 3 tokens; with one decode program
    # in flight behind the host (the default) the finish is SEEN by the
    # step after the one that computed it
    eng.step(); eng.step(); eng.step()
    # run-to-completion: the freed slot is NOT refilled mid-batch
    assert eng.active == 1 and eng.queue_depth == 1
    outs = eng.drain()
    for rid, (p, n) in zip(ids, [([3, 4], 3), ([5], 5), ([7, 2], 4)]):
        np.testing.assert_array_equal(
            outs[rid], generate(lm, p, length=n, temperature=0.0))


def test_engine_rejects_bad_requests():
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=2)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit([1, 2, 3], max_new_tokens=100)       # would overflow
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit([], max_new_tokens=4)
    eng.submit([1, 2, 3], max_new_tokens=4)             # at the edge: fine
    with pytest.raises(ValueError, match="admission"):
        ServingEngine(lm, admission="psychic")
    with pytest.raises(ValueError, match="batched"):
        ServingEngine(lm, admission="per_request", prefix_cache=True)


# -- lifecycle: cancel + bounded finished ledger ---------------------------

def test_cancel_waiting_and_running_requests():
    """A cancelled WAITING request is dequeued for good: it never takes
    a slot, emits nothing, and is reported state='cancelled'. A
    cancelled RUNNING request frees its slot immediately, keeps the
    tokens it had already emitted, and never emits another."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=1)       # single slot forces queueing
    a = eng.submit([3, 7], max_new_tokens=4)
    b = eng.submit([5, 2], max_new_tokens=4)
    c = eng.submit([9], max_new_tokens=3)
    # a runs; b, c wait. The second step launches a's second decode
    # and only then reads its first token back (one program stays in
    # flight behind the host by default)
    eng.step(); eng.step()
    assert eng.cancel(b)
    assert not eng.cancel(b)                 # already cancelled: no-op
    assert eng.queue_depth == 1              # only c still waits
    # RUNNING cancel, with a's second decode still in flight: a has
    # emitted one token; its slot frees NOW, its output freezes (the
    # in-flight token is thrown away) — c gets the slot on the next step
    assert eng.cancel(a)
    assert eng.request(a).state == "cancelled"
    out_a = list(eng.request(a).output)
    assert len(out_a) == 1
    assert eng.pool.free_slots == 1
    outs = eng.drain()
    assert a not in outs and b not in outs   # neither reached FINISHED
    assert list(eng.request(a).output) == out_a   # frozen at cancel
    assert eng.request(b).state == "cancelled"
    assert eng.request(b).done_reason is None
    assert eng.result(b) is not None and len(eng.result(b)) == 0
    np.testing.assert_array_equal(
        outs[c], generate(lm, [9], length=3, temperature=0.0))
    assert eng.pool.free_slots == 1
    total, n = eng.metrics.metrics.get("serving/cancelled")
    assert (total, n) == (2.0, 2)


def test_pop_result_and_keep_finished_bound_the_ledger():
    """pop_result() consumes an output; keep_finished=N evicts the
    oldest finished entries so a long-lived engine stays bounded."""
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=2, keep_finished=2)
    rids = [eng.submit([3, i + 2], max_new_tokens=3) for i in range(5)]
    eng.drain()
    # only the 2 most recently finished survive
    assert len(eng._finished) == 2
    assert eng.result(rids[0]) is None       # evicted oldest-first
    kept = [r for r in rids if eng.result(r) is not None]
    assert len(kept) == 2
    out = eng.pop_result(kept[0])
    assert out is not None and len(out) == 3
    assert eng.result(kept[0]) is None       # consumed
    assert eng.pop_result(kept[0]) is None   # second pop: gone
    with pytest.raises(ValueError, match="keep_finished"):
        ServingEngine(lm, n_slots=2, keep_finished=-1)


# -- metrics ---------------------------------------------------------------

def test_engine_metrics_counters():
    """ServingMetrics rides the training plane's Metrics surface: queue
    depth / occupancy / TTFT / latency / tokens counters all populate and
    summary() derives throughput + TTFT percentiles."""
    from bigdl_tpu.optim.metrics import Metrics
    from bigdl_tpu.serving import ServingEngine, ServingMetrics

    backing = Metrics()
    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=2,
                        metrics=ServingMetrics(backing))
    for p, n in [([3, 7], 4), ([5], 3), ([2, 9, 4], 5)]:
        eng.submit(p, max_new_tokens=n)
    eng.drain()

    s = eng.metrics.summary()
    assert s["serving/finished"] == 1.0                 # mean of ones
    total, n_req = backing.get("serving/finished")
    assert (total, n_req) == (3.0, 3)
    total_tok, _ = backing.get("serving/tokens_out")
    assert total_tok == 4 + 3 + 5
    assert s["serving/tokens_per_sec"] > 0
    assert 0 < s["serving/slot_occupancy"] <= 1.0
    assert s["serving/ttft_p50_s"] > 0
    assert s["serving/ttft_p50_s"] <= s["serving/ttft_p99_s"]
    _, n_ttft = backing.get("serving/ttft_s")
    assert n_ttft == 3                                  # one TTFT per request
    # the underlying Metrics is the standard observability object — a
    # TrainSummary-style consumer can read the same counters
    assert backing.mean("serving/queue_depth") >= 0.0
    # per-reason disposition counters: every request leaving the engine
    # lands in exactly one serving/finish_<reason> bucket (three cap out
    # on length; a fourth is cancelled while waiting — the buckets sum
    # to every submitted request's fate), and the vocabulary is CLOSED —
    # an unknown reason raises instead of minting an unaccounted counter
    # (the SRV205 contract's runtime half)
    assert s["serving/finish_length"] == 3.0
    assert "serving/finish_eos" not in s
    c = eng.submit([4, 8], max_new_tokens=2)
    assert eng.cancel(c)
    s = eng.metrics.summary()
    assert s["serving/finish_cancelled"] == 1.0
    total, _ = eng.metrics.metrics.get("serving/submitted")
    assert sum(v for k, v in s.items()
               if k.startswith("serving/finish_")) == total == 4
    with pytest.raises(ValueError, match="FINISH_REASONS"):
        eng.metrics.on_finish_reason("oom")   # analysis: ok: SRV205


# -- batch decode step (the model-layer factor the engine rides on) --------

def test_host_step_split_metric():
    """Every super-step records its host-vs-device split:
    serving/host_step_s samples land one per decode step, host +
    device account for (at most) the step wall, and summary() derives
    the p50/p99 the async refactor's acceptance will cite."""
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=2)
    eng.submit([3, 7], max_new_tokens=4)
    eng.submit([5, 2], max_new_tokens=4)
    eng.drain()

    total, n = eng.metrics.metrics.get("serving/host_step_s")
    _, n_dec = eng.metrics.metrics.get("serving/decode_step_s")
    assert n == n_dec and n >= 4          # one split sample per decode step
    assert total >= 0.0
    assert eng.metrics.device_seconds > 0.0
    s = eng.metrics.summary()
    assert s["serving/host_step_p50_s"] <= s["serving/host_step_p99_s"]
    pct = eng.metrics.host_step_percentiles()
    assert set(pct) == {"p50", "p90", "p99"}

    # the pairing survives fault recovery: a recovered step's discarded
    # outputs still cost host time, so the split sample lands for every
    # decode_step sample — the series stay comparable one for one
    from bigdl_tpu.serving import FaultInjector

    eng2 = ServingEngine(_make_lm(), n_slots=2,
                         faults=FaultInjector(seed=1, p_garbage=0.4))
    eng2.submit([3, 7], max_new_tokens=4)
    eng2.submit([5, 2], max_new_tokens=4)
    eng2.drain()
    _, n2 = eng2.metrics.metrics.get("serving/host_step_s")
    _, n2_dec = eng2.metrics.metrics.get("serving/decode_step_s")
    assert n2 == n2_dec and eng2.metrics.metrics.get(
        "serving/retries")[1] > 0

    # the split's windowed half: at dispatch_ahead>0 the residue's
    # device side is the BLOCKED time (fence_wait — decode_step
    # overlaps host work under a window and no longer feeds
    # device_seconds), fence_wait pairs one for one with decode_step,
    # and the pairing survives the drain teardown's out-of-step flush
    eng3 = ServingEngine(_make_lm(), n_slots=2, dispatch_ahead=2)
    eng3.submit([3, 7], max_new_tokens=4)
    eng3.submit([5, 2], max_new_tokens=4)
    eng3.drain()
    _, n3 = eng3.metrics.metrics.get("serving/host_step_s")
    _, n3_dec = eng3.metrics.metrics.get("serving/decode_step_s")
    _, n3_fence = eng3.metrics.metrics.get("serving/fence_wait_s")
    assert n3 == n3_dec == n3_fence
    assert "decode_step" not in eng3.metrics.DEVICE_PHASES
    assert "fence_wait" in eng3.metrics.DEVICE_PHASES


def test_batch_decode_step_matches_single_row(rng):
    """Per-row-position decode: a row stepped inside a shared pool (other
    rows active at different depths) matches the single-request decode
    step position-for-position; inactive rows stay bitwise untouched."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import (
        make_batch_decode_step, make_decode_step, serving_params,
    )

    lm = _make_lm()
    step1, init1 = make_decode_step(lm)
    stepN, initN = make_batch_decode_step(lm)
    P = serving_params(lm, None)
    toks = rng.randint(0, 29, size=(6,))

    ref, c1 = [], init1(1)
    for t in toks:
        lp, c1 = step1(P, jnp.asarray([int(t)]), c1)
        ref.append(np.asarray(lp)[0])

    N = 3
    cN = initN(N)
    got2, got0 = [], []
    for i, t in enumerate(toks):
        tokens = np.zeros((N,), np.int32)
        active = np.zeros((N,), bool)
        tokens[2], active[2] = int(t), True
        if i >= 2:                      # row 0 joins two steps later
            tokens[0], active[0] = int(toks[i - 2]), True
        before_k0_row1 = np.asarray(cN["k0"])[1].copy()
        lp, cN = stepN(P, jnp.asarray(tokens), jnp.asarray(active), cN)
        # inactive row 1: cache and pos bitwise untouched
        np.testing.assert_array_equal(np.asarray(cN["k0"])[1],
                                      before_k0_row1)
        got2.append(np.asarray(lp)[2])
        if i >= 2:
            got0.append(np.asarray(lp)[0])
    assert int(np.asarray(cN["pos"])[1]) == 0
    for a, b in zip(ref, got2):
        assert_close(a, b, atol=1e-5)
    for a, b in zip(ref, got0):
        assert_close(a, b, atol=1e-5)


def test_prefill_rejects_partially_filled_carry():
    """The fresh-carry contract (ADVICE r5): prefill on a carry with
    pos != 0 must raise instead of silently overwriting rows 0..P-1."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import (
        make_decode_step, make_prefill_step, serving_params,
    )

    lm = _make_lm()
    step, init_carry = make_decode_step(lm)
    prefill = make_prefill_step(lm)
    P = serving_params(lm, None)
    carry = init_carry(1)
    _, carry = step(P, jnp.asarray([3]), carry)         # pos is now 1
    with pytest.raises(ValueError, match="fresh carry"):
        prefill(P, jnp.asarray([[1, 2]], jnp.int32), carry)
    # a fresh carry still works
    _, c2 = prefill(P, jnp.asarray([[1, 2]], jnp.int32), init_carry(1))
    assert int(np.asarray(c2["pos"])[0]) == 2


def test_step_cache_reuses_jitted_steps():
    """get_*_step return the SAME objects per (model, dtype) — repeated
    generate()/engine construction stops paying XLA compiles (ADVICE r5);
    distinct dtypes and models still get distinct entries."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import (
        get_batch_decode_step, get_decode_step, get_prefill_step,
    )

    lm = _make_lm()
    lm2 = _make_lm(seed=23)
    assert get_decode_step(lm) is get_decode_step(lm)
    assert get_prefill_step(lm) is get_prefill_step(lm)
    assert get_batch_decode_step(lm) is get_batch_decode_step(lm)
    assert get_decode_step(lm) is not get_decode_step(lm, jnp.bfloat16)
    assert get_decode_step(lm) is not get_decode_step(lm2)


# -- bench registration smoke (tier-1, small/CPU) --------------------------

def test_serving_bench_smoke():
    """benchmarks/serving_bench.py runs end-to-end on a tiny CPU config
    and the engine beats arrival-ordered sequential serving (the full-
    size acceptance run uses the defaults: ≥ 2x on 8+ requests)."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "benchmarks"))
    try:
        import serving_bench
    finally:
        sys.path.pop(0)

    # stagger 0: all requests arrive up front, so neither path sleeps on
    # wall-clock arrivals — the ratio is the pure batching win, stable
    # under CI load (wall-clock staggering made the assert flaky)
    out = serving_bench.run(model="tiny", n_requests=8, gen_tokens=24,
                            stagger_ms=0.0, n_slots=8)
    assert out["engine"]["tokens"] == out["sequential"]["tokens"] == 192
    assert out["engine"]["tokens_per_sec"] > 0
    assert set(out["engine"]["ttft"]) == {"p50_ms", "p90_ms", "p99_ms"}
    # loose floor for a noisy shared CPU box (this config measures ~2x;
    # the ≥2x acceptance number is the bench's own default run — see
    # docs/serving.md); near-1x would mean batching is broken outright
    assert out["speedup"] > 1.3, out
