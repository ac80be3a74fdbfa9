"""Multi-tenant pooled LoRA serving (bigdl_tpu/serving/lora.py + the
engine's adapter plane): bank alloc/retain/free lifecycle, the
null-adapter token-identity contract against a no-bank engine (fp32 +
bf16), zero extra compiles for mixed base/adapted traffic, fixed-seed
replay through preemption and decode-pool failover, the speculative
draft pin, and sharded DP/TP parity."""

import numpy as np
import pytest


def _make_lm(V=29, hidden=32, heads=4, layers=2, max_len=48, seed=9):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(seed)
    lm = TransformerLM(V, hidden_size=hidden, n_heads=heads,
                       n_layers=layers, max_len=max_len)
    lm._ensure_params()
    lm.evaluate()
    return lm


@pytest.fixture(scope="module")
def lm():
    return _make_lm()


@pytest.fixture(scope="module")
def bank(lm):
    """One 4-slot bank for the module; slots 1-2 pre-allocated with
    visible-amplitude factors (rank-2, amp large enough that adapted
    logits actually diverge on this tiny model)."""
    from bigdl_tpu.serving import AdapterBank

    b = AdapterBank(lm, rank=2, n_slots=4)
    b.alloc(b.random_factors(seed=1, amp=1.0))      # id 1
    b.alloc(b.random_factors(seed=2, amp=1.0))      # id 2
    return b


def _trace(vocab=29, n=6, seed=3):
    from bigdl_tpu.serving import SamplingParams

    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        prompt = rng.randint(1, vocab + 1, size=([3, 7, 5][i % 3],)).tolist()
        sp = (SamplingParams(temperature=0.8, top_k=10, seed=100 + i)
              if i % 2 else None)
        out.append((prompt, 6, sp))
    return out


# -- bank lifecycle ---------------------------------------------------------

def test_bank_lifecycle_and_validation(lm):
    from bigdl_tpu.serving import AdapterBank

    b = AdapterBank(lm, rank=2, n_slots=3)
    assert b.n_free == 2                    # slot 0 is the null adapter
    factors = b.random_factors(seed=5)
    aid = b.alloc(factors)
    assert aid != 0 and b.is_live(aid) and b.live == {aid: 1}
    b.retain(aid)
    assert b.live[aid] == 2
    b.free(aid)                             # refcount 2 -> 1: still live
    assert b.is_live(aid)
    b.free(aid)                             # 1 -> 0: slot returns
    assert not b.is_live(aid) and b.n_free == 2
    # freed rows are ZEROED — a recycled slot must not leak the old
    # tenant's factors into the gather
    for k in b.arrays:
        assert not np.any(b.arrays[k][aid])
    # null adapter is permanent
    with pytest.raises(ValueError):
        b.free(0)
    b.retain(0)                             # no-op, never raises
    # unknown keys / wrong shapes rejected before any row is written
    with pytest.raises(KeyError):
        b.alloc({"nope_a": np.zeros((2, 2), np.float32)})
    bad = dict(factors)
    k0 = next(iter(bad))
    bad[k0] = np.zeros((1, 1), np.float32)
    with pytest.raises(ValueError):
        b.alloc(bad)
    # exhaustion is loud
    b.alloc(b.random_factors(seed=6))
    b.alloc(b.random_factors(seed=7))
    with pytest.raises(RuntimeError):
        b.alloc(b.random_factors(seed=8))
    # retain/free of a dead id is loud
    with pytest.raises(KeyError):
        b.retain(99)


def test_engine_submit_validation(lm, bank):
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=2, adapters=bank)
    with pytest.raises(ValueError, match="adapter"):
        eng.submit([3, 2], max_new_tokens=2, adapter_id=3)   # not live
    plain = ServingEngine(lm, n_slots=2)
    with pytest.raises(ValueError, match="adapter"):
        plain.submit([3, 2], max_new_tokens=2, adapter_id=1)  # no bank
    # per-request admission has no batch prefill plane for the bank
    with pytest.raises(ValueError, match="adapters require"):
        ServingEngine(lm, n_slots=2, adapters=bank,
                      admission="per_request")


# -- THE acceptance contract: null adapter == pre-PR engine -----------------

@pytest.mark.parametrize("dtype_name", ["fp32", "bf16"])
def test_null_adapter_token_identical_to_plain_engine(dtype_name, lm, bank):
    """An adapter-enabled engine serving only null-adapter traffic is
    token-identical to an engine built without a bank: the id-0 rows
    gather all-zero factors and the delta vanishes exactly, in both
    dtypes. The chosen log-probs are held to 1e-6 at fp32, not to the
    bit: the two engines run two separately compiled programs (one
    carries the gathers and the ``+ 0.0`` of every site), and XLA is
    free to order a float32 reduction differently in each — the last
    bit of one log-prob in this trace (2.4e-7). At bf16 they come out
    equal and are held to that."""
    import jax.numpy as jnp

    from bigdl_tpu.serving import ServingEngine

    dt = None if dtype_name == "fp32" else jnp.bfloat16
    trace = _trace()

    plain = ServingEngine(lm, n_slots=3, seed=11, compute_dtype=dt)
    r0 = [plain.submit(p, max_new_tokens=n, sampling=sp)
          for p, n, sp in trace]
    o0 = plain.drain()

    eng = ServingEngine(lm, n_slots=3, seed=11, compute_dtype=dt,
                        adapters=bank)
    r1 = [eng.submit(p, max_new_tokens=n, sampling=sp)
          for p, n, sp in trace]
    o1 = eng.drain()
    for a, b in zip(r0, r1):
        np.testing.assert_array_equal(o0[a], o1[b])
        if dt is None:
            np.testing.assert_allclose(plain.logprobs(a), eng.logprobs(b),
                                       rtol=0, atol=1e-6)
        else:
            np.testing.assert_array_equal(plain.logprobs(a),
                                          eng.logprobs(b))


def test_adapted_rows_actually_diverge(lm, bank):
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=3, seed=11, adapters=bank)
    r0 = eng.submit([3, 7, 2], max_new_tokens=8)
    r1 = eng.submit([3, 7, 2], max_new_tokens=8, adapter_id=1)
    r2 = eng.submit([3, 7, 2], max_new_tokens=8, adapter_id=2)
    o = eng.drain()
    assert list(o[r1]) != list(o[r0])        # adapter changes the stream
    assert list(o[r2]) != list(o[r1])        # ...per tenant


def test_finish_releases_refcount(lm):
    from bigdl_tpu.serving import AdapterBank, ServingEngine

    b = AdapterBank(lm, rank=2, n_slots=3)
    aid = b.alloc(b.random_factors(seed=5, amp=1.0))
    eng = ServingEngine(lm, n_slots=2, adapters=b)
    rid = eng.submit([3, 2], max_new_tokens=3, adapter_id=aid)
    assert b.live[aid] == 2                  # submit retained
    eng.drain()
    assert b.live[aid] == 1                  # finish released
    # cancellation releases too
    r2 = eng.submit([3, 2, 4], max_new_tokens=3, adapter_id=aid)
    assert b.live[aid] == 2
    eng.cancel(r2)
    assert b.live[aid] == 1
    b.free(aid)
    assert not b.is_live(aid)
    assert rid in eng._finished


# -- one program, mixed tenants --------------------------------------------

def test_mixed_traffic_zero_extra_compiles(lm, bank):
    """Base-only traffic, then mixed 3-tenant traffic, on the same
    adapter-enabled engine: the second wave compiles NOTHING new in
    decode or prefill — adapter ids are runtime rows of the one
    program."""
    from tests.compile_guards import assert_compile_count, compile_count

    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=3, seed=11, adapters=bank)
    for p, n, sp in _trace():
        eng.submit(p, max_new_tokens=n, sampling=sp)
    eng.drain()
    decode0 = compile_count(eng._step_fn)
    prefill0 = compile_count(eng._batch_prefill_fn)
    assert decode0 == 1

    for i, (p, n, sp) in enumerate(_trace(seed=7)):
        eng.submit(p, max_new_tokens=n, sampling=sp,
                   adapter_id=[0, 1, 2][i % 3])
    eng.drain()
    assert_compile_count(eng._step_fn, decode0, what="mixed decode")
    assert_compile_count(eng._batch_prefill_fn, prefill0,
                         what="mixed prefill")


# -- replay -----------------------------------------------------------------

def test_adapted_replay_through_preemption(lm, bank):
    """A fixed-seed adapted stream evicted mid-flight by a higher
    priority resumes draw-for-draw: the adapter id rides the preemption
    stash (row_state/restore_row) and the recycled slot re-gathers the
    same tenant's factors."""
    from bigdl_tpu.serving import SamplingParams, ServingEngine

    sp = SamplingParams(temperature=0.9, top_k=10, seed=31)
    base = ServingEngine(lm, n_slots=2, adapters=bank)
    r0 = base.submit([3, 7, 2, 9, 4], max_new_tokens=10, sampling=sp,
                     adapter_id=1)
    want = base.drain()[r0]

    eng = ServingEngine(lm, n_slots=1, policy="priority", adapters=bank)
    r1 = eng.submit([3, 7, 2, 9, 4], max_new_tokens=10, sampling=sp,
                    adapter_id=1, priority=0)
    for _ in range(3):
        eng.step()
    eng.submit([5, 5], max_new_tokens=2, priority=5)   # forces eviction
    outs = eng.drain()
    assert eng.request(r1).preemptions >= 1
    np.testing.assert_array_equal(outs[r1], want)


def test_row_state_carries_adapter(lm, bank):
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=2, adapters=bank)
    rid = eng.submit([3, 7, 2], max_new_tokens=6, adapter_id=2)
    eng.step()
    slot = next(s for s, r in eng.scheduler.running.items()
                if r.req_id == rid)
    payload = eng.pool.row_state(slot)
    assert payload["adapter"] == 2
    # pre-adapter payloads (no key) restore as the null adapter
    del payload["adapter"]
    eng.pool.restore_row(slot, payload)
    assert eng.pool.adapter_ids[slot] == 0
    eng.drain()


@pytest.mark.disagg
def test_adapted_replay_through_pool_failover(lm, bank):
    """Mid-stream decode-pool kill: adapted + base rows all land
    token-identical to the monolithic engine — the adapter id crosses
    the wire in the row payload and in the replay handoff's request
    meta."""
    from bigdl_tpu.serving import (
        DisaggregatedEngine, SamplingParams, ServingEngine)

    sps = [SamplingParams(temperature=0.8, top_k=10, seed=40 + i)
           for i in range(4)]
    prompts = [[3, 7, 2], [5, 1, 8, 2], [9, 4], [6, 6, 6]]
    aids = [0, 1, 2, 1]

    mono = ServingEngine(lm, n_slots=4, seed=7, adapters=bank)
    mr = [mono.submit(p, max_new_tokens=8, sampling=sp, adapter_id=a)
          for p, sp, a in zip(prompts, sps, aids)]
    want = mono.drain()

    d = DisaggregatedEngine(lm, prefill_slots=4, decode_slots=2,
                            decode_pools=2, seed=7, adapters=bank)
    dr = [d.submit(p, max_new_tokens=8, sampling=sp, adapter_id=a)
          for p, sp, a in zip(prompts, sps, aids)]
    for _ in range(3):
        d.step()
    d.kill_pool(0)                           # strands mid-stream rows
    got = d.drain()
    for a, b in zip(mr, dr):
        np.testing.assert_array_equal(want[a], got[b])


def test_payload_wire_roundtrip_keeps_adapter(lm, bank):
    from bigdl_tpu.serving import ServingEngine
    from bigdl_tpu.serving.disagg import (
        pack_payload, request_from_meta, request_meta, unpack_payload)

    eng = ServingEngine(lm, n_slots=2, adapters=bank)
    rid = eng.submit([3, 7, 2], max_new_tokens=6, adapter_id=1)
    eng.step()
    req = next(r for r in eng.scheduler.running.values()
               if r.req_id == rid)
    payload = eng.pool.row_state(req.slot)
    blob = pack_payload(request_meta(req), payload)
    meta, back = unpack_payload(blob)
    assert back["adapter"] == 1
    assert request_from_meta(meta).adapter_id == 1
    eng.drain()


# -- speculative pin --------------------------------------------------------

def test_speculative_pins_drafts_to_null_adapter(lm, bank):
    """On a speculative engine an adapted submit must pin its draft
    budget to 0 (the draft model has no adapter plane); with the pin,
    the adapted stream matches the non-speculative adapter engine
    token for token, and null-adapter rows still draft."""
    from bigdl_tpu.serving import ServingEngine, SpeculativeConfig

    draft = _make_lm(hidden=16, heads=2, layers=1, seed=21)
    base = ServingEngine(lm, n_slots=3, seed=7, adapters=bank)
    b1 = base.submit([3, 7, 2], max_new_tokens=8, adapter_id=1)
    b2 = base.submit([5, 1, 8], max_new_tokens=8)
    want = base.drain()

    se = ServingEngine(lm, n_slots=3, seed=7, adapters=bank,
                       speculative=SpeculativeConfig(draft, k=3))
    with pytest.raises(ValueError, match="draft_tokens=0"):
        se.submit([3, 7, 2], max_new_tokens=8, adapter_id=1)
    s1 = se.submit([3, 7, 2], max_new_tokens=8, adapter_id=1,
                   draft_tokens=0)
    s2 = se.submit([5, 1, 8], max_new_tokens=8)
    got = se.drain()
    np.testing.assert_array_equal(want[b1], got[s1])
    np.testing.assert_array_equal(want[b2], got[s2])
    # the verify plane really ran (speculation stayed on for the mix)
    assert se.metrics.summary()["serving/spec_rows"] > 0


# -- sharded plane ----------------------------------------------------------

@pytest.mark.mesh
@pytest.mark.parametrize("parallelism", [{"data": 4},
                                         {"data": 2, "model": 2}])
def test_sharded_adapter_parity(bank, parallelism):
    """Slot-DP and DP x TP meshes serve the mixed-tenant trace
    token-identically to the unsharded adapter engine: the bank's slot
    axis is replicated, its model axes shard with the TP plane, and
    the row-parallel delta folds in before the psum."""
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    trace = _trace()
    aids = [0, 1, 2, 0, 1, 2]

    def run(**kw):
        eng = ServingEngine(lm, n_slots=4, seed=11, adapters=bank, **kw)
        rids = [eng.submit(p, max_new_tokens=n, sampling=sp, adapter_id=a)
                for (p, n, sp), a in zip(trace, aids)]
        return eng, rids, eng.drain()

    e0, r0, o0 = run()
    e1, r1, o1 = run(parallelism=parallelism)
    for a, b in zip(r0, r1):
        np.testing.assert_array_equal(o0[a], o1[b])
        np.testing.assert_allclose(e0.logprobs(a), e1.logprobs(b),
                                   atol=2e-5)
