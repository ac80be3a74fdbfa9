"""Batched admission subsystem (bigdl_tpu/serving/admission.py +
make_batch_prefill_step + PrefixCache): masked multi-row prefill parity
with the per-row prefill, token-for-token engine parity between batched
and per-request admission across ragged prompt lengths, the bounded
prefill-compile guarantee, and prefix-cache hit/refcount/eviction
invariants."""

import numpy as np
import pytest

from tests.oracle import assert_close
from tests.test_serving import _make_lm


# -- make_batch_prefill_step (the model-layer factor) ----------------------

@pytest.mark.parametrize("dtype_name", ["fp32", "bf16"])
def test_batch_prefill_matches_per_row_prefill(dtype_name, rng):
    """One masked (B, L) prefill over RAGGED rows must reproduce each
    row's private make_prefill_step result: identical cache K/V in the
    valid region, matching last-position logprobs, and advanced pos."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import (
        make_batch_decode_step, make_batch_prefill_step, make_decode_step,
        make_prefill_step, serving_params,
    )

    dtype = None if dtype_name == "fp32" else jnp.bfloat16
    lm = _make_lm()
    P = serving_params(lm, dtype)
    prefill1 = make_prefill_step(lm, dtype)
    prefillB = make_batch_prefill_step(lm, dtype)
    _, init1 = make_decode_step(lm, dtype)
    _, initN = make_batch_decode_step(lm, dtype)

    lens = [4, 7, 1, 6]
    L = 8
    toks = np.zeros((len(lens), L), np.int32)
    prompts = [rng.randint(0, 29, size=(n,)) for n in lens]
    for j, p in enumerate(prompts):
        toks[j, :len(p)] = p
    lpB, cB = prefillB(P, jnp.asarray(toks),
                       np.asarray(lens, np.int32), initN(len(lens)))
    assert np.asarray(cB["pos"]).tolist() == lens
    atol, rtol = (1e-5, 1e-4) if dtype is None else (5e-2, 5e-2)
    for j, p in enumerate(prompts):
        lp1, c1 = prefill1(P, jnp.asarray(p[None]), init1(1))
        assert_close(np.asarray(lpB)[j], np.asarray(lp1)[0],
                     atol=atol, rtol=rtol)
        for i in range(2):
            assert_close(np.asarray(cB[f"k{i}"])[j, :len(p)],
                         np.asarray(c1[f"k{i}"])[0, :len(p)],
                         atol=atol, rtol=rtol)
            assert_close(np.asarray(cB[f"v{i}"])[j, :len(p)],
                         np.asarray(c1[f"v{i}"])[0, :len(p)],
                         atol=atol, rtol=rtol)


def test_batch_prefill_ballast_rows_untouched(rng):
    """lengths == 0 rows are pure ballast (the batch-decode ``active``
    convention): cache and pos bitwise identical after the call."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import (
        make_batch_decode_step, make_batch_prefill_step, serving_params,
    )

    lm = _make_lm()
    P = serving_params(lm, None)
    prefillB = make_batch_prefill_step(lm)
    _, initN = make_batch_decode_step(lm)
    carry = initN(3)
    toks = np.zeros((3, 4), np.int32)
    toks[0, :3] = rng.randint(0, 29, size=(3,))
    before = {k: np.asarray(v).copy() for k, v in carry.items()}
    _, out = prefillB(P, jnp.asarray(toks), np.asarray([3, 0, 0], np.int32),
                      carry)
    assert np.asarray(out["pos"]).tolist() == [3, 0, 0]
    for key in before:
        if key == "pos":
            continue
        np.testing.assert_array_equal(np.asarray(out[key])[1:],
                                      before[key][1:])


def test_batch_prefill_suffix_continuation_matches_full(rng):
    """A nonzero start offset (the prefix-cache suffix path) must land
    on the same state as one full prefill: prefix-chunk + suffix-chunk
    == whole prompt, K/V and logits alike."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import (
        make_batch_decode_step, make_batch_prefill_step, serving_params,
    )

    lm = _make_lm()
    P = serving_params(lm, None)
    prefillB = make_batch_prefill_step(lm)
    _, initN = make_batch_decode_step(lm)
    full = rng.randint(0, 29, size=(9,))

    lp_full, c_full = prefillB(P, jnp.asarray(full[None]),
                               np.asarray([9], np.int32), initN(1))
    _, c_pre = prefillB(P, jnp.asarray(full[None, :5]),
                        np.asarray([5], np.int32), initN(1))
    sfx = np.zeros((1, 8), np.int32)          # padded suffix bucket
    sfx[0, :4] = full[5:]
    lp_cont, c_cont = prefillB(P, jnp.asarray(sfx),
                               np.asarray([4], np.int32), c_pre)
    assert int(np.asarray(c_cont["pos"])[0]) == 9
    assert_close(np.asarray(lp_cont)[0], np.asarray(lp_full)[0], atol=1e-5)
    for i in range(2):
        assert_close(np.asarray(c_cont[f"k{i}"])[0, :9],
                     np.asarray(c_full[f"k{i}"])[0, :9], atol=1e-5)


def test_batch_prefill_rejects_overflow_and_shape_mismatch():
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import (
        make_batch_decode_step, make_batch_prefill_step, serving_params,
    )

    lm = _make_lm()
    P = serving_params(lm, None)
    prefillB = make_batch_prefill_step(lm)
    _, initN = make_batch_decode_step(lm)
    with pytest.raises(ValueError, match="lengths"):
        prefillB(P, jnp.zeros((2, 4), jnp.int32),
                 np.asarray([5, 1], np.int32), initN(2))   # length > L
    with pytest.raises(ValueError, match="max_len"):
        prefillB(P, jnp.zeros((1, 48), jnp.int32),
                 np.asarray([48], np.int32),
                 {**initN(1), "pos": jnp.ones((1,), jnp.int32)})
    with pytest.raises(ValueError, match="rows"):
        prefillB(P, jnp.zeros((2, 4), jnp.int32),
                 np.asarray([1, 1], np.int32), initN(3))   # B mismatch


# -- engine parity (THE acceptance contract) -------------------------------

@pytest.mark.parametrize("dtype_name", ["fp32", "bf16"])
def test_batched_admission_parity_with_per_request(dtype_name, rng):
    """Ragged mixed-arrival trace (prompt lengths 1..12 including
    single-token prompts, fewer slots than requests so rows recycle
    mid-flight): batched admission must be token-for-token identical to
    PR 1's per-request admission AND to sequential generate()."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    dtype = None if dtype_name == "fp32" else jnp.bfloat16
    lm = _make_lm()
    reqs = [([int(rng.randint(1, 30))], 4)]        # a 1-token prompt
    for _ in range(8):
        plen = int(rng.randint(2, 13))
        reqs.append((rng.randint(1, 30, size=(plen,)).tolist(),
                     int(rng.randint(3, 10))))

    outs = {}
    for mode in ("batched", "per_request"):
        eng = ServingEngine(lm, n_slots=3, compute_dtype=dtype,
                            admission=mode)
        ids = [eng.submit(*r) for r in reqs[:3]]
        eng.step(); eng.step()                     # staggered arrivals
        ids += [eng.submit(*r) for r in reqs[3:]]
        res = eng.drain()
        outs[mode] = [res[rid] for rid in ids]
        assert eng.pool.free_slots == eng.pool.n_slots
    for j, (prompt, n_new) in enumerate(reqs):
        want = generate(lm, prompt, length=n_new, temperature=0.0,
                        compute_dtype=dtype)
        np.testing.assert_array_equal(
            outs["batched"][j], want,
            err_msg=f"req {j} prompt={prompt} dtype={dtype_name}")
        np.testing.assert_array_equal(outs["batched"][j],
                                      outs["per_request"][j])


def test_prefix_cache_engine_parity_and_hits(rng):
    """Shared-system-prompt traffic through a prefix-cached engine:
    outputs stay token-for-token equal to generate(), and repeat
    prefixes actually HIT (full, truncated, and suffix partial hits)."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=2, admission="batched",
                        prefix_cache=True)
    sys_p = [5, 9, 3, 7, 2, 8]
    reqs = [(sys_p + rng.randint(1, 30, size=(3,)).tolist(), 5)
            for _ in range(4)]
    reqs.append((reqs[0][0], 5))                  # identical: full hit
    reqs.append((sys_p + [4], 5))                 # shorter: truncated hit
    ids = [eng.submit(*r) for r in reqs]
    outs = eng.drain()
    for rid, (p, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            outs[rid], generate(lm, p, length=n, temperature=0.0),
            err_msg=f"prompt={p}")
    st = eng.prefix_cache.stats()
    assert st["hits"] >= 3 and st["hit_tokens"] >= 3 * len(sys_p)
    assert eng.metrics.summary()["serving/prefix_hit_rate"] > 0


# -- the bounded-compile guarantee -----------------------------------------

def test_prefill_compile_count_bounded_by_buckets(rng):
    """Admitting prompts of MANY distinct lengths must trace a number of
    prefill programs bounded by the power-of-two bucket count — not by
    the number of distinct lengths (PR 1's per-request path compiled one
    program per novel length, mid-admission)."""
    from bigdl_tpu.serving import ServingEngine, bucket_len
    from tests.compile_guards import assert_compile_count

    lm = _make_lm()
    eng = ServingEngine(lm, n_slots=16, admission="batched")
    plens = list(range(2, 14))                    # prefill lens 1..12
    rng.shuffle(plens)
    ids = [eng.submit(rng.randint(1, 30, size=(n,)).tolist(),
                      max_new_tokens=3) for n in plens]
    eng.step()                                    # ONE admission round
    distinct = {n - 1 for n in plens}
    buckets = {bucket_len(n - 1, eng.max_len) for n in plens}
    traced = eng.admitter.traced_shapes
    assert len(traced) <= len(buckets) < len(distinct)
    # the jit cache agrees with our shape ledger
    assert_compile_count(eng._batch_prefill_fn, len(traced),
                         what="first admission wave")
    total_compiles, _ = eng.metrics.metrics.get(
        "serving/prefill_bucket_compiles")
    assert total_compiles == len(traced)
    eng.drain()
    # a second wave of the same lengths re-traces NOTHING
    for n in plens:
        eng.submit(rng.randint(1, 30, size=(n,)).tolist(), max_new_tokens=3)
    eng.drain()
    assert_compile_count(eng._batch_prefill_fn, len(traced),
                         what="repeat lengths, same engine")
    assert len(eng.admitter.traced_shapes) == len(traced)
    # a SECOND engine over the same warm model shares the jitted step:
    # same shapes routed, zero new compiles reported
    eng2 = ServingEngine(lm, n_slots=16, admission="batched")
    for n in plens:
        eng2.submit(rng.randint(1, 30, size=(n,)).tolist(),
                    max_new_tokens=3)
    eng2.drain()
    assert len(eng2.admitter.traced_shapes) == len(traced)
    compiles2, _ = eng2.metrics.metrics.get(
        "serving/prefill_bucket_compiles")
    assert compiles2 == 0
    assert_compile_count(eng2._batch_prefill_fn, len(traced),
                         what="second engine, warm model")


# -- PrefixCache unit invariants -------------------------------------------

def _fake_carry(n_tokens, tag=0.0):
    """A carry-shaped stand-in (the cache never inspects leaves beyond
    'pos', so plain numpy is fine for unit tests)."""
    import jax.numpy as jnp

    return {"pos": jnp.full((1,), n_tokens, jnp.int32),
            "k0": np.full((1, 4), tag, np.float32)}


def test_prefix_cache_lookup_hit_miss_and_truncation():
    from bigdl_tpu.serving import PrefixCache

    pc = PrefixCache(max_entries=8)
    assert pc.acquire([1, 2, 3]) == (None, 0, None)     # cold miss
    pc.insert([1, 2, 3, 4], _fake_carry(4, tag=1.0))
    # exact full hit
    carry, m, lease = pc.acquire([1, 2, 3, 4])
    assert m == 4 and carry["k0"][0, 0] == 1.0
    pc.release(lease)
    # longest-prefix (truncated) hit: cached 4 tokens serve a 2-token
    # prefix with pos clamped, same buffers
    carry, m, lease = pc.acquire([1, 2, 9, 9])
    assert m == 2 and int(np.asarray(carry["pos"])[0]) == 2
    assert carry["k0"][0, 0] == 1.0
    pc.release(lease)
    # divergence at the first token: miss
    assert pc.acquire([7, 1, 2]) == (None, 0, None)
    # deeper entries win over shallower ones
    pc.insert([1, 2], _fake_carry(2, tag=2.0))
    carry, m, lease = pc.acquire([1, 2, 3, 4, 5])
    assert m == 4 and carry["k0"][0, 0] == 1.0
    pc.release(lease)
    assert pc.entries == 2 and pc.hit_rate() > 0
    with pytest.raises(ValueError, match="empty"):
        pc.insert([], _fake_carry(0))


def test_prefix_cache_refcount_and_lru_eviction():
    """Invariants: leases pin entries against eviction, refcounts never
    go negative, eviction is LRU among refs==0 entries, and a
    fully-leased cache overflows rather than dropping live state."""
    from bigdl_tpu.serving import PrefixCache

    pc = PrefixCache(max_entries=2)
    pc.insert([1, 1], _fake_carry(2, tag=1.0))
    pc.insert([2, 2], _fake_carry(2, tag=2.0))
    _, _, lease1 = pc.acquire([1, 1])             # pin entry 1
    assert lease1.refs == 1
    pc.insert([3, 3], _fake_carry(2, tag=3.0))    # over capacity
    # entry 2 (LRU among refs==0) evicted; pinned entry 1 survives
    assert pc.entries == 2
    assert pc.acquire([2, 2]) == (None, 0, None)
    c, m, l3 = pc.acquire([3, 3])
    assert m == 2
    pc.release(l3)
    pc.release(lease1)
    with pytest.raises(ValueError, match="release"):
        pc.release(lease1)                        # refcount can't go < 0
    # everything leased → insert overflows instead of evicting live state
    _, _, la = pc.acquire([1, 1])
    _, _, lb = pc.acquire([3, 3])
    pc.insert([4, 4], _fake_carry(2, tag=4.0))
    assert pc.entries == 3                        # temporary overflow
    pc.release(la); pc.release(lb)
    pc.insert([5, 5], _fake_carry(2, tag=5.0))    # now eviction catches up
    assert pc.entries == 2
    with pytest.raises(ValueError, match="max_entries"):
        PrefixCache(0)


# -- bench scenario smoke (tier-1, small/CPU) ------------------------------

def test_admission_bench_smoke():
    """benchmarks/serving_bench.py --scenario admission on a small
    config: identical outputs, a compiled-prefill set bounded by the
    bucket count (vs one program per distinct length on the per-request
    path), fewer prefill launches, and real prefix hits."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "benchmarks"))
    try:
        import serving_bench
    finally:
        sys.path.pop(0)

    out = serving_bench.run_admission(model="tiny", n_requests=12,
                                      gen_tokens=3, n_slots=6,
                                      shared_frac=0.6, prefix_len=8)
    assert out["outputs_match"]
    # the bounded-compile acceptance: per-request compiles per DISTINCT
    # length; batched compiles per bucket (+ suffix-continuation shapes)
    assert out["per_request"]["prefill_programs"] \
        == out["distinct_prompt_lengths"]
    assert out["batched"]["prefill_programs"] \
        <= out["length_buckets"] + 2
    # admission work must come DOWN, counted and not timed (a ratio of
    # two wall times read 0.89 under six workers' load): one prefill
    # launch a request on the per-request path, fewer on the batched one
    # (a wave seats several; a full prefix hit launches nothing)
    assert out["per_request"]["prefill_calls"] == out["requests"]
    assert out["batched"]["prefill_calls"] \
        < out["per_request"]["prefill_calls"], out
    assert out["batched"]["prefix_hit_tokens"] > 0


def test_bucket_len():
    from bigdl_tpu.serving import bucket_len

    assert [bucket_len(n, 48) for n in (1, 2, 3, 5, 16, 17, 47, 300)] \
        == [1, 2, 4, 8, 16, 32, 48, 48]
    with pytest.raises(ValueError, match="positive"):
        bucket_len(0, 48)
