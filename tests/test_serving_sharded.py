"""Sharded serving plane (serving/sharded.py) on the 8-virtual-device
CPU mesh: token-identical parity of sharded vs unsharded engines
(slot-data-parallel, tensor-parallel, and combined meshes; fp32 + bf16;
mixed greedy/sampled traffic with evict/readmit), the one-compiled-
program-per-engine guard, seed reproducibility across mesh shapes,
balanced cross-shard allocation, and the new shard metrics."""

import numpy as np
import pytest

pytestmark = pytest.mark.mesh


def _build_lm(vocab=96, hidden=32, heads=4, layers=2, max_len=64, seed=17):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(seed)
    lm = TransformerLM(vocab, hidden_size=hidden, n_heads=heads,
                       n_layers=layers, max_len=max_len, output="logits")
    lm._ensure_params()
    lm.evaluate()
    return lm


@pytest.fixture(scope="module")
def lm():
    return _build_lm()


def _trace(n=10, vocab=96, seed=3):
    """Mixed greedy/sampled requests over a few prompt lengths; more
    requests than any test engine has slots, so later requests are
    admitted into evicted rows (the readmission path)."""
    from bigdl_tpu.serving import SamplingParams

    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        plen = [3, 7, 12][i % 3]
        prompt = rng.randint(1, vocab + 1, size=(plen,)).tolist()
        sp = (SamplingParams(temperature=0.8, top_k=20, seed=100 + i)
              if i % 2 else None)
        out.append((prompt, 8, sp))
    return out


def _run(lm, trace, **kw):
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, **kw)
    rids = [eng.submit(p, max_new_tokens=n, sampling=sp)
            for p, n, sp in trace]
    outs = eng.drain()
    return eng, rids, outs


def _assert_identical(eng_a, rids_a, outs_a, eng_b, rids_b, outs_b):
    for ra, rb in zip(rids_a, rids_b):
        np.testing.assert_array_equal(outs_a[ra], outs_b[rb])
        np.testing.assert_allclose(eng_a.logprobs(ra), eng_b.logprobs(rb),
                                   atol=2e-5)


@pytest.fixture(scope="module")
def baseline(lm):
    """Unsharded engine outputs for the standard trace — the oracle
    every mesh shape must reproduce token for token."""
    return _run(lm, _trace(), n_slots=4)


@pytest.mark.parametrize("parallelism", [{"data": 4},
                                         {"data": 2, "model": 2}])
def test_sharded_engine_token_identical(lm, baseline, parallelism):
    """Sharded engines (slot-DP and combined DP x TP meshes) serve the
    mixed greedy/sampled evict/readmit trace token-identically."""
    e0, r0, o0 = baseline
    e1, r1, o1 = _run(lm, _trace(), n_slots=4, parallelism=parallelism)
    _assert_identical(e0, r0, o0, e1, r1, o1)
    # sampled logprobs ride along: slot-DP is bitwise, TP to round-off
    assert e1.pool.n_shards == parallelism.get("data", 1)


def test_tensor_parallel_token_identical(lm, baseline):
    """Pure tensor parallelism (4-way heads/MLP sharding, two psums per
    block) reproduces the unsharded token stream."""
    e0, r0, o0 = baseline
    e1, r1, o1 = _run(lm, _trace(), n_slots=4, parallelism={"model": 4})
    _assert_identical(e0, r0, o0, e1, r1, o1)


def test_sharded_bf16_with_prefix_cache_token_identical(lm):
    """bf16 serving dtype + prefix cache on a 4-way slot-DP mesh vs the
    unsharded bf16 engine: identical tokens (shared-prefix clones land
    on the owning shard through the mesh-pinned scatter)."""
    import jax.numpy as jnp

    base = [5, 9, 13]                       # shared prefix
    rng = np.random.RandomState(11)
    trace = []
    for i in range(8):
        tail = rng.randint(1, 97, size=(3 + i % 4,)).tolist()
        trace.append((base + tail, 6, None))
    e0, r0, o0 = _run(lm, trace, n_slots=4, compute_dtype=jnp.bfloat16,
                      prefix_cache=True)
    e1, r1, o1 = _run(lm, trace, n_slots=4, compute_dtype=jnp.bfloat16,
                      prefix_cache=True, parallelism={"data": 4})
    _assert_identical(e0, r0, o0, e1, r1, o1)
    assert e1.metrics.summary().get("serving/prefix_hit_rate", 0) > 0


def test_tensor_parallel_bf16_token_identical(lm):
    """bf16 + tensor parallelism: the row-parallel projections must
    accumulate fp32 through the psum and round ONCE (regression for the
    per-chip-rounding drift that flipped greedy argmaxes on near-tied
    bf16 logits — caught by the user-style verify drive, not the fp32
    parity tests)."""
    import jax.numpy as jnp

    e0, r0, o0 = _run(lm, _trace(), n_slots=4, compute_dtype=jnp.bfloat16)
    e1, r1, o1 = _run(lm, _trace(), n_slots=4, compute_dtype=jnp.bfloat16,
                      parallelism={"data": 2, "model": 2})
    for ra, rb in zip(r0, r1):
        np.testing.assert_array_equal(o0[ra], o1[rb])


def test_per_request_admission_on_mesh(lm, baseline):
    """The per_request (B=1 prefill) admission path also routes into the
    sharded pool correctly."""
    e0, r0, o0 = baseline
    e1, r1, o1 = _run(lm, _trace(), n_slots=4, admission="per_request",
                      parallelism={"data": 2})
    _assert_identical(e0, r0, o0, e1, r1, o1)


def test_one_decode_program_regardless_of_mesh_size():
    """Compile-count regression guard: every engine — unsharded, 2-way,
    4-way slot-DP, 2-way TP — runs its whole trace through exactly ONE
    compiled decode program (fresh model per engine so each owns its
    step cache)."""
    from tests.compile_guards import assert_compile_count

    for kw in ({}, {"parallelism": {"data": 2}},
               {"parallelism": {"data": 4}},
               {"parallelism": {"model": 2}}):
        lm = _build_lm()
        eng, _, _ = _run(lm, _trace(6), n_slots=4, **kw)
        assert_compile_count(eng._step_fn, 1, what=repr(kw))


def test_seed_reproducible_across_mesh_shapes(lm):
    """A fixed-seed sampled request emits the same token stream on every
    mesh shape (lanes are request-keyed, never slot- or shard-keyed)."""
    from bigdl_tpu.serving import SamplingParams

    prompt = [4, 19, 33, 2]
    sp = SamplingParams(temperature=1.1, top_p=0.9, seed=1234)
    streams = []
    for kw in ({}, {"parallelism": {"data": 2}},
               {"parallelism": {"data": 4}},
               {"parallelism": {"model": 2}}):
        eng, rids, outs = _run(lm, [(prompt, 10, sp)], n_slots=4, **kw)
        streams.append(outs[rids[0]])
    for s in streams[1:]:
        np.testing.assert_array_equal(streams[0], s)


def test_balanced_allocation_and_slot_routing(lm):
    """ShardedKVPool invariants: contiguous slot→(shard, row) blocks,
    least-loaded allocation (one slot per shard before any second), and
    free/realloc keeping both free-list views consistent."""
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=8, parallelism={"data": 4})
    pool = eng.pool
    assert pool.n_shards == 4 and pool.rows_per_shard == 2
    assert pool.slot_shard(0) == (0, 0)
    assert pool.slot_shard(5) == (2, 1)
    with pytest.raises(ValueError):
        pool.slot_shard(8)
    slots = [pool.alloc() for _ in range(4)]
    assert sorted(pool.slot_shard(s)[0] for s in slots) == [0, 1, 2, 3]
    assert pool.used_per_shard() == [1, 1, 1, 1]
    pool.free(slots[1])
    assert pool.used_per_shard() == [1, 0, 1, 1]
    nxt = pool.alloc()                      # least-loaded shard refills
    assert pool.slot_shard(nxt)[0] == pool.slot_shard(slots[1])[0]
    for s in [slots[0], slots[2], slots[3], nxt]:
        pool.free(s)
    assert pool.free_slots == 8 and pool.used_per_shard() == [0] * 4
    assert "n_shards=4" in repr(pool)


def test_kvpool_repr_and_occupancy_guard():
    """Satellite: base-pool repr and the n_slots==0 occupancy guard."""
    import jax.numpy as jnp

    from bigdl_tpu.serving import KVPool

    def init(n):
        return {"pos": jnp.zeros((n,), jnp.int32),
                "k0": jnp.zeros((n, 4, 4)), "v0": jnp.zeros((n, 4, 4))}

    pool = KVPool(init, 2)
    r = repr(pool)
    assert "n_slots=2" in r and "n_shards" not in r
    pool.alloc()
    assert "used=1" in repr(pool) and pool.occupancy() == 0.5
    # the guard: a (hypothetical) zero-capacity pool reports 0.0, never
    # ZeroDivisionError mid-serving
    pool.n_slots = 0
    assert pool.occupancy() == 0.0
    with pytest.raises(ValueError):
        KVPool(init, 0)


def test_mesh_and_parallelism_validation(lm):
    from bigdl_tpu.serving import ServingEngine
    from bigdl_tpu.serving.sharded import make_mesh

    with pytest.raises(ValueError, match="not divisible"):
        ServingEngine(lm, n_slots=5, parallelism={"data": 4})
    with pytest.raises(ValueError, match="n_heads"):
        ServingEngine(lm, n_slots=8, parallelism={"model": 8})
    with pytest.raises(ValueError, match="unknown parallelism"):
        ServingEngine(lm, n_slots=8, parallelism={"tensor": 2})
    with pytest.raises(ValueError, match="1x1 mesh"):
        ServingEngine(lm, n_slots=8, parallelism={})
    with pytest.raises(ValueError, match="devices"):
        make_mesh(data=64)


def test_shard_metrics_surfaced(lm):
    """mesh_shape, per-shard occupancy, and admission imbalance ride
    through ServingMetrics; the balanced allocator keeps imbalance <= 1
    row under drain-style traffic."""
    eng, _, _ = _run(lm, _trace(8), n_slots=4, parallelism={"data": 4})
    s = eng.metrics.summary()
    assert s["serving/mesh_data_shards"] == 4.0
    assert s["serving/mesh_model_shards"] == 1.0
    assert "serving/shard_occupancy_min" in s
    assert "serving/shard_occupancy_max" in s
    vals = eng.metrics.metrics.values("serving/shard_imbalance")
    assert vals and max(vals) <= 1.0


def test_sharded_bench_smoke():
    """--scenario sharded runs end to end on a tiny config and reports
    an output-identity verdict."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "benchmarks"))
    try:
        import serving_bench
    finally:
        sys.path.pop(0)
    res = serving_bench.run_sharded(model="tiny", n_requests=6,
                                    gen_tokens=4, n_slots=4,
                                    data_shards=4)
    assert res["outputs_match"] is True
    assert res["sharded"]["decode_programs"] == 1
    assert res["mesh"] == {"data": 4, "model": 1}
