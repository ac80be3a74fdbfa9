"""Speculative decoding (bigdl_tpu/serving/speculative.py +
make_batch_verify_step): greedy token-identity with the baseline engine
and generate() (with GOOD and with GARBAGE drafts — the emitted stream
is draft-independent by construction), fixed-seed replay of rejection
sampling across speculative/normal engines, eviction/readmission, and
batched-vs-per_request admission, the one-verify-program compile guard
for mixed speculative/normal traffic, stop machinery through multi-token
chunks, KV-rollback/pool invariants, accept-rate metrics, the sharded
plane, and the bench smoke."""

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
    """The target model — one per module so every engine shares the
    cached jitted steps."""
    return _make_lm()


@pytest.fixture(scope="module")
def good_draft():
    """A weight-tied draft (same seed, same config): proposals track the
    target's greedy path, so acceptance is high — the 'trained draft'
    stand-in untrained bench models allow."""
    return _make_lm()


@pytest.fixture(scope="module")
def bad_draft():
    """An uncorrelated draft (different seed/width/depth): proposals are
    noise, acceptance ~0 — correctness must not care."""
    return _make_lm(seed=31, hidden=16, heads=2, layers=1)


def _spec(draft, k=3):
    from bigdl_tpu.serving import SpeculativeConfig

    return SpeculativeConfig(draft, k=k)


# -- config validation ------------------------------------------------------

def test_speculative_config_validation(lm, good_draft):
    from bigdl_tpu.serving import ServingEngine, SpeculativeConfig

    with pytest.raises(ValueError, match="k must be >= 1"):
        SpeculativeConfig(good_draft, k=0)
    with pytest.raises(ValueError, match="vocab"):
        ServingEngine(lm, n_slots=2,
                      speculative=_spec(_make_lm(V=17, seed=3)))
    with pytest.raises(ValueError, match="max_len"):
        ServingEngine(lm, n_slots=2,
                      speculative=_spec(_make_lm(max_len=24, seed=3)))
    eng = ServingEngine(lm, n_slots=2, speculative=_spec(good_draft))
    with pytest.raises(ValueError, match="draft_tokens"):
        eng.submit([3], max_new_tokens=4, draft_tokens=-1)


# -- greedy token identity (THE acceptance contract) ------------------------

@pytest.mark.parametrize("which", ["good", "bad"])
def test_greedy_spec_matches_generate(which, lm, good_draft, bad_draft,
                                      rng):
    """Greedy speculative output is token-identical to sequential
    generate(temperature=0) — with a high-acceptance draft AND with a
    garbage draft (a wrong draft costs steps, never tokens)."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    draft = good_draft if which == "good" else bad_draft
    eng = ServingEngine(lm, n_slots=3, speculative=_spec(draft))
    reqs = []
    for _ in range(5):
        plen = int(rng.randint(1, 6))
        reqs.append((rng.randint(1, 30, size=(plen,)).tolist(),
                     int(rng.randint(3, 10))))
    rids = [eng.submit(p, max_new_tokens=n) for p, n in reqs]
    outs = eng.drain()
    for rid, (p, n) in zip(rids, reqs):
        want = generate(lm, p, length=n, temperature=0.0)
        np.testing.assert_array_equal(outs[rid], want,
                                      err_msg=f"prompt={p} draft={which}")
    assert eng.pool.free_slots == eng.pool.n_slots


def _top2_gaps(lm, prompt, stream, compute_dtype):
    """Best minus second-best next-token log-prob at every position of
    ``stream`` (1-based ids a greedy engine emitted after ``prompt``),
    from the B=1 programs at ``compute_dtype``."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import (
        get_decode_step, get_prefill_step, serving_params,
    )

    step, init_carry = get_decode_step(lm, compute_dtype)
    params = jax.device_put(serving_params(lm, compute_dtype))
    carry = init_carry(1)
    fed = [t - 1 for t in prompt]
    if len(fed) > 1:
        _, carry = get_prefill_step(lm, compute_dtype)(
            params, jnp.asarray([fed[:-1]], jnp.int32), carry)
    gaps, tok = [], fed[-1]
    for t in stream:
        logp, carry = step(params, jnp.asarray([tok], jnp.int32), carry)
        best = np.sort(np.asarray(logp[0]))[-2:]
        gaps.append(float(best[1] - best[0]))
        tok = int(t) - 1
    return gaps


def test_greedy_spec_matches_baseline_engine_bf16(lm, good_draft):
    """bf16 serving dtype through the speculative engine equals the
    bf16 baseline engine token for token (greedy) wherever bf16 can
    tell the baseline's best token from its second best.

    The verify step scores a chunk where the baseline scores one token
    a step: two separately compiled programs that round bf16
    activations in a different order (make_batch_verify_step's
    numerics caveat). Logits below 4 in magnitude are bf16 numbers
    2**-6 apart, so a best and a second best closer than two such steps
    is a tie either program may break either way; this untrained model
    has one (0.005) in this trace. There the two engines' chosen
    log-probs must still agree to one step, and the rest of that
    request — a different context from then on — is not compared. The
    fp32 and int8 twins beside this test stay exact."""
    import jax.numpy as jnp

    from bigdl_tpu.serving import ServingEngine

    step = 2.0 ** -6
    reqs = [([3, 7, 2], 8), ([5], 6), ([9, 1, 4], 7)]
    base = ServingEngine(lm, n_slots=3, compute_dtype=jnp.bfloat16)
    rb = [base.submit(p, max_new_tokens=n) for p, n in reqs]
    outs_b = base.drain()
    spec = ServingEngine(lm, n_slots=3, compute_dtype=jnp.bfloat16,
                         speculative=_spec(good_draft))
    rs = [spec.submit(p, max_new_tokens=n) for p, n in reqs]
    outs_s = spec.drain()
    held = 0
    for (p, n), a, b in zip(reqs, rb, rs):
        gaps = _top2_gaps(lm, p, outs_b[a], jnp.bfloat16)
        assert len(outs_s[b]) == len(outs_b[a]) == n
        for j, gap in enumerate(gaps):
            assert abs(base.logprobs(a)[j] - spec.logprobs(b)[j]) <= step, \
                (p, j)
            if gap > 2 * step:
                assert outs_b[a][j] == outs_s[b][j], (p, j, gap)
                held += 1
            elif outs_b[a][j] != outs_s[b][j]:
                break                  # a tie, broken the other way
    # nearly every position is held to token identity, not excused
    assert held >= 0.75 * sum(n for _, n in reqs)


def test_greedy_spec_matches_baseline_engine_int8(lm, good_draft,
                                                  bad_draft):
    """int8-KV speculative vs the int8-KV baseline engine (greedy,
    pinned config), good AND garbage drafts. Since the accepted-only
    scale merge, draft-independence is EXACT on the int8 cache too:
    the verify step's chunk attention reads float chunk K/V and the
    quantized scatter + grow-only merge cover accepted columns only,
    so a rejected draft touches neither scales nor stored bytes (the
    byte-level pin lives in tests/test_serving_kv_quant.py::
    test_int8_draft_independence_exact); spec-vs-BASELINE parity
    remains a pinned-config contract (the chunked step sees its own
    K/V unrounded where plain decode reads the roundtripped write —
    a sub-quantum numerics difference near-tied argmaxes could
    notice)."""
    from bigdl_tpu.serving import ServingEngine

    rng = np.random.RandomState(11)
    reqs = [(rng.randint(1, 30,
                         size=(int(rng.randint(1, 7)),)).tolist(),
             int(rng.randint(4, 11))) for _ in range(6)]
    outs = {}
    for name, spec in (("base", None), ("good", _spec(good_draft)),
                       ("bad", _spec(bad_draft))):
        eng = ServingEngine(lm, n_slots=3, kv_dtype="int8",
                            speculative=spec)
        rids = [eng.submit(p, max_new_tokens=n) for p, n in reqs]
        drained = eng.drain()
        outs[name] = [list(drained[r]) for r in rids]
    assert outs["good"] == outs["base"]
    assert outs["bad"] == outs["base"]


# -- fixed-seed replay of rejection sampling --------------------------------

def test_seed_replay_across_spec_and_normal_engines(lm, good_draft,
                                                    bad_draft):
    """A fixed-seed sampled request emits ONE stream: through the plain
    engine, through a speculative engine (good or garbage draft, mixed
    with normal draft_tokens=0 neighbors), and after readmission into a
    recycled slot — the verify step's draws ride the same RNG lane
    splits the baseline sampler consumes, and the lane advances by
    exactly the emitted count."""
    from bigdl_tpu.serving import SamplingParams, ServingEngine

    sp = SamplingParams(temperature=0.9, top_k=8, top_p=0.95, seed=123)
    prompt = [3, 7, 2]

    base = ServingEngine(lm, n_slots=3)
    rb = base.submit(prompt, max_new_tokens=8, sampling=sp)
    want = base.drain()[rb]

    for draft in (good_draft, bad_draft):
        eng = ServingEngine(lm, n_slots=3, speculative=_spec(draft))
        r = eng.submit(prompt, max_new_tokens=8, sampling=sp)
        eng.submit([4, 4], max_new_tokens=5, draft_tokens=0,
                   sampling=SamplingParams(temperature=1.3, seed=7))
        eng.submit([9], max_new_tokens=8)
        np.testing.assert_array_equal(eng.drain()[r], want)

    # eviction/readmission: a 1-slot engine recycles slot 0 from a
    # previous occupant — the replay survives because lanes are
    # request-keyed and the draft cache re-prefills from the prompt
    eng1 = ServingEngine(lm, n_slots=1, speculative=_spec(bad_draft, k=2))
    eng1.submit([1, 2], max_new_tokens=3,
                sampling=SamplingParams(temperature=1.1, seed=55))
    eng1.drain()
    r2 = eng1.submit(prompt, max_new_tokens=8, sampling=sp)
    np.testing.assert_array_equal(eng1.drain()[r2], want)


def test_seed_replay_across_admission_modes(lm, good_draft):
    """batched vs per_request admission feed the SAME speculative
    stream (the draft prefill rides slot configuration, not the
    admission pipeline)."""
    from bigdl_tpu.serving import SamplingParams, ServingEngine

    sp = SamplingParams(temperature=0.8, top_k=6, seed=77)
    outs = []
    for admission in ("batched", "per_request"):
        eng = ServingEngine(lm, n_slots=2, admission=admission,
                            speculative=_spec(good_draft))
        r = eng.submit([5, 9, 2, 2], max_new_tokens=7, sampling=sp)
        eng.submit([1], max_new_tokens=4)
        outs.append(eng.drain()[r])
    np.testing.assert_array_equal(outs[0], outs[1])


# -- compile-count guard ----------------------------------------------------

def test_mixed_spec_traffic_one_verify_program(lm, good_draft):
    """Mixed speculative traffic — greedy speculative, sampled, normal
    draft_tokens=0, budget-capped tails, several admission waves — adds
    ZERO verify-program compiles: per-row draft length is runtime data
    of one fixed-width program, exactly as knob mixes are for the
    decode step. (The fresh-model 1-verify-vs-1-decode equality with a
    plain engine is pinned by test_speculative_bench_smoke, where each
    engine owns a private step cache.)"""
    from bigdl_tpu.serving import SamplingParams, ServingEngine
    from tests.compile_guards import assert_compile_count, compile_count

    eng = ServingEngine(lm, n_slots=3, speculative=_spec(good_draft))
    eng.submit([3, 7, 2], max_new_tokens=6)
    eng.drain()
    # the (n_slots, width) shape is traced now; everything after is mix
    base_v = compile_count(eng._spec.verify_fn)
    base_d = compile_count(eng._spec._draft_step_fn)
    eng.submit([3, 7, 2], max_new_tokens=6)
    eng.submit([5], max_new_tokens=4, draft_tokens=0)
    eng.submit([9, 1], max_new_tokens=5, sampling=SamplingParams(
        temperature=0.8, top_k=5, seed=1))
    eng.drain()
    # second wave with different mixes/budgets — still the same program
    eng.submit([2, 2], max_new_tokens=3, draft_tokens=1)
    eng.submit([8], max_new_tokens=9, sampling=SamplingParams(
        temperature=1.2, top_p=0.9, min_tokens=2, seed=2))
    eng.drain()
    assert_compile_count(eng._spec.verify_fn, base_v,
                         what="speculative verify")
    assert_compile_count(eng._spec._draft_step_fn, base_d,
                         what="draft decode")


# -- stop machinery through chunks ------------------------------------------

def test_stop_conditions_truncate_chunks(lm, good_draft):
    """eos / stop tokens / stop sequences / min-tokens behave exactly
    like the baseline even when they fire MID-CHUNK: the emission loop
    applies the per-token finish rule in order and discards the chunk
    tail the baseline would never have sampled."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import SamplingParams, ServingEngine

    free = generate(lm, [3, 7], length=8, temperature=0.0)
    eos = int(free[3])
    cut = int(np.where(free == eos)[0][0])

    eng = ServingEngine(lm, n_slots=2, speculative=_spec(good_draft))
    a = eng.submit([3, 7], max_new_tokens=8, eos_id=eos)
    b = eng.submit([3, 7], max_new_tokens=8)
    outs = eng.drain()
    np.testing.assert_array_equal(outs[a], free[:cut + 1])
    np.testing.assert_array_equal(outs[b], free)
    assert eng.request(a).done_reason == "eos"
    assert eng.request(b).done_reason == "length"

    st = int(free[2])
    c = eng.submit([3, 7], max_new_tokens=8,
                   sampling=SamplingParams(stop_token_ids=(st,)))
    outs = eng.drain()
    assert len(outs[c]) == 3 and outs[c][-1] == st
    assert eng.request(c).done_reason == "stop"

    seq = tuple(int(t) for t in free[1:3])
    d = eng.submit([3, 7], max_new_tokens=8,
                   sampling=SamplingParams(stop_sequences=(seq,)))
    outs = eng.drain()
    assert tuple(outs[d][-2:]) == seq and len(outs[d]) == 3

    # min_tokens: the chunk budget drops to 0 while the ban is up, so
    # the banned-eos window is served step-exactly like the baseline
    e = eng.submit([3, 7], max_new_tokens=8, eos_id=eos,
                   sampling=SamplingParams(min_tokens=6))
    outs = eng.drain()
    assert len(outs[e]) >= 6
    assert not np.any(np.asarray(outs[e][:5]) == eos)


# -- rollback / pool invariants ---------------------------------------------

def test_rollback_and_draft_pool_lifecycle(lm, bad_draft):
    """The accepted-prefix rollback keeps both position counters
    consistent: after a drain the pool is empty, target and draft pos
    reset with their slots, and a LONG generation through a
    high-rejection draft (max rollback churn) still matches
    generate()."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=2, speculative=_spec(bad_draft, k=3))
    r = eng.submit([3, 7, 2], max_new_tokens=20)
    outs = eng.drain()
    np.testing.assert_array_equal(
        outs[r], generate(lm, [3, 7, 2], length=20, temperature=0.0))
    assert eng.pool.free_slots == eng.pool.n_slots
    assert not np.asarray(eng.pool.carry["pos"]).any()
    assert not np.asarray(eng.pool.draft_carry["pos"]).any()
    # draft-pool misuse raises like the target pool's
    with pytest.raises(ValueError, match="not allocated"):
        eng.pool.set_draft_pos(0, 3)


def test_cancel_running_mid_chunk(lm, good_draft):
    """Cancelling a RUNNING row between super-steps frees BOTH its
    target and draft slots (one allocator, two caches), freezes its
    output (no post-cancel tokens, ever), and leaves the engine
    serving its neighbors unperturbed — including a neighbor admitted
    into the recycled slot afterwards."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=2, speculative=_spec(good_draft, k=3))
    a = eng.submit([3, 7, 2], max_new_tokens=20)
    b = eng.submit([5, 1], max_new_tokens=12)
    for _ in range(2):
        eng.step()                     # both rows mid-stream, chunks landed
    assert eng.cancel(a)
    frozen = list(eng.request(a).output)
    assert frozen and len(frozen) < 20
    assert eng.request(a).state == "cancelled"
    assert eng.pool.free_slots == 1    # a's slot (target+draft) freed NOW
    assert not eng.cancel(a)           # already cancelled: no-op
    # a recycled-slot admission decodes correctly next to the survivor
    c = eng.submit([9], max_new_tokens=5)
    outs = eng.drain()
    assert a not in outs               # cancelled rows never FINISH
    assert list(eng.request(a).output) == frozen
    np.testing.assert_array_equal(
        outs[b], generate(lm, [5, 1], length=12, temperature=0.0))
    np.testing.assert_array_equal(
        outs[c], generate(lm, [9], length=5, temperature=0.0))
    assert eng.pool.free_slots == eng.pool.n_slots
    assert not np.asarray(eng.pool.carry["pos"]).any()
    assert not np.asarray(eng.pool.draft_carry["pos"]).any()


def test_attach_draft_guards(lm, good_draft):
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=2, speculative=_spec(good_draft))
    with pytest.raises(ValueError, match="already attached"):
        eng.pool.attach_draft(eng._spec._draft_init)
    plain = ServingEngine(lm, n_slots=2)
    with pytest.raises(ValueError, match="no draft carry"):
        plain.pool.set_draft_pos(0, 0)


# -- metrics ----------------------------------------------------------------

def test_spec_metrics_accounting(lm, good_draft):
    """draft_tokens/accepted_tokens/spec_rows land per super-step and
    summary() derives accept_rate and tokens_per_step; emitted tokens
    = accepted + row-steps exactly (every row emits one non-draft draw
    per step)."""
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=2, speculative=_spec(good_draft))
    r1 = eng.submit([3, 7], max_new_tokens=8)
    r2 = eng.submit([5, 1], max_new_tokens=8)
    outs = eng.drain()
    s = eng.metrics.summary()
    n_acc, _ = eng.metrics.metrics.get("serving/accepted_tokens")
    n_rows, _ = eng.metrics.metrics.get("serving/spec_rows")
    n_draft, _ = eng.metrics.metrics.get("serving/draft_tokens")
    assert n_acc + n_rows == len(outs[r1]) + len(outs[r2])
    assert 0.0 <= s["serving/accept_rate"] <= 1.0
    assert s["serving/accept_rate"] == pytest.approx(n_acc / n_draft)
    assert s["serving/tokens_per_step"] > 1.0   # weight-tied draft
    assert s["serving/tokens_per_step"] == pytest.approx(
        (n_acc + n_rows) / n_rows)
    # the target side is the verify step, which reads the whole window:
    # what the pooled decode kernel would fetch is not sampled
    m = eng.metrics.metrics
    assert m.values("serving/batch_active")
    assert not m.values("serving/kv_fetched_bytes")


# -- sharded plane ----------------------------------------------------------

@pytest.mark.mesh
def test_sharded_speculative_parity(lm, good_draft):
    """Speculative serving on a 4-way slot-DP mesh and a DP2xTP2 mesh
    is token-identical to the unsharded speculative engine (draft
    weights replicated, draft carry rows sharded over data, verify
    lowered like the decode step)."""
    from bigdl_tpu.serving import SamplingParams, ServingEngine

    reqs = [([3, 7, 2], 8, SamplingParams(temperature=0.9, top_k=8,
                                          seed=5)),
            ([5], 6, None), ([9, 1, 4], 7, None), ([2, 2], 5, None)]

    def run(parallelism):
        eng = ServingEngine(lm, n_slots=4, parallelism=parallelism,
                            speculative=_spec(good_draft))
        rids = [eng.submit(p, max_new_tokens=n, sampling=sp)
                for p, n, sp in reqs]
        outs = eng.drain()
        assert eng.pool.free_slots == eng.pool.n_slots
        return [outs[r] for r in rids]

    base = run(None)
    for par in ({"data": 4}, {"data": 2, "model": 2}):
        got = run(par)
        for a, b in zip(base, got):
            np.testing.assert_array_equal(a, b, err_msg=str(par))


@pytest.mark.mesh
@pytest.mark.parametrize("par", [{"data": 4}, {"data": 2, "model": 2}],
                         ids=["data4", "dp2tp2"])
def test_draft_step_is_built_for_the_planes_mesh(lm, good_draft, par):
    """The draft's decode step is a plain jit that XLA partitions by
    rows on every plane, and on a TPU its attention kernel has to be
    told which rows a chip holds: the speculator builds it with the
    plane's mesh and no model axis (tests/test_decode_layout.py compiles
    that step for both meshes of a v5e; without the mesh it does not
    lower there)."""
    from bigdl_tpu.models.transformer import get_batch_decode_step
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=4, parallelism=par,
                        speculative=_spec(good_draft))
    step, _ = get_batch_decode_step(good_draft, eng.compute_dtype,
                                    mesh=eng.mesh, model_axis=None)
    assert eng._spec._draft_step_fn is step
    assert step is not get_batch_decode_step(good_draft,
                                             eng.compute_dtype)[0]


# -- bench registration smoke (tier-1, small/CPU) ---------------------------

def test_speculative_bench_smoke():
    """benchmarks/serving_bench.py --scenario speculative runs
    end-to-end on a tiny CPU config and pins the subsystem's hard
    claims: zero extra target-side compiles on the mixed trace,
    byte-identical greedy outputs, tokens-per-step > 1."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "benchmarks"))
    try:
        import serving_bench
    finally:
        sys.path.pop(0)

    out = serving_bench.run_speculative(model="tiny", n_requests=8,
                                        gen_tokens=10, n_slots=4,
                                        draft_k=3)
    assert out["extra_target_compiles"] == 0, out
    assert out["greedy_outputs_match"] is True, out
    assert out["speculative"]["target_programs"] == 1
    assert out["draft_programs"] == 1
    assert out["tokens_per_step"] > 1.0, out
    assert 0.0 < out["accept_rate"] <= 1.0
