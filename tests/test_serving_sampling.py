"""Per-row sampled decoding (bigdl_tpu/serving/sampling.py): greedy
degradation parity, fixed-seed reproducibility across batching and
eviction/readmission, the zero-extra-compiles guarantee for mixed
sampling knobs, stop sets (per-request eos / stop tokens / stop
sequences / min-tokens ban), the logprobs surface, and the sampling
metrics + bench smoke."""

import numpy as np
import pytest

from bigdl_tpu.serving.sampling import K_CAP


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
    """One model for the whole module — every engine over it shares the
    cached jitted steps, so the file pays each (dtype, n_slots) compile
    once."""
    return _make_lm()


# -- params surface --------------------------------------------------------

def test_sampling_params_validation():
    from bigdl_tpu.serving.sampling import MAX_BAN_IDS, SamplingParams

    sp = SamplingParams()                        # default is greedy
    assert sp.is_greedy and sp.temperature == 0.0
    assert SamplingParams.greedy().is_greedy
    assert not SamplingParams(temperature=0.7).is_greedy
    # list inputs canonicalize to hashable tuples
    sp = SamplingParams(stop_token_ids=[3, 5], stop_sequences=[[1, 2]])
    assert sp.stop_token_ids == (3, 5)
    assert sp.stop_sequences == ((1, 2),)
    for bad in [dict(temperature=-0.1), dict(top_k=-1), dict(top_p=0.0),
                dict(top_p=1.5), dict(repetition_penalty=0.0),
                dict(min_tokens=-1), dict(max_tokens=0),
                dict(stop_token_ids=(0,)), dict(stop_sequences=((),)),
                dict(stop_sequences=((1, -2),)),
                dict(stop_token_ids=tuple(range(1, MAX_BAN_IDS + 1)))]:
        with pytest.raises(ValueError):
            SamplingParams(**bad)


# -- greedy degradation (THE acceptance contract) --------------------------

@pytest.mark.parametrize("dtype_name", ["fp32", "bf16"])
def test_temperature_zero_matches_greedy_generate(dtype_name, lm, rng):
    """temperature=0 rows of the sampled step degrade EXACTLY to argmax:
    engine outputs (default params AND explicit greedy SamplingParams)
    are token-for-token identical to sequential generate(temperature=0)
    — fp32 and bf16 serving params."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import SamplingParams, ServingEngine

    dtype = None if dtype_name == "fp32" else jnp.bfloat16
    reqs = []
    for _ in range(5):
        plen = int(rng.randint(1, 6))
        reqs.append((rng.randint(1, 30, size=(plen,)).tolist(),
                     int(rng.randint(3, 10))))
    eng = ServingEngine(lm, n_slots=3, compute_dtype=dtype)
    ids = []
    for i, (p, n) in enumerate(reqs):
        sp = SamplingParams.greedy() if i % 2 else None
        ids.append(eng.submit(p, max_new_tokens=n, sampling=sp))
    outs = eng.drain()
    for rid, (p, n) in zip(ids, reqs):
        want = generate(lm, p, length=n, temperature=0.0,
                        compute_dtype=dtype)
        np.testing.assert_array_equal(
            outs[rid], want, err_msg=f"prompt={p} dtype={dtype_name}")
    assert eng.pool.free_slots == eng.pool.n_slots


# -- fixed-seed reproducibility --------------------------------------------

def test_fixed_seed_reproducible_across_batching_and_readmission(lm):
    """One seeded request must produce ONE token stream: batched with
    arbitrary neighbors (any slot), sequentially via generate() (the
    same sample_rows + lane_key), and readmitted into a recycled slot
    after another request's eviction."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import SamplingParams, ServingEngine

    sp = SamplingParams(temperature=0.9, top_k=8, top_p=0.95, seed=123)
    prompt = [3, 7, 2]

    want = generate(lm, prompt, length=8, sampling=sp)
    assert len(want) == 8

    # batched: greedy + differently-seeded sampled neighbors
    eng = ServingEngine(lm, n_slots=3)
    r = eng.submit(prompt, max_new_tokens=8, sampling=sp)
    eng.submit([4, 4], max_new_tokens=5,
               sampling=SamplingParams(temperature=1.3, seed=7))
    eng.submit([9], max_new_tokens=8)
    outs = eng.drain()
    np.testing.assert_array_equal(outs[r], want)

    # readmission: a single-slot engine recycles slot 0 from a previous
    # occupant — the lane is seeded from the REQUEST, not the slot
    eng1 = ServingEngine(lm, n_slots=1)
    eng1.submit([1, 2], max_new_tokens=3,
                sampling=SamplingParams(temperature=1.1, seed=55))
    eng1.drain()
    r2 = eng1.submit(prompt, max_new_tokens=8, sampling=sp)
    np.testing.assert_array_equal(eng1.drain()[r2], want)

    # same engine, same explicit seed, resubmitted → same stream again
    r3 = eng1.submit(prompt, max_new_tokens=8, sampling=sp)
    np.testing.assert_array_equal(eng1.drain()[r3], want)

    # seed=None draws a fresh engine-derived lane per request id (so a
    # resubmit is NOT forced to repeat — over several tries the free
    # lane must diverge somewhere for a 29-vocab softmax at temp 1.3)
    free_sp = SamplingParams(temperature=1.3)
    outs = []
    for _ in range(4):
        rid = eng1.submit(prompt, max_new_tokens=8, sampling=free_sp)
        outs.append(eng1.drain()[rid])
    assert any(not np.array_equal(outs[0], o) for o in outs[1:])


# -- compile-count guard ---------------------------------------------------

def test_mixed_knobs_add_zero_decode_compiles(lm):
    """ONE compiled decode program serves every knob mix: a greedy-only
    engine and a mixed greedy/sampled engine (same n_slots) share the
    same single trace — changing per-request knobs is runtime data,
    never a recompile (the acceptance criterion)."""
    from bigdl_tpu.serving import SamplingParams, ServingEngine
    from tests.compile_guards import assert_compile_count, compile_count

    eng_g = ServingEngine(lm, n_slots=3)
    for p in ([3, 7, 2], [5], [9, 1]):
        eng_g.submit(p, max_new_tokens=4)
    eng_g.drain()
    base = compile_count(eng_g._step_fn)
    assert base >= 1

    eng_m = ServingEngine(lm, n_slots=3)
    eng_m.submit([3, 7, 2], max_new_tokens=4)
    eng_m.submit([5], max_new_tokens=4, sampling=SamplingParams(
        temperature=0.8, top_k=5, seed=1))
    eng_m.submit([9, 1], max_new_tokens=4, sampling=SamplingParams(
        temperature=1.2, top_p=0.9, repetition_penalty=1.3,
        presence_penalty=0.5, frequency_penalty=0.2, min_tokens=2,
        seed=2))
    eng_m.drain()
    # second wave with yet other knob mixes — still the same program
    eng_m.submit([2, 2], max_new_tokens=3, sampling=SamplingParams(
        temperature=0.6, top_k=3, top_p=0.7, seed=9))
    eng_m.drain()
    assert_compile_count(eng_m._step_fn, base, what="mixed-knob engine")
    assert eng_m._step_fn is eng_g._step_fn        # the shared cached step


# -- stop sets -------------------------------------------------------------

def test_per_request_eos_stop_tokens_sequences_min_tokens(lm):
    """Per-request stop machinery: private eos per request, stop TOKEN
    ids evict like an extra eos set (reason 'stop'), stop SEQUENCES
    match on host against the output tail, and min_tokens bans
    eos/stop tokens on device until the floor is met."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import SamplingParams, ServingEngine

    free = generate(lm, [3, 7], length=8, temperature=0.0)
    eos = int(free[3])                     # a token greedy WILL emit
    cut = int(np.where(free == eos)[0][0])

    eng = ServingEngine(lm, n_slots=2)
    # per-request eos: same prompt, one stops at its private eos, the
    # other (no eos) runs to length — eos is not engine-wide state
    a = eng.submit([3, 7], max_new_tokens=8, eos_id=eos)
    b = eng.submit([3, 7], max_new_tokens=8)
    outs = eng.drain()
    np.testing.assert_array_equal(outs[a], free[:cut + 1])
    np.testing.assert_array_equal(outs[b], free)
    assert eng.request(a).done_reason == "eos"
    assert eng.request(b).done_reason == "length"

    # stop token ids: an extra per-request eos set, reason 'stop'
    st = int(free[2])
    c = eng.submit([3, 7], max_new_tokens=8,
                   sampling=SamplingParams(stop_token_ids=(st,)))
    outs = eng.drain()
    assert len(outs[c]) == 3 and outs[c][-1] == st
    assert eng.request(c).done_reason == "stop"

    # stop sequences: host-side tail match, token run included
    seq = tuple(int(t) for t in free[1:3])
    d = eng.submit([3, 7], max_new_tokens=8,
                   sampling=SamplingParams(stop_sequences=(seq,)))
    outs = eng.drain()
    assert tuple(outs[d][-2:]) == seq and len(outs[d]) == 3
    assert eng.request(d).done_reason == "stop"

    # min_tokens: the eos that would fire at step 4 is BANNED on device
    # (greedy takes the runner-up) until >= 6 tokens exist
    e = eng.submit([3, 7], max_new_tokens=8, eos_id=eos,
                   sampling=SamplingParams(min_tokens=6))
    outs = eng.drain()
    assert len(outs[e]) >= 6
    assert not np.any(np.asarray(outs[e][:5]) == eos)

    # generate() honors the same stop machinery
    g = generate(lm, [3, 7], length=8,
                 sampling=SamplingParams(stop_sequences=(seq,)))
    np.testing.assert_array_equal(g, outs[d])


# -- logprobs --------------------------------------------------------------

def test_chosen_token_logprobs_surface(lm):
    """The fused epilogue reports the chosen token's RAW model log-prob
    per step: engine.logprobs() matches generate(return_logprobs=True)
    for the same greedy request (same tokens, float-round-off close),
    one finite value per output token."""
    from bigdl_tpu.models.transformer import generate
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=3)
    rid = eng.submit([3, 7, 2], max_new_tokens=6)
    outs = eng.drain()
    lp = eng.logprobs(rid)
    ids, glp = generate(lm, [3, 7, 2], length=6, temperature=0.0,
                        return_logprobs=True)
    np.testing.assert_array_equal(outs[rid], ids)
    assert lp.shape == (6,) and np.isfinite(lp).all()
    assert (lp <= 0).all()                     # log-probs
    np.testing.assert_allclose(lp, glp, atol=1e-5)
    assert eng.logprobs(12345) is None
    # the Request record carries them too
    assert len(eng.request(rid).logprobs) == 6


# -- metrics ---------------------------------------------------------------

def test_sampling_metrics_counters(lm):
    """serving/rows_sampled vs rows_greedy per step, derived
    sampled_row_frac, and per-request mean_logprob land in summary()."""
    from bigdl_tpu.serving import SamplingParams, ServingEngine

    eng = ServingEngine(lm, n_slots=2)
    eng.submit([3, 7], max_new_tokens=4)
    eng.submit([5, 1], max_new_tokens=4,
               sampling=SamplingParams(temperature=1.0, seed=3))
    eng.drain()
    s = eng.metrics.summary()
    assert s["serving/sampled_row_frac"] == pytest.approx(0.5)
    total_s, _ = eng.metrics.metrics.get("serving/rows_sampled")
    total_g, _ = eng.metrics.metrics.get("serving/rows_greedy")
    assert total_s == 4 and total_g == 4
    assert np.isfinite(s["serving/mean_logprob"])
    _, n_fin = eng.metrics.metrics.get("serving/mean_logprob")
    assert n_fin == 2                          # one per finished request
    # plain temperature sampling filters nothing, and a top_k under the
    # cap is narrow: no step of this traffic sorted the vocabulary
    assert eng.metrics.metrics.get("serving/sampler_wide") == (0.0, 4)
    eng.submit([3, 7], max_new_tokens=4, sampling=SamplingParams(
        temperature=0.8, top_k=20, seed=5))
    eng.drain()
    assert eng.metrics.metrics.get("serving/sampler_wide") == (0.0, 8)
    eng.submit([5, 1], max_new_tokens=2, sampling=SamplingParams(
        temperature=1.0, top_p=0.9, seed=3))     # a nucleus-only row
    eng.drain()
    assert eng.metrics.metrics.get("serving/sampler_wide") == (2.0, 10)


# -- the selection behind top-k / top-p (PR 35) -----------------------------

def _sorted_reference(ls, top_k, top_p):
    """The plain reference: the whole-vocabulary sort that
    ``sample_rows`` ran for every row until PR 35, kept here as it was
    but for its last line: ``top_p == 1`` is no restriction. (The
    float32 cumulative sum can reach 1.0 before the row's end, and the
    sort then dropped a tail of ~1e-7 of the mass from such a row.)
    Returns the kept mask of the scaled log-probs ``ls``."""
    import jax
    import jax.numpy as jnp

    V = ls.shape[1]
    sl = -jnp.sort(-ls, axis=-1)
    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
    kth = jnp.take_along_axis(sl, (k_eff - 1)[:, None], axis=-1)
    slm = jnp.where(sl < kth, -1e30, sl)
    ps = jax.nn.softmax(slm, axis=-1)
    cum = jnp.cumsum(ps, axis=-1)
    keep = (cum - ps) < top_p[:, None]
    cut = jnp.min(jnp.where(keep, slm, jnp.inf), axis=-1)[:, None]
    return ~((ls < kth) | ((ls < cut) & (top_p[:, None] < 1.0)))


def _reference_draw(ls, temp, kept, keys):
    """The draw of ``sample_rows`` over a given kept mask."""
    import jax
    import jax.numpy as jnp

    split = jax.vmap(jax.random.split)(keys)
    sampled = jax.vmap(jax.random.categorical)(
        split[:, 1], jnp.where(kept, ls, -1e30))
    return jnp.where(temp > 0.0, sampled, jnp.argmax(ls, axis=-1)), \
        split[:, 0]


def _tied_logp(shape, seed, k):
    """Random log-probs with exact ties planted where a selection could
    go wrong: row 0 at its k-th largest value (ranks k-1 .. k+2), row 1
    across the cap's edge (ranks K_CAP-3 .. K_CAP+5), row 2 at both."""
    import jax

    x = np.random.RandomState(seed).randn(*shape).astype(np.float32) * 2
    order = np.argsort(-x, axis=-1)

    def tie(row, lo, hi):
        x[row, order[row, lo:hi]] = x[row, order[row, lo]]

    if k:
        tie(0, k - 1, k + 3)
        tie(2, k - 1, k + 3)
    tie(1, K_CAP - 3, K_CAP + 6)
    tie(2, K_CAP - 3, K_CAP + 6)
    # NOT renormalised: a log_softmax would round the planted ties apart
    return jax.numpy.asarray(x)


def _knob_arrays(n, vocab=None, **rows):
    import jax.numpy as jnp

    from bigdl_tpu.serving.sampling import make_knob_rows

    knobs = make_knob_rows(n, vocab=vocab)
    for name, vals in rows.items():
        knobs[name][:] = vals
    return {k: jnp.asarray(v) for k, v in knobs.items()}


@pytest.mark.parametrize("shape,top_k,top_p", [
    (shape, top_k, top_p)
    for shape in [(4, 1000), (3, 50257)]
    for top_k in [1, 50, K_CAP, K_CAP + 1, 0]
    for top_p in [1.0, 0.9, 0.3]
] + [((32, 50257), 50, 1.0)])      # a serving cell's shape and knobs
def test_selection_keeps_what_the_whole_sort_keeps(shape, top_k, top_p):
    """For every sampled row the kept set equals the plain reference's,
    so the token and the returned key are bit-equal to its draw. The
    last row is greedy (the argmax whatever the selection says)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.serving.sampling import (
        get_sampler, row_thresholds, wide_rows,
    )

    k, n = top_k, shape[0]
    logp = _tied_logp(shape, seed=100 * k + n, k=k)
    temp = np.full((n,), 0.8, np.float32)
    temp[-1] = 0.0
    knobs = _knob_arrays(n, temperature=temp, top_k=k, top_p=top_p)
    t, tk, tp = knobs["temperature"], knobs["top_k"], knobs["top_p"]
    ls = logp / jnp.maximum(t, 1e-6)[:, None]
    kept_ref = np.asarray(_sorted_reference(ls, tk, tp))
    thr = jax.jit(row_thresholds)(ls, t, tk, tp)
    kept = np.asarray(ls >= thr)
    sampled = np.asarray(t) > 0
    _, wide = wide_rows(np.asarray(t), np.asarray(tk), np.asarray(tp))
    assert wide[:-1].all() == (k == 0 and top_p < 1 or k > K_CAP)
    assert np.array_equal(kept[sampled], kept_ref[sampled])
    if k and top_p == 1:
        # the planted ties are in play: row 0 keeps its 3 ties past k
        assert kept_ref[0].sum() == k + 3
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n) + 5)
    zeros = jnp.zeros(shape, jnp.int32)
    tok, lp, new_keys, _ = get_sampler()(
        logp, keys, knobs, zeros, zeros.astype(bool))
    ref_tok, ref_keys = _reference_draw(ls, t, jnp.asarray(kept_ref), keys)
    assert np.array_equal(np.asarray(new_keys), np.asarray(ref_keys))
    assert np.array_equal(np.asarray(tok), np.asarray(ref_tok))
    assert np.array_equal(np.asarray(lp),
                          np.asarray(logp)[np.arange(n), np.asarray(tok)])
    assert int(tok[-1]) == int(np.argmax(np.asarray(logp)[-1]))


_KNOB_TABLE = [
    # temperature, top_k, top_p -> filters, wide
    (0.0, 0, 1.0, False, False),        # greedy
    (0.0, 50, 0.9, False, False),       # greedy whatever else it says
    (0.8, 0, 1.0, False, False),        # plain temperature sampling
    (0.8, 50, 1.0, True, False),        # the cells' sampled rows
    (0.8, 50, 0.9, True, False),
    (1.3, K_CAP, 0.5, True, False),     # at the cap: still narrow
    (1.3, K_CAP + 1, 1.0, True, True),  # over the cap
    (0.8, 0, 0.9, True, True),          # a nucleus with no top_k
]


@pytest.mark.parametrize("row", range(len(_KNOB_TABLE)))
def test_wide_rule_same_under_numpy_and_jax(row):
    """The ONE rule: the engine's host counter (numpy) and the traced
    sampler (jax.numpy) evaluate the same expression to the same
    answer."""
    import jax.numpy as jnp

    from bigdl_tpu.serving.sampling import wide_rows

    temp, k, p, filters, wide = _KNOB_TABLE[row]
    for xp in (np, jnp):
        f, w = wide_rows(xp.asarray([temp], xp.float32),
                         xp.asarray([k], xp.int32),
                         xp.asarray([p], xp.float32))
        assert (bool(f[0]), bool(w[0])) == (filters, wide), xp.__name__


@pytest.mark.parametrize("neighbour", ["alone", "wide", "stale_wide_slot"])
def test_narrow_row_stream_independent_of_neighbours(neighbour):
    """A narrow row draws the same stream alone, beside a nucleus-only
    row (the step sorts, the row does not read the sort), and beside an
    INACTIVE slot whose stale knobs are wide (the step must not sort
    for it: the rule reads the rows that decode)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.serving.sampling import get_sampler, lane_key, wide_rows

    v, steps = 1000, 6
    rng = np.random.RandomState(3)
    logps = jnp.asarray(rng.randn(steps, 1, v).astype(np.float32) * 2)
    other = jnp.asarray(rng.randn(steps, 1, v).astype(np.float32) * 2)

    def stream(n, knobs, active):
        keys = jnp.stack([lane_key(7)] * n)
        counts = jnp.zeros((n, v), jnp.int32)
        pmask = jnp.zeros((n, v), bool)
        out = []
        for i in range(steps):
            logp = jnp.concatenate([logps[i]] + [other[i]] * (n - 1))
            tok, _, keys, counts = get_sampler()(
                logp, keys, knobs, counts, pmask, active)
            out.append(int(tok[0]))
        return out

    narrow = dict(temperature=0.8, top_k=50, top_p=0.9)
    alone = stream(1, _knob_arrays(1, **narrow), None)
    if neighbour == "alone":
        assert len(set(alone)) > 1
        return
    knobs = _knob_arrays(2, temperature=[0.8, 0.8], top_k=[50, 0],
                         top_p=[0.9, 0.9])
    active = jnp.asarray([True, neighbour == "wide"])
    _, wide = wide_rows(*(np.asarray(knobs[k]) for k in
                          ("temperature", "top_k", "top_p")))
    assert list(wide) == [False, True]
    assert bool((wide & np.asarray(active)).any()) == (neighbour == "wide")
    assert stream(2, knobs, active) == alone


@pytest.mark.parametrize("top_p", [1.0, 0.5])
def test_constraint_leaving_fewer_than_top_k(top_p):
    """A constrained row whose ``allow`` leaves 3 tokens under
    ``top_k=50``: the k-th value is a disallowed token's, nothing
    allowed is lost, and the draw is the reference's."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.serving.sampling import get_sampler

    n, v = 2, 1000
    logp = jax.nn.log_softmax(jnp.asarray(
        np.random.RandomState(11).randn(n, v).astype(np.float32)), -1)
    knobs = _knob_arrays(n, vocab=v, temperature=0.8, top_k=50,
                         top_p=top_p)
    allowed = np.zeros((n, v), bool)
    allowed[0, [5, 17, 400]] = True
    allowed[1] = True
    knobs["allow"] = jnp.asarray(allowed)
    zeros = jnp.zeros((n, v), jnp.int32)
    t = knobs["temperature"]
    ls = jnp.where(knobs["allow"], logp, -1e30) / t[:, None]
    kept_ref = _sorted_reference(ls, knobs["top_k"], knobs["top_p"])
    seen = set()
    for seed in range(8):
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n) + 10 * seed)
        tok, _, new_keys, _ = get_sampler()(
            logp, keys, knobs, zeros, zeros.astype(bool))
        ref_tok, ref_keys = _reference_draw(ls, t, kept_ref, keys)
        assert np.array_equal(np.asarray(tok), np.asarray(ref_tok))
        assert np.array_equal(np.asarray(new_keys), np.asarray(ref_keys))
        seen.add(int(tok[0]))
    assert seen <= {5, 17, 400}
    assert top_p < 1 or len(seen) > 1


def test_freed_slot_with_stale_wide_knobs_is_not_counted(lm):
    """The engine writes a slot's knob row at admission only, so a
    finished nucleus-only request leaves its wide knobs behind:
    ``serving/sampler_wide`` reads 1 while it runs and 0 once its slot
    stands free beside a narrow row."""
    from bigdl_tpu.serving import SamplingParams, ServingEngine

    eng = ServingEngine(lm, n_slots=2)
    eng.submit([3, 7], max_new_tokens=12, sampling=SamplingParams(
        temperature=0.8, top_k=5, seed=1))
    eng.submit([5, 1], max_new_tokens=3, sampling=SamplingParams(
        temperature=0.8, top_p=0.9, seed=2))
    eng.drain()
    series = eng.metrics.metrics.values("serving/sampler_wide")
    assert series[:3] == [1.0, 1.0, 1.0]
    assert len(series) == 12 and not any(series[3:])
    assert (eng._knobs["top_p"] < 1).any()      # the stale row is there
    assert eng.metrics.summary()["serving/sampler_wide"] \
        == pytest.approx(3 / 12)


# -- bench registration smoke (tier-1, small/CPU) --------------------------

def test_sampling_bench_smoke():
    """benchmarks/serving_bench.py --scenario sampling runs end-to-end
    on a tiny CPU config and pins the subsystem's two hard claims:
    zero extra decode compiles for mixed knobs, and greedy rows
    unperturbed by sampled neighbors."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "benchmarks"))
    try:
        import serving_bench
    finally:
        sys.path.pop(0)

    out = serving_bench.run_sampling(model="tiny", n_requests=8,
                                     gen_tokens=12, n_slots=4)
    assert out["extra_decode_compiles"] == 0, out
    assert out["greedy_rows_match"] is True, out
    assert out["mixed"]["decode_programs"] == 1
    assert out["greedy"]["tokens_per_sec"] > 0
    assert out["mixed"]["tokens_per_sec"] > 0
    assert out["sampled_row_frac"] == pytest.approx(0.5)


def test_sampler_bench_smoke():
    """benchmarks/serving_bench.py --scenario sampler times
    ``sample_rows`` alone under its three knob mixes; off the chip the
    profiler's trace holds no device plane and only the wall time is
    reported."""
    from benchmarks import serving_bench

    out = serving_bench.run_sampler(shapes=[(4, 300)], reps=2)
    assert sorted(out["wall_ms"]) == [
        "4x300/greedy", "4x300/half_top_k", "4x300/one_nucleus"]
    assert all(ms > 0 for ms in out["wall_ms"].values())
    assert out["device_ms"] == {} and out["platform"] == "cpu"
