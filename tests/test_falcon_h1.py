"""The Falcon-H1 family (models/falcon_h1.py) against the plain
reference's equations (benchmark/configs/falcon_h1_reference.py), at a
toy size with seeded random weights: the whole-sequence forward, the
chunked scan, the padded prefill, prefill -> pool -> decode through
``ServingEngine``, and the pool's contracts for recurrent state.

Tolerances compare LOGITS. The toy model's logits have a standard
deviation of ~1e-3 (the published multipliers shrink them), so every
float32 tolerance is relative to that spread: float32 round-off reads
~1e-6 of it here, bfloat16 anywhere in the path ~1e-2.
"""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import falcon_h1
from bigdl_tpu.models.falcon_h1 import FalconH1LM
from bigdl_tpu.serving import SamplingParams, ServingEngine
from bigdl_tpu.serving.kv_pool import leaf_kind
from bigdl_tpu.utils.random_gen import RNG

ROOT = pathlib.Path(__file__).resolve().parents[1]
TOY = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
    rms_norm_eps=1e-5, rope_theta=100000000000,
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    attention_in_multiplier=1, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284])
MAX_LEN = 64
#: float32 program against the float32 reference, as a share of the
#: logits' standard deviation: round-off of sums of at most 128 terms
#: over 2 layers reads ~1e-6; bfloat16 anywhere reads ~1e-2
F32_OF_STD = 2e-5
#: log-probs sit near -log(512) = -6.24, where one float32 ulp is
#: 4.8e-7: two roundings (log-softmax here, and in the reference); the
#: same bfloat16 error of ~1e-2 of the spread reads ~1e-5
LOGP_ATOL = 2e-6


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "falcon_h1_reference",
        ROOT / "benchmark" / "configs" / "falcon_h1_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load_reference()


@pytest.fixture(scope="module")
def lm():
    RNG.set_seed(11)
    model = FalconH1LM(TOY, max_len=MAX_LEN)
    model.evaluate()
    model._ensure_params()
    return model


def _tokens(seed, *shape):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"] + 1, size=shape)


def _ref_logits(lm, seq):
    seq = jnp.asarray(seq, jnp.int32)
    return np.asarray(REF.logits_at(lm.params, seq, jnp.arange(len(seq)),
                                    TOY))


# ---------------------------------------------------------------- forward


def test_whole_sequence_logits_match_the_reference(lm):
    toks = _tokens(0, 2, 21)          # 21: not a multiple of the chunk 8
    got = np.asarray(lm.forward(toks))
    assert got.shape == (2, 21, TOY["vocab_size"])
    for row in range(2):
        want = _ref_logits(lm, toks[row])
        assert np.abs(got[row] - want).max() <= F32_OF_STD * want.std()


def test_the_float32_tolerance_would_fail_bfloat16(lm):
    """The tolerance is tight enough that computing in bfloat16 where
    float32 is stated fails it."""
    toks = _tokens(1, 1, 21)
    want = _ref_logits(lm, toks[0])
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), lm.params)
    got, _ = lm.apply(low, toks)
    err = np.abs(np.asarray(got[0], np.float32) - want).max()
    assert err > 50 * F32_OF_STD * want.std()


def test_parameters_are_created_in_the_stated_dtype():
    RNG.set_seed(5)
    low = FalconH1LM(TOY, max_len=MAX_LEN, param_dtype="bfloat16")
    low._ensure_params()
    leaves = jax.tree_util.tree_leaves(low.params)
    assert {leaf.dtype.name for leaf in leaves} == {"bfloat16"}
    assert low.grad_params is None        # the family does not train
    mixer = low.params["layers"][0]["mixer"]
    np.testing.assert_allclose(
        np.asarray(mixer["A_log"], np.float32),
        np.log(np.arange(1, TOY["mamba_n_heads"] + 1)), rtol=1e-2)
    assert np.asarray(mixer["D"], np.float32).tolist() == [1.0] * 4
    # the engine takes them as they are: no second copy
    eng = ServingEngine(low, n_slots=2, compute_dtype=jnp.bfloat16)
    for mine, theirs in zip(jax.tree_util.tree_leaves(eng.params), leaves):
        assert mine is theirs


def _scan_step(x, Bm, Cm, dt, A, S):
    """One step of the recurrence itself, over every row: ``S' = e^{dt
    A} S + dt x (x) B``, ``y = S' C``. Shapes as
    :func:`falcon_h1._scan_chunked` with T = 1. Written apart from
    ``ops/ssm_decode.py``, so that both of its paths are held to it."""
    x0 = x[:, 0].astype(jnp.float32)                           # B,G,hg,d
    B0 = Bm[:, 0].astype(jnp.float32)[:, :, None, None]        # B,G,1,1,N
    C0 = Cm[:, 0].astype(jnp.float32)[:, :, None, None]
    dt0 = dt[:, 0]                                             # B,G,hg
    S = S * jnp.exp(dt0 * A)[..., None, None] \
        + (dt0[..., None] * x0)[..., None] * B0
    return jnp.sum(S * C0, axis=-1)[:, None], S


@pytest.mark.parametrize("length", [1, 7, 8, 13, 21])
def test_chunked_scan_matches_the_recurrence(length):
    """Chunks of 8 against the token-by-token recurrence, at lengths that
    are and are not multiples of the chunk."""
    cfg = falcon_h1.FalconH1Config.from_dict(
        dict(TOY, mamba_rms_norm=True))
    rng = np.random.default_rng(length)
    B, G, hg, d, N = 2, 2, 2, 16, 16
    x = jnp.asarray(rng.standard_normal((B, length, G, hg, d)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, length, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, length, G, N)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 1.5, (B, length, G, hg)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 4.0, (G, hg)), jnp.float32)
    y, S = falcon_h1._scan_chunked(cfg, x, Bm, Cm, dt, A)
    S_ref = jnp.zeros((B, G, hg, d, N), jnp.float32)
    ys = []
    for t in range(length):
        y_t, S_ref = _scan_step(
            x[:, t:t + 1], Bm[:, t:t + 1], Cm[:, t:t + 1], dt[:, t:t + 1],
            A, S_ref)
        ys.append(y_t)
    y_ref = jnp.concatenate(ys, axis=1)
    # float32 round-off of sums of at most 8 + 16 terms
    assert np.abs(np.asarray(y - y_ref)).max() <= \
        1e-5 * np.abs(np.asarray(y_ref)).max()
    assert np.abs(np.asarray(S - S_ref)).max() <= \
        1e-5 * np.abs(np.asarray(S_ref)).max()


# ------------------------------------------------- the decode step's state


#: which of 8 slots decode
MASKS = {"all": [1] * 8, "none": [0] * 8, "one": [0, 0, 1, 0, 0, 0, 0, 0],
         "last": [0] * 7 + [1], "scattered": [1, 0, 0, 1, 1, 0, 1, 0]}


def _state_step_inputs(seed, G=2, hg=2, d=128, N=256, n=8):
    """One decode step's scan inputs for ``n`` slots at the published
    head and state widths (128 x 256), bfloat16 projections as the
    served program has them, a float32 state with negative zeros in it
    (adding 0.0 would flip their sign)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, G, hg, d)), jnp.bfloat16)
    Bm, Cm = (jnp.asarray(rng.standard_normal((n, G, N)), jnp.bfloat16)
              for _ in range(2))
    dt = jnp.asarray(rng.uniform(0.05, 1.5, (n, G, hg)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 4.0, (G, hg)), jnp.float32)
    S = rng.standard_normal((n, G * hg, d, N)).astype(np.float32)
    S[..., 0] = -0.0
    return x, Bm, Cm, dt, A, jnp.asarray(S)


@pytest.mark.parametrize("groups,heads", [(2, 2), (1, 4)],
                         ids=["2x2-heads", "1x4-heads"])
@pytest.mark.parametrize("impl", ["kernel", "reference"])
@pytest.mark.parametrize("mask", sorted(MASKS))
def test_state_decode_step_matches_the_recurrence(mask, impl, groups, heads):
    """``ops/ssm_decode.py`` (the kernel in interpret mode, and the jnp
    path the CPU serves with) against the recurrence over every row, in
    two scan groups and in one: a decoding row's ``y`` and new state to
    float32 round-off, every other row's state bitwise as it was and its
    ``y`` zeros."""
    from bigdl_tpu.ops.ssm_decode import ssm_decode

    x, Bm, Cm, dt, A, S = _state_step_inputs(seed=len(mask), G=groups,
                                             hg=heads)
    n, G, hg, d = x.shape
    active = np.asarray(MASKS[mask], bool)
    kw = {} if impl == "reference" else dict(interpret=True)
    y, S_new = jax.jit(lambda *a: ssm_decode(*a, impl=impl, **kw))(
        x, Bm, Cm, dt, A, S, jnp.asarray(active))
    y_ref, S_ref = _scan_step(x[:, None], Bm[:, None], Cm[:, None],
                              dt[:, None], A, S.reshape(n, G, hg, d, -1))
    y, S_new, S_before = (np.asarray(a) for a in (y, S_new, S))
    y_ref = np.asarray(y_ref[:, 0])
    S_ref = np.asarray(S_ref).reshape(S_before.shape)
    assert y.shape == y_ref.shape and S_new.shape == S_before.shape
    for r in range(n):
        if active[r]:
            # float32: one rounding a term, sums of 256 terms in y
            assert np.abs(S_new[r] - S_ref[r]).max() <= \
                1e-6 * np.abs(S_ref[r]).max()
            assert np.abs(y[r] - y_ref[r]).max() <= \
                1e-5 * np.abs(y_ref[r]).max()
        else:
            assert S_new[r].tobytes() == S_before[r].tobytes()
            assert not y[r].any() and not np.signbit(y[r]).any()


def test_state_decode_step_refuses_a_state_it_cannot_update():
    from bigdl_tpu.ops.ssm_decode import ssm_decode

    x, Bm, Cm, dt, A, S = _state_step_inputs(seed=0, d=8, N=8, n=2)
    active = jnp.asarray([True, False])
    for impl in ("kernel", "reference"):
        with pytest.raises(ValueError, match="float32"):
            ssm_decode(x, Bm, Cm, dt, A, S.astype(jnp.bfloat16), active,
                       impl=impl)
        with pytest.raises(ValueError, match="^S "):
            ssm_decode(x, Bm, Cm, dt, A, S[:, :2], active, impl=impl)


# ----------------------------------------------------------- padded prefill


def _prefill(lm, toks, lengths, dtype=None):
    fam = lm.serving_family()
    prefill = fam.batch_prefill_step(dtype)
    zero = fam.init_carry(dtype)(toks.shape[0])
    logp, carry = prefill(fam.params(dtype), jnp.asarray(toks - 1),
                          np.asarray(lengths, np.int32), zero)
    return np.asarray(logp), jax.tree_util.tree_map(np.asarray, carry)


def test_padded_bucket_rows_equal_their_own_unpadded_prefill(lm):
    """Rows of unequal length and ballast rows in one bucket: each row's
    scan state, convolution window, K/V and last logits are those of its
    own unpadded prefill; ballast leaves zeros."""
    L, lengths = 16, [13, 5, 0, 16, 1, 0]
    toks = _tokens(2, len(lengths), L)
    logp, carry = _prefill(lm, toks, lengths)
    assert carry["pos"].tolist() == lengths
    for r, n in enumerate(lengths):
        if n == 0:
            for key, leaf in carry.items():
                if leaf_kind(key) in ("kv", "state"):
                    assert not leaf[r].any(), key
            continue
        own_logp, own = _prefill(lm, toks[r:r + 1, :n], [n])
        want = _ref_logits(lm, toks[r, :n])[-1]
        want_logp = want - np.log(np.exp(want).sum())
        assert np.abs(logp[r] - own_logp[0]).max() <= LOGP_ATOL
        assert np.abs(logp[r] - want_logp).max() <= LOGP_ATOL
        for key, leaf in carry.items():
            kind = leaf_kind(key)
            if kind == "state":
                scale = np.abs(own[key]).max()
                assert np.abs(leaf[r] - own[key][0]).max() <= 1e-5 * scale, key
            elif kind == "kv":
                scale = np.abs(own[key]).max()
                assert np.abs(leaf[r, :n] - own[key][0, :n]).max() \
                    <= 1e-5 * scale, key
                assert not leaf[r, n:].any(), key     # nothing past the row


def test_prefill_refuses_lengths_outside_the_bucket(lm):
    toks = _tokens(3, 2, 8)
    with pytest.raises(ValueError, match="lengths"):
        _prefill(lm, toks, [9, 1])


# ------------------------------------------------- decode against the pool


def _teacher_forced_decode(lm, prompt, rest, dtype=None):
    """Prefill ``prompt[:-1]``, then feed ``prompt[-1]`` and ``rest``
    one token a step through the block's decode shape; returns the
    logits after each fed token."""
    fam = lm.serving_family()
    cfg, params = lm.config, fam.params(dtype)
    n = len(prompt) - 1
    toks = np.zeros((2, 16), np.int64)      # row 1 is ballast throughout
    toks[0, :n] = prompt[:-1]
    _, carry = fam.batch_prefill_step(dtype)(
        params, jnp.asarray(toks - 1), np.asarray([n, 0], np.int32),
        fam.init_carry(dtype)(2))

    @jax.jit
    def step(params, token, carry):
        pos, active = carry["pos"], jnp.asarray([True, False])
        x, new = falcon_h1._layers(cfg, params, token[:, None], pos[:, None],
                                   active[:, None], carry, decode=True,
                                   dtype=fam._dtype(dtype))
        new["pos"] = pos + active
        return falcon_h1._logits(cfg, params, x[:, 0]), new

    out = []
    for tok in [prompt[-1]] + list(rest):
        logits, carry = step(params, jnp.asarray([tok - 1, 0]), carry)
        out.append(np.asarray(logits[0], np.float32))
    return np.stack(out), carry


def test_prefill_then_decode_reproduces_the_reference_logits(lm):
    seq = _tokens(4, 30)
    prompt, rest = seq[:11], seq[11:]
    got, carry = _teacher_forced_decode(lm, prompt, rest[:-1])
    want = _ref_logits(lm, seq[:-1])[10:]
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= F32_OF_STD * want.std()
    # the ballast row never moved
    for key, leaf in carry.items():
        if leaf_kind(key) in ("kv", "state", "pos"):
            assert not np.asarray(leaf[1]).any(), key


def test_decode_through_the_state_kernel_reproduces_the_reference_logits(
        lm, monkeypatch):
    """The decode step with its state update in the Pallas kernel (the
    path a TPU takes; interpret mode here) serves the reference's logits
    to float32 round-off, and the ballast row never moves."""
    import functools

    from bigdl_tpu.ops import ssm_decode as module

    monkeypatch.setattr(module, "ssm_decode", functools.partial(
        module.ssm_decode, impl="kernel", interpret=True))
    seq = _tokens(4, 30)
    got, carry = _teacher_forced_decode(lm, seq[:11], seq[11:-1])
    want = _ref_logits(lm, seq[:-1])[10:]
    assert np.abs(got - want).max() <= F32_OF_STD * want.std()
    for key, leaf in carry.items():
        if leaf_kind(key) in ("kv", "state", "pos"):
            assert not np.asarray(leaf[1]).any(), key


def test_bfloat16_decode_stays_within_the_served_slack(lm):
    """bfloat16 compute (float32 scan state): the logits stay within the
    slack the benchmark's comparison allows a served token, 0.06 of the
    logits' spread, with room."""
    seq = _tokens(5, 30)
    got, _ = _teacher_forced_decode(lm, seq[:11], seq[11:-1], jnp.bfloat16)
    want = _ref_logits(lm, seq[:-1])[10:]
    spread = float(np.mean(want.max(-1) - np.median(want, -1)))
    assert np.abs(got - want).max() <= 0.03 * spread


def _random_carry(lm, n_slots, seed):
    """A carry with something in every leaf (and negative zeros in the
    float ones: an update that adds 0.0 would flip their sign)."""
    rng = np.random.default_rng(seed)
    carry = lm.serving_family().init_carry(None)(n_slots)
    out = {}
    for key, leaf in carry.items():
        if leaf.dtype == jnp.bool_:
            val = rng.integers(0, 2, leaf.shape).astype(bool)
        elif jnp.issubdtype(leaf.dtype, jnp.integer):
            val = rng.integers(0, 20, leaf.shape)
        else:
            val = rng.standard_normal(leaf.shape)
            val[..., 0] = -0.0
        out[key] = jnp.asarray(val, leaf.dtype)
    return out


def test_inactive_rows_are_bitwise_untouched_in_every_leaf(lm):
    fam = lm.serving_family()
    step, _ = fam.decode_step()
    from bigdl_tpu.serving.sampling import make_knob_rows

    carry = _random_carry(lm, 4, seed=6)
    before = {k: np.asarray(v).copy() for k, v in carry.items()}
    active = np.asarray([True, False, True, False])
    knobs = {k: jnp.asarray(v) for k, v in
             make_knob_rows(4, vocab=TOY["vocab_size"]).items()}
    _, _, after = step(fam.params(), jnp.asarray([3, 4, 5, 6], jnp.int32),
                       jnp.asarray(active), carry, knobs)
    kinds = set()
    for key, leaf in after.items():
        leaf = np.asarray(leaf)
        kinds.add(leaf_kind(key))
        for row in np.flatnonzero(~active):
            assert leaf[row].tobytes() == before[key][row].tobytes(), key
        if leaf_kind(key) in ("state", "pos"):
            for row in np.flatnonzero(active):
                assert leaf[row].tobytes() != before[key][row].tobytes(), key
    assert kinds == {"pos", "kv", "state", "lane"}


# ------------------------------------------------------ through the engine


def _served(lm, jobs, **engine_kw):
    eng = ServingEngine(lm, **engine_kw)
    rids = [eng.submit(list(map(int, p)), max_new_tokens=n, sampling=s)
            for p, n, s in jobs]
    outs = eng.drain()
    return eng, [(outs[r], eng.logprobs(r)) for r in rids]


def test_engine_serves_the_reference_distribution(lm):
    """prefill -> pool -> N decode steps through ServingEngine: greedy
    rows emit the reference's argmax and every chosen log-prob is the
    reference's, teacher-forced on the served tokens."""
    jobs = [(_tokens(7, 13), 12, None), (_tokens(8, 5), 9, None),
            (_tokens(9, 1), 7, None), (_tokens(10, 2), 6, None),
            (_tokens(7, 13), 8, SamplingParams(temperature=0.8, top_k=5,
                                               seed=4))]
    eng, served = _served(lm, jobs, n_slots=4)
    assert eng.pool.state_bytes_per_slot > 0
    for (prompt, _, sampling), (out, logp) in zip(jobs, served):
        seq = list(prompt) + list(out)
        logits = _ref_logits(lm, seq[:-1])[len(prompt) - 1:]
        ref_logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        if sampling is None:
            assert (logits.argmax(-1) + 1 == out).all()
        chosen = ref_logp[np.arange(len(out)), np.asarray(out) - 1]
        np.testing.assert_allclose(logp, chosen, atol=LOGP_ATOL, rtol=0)


def test_bfloat16_engine_tokens_sit_within_the_benchmarks_slack(lm):
    jobs = [(_tokens(11, 13), 12, None), (_tokens(12, 6), 12, None)]
    _, served = _served(lm, jobs, n_slots=4, compute_dtype=jnp.bfloat16)
    for (prompt, _, _), (out, _) in zip(jobs, served):
        seq = list(prompt) + list(out)
        logits = _ref_logits(lm, seq[:-1])[len(prompt) - 1:]
        spread = float(np.mean(logits.max(-1) - np.median(logits, -1)))
        short = logits.max(-1) - logits[np.arange(len(out)),
                                        np.asarray(out) - 1]
        assert short.max() <= 0.06 * spread


@pytest.mark.parametrize("second", [13, 2, 1],
                         ids=["prefilled", "one-token-prefill", "no-prefill"])
def test_a_recycled_slot_serves_as_a_fresh_engine_would(lm, second):
    """One slot, two occupants: the second gets nothing of the first's
    scan state, window or cache: also where its prompt is one token and
    no prefill overwrites the slot."""
    first = (_tokens(13, 14), 10, None)
    job = (_tokens(14, second), 8, SamplingParams(temperature=0.7, top_k=8,
                                                  seed=9))
    _, both = _served(lm, [first, job], n_slots=1)
    _, alone = _served(lm, [job], n_slots=1)
    assert np.array_equal(both[1][0], alone[0][0])
    assert np.array_equal(both[1][1], alone[0][1])


# the second family through the dispatch-ahead window: five requests of
# staggered lengths through two slots, greedy and seeded-sampled, so that
# rows finish while a decode is in flight and their slots are re-admitted
_SWEEP_JOBS = [
    (_tokens(21, 9), 5, None),
    (_tokens(22, 4), 11, SamplingParams(temperature=0.8, top_k=6, seed=3)),
    (_tokens(23, 6), 7, None),
    (_tokens(24, 1), 9, SamplingParams(temperature=1.1, seed=12)),
    (_tokens(25, 12), 6, None)]


def _sweep(lm, jobs, **engine_kw):
    """Serve ``jobs`` through two slots; beside the streams, what every
    ``pool.free()`` left in the freed row's ``state`` and ``pos`` leaves
    (read back at once: after whatever was in flight for the row)."""
    eng = ServingEngine(lm, n_slots=2, **engine_kw)
    freed, free = [], eng.pool.free

    def checked_free(slot):
        free(slot)
        freed.append(all(
            not np.asarray(leaf[slot]).any()
            for key, leaf in eng.pool.carry.items()
            if leaf_kind(key) in ("state", "pos")))

    eng.pool.free = checked_free
    rids = [eng.submit(list(map(int, p)), max_new_tokens=n, sampling=s)
            for p, n, s in jobs]
    outs = eng.drain()
    return eng, [(outs[r], eng.logprobs(r)) for r in rids], freed


@pytest.fixture(scope="module")
def sweep_oracle(lm):
    return _sweep(lm, _SWEEP_JOBS, dispatch_ahead=0)[1]


@pytest.mark.parametrize("W", [0, 1, 2])
def test_window_sweep_is_byte_identical_and_frees_clean(lm, sweep_oracle, W):
    """Token streams AND log-probs at depth W are the W=0 ones to the
    byte. A row that finishes at the consume of step k has had its scan
    state, convolution window and K/V row advanced once more by the
    step in flight: ``free()`` zeroes the state leaves AFTER it (the
    reset is launched on the committed carry), so every freed row reads
    back zeros before its slot's next prefill."""
    eng, served, freed = _sweep(lm, _SWEEP_JOBS, dispatch_ahead=W)
    for (out, logp), (want, want_logp) in zip(served, sweep_oracle):
        assert np.asarray(out).tobytes() == np.asarray(want).tobytes()
        assert np.asarray(logp).tobytes() == np.asarray(want_logp).tobytes()
    assert len(freed) == len(_SWEEP_JOBS) and all(freed)
    assert not eng._window and eng.pool.free_slots == 2
    chained = eng.metrics.metrics.values("serving/decode_chained")
    assert (sum(chained) > 0) == (W > 0)
    # the overshoot is no emitted token and no step sample
    m = eng.metrics.metrics
    assert m.get("serving/batch_active")[0] == sum(n for _, n, _ in _SWEEP_JOBS)
    assert min(m.values("serving/state_in_use_bytes")) > 0


@pytest.mark.parametrize("W", [0, 1, 2])
def test_window_sweep_next_occupant_serves_as_in_a_fresh_engine(lm, W):
    """The third, fourth and fifth requests take slots whose previous
    row was stepped once past its end: each serves the stream it
    produces alone in a fresh engine."""
    _, served, _ = _sweep(lm, _SWEEP_JOBS, dispatch_ahead=W)
    for job, (out, logp) in list(zip(_SWEEP_JOBS, served))[2:]:
        _, alone, _ = _sweep(lm, [job], dispatch_ahead=W)
        assert np.array_equal(out, alone[0][0])
        np.testing.assert_allclose(logp, alone[0][1], atol=LOGP_ATOL, rtol=0)


def test_row_state_free_restore_is_byte_identical(lm):
    eng = ServingEngine(lm, n_slots=3)
    for seed, n in ((15, 12), (16, 7)):
        eng.submit(list(map(int, _tokens(seed, n))), max_new_tokens=20)
    for _ in range(5):
        eng.step()
    pool = eng.pool
    slot = sorted(eng.scheduler.running)[0]
    payload = pool.row_state(slot)
    saved = {k: np.asarray(v).copy() for k, v in payload["carry"].items()}
    assert {leaf_kind(k) for k in saved} == {"pos", "kv", "state", "lane"}
    assert any(saved[k].any() for k in saved if leaf_kind(k) == "state")
    req = eng.scheduler.running[slot]
    eng.scheduler.requeue(req)
    pool.free(slot)
    # a freed slot holds no state and no position
    for key, leaf in pool.carry.items():
        if leaf_kind(key) in ("state", "pos"):
            assert not np.asarray(leaf[slot]).any(), key
    other = pool.alloc()
    pool.restore_row(other, payload)
    back = pool.read_row(other)
    for key, want in saved.items():
        assert np.asarray(back[key]).tobytes() == want.tobytes(), key


def test_preempted_row_resumes_its_stream(lm):
    """Priority preemption stashes the row (state leaves included) and
    restores it: the victim's stream is the unpreempted one."""
    low = list(map(int, _tokens(17, 9)))
    high = list(map(int, _tokens(18, 6)))
    plain = ServingEngine(lm, n_slots=1)
    rid = plain.submit(low, max_new_tokens=14)
    want = plain.drain()[rid]
    eng = ServingEngine(lm, n_slots=1, policy="priority")
    rid_low = eng.submit(low, max_new_tokens=14, priority=0)
    for _ in range(4):
        eng.step()
    eng.submit(high, max_new_tokens=5, priority=5)
    outs = eng.drain()
    assert eng.metrics.summary()["serving/preempted"] >= 1
    assert np.array_equal(outs[rid_low], want)


def test_state_counters_and_series(lm):
    eng, _ = _served(lm, [(_tokens(19, 9), 6, None),
                          (_tokens(20, 4), 6, None)], n_slots=4)
    cfg, pool, m = lm.config, eng.pool, eng.metrics.metrics
    per_layer = cfg.mamba_n_heads * cfg.mamba_d_head * cfg.mamba_d_state * 4 \
        + (cfg.mamba_d_conv - 1) * cfg.conv_dim * 4
    assert pool.state_bytes_per_slot == cfg.num_hidden_layers * per_layer
    assert pool.kv_bytes_per_slot == cfg.num_hidden_layers * 2 * MAX_LEN \
        * cfg.num_key_value_heads * cfg.head_dim * 4
    assert m.get("serving/state_bytes_per_slot")[0] == \
        pool.state_bytes_per_slot
    series = m.values("serving/state_in_use_bytes")
    assert len(series) == len(m.values("serving/kv_used_share"))
    assert max(series) == 2 * pool.state_bytes_per_slot
    assert set(series) <= {k * pool.state_bytes_per_slot for k in (1, 2)}


def test_a_family_without_state_samples_no_state_series():
    from bigdl_tpu.models import TransformerLM

    RNG.set_seed(3)
    gpt = TransformerLM(64, hidden_size=32, n_heads=2, n_layers=1,
                        max_len=32)
    eng = ServingEngine(gpt, n_slots=2)
    eng.submit([3, 4, 5], max_new_tokens=3)
    eng.drain()
    assert eng.pool.state_bytes_per_slot == 0
    assert eng.metrics.metrics.values("serving/state_in_use_bytes") == []
    assert eng.pool._reset_keys == ["pos"]


@pytest.mark.parametrize("key,kind", [
    ("pos", "pos"), ("k0", "kv"), ("v11", "kv"), ("k3_scale", "scale"),
    ("rng", "lane"), ("tok_counts", "lane"), ("prompt_mask", "lane"),
    ("ssm0", "state"), ("conv7", "state")])
def test_leaf_kinds(key, kind):
    assert leaf_kind(key) == kind


@pytest.mark.parametrize("option,kwargs", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("speculative", dict(speculative=object())),
    ("adapters", dict(adapters=object())),
    ("kv_dtype", dict(kv_dtype="int8", compute_dtype=jnp.bfloat16)),
    ("mesh", dict(mesh=object())),
    ("parallelism", dict(parallelism={"data": 2})),
    ("admission", dict(admission="chunked")),
    ("admission", dict(admission="per_request")),
    ("tier", dict(tier=True))])
def test_unsupported_engine_options_raise_by_name(lm, option, kwargs):
    with pytest.raises(ValueError, match=f"^{option}=.*FalconH1LM"):
        ServingEngine(lm, n_slots=2, **kwargs)


def test_grouped_folded_decode_attention_matches_repeated_heads():
    from bigdl_tpu.ops.decode_attention import (
        decode_attention_reference, folded_decode_attention,
    )

    n, h, g, d, L = 3, 6, 2, 8, 10
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((n, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((n, L, g * d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n, L, g * d)), jnp.float32)
    pos = jnp.asarray([0, 4, 9])
    got = folded_decode_attention(q, k, v, pos)

    def repeated(a):
        return jnp.repeat(a.reshape(n, L, g, d), h // g,
                          axis=2).reshape(n, L, h * d)

    want = decode_attention_reference(q, repeated(k), repeated(v), pos)
    np.testing.assert_allclose(got, want, atol=2e-6)
    with pytest.raises(ValueError, match="K/V heads"):
        folded_decode_attention(q, k[..., :12], v[..., :12], pos)
