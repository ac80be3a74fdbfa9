"""The ``glm4_moe_lite`` family (``models/glm_moe_lite.py``) against its
plain reference (``benchmark/configs/glm_moe_lite_reference.py``: float32,
highest precision, the EXPANDED form, no cache), on seeded weights at a
toy size: the whole-sequence forward, the absorbed decode path over a
cache the expanded path filled, prefill-then-decode through
``ServingEngine``, the shares of the routed experts, and the pool with
ONE cache leaf a layer."""

from __future__ import annotations

import importlib
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import glm_moe_lite
from bigdl_tpu.models.glm_moe_lite import GlmMoeLiteLM
from bigdl_tpu.parallel.moe import routed_experts
from bigdl_tpu.serving import SamplingParams, ServingEngine
from bigdl_tpu.serving.kv_pool import leaf_kind
from bigdl_tpu.serving.sampling import make_knob_rows
from bigdl_tpu.utils.random_gen import RNG

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: the cell's layer kinds at the least depth that has both (one dense
#: layer, two expert layers), 2 of 16 experts held (share 1 of 8),
#: top-2, a latent of 32 beside a rotary key of 4 in a cache window of 64
TOY = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
    kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    n_routed_experts=2, num_experts_per_tok=2, n_shared_experts=1,
    norm_topk_prob=True, routed_scaling_factor=1.8, topk_method="noaux_tc",
    rms_norm_eps=1e-5, rope_theta=1000000,
    expert_share={"index": 1, "of": 8})
MAX_LEN = 64
#: the stored cache row: 32 + 4 columns in one lane tile of 128
ROW = 128
#: float32 program against the float32 reference, as a share of the
#: logits' standard deviation: round-off of sums of at most 128 terms
#: over 3 layers reads ~2e-6; bfloat16 anywhere reads ~1e-2
F32_OF_STD = 2e-5
#: log-probs sit near -log(512) = -6.24, where one float32 ulp is
#: 4.8e-7: two roundings (log-softmax here, and in the reference)
LOGP_ATOL = 2e-6


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "glm_moe_lite_reference",
        ROOT / "benchmark" / "configs" / "glm_moe_lite_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load_reference()


def _model(seed, config=TOY, **kw):
    RNG.set_seed(seed)
    model = GlmMoeLiteLM(config, max_len=MAX_LEN, **kw)
    model.evaluate()
    model._ensure_params()
    return model


@pytest.fixture(scope="module")
def lm():
    return _model(11)


def _tokens(seed, *shape):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"] + 1, size=shape)


_REF_FN = []


def _ref_logits(params, seq):
    """The reference's logits at every position of ``seq``: one compiled
    forward over the sequence padded to the cache window (causal: what
    follows a position does not reach it)."""
    if not _REF_FN:
        _REF_FN.append(jax.jit(lambda p, padded: REF.logits_and_ties(
            p, padded, jnp.arange(MAX_LEN), TOY)[0]))
    padded = np.ones((MAX_LEN,), np.int32)
    padded[:len(seq)] = seq
    return np.asarray(_REF_FN[0](params, jnp.asarray(padded)))[:len(seq)]


def _with(params, path, leaf):
    """``params`` with the leaf at ``path`` (keys / layer indices)
    replaced."""
    if not path:
        return leaf
    if isinstance(params, list):
        return [_with(p, path[1:], leaf) if i == path[0] else p
                for i, p in enumerate(params)]
    return {k: _with(v, path[1:], leaf) if k == path[0] else v
            for k, v in params.items()}


def _pinned_routing(params):
    """The same parameters under a router bias that decides the top-k
    whatever the scores (experts 2 and 3, both held by share 1): what
    bfloat16 costs where no choice can flip."""
    for i in range(TOY["first_k_dense_replace"], TOY["num_hidden_layers"]):
        bias = -10.0 * jnp.abs(jnp.arange(16, dtype=jnp.float32) - 2.5)
        params = _with(params, ("layers", i, "moe", "router", "bias"), bias)
    return params


# ---------------------------------------------------------------- forward


def test_whole_sequence_logits_match_the_reference(lm):
    toks = _tokens(0, 2, 41)
    got = np.asarray(lm.forward(toks))
    assert got.shape == (2, 41, TOY["vocab_size"])
    for row in range(2):
        want = _ref_logits(lm.params, toks[row])
        assert np.abs(got[row] - want).max() <= F32_OF_STD * want.std()


def test_the_float32_tolerance_would_fail_bfloat16(lm):
    toks = _tokens(1, 1, 30)
    want = _ref_logits(lm.params, toks[0])
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        if a.ndim > 1 else a, lm.params)
    got, _ = lm.apply(low, toks)
    assert np.abs(np.asarray(got[0]) - want).max() > 20 * F32_OF_STD \
        * want.std()


def test_bfloat16_forward_where_no_choice_can_flip():
    """bfloat16 parameters and compute with the top-k pinned: the logits
    stay within 3% of their deviation of the float32 reference's (a
    bfloat16 significand is 8 bits: 0.4% a rounding, through 3 layers of
    sums of up to 128 terms)."""
    low = _model(12, param_dtype="bfloat16")
    low.params = _pinned_routing(low.params)
    toks = _tokens(2, 1, 41)
    want = _ref_logits(low.params, toks[0])
    got, _ = low.apply(low.params, toks)
    assert got.dtype == jnp.float32
    assert np.abs(np.asarray(got[0]) - want).max() <= 0.03 * want.std()


def test_parameters_are_created_in_the_stated_dtype():
    low = _model(13, param_dtype="bfloat16")
    kinds = {(str(path[-1]), leaf.dtype.name) for path, leaf in
             jax.tree_util.tree_flatten_with_path(low.params)[0]}
    assert {d for n, d in kinds if "bias" not in n} == {"bfloat16"}
    assert {d for n, d in kinds if "bias" in n} == {"float32"}
    attn = low.params["layers"][1]["attn"]
    assert {k: v.shape for k, v in attn.items()} == {
        "wqa": (64, 24), "q_norm": (24,), "wqb": (24, 4 * 16),
        "wkva": (64, 36), "kv_norm": (32,), "w_uk": (32, 4 * 12),
        "w_uv": (32, 4 * 16), "wo": (4 * 16, 64)}
    assert low.params["layers"][1]["moe"]["router"]["w"].shape == (64, 16)
    assert low.params["layers"][1]["moe"]["experts"]["gate"].shape \
        == (2, 64, 32)
    assert "mlp" in low.params["layers"][0]


# ---------------------------------------------- absorbed equals expanded


def test_the_absorbed_path_over_a_cache_equals_the_expanded_path(lm):
    """One layer's attention in float32: the expanded path over 20
    tokens fills the cache rows; the absorbed path for token 19 against
    the rows of tokens 0..18 (it writes its own) gives the expanded
    path's output at 19, to 1e-5 of its size; and the cache row it
    wrote is the expanded path's."""
    cfg, p = lm.config, lm.params["layers"][1]["attn"]
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((2, 20, 64)), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(20)[None], (2, 20))
    valid = jnp.ones((2, 20), bool)
    with jax.default_matmul_precision("highest"):
        want, rows = glm_moe_lite._attention(cfg, p, a, qpos, valid, None,
                                             MAX_LEN)
        assert rows.shape == (2, 20, ROW)
        assert not np.asarray(rows[..., 36:]).any()      # the lane padding
        cache = jnp.zeros((2, MAX_LEN, ROW), jnp.float32
                          ).at[:, :19].set(rows[:, :19])
        got, new = glm_moe_lite._attention(
            cfg, p, a[:, 19:], qpos[:, 19:], valid[:, 19:], cache, None)
    want = np.asarray(want[:, 19])
    assert np.abs(np.asarray(got[:, 0]) - want).max() \
        <= 1e-5 * np.abs(want).max()
    np.testing.assert_allclose(np.asarray(new[:, 19]),
                               np.asarray(rows[:, 19]), rtol=1e-6, atol=1e-7)


def _skip_norm_of(width):
    real = glm_moe_lite.rms_norm
    return lambda x, w, eps: x if w.shape == (width,) else real(x, w, eps)


def _alter(monkeypatch, name):
    """Leave one part of the latent attention out of the PROGRAM."""
    ops = importlib.import_module("bigdl_tpu.ops.decode_attention")

    if name in ("q_norm", "kv_norm"):
        width = TOY["q_lora_rank" if name == "q_norm" else "kv_lora_rank"]
        monkeypatch.setattr(glm_moe_lite, "rms_norm", _skip_norm_of(width))
    elif name == "shared_rotary_key":
        real = glm_moe_lite.rope
        monkeypatch.setattr(
            glm_moe_lite, "rope", lambda x, pos, theta:
            x if x.shape[2] == 1 else real(x, pos, theta))
    elif name == "scale":
        real_b, real_d = glm_moe_lite.blocked_attention, ops.decode_attention
        monkeypatch.setattr(
            glm_moe_lite, "blocked_attention",
            lambda q, k, v, window, scale: real_b(q, k, v, window, 1.0))
        monkeypatch.setattr(
            ops, "decode_attention",
            lambda *a, scale=None, **kw: real_d(*a, scale=1.0, **kw))
    else:
        assert name == "v_width"
        real_d = ops.decode_attention

        def narrow(*a, v_width=None, **kw):
            out = real_d(*a, v_width=v_width - 8, **kw)
            return jnp.pad(out, [(0, 0), (0, 0), (0, 8)])

        monkeypatch.setattr(ops, "decode_attention", narrow)


@pytest.mark.parametrize("name", ["q_norm", "kv_norm", "shared_rotary_key",
                                  "scale", "v_width"])
def test_each_part_of_the_latent_attention_shows_in_the_logits(
        monkeypatch, name):
    """Prefill and decode through the engine with one part altered (a
    latent norm left out, the shared key unrotated, the ``1 / sqrt(16)``
    scale dropped, the values cut to 24 of the latent's 32 columns): the
    chosen log-probs leave the reference's by fifty tolerances and more
    (at this size the rotary part is 4 of a head's 16 columns)."""
    _alter(monkeypatch, name)
    altered = _model(11)
    prompt = _tokens(5, 21)
    eng = ServingEngine(altered, n_slots=2)
    rid = eng.submit(prompt.tolist(), max_new_tokens=6)
    out = eng.drain()[rid]
    seq = list(prompt) + list(out)
    logits = _ref_logits(altered.params, seq[:-1])[len(prompt) - 1:]
    ref_logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    chosen = ref_logp[np.arange(len(out)), np.asarray(out) - 1]
    # the first token is the prefill's: the v_width cut is decode's only
    assert np.abs(eng.logprobs(rid) - chosen)[1:].max() > 50 * LOGP_ATOL


@pytest.mark.parametrize("name", sorted(REF.CONTROLS))
def test_each_control_of_the_reference_moves_its_logits(lm, name):
    toks = jnp.asarray(_tokens(6, 30))
    at = jnp.arange(30)
    want = np.asarray(REF.logits_and_ties(lm.params, toks, at, TOY)[0])
    got = np.asarray(REF.logits_and_ties(lm.params, toks, at, TOY,
                                         leave_out=(name,))[0])
    assert np.abs(got - want).max() > 50 * F32_OF_STD * want.std()


def test_the_reference_refuses_a_control_it_does_not_know(lm):
    with pytest.raises(ValueError, match="leave_out"):
        REF.hidden_states(lm.params, jnp.asarray(_tokens(6, 4)), TOY,
                          leave_out=("gate",))


# ------------------------------------------------------- the routed layer


def test_the_shares_add_up_to_the_uncut_layer(lm):
    """The routed parts that all 8 shares give, plus the shared expert
    counted once, equal the uncut reference's MoE layer (a non-zero
    ``e_score_correction_bias``: selection by biased, weighting by
    unbiased scores)."""
    rng = np.random.default_rng(6)
    m = jnp.asarray(rng.standard_normal((30, 64)), jnp.float32)
    layer = lm.params["layers"][2]["moe"]
    router = dict(layer["router"], bias=jnp.asarray(
        0.2 * rng.standard_normal((16,)), jnp.float32))
    all_experts = {n: jnp.asarray(0.02 * rng.standard_normal(
        (16,) + v.shape[1:]), jnp.float32)
        for n, v in layer["experts"].items()}
    uncut = dict(TOY, n_routed_experts=16,
                 expert_share={"index": 0, "of": 1})
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF._moe(
            {"router": router, "shared": layer["shared"],
             "experts": all_experts}, m, uncut)[0])
        shared = np.asarray(REF._swiglu(layer["shared"], m))
    total = shared.copy()
    for index in range(8):
        mine = {n: v[2 * index:2 * index + 2]
                for n, v in all_experts.items()}
        part, _ = routed_experts(m, router, mine, 2 * index, 2,
                                 route_scale=TOY["routed_scaling_factor"])
        total += np.asarray(part)
        # and each share alone is the reference's with that share
        cut = dict(TOY, expert_share={"index": index, "of": 8})
        with jax.default_matmul_precision("highest"):
            alone = np.asarray(REF._moe(
                {"router": router, "shared": layer["shared"],
                 "experts": mine}, m, cut)[0])
        assert np.abs(shared + np.asarray(part) - alone).max() \
            <= 1e-5 * np.abs(alone).max()
    assert np.abs(total - want).max() <= 1e-5 * np.abs(want).max()


def test_a_position_under_the_tie_margin_is_not_judged(lm, monkeypatch):
    toks = jnp.asarray(_tokens(7, 40))
    at = jnp.arange(40)
    logits, tie = REF.logits_and_ties(lm.params, toks, at, TOY)
    tie = np.asarray(tie)
    monkeypatch.setattr(REF, "TIE_MARGIN", float(np.median(tie)))
    judged = np.asarray(REF.logits_at(lm.params, toks, at, TOY))
    under = tie < np.median(tie)
    assert under.any() and not under.all()
    assert not judged[under].any()
    assert np.array_equal(judged[~under], np.asarray(logits)[~under])


# ----------------------------------------------------------- padded prefill


def test_padded_bucket_rows_are_the_reference_s_and_zero_beyond(lm):
    """A right-padded block: each row's last log-probs are the
    reference's at its own length, its fresh leaf is the bucket long
    and zero beyond the row's length."""
    toks = _tokens(8, 3, 32)
    lengths = [32, 7, 19]
    fam = lm.serving_family()
    logp, rows = fam.batch_prefill_step()(
        fam.params(), jnp.asarray(toks - 1), np.asarray(lengths, np.int32))
    assert sorted(rows) == ["k0", "k1", "k2", "pos"]
    assert np.asarray(rows["pos"]).tolist() == lengths
    for r, n in enumerate(lengths):
        logits = _ref_logits(lm.params, toks[r, :n])[-1]
        want = logits - np.log(np.exp(logits).sum())
        np.testing.assert_allclose(np.asarray(logp[r]), want,
                                   atol=LOGP_ATOL, rtol=0)
        leaf = np.asarray(rows["k1"][r])
        assert leaf.shape == (32, ROW)
        assert not leaf[n:].any() and np.abs(leaf[:n, :36]).min() > 0


def test_prefill_refuses_lengths_outside_the_bucket(lm):
    fam = lm.serving_family()
    pre = fam.batch_prefill_step()
    with pytest.raises(ValueError, match="lengths must lie"):
        pre(fam.params(), jnp.zeros((2, 8), jnp.int32), [9, 3])
    with pytest.raises(ValueError, match="tokens must be"):
        pre(fam.params(), jnp.zeros((2, 8), jnp.int32), [3])


# ------------------------------------------------------ the decode program


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
            val = rng.integers(0, 60, leaf.shape)
        else:
            val = rng.standard_normal(leaf.shape)
            val[..., 0] = -0.0
        out[key] = jnp.asarray(val, leaf.dtype)
    return out


def test_inactive_rows_are_bitwise_untouched_in_every_leaf(lm):
    """The ballast contract with one cache leaf a layer, and the step's
    fourth result: the token counts of the ACTIVE rows only."""
    fam = lm.serving_family()
    step, _ = fam.decode_step()
    carry = _random_carry(lm, 4, seed=6)
    assert sorted(k for k in carry if leaf_kind(k) == "kv") \
        == ["k0", "k1", "k2"]                      # and no v{i}
    before = {k: np.asarray(v).copy() for k, v in carry.items()}
    active = np.asarray([True, False, True, False])
    knobs = {k: jnp.asarray(v) for k, v in
             make_knob_rows(4, vocab=TOY["vocab_size"]).items()}
    _, _, after, counts = step(
        fam.params(), jnp.asarray([3, 4, 5, 6], jnp.int32),
        jnp.asarray(active), carry, knobs)
    kinds = set()
    for key, leaf in after.items():
        leaf = np.asarray(leaf)
        kinds.add(leaf_kind(key))
        for row in np.flatnonzero(~active):
            assert leaf[row].tobytes() == before[key][row].tobytes(), key
        if leaf_kind(key) in ("kv", "pos"):
            for row in np.flatnonzero(active):
                assert leaf[row].tobytes() != before[key][row].tobytes(), key
    assert kinds == {"pos", "kv", "lane"}
    counts = np.asarray(counts)
    assert counts.shape == (2, 2)            # expert layers x held
    assert (counts.sum(-1) <= 2 * 2).all()   # 2 active rows, top-2


# ------------------------------------------------------ through the engine


def _served(lm, jobs, **engine_kw):
    eng = ServingEngine(lm, **engine_kw)
    rids = [eng.submit(list(map(int, p)), max_new_tokens=n, sampling=s)
            for p, n, s in jobs]
    outs = eng.drain()
    return eng, [(outs[r], eng.logprobs(r)) for r in rids]


def _check_against_reference(lm, jobs, served):
    for (prompt, _, sampling), (out, logp) in zip(jobs, served):
        seq = list(prompt) + list(out)
        logits = _ref_logits(lm.params, seq[:-1])[len(prompt) - 1:]
        ref_logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        if sampling is None:
            assert (logits.argmax(-1) + 1 == out).all()
        chosen = ref_logp[np.arange(len(out)), np.asarray(out) - 1]
        np.testing.assert_allclose(logp, chosen, atol=LOGP_ATOL, rtol=0)


def test_engine_serves_the_reference_distribution(lm):
    """prefill (expanded) -> pool -> decode (absorbed) through
    ServingEngine: greedy rows emit the reference's argmax and every
    chosen log-prob is the reference's, teacher-forced on the served
    tokens. Six requests through four slots (rows admitted and freed in
    between, slots reused), one decoding to 60 of the 64 positions, past
    ``max_len / 2``."""
    jobs = [(_tokens(20, 6), 54, None), (_tokens(21, 23), 20, None),
            (_tokens(22, 40), 20, None), (_tokens(23, 1), 18, None),
            (_tokens(24, 33), 12, SamplingParams(temperature=0.8, top_k=5,
                                                 seed=4)),
            (_tokens(25, 9), 20, None)]
    eng, served = _served(lm, jobs, n_slots=4)
    _check_against_reference(lm, jobs, served)
    assert eng.pool.max_len == MAX_LEN
    assert eng.pool.free_slots == 4
    # one prefill program a bucket, four rows each (no bound reached)
    assert sorted(eng.admitter.traced_shapes) == [(4, 8), (4, 32), (4, 64)]


def test_bfloat16_serving_stays_within_the_served_slack_without_flips():
    """bfloat16 parameters and compute through the engine, the top-k
    pinned (see the forward's bfloat16 test): every served token's
    reference logit is within 0.03 of the logits' spread of the best
    one, half of what the benchmark's comparison allows."""
    low = _model(12, param_dtype="bfloat16")
    low.params = _pinned_routing(low.params)
    jobs = [(_tokens(30, 21), 24, None), (_tokens(31, 30), 20, None)]
    _, served = _served(low, jobs, n_slots=2, compute_dtype=jnp.bfloat16)
    for (prompt, _, _), (out, _) in zip(jobs, served):
        seq = list(prompt) + list(out)
        logits = _ref_logits(low.params, seq[:-1])[len(prompt) - 1:]
        spread = float(np.mean(logits.max(-1) - np.median(logits, -1)))
        short = logits.max(-1) - logits[np.arange(len(out)),
                                        np.asarray(out) - 1]
        assert short.max() <= 0.03 * spread


def test_the_series_of_the_step_read_true_for_one_leaf_a_layer(lm):
    """The per-expert counts are read back with the tokens, and the
    K/V series count ONE leaf of ``ROW`` columns a layer: what a
    position costs in the pool as stored rides every step's sample."""
    jobs = [(_tokens(27, 30), 10, None), (_tokens(28, 7), 10, None)]
    eng, _ = _served(lm, jobs, n_slots=4)
    m = eng.metrics.metrics
    steps = len(m.values("serving/batch_active"))
    for name in ("expert_pairs", "experts_hit", "expert_load_max",
                 "kv_held_bytes", "kv_fetched_bytes"):
        assert len(m.values(f"serving/{name}")) == steps, name
    pairs = np.asarray(m.values("serving/expert_pairs"))
    active = np.asarray(m.values("serving/batch_active"))
    assert (pairs <= active * 2 * 2).all() and pairs.max() > 0
    assert (np.asarray(m.values("serving/experts_hit")) <= 4).all()
    row = ROW * 4                      # one layer's position, float32
    assert eng.pool.n_layers == 3
    assert eng.pool.kv_position_bytes == 3 * row
    assert eng.pool.kv_bytes_per_slot == 3 * MAX_LEN * row
    assert eng.pool.kv_held_bytes(5) == 3 * 5 * row
    assert eng.pool.kv_held_bytes(70) == 3 * MAX_LEN * row
    # set at construction, and repeated with every step's sample
    position = m.values("serving/kv_position_bytes")
    assert set(position) == {3.0 * row} and len(position) == steps + 1
    assert eng.metrics.summary()["serving/kv_position_bytes"] == 3 * row
    held = np.asarray(m.values("serving/kv_held_bytes"))
    assert eng.pool.kv_held_bytes(30) < held.max() \
        <= 2 * eng.pool.kv_held_bytes(40)
    # the kernel's block is 128 positions here (the leaf is padded up
    # to one): a decoding row fetches one block a layer, ONCE
    fetched = set(m.values("serving/kv_fetched_bytes"))
    assert fetched <= {3 * 128 * row, 2 * 3 * 128 * row} and fetched


def test_other_families_report_a_position_s_bytes_too():
    """GPT-2's K and V of every layer."""
    from bigdl_tpu.models.transformer import TransformerLM

    RNG.set_seed(2)
    gpt = TransformerLM(vocab_size=64, hidden_size=16, n_heads=2,
                        n_layers=2, max_len=32)
    gpt.evaluate()
    eng = ServingEngine(gpt, n_slots=2)
    assert eng.pool.kv_position_bytes == 2 * 2 * 16 * 4
    assert eng.metrics.summary()["serving/kv_position_bytes"] == 256


REFUSED = {"prefix_cache": True, "speculative": object(),
           "adapters": object(), "kv_dtype": "int8", "mesh": object(),
           "parallelism": {"data": 2}, "tier": True}


@pytest.mark.parametrize("option", sorted(REFUSED) + ["chunked",
                                                      "per_request"])
def test_the_family_refuses_by_name_what_it_does_not_build(lm, option):
    kw = {"admission": option} if option in ("chunked", "per_request") \
        else {option: REFUSED[option]}
    name = "admission" if "admission" in kw else option
    with pytest.raises(ValueError, match=f"^{name}="):
        ServingEngine(lm, n_slots=2, **kw)


@pytest.mark.parametrize("flag, value", [
    ("n_group", 2), ("topk_group", 2), ("n_shared_experts", 2),
    ("rope_scaling", {"type": "yarn"}), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("topk_method", "greedy"), ("partial_rotary_factor", 0.5),
    ("num_nextn_predict_layers", 1), ("num_key_value_heads", 2)])
def test_the_configuration_refuses_by_name_what_is_not_built(flag, value):
    with pytest.raises(ValueError, match=f"^{flag}="):
        glm_moe_lite.GlmMoeLiteConfig.from_dict(dict(TOY, **{flag: value}))


def test_the_published_row_is_576_values_in_five_lanes():
    cfg = glm_moe_lite.GlmMoeLiteConfig.from_dict(dict(
        TOY, kv_lora_rank=512, qk_rope_head_dim=64))
    assert (cfg.latent_width, cfg.row_width) == (576, 640)
    assert (cfg.router_experts, cfg.expert_offset) == (16, 2)


# ------------------------------------------------------------------ the pool


def test_a_freed_slot_s_stale_rows_are_unseen_by_the_next_occupant(lm):
    """A slot whose last occupant filled 58 positions is freed
    (``free()`` leaves the leaf) and given to a SHORT request: none of
    the old rows is visible behind ``pos``, its tokens are the
    reference's."""
    eng = ServingEngine(lm, n_slots=4)
    long_rid = eng.submit(_tokens(40, 38).tolist(), max_new_tokens=20)
    eng.step()
    (slot,) = eng.scheduler.running
    eng.drain()
    stale = np.asarray(eng.pool.carry["k1"][slot]).copy()
    assert np.abs(stale[:57, :36]).max(axis=-1).min() > 0
    assert int(eng.pool.carry["pos"][slot]) == 0
    short = _tokens(41, 7)
    rid = eng.submit(short.tolist(), max_new_tokens=8)
    eng.step()
    assert list(eng.scheduler.running) == [slot]      # the same slot
    outs = eng.drain()
    _check_against_reference(lm, [(short, 8, None)],
                             [(outs[rid], eng.logprobs(rid))])
    now = np.asarray(eng.pool.carry["k1"][slot])
    assert np.array_equal(now[15:], stale[15:])
    assert len(outs[long_rid]) == 20


def test_row_state_round_trips_one_leaf_a_layer(lm):
    eng = ServingEngine(lm, n_slots=4)
    eng.submit(_tokens(42, 30).tolist(), max_new_tokens=30)
    for _ in range(6):
        eng.step()
    eng.flush_window()
    (slot,) = eng.scheduler.running
    payload = eng.row_state(slot)
    assert sorted(k for k in payload["carry"] if leaf_kind(k) == "kv") \
        == ["k0", "k1", "k2"]
    assert payload["carry"]["k0"].shape == (1, MAX_LEN, ROW)
    other = eng.pool.alloc()
    assert other != slot
    eng.pool.restore_row(other, payload)
    for key, leaf in eng.pool.carry.items():
        if leaf_kind(key) in ("kv", "pos", "lane"):
            assert np.asarray(leaf[other]).tobytes() \
                == np.asarray(leaf[slot]).tobytes(), key
    eng.pool.free(other)
    assert int(eng.pool.carry["pos"][other]) == 0


# ---------------------------------------------------------------- admission


def test_a_wave_s_rows_follow_its_bucket_under_the_token_bound(lm):
    """The published bound gives the cell's 8 / 4 / 2 / 1 rows at the
    2,048 / 4,096 / 8,192 / 16,384 buckets, and the prefill is handed
    no carry."""
    eng = ServingEngine(lm, n_slots=32)
    assert eng.admitter.token_bound == 16384
    assert [eng.admitter.wave_rows(L) for L in
            (512, 2048, 4096, 8192, 16384)] == [32, 8, 4, 2, 1]
    assert eng.admitter._zero_carry() is None
