"""The ``afmoe`` family (models/afmoe.py, parallel/moe.py's serving layer)
against the plain reference's equations
(benchmark/configs/afmoe_reference.py), at a toy size with seeded random
weights: the whole-sequence forward, the padded prefill with rings that
wrap, prefill -> pool -> decode through ``ServingEngine``, the shares of
the experts, routing, the pool's contracts for leaves of two lengths,
and admission's waves under a token bound.

Tolerances compare LOGITS, each as a share of the logits' standard
deviation (~1 at this size): float32 round-off over 3 layers reads ~3e-6
of it, bfloat16 anywhere in the path ~1e-2 where no top-k choice flips
and about the whole of it where one does (see the bfloat16 tests).
"""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import afmoe, decoder_ops
from bigdl_tpu.models.afmoe import AfmoeLM
from bigdl_tpu.parallel.moe import route_top_k, routed_experts
from bigdl_tpu.serving import SamplingParams, ServingEngine
from bigdl_tpu.serving.kv_pool import leaf_kind
from bigdl_tpu.serving.sampling import make_knob_rows
from bigdl_tpu.utils.random_gen import RNG

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: the cell's layer kinds at the least depth that has them all (a dense
#: sliding layer, a full and a sliding expert layer), 4 of 16 experts
#: held, top-2, a window of 16 in a cache window of 64
TOY = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=4, num_experts_per_tok=2, num_shared_experts=1,
    route_norm=True, route_scale=2.448, score_func="sigmoid",
    sliding_window=16,
    layer_types=["sliding_attention", "full_attention",
                 "sliding_attention"],
    rms_norm_eps=1e-5, rope_theta=10000, mup_enabled=True,
    expert_share={"index": 1, "of": 4})
MAX_LEN, WINDOW = 64, 16
#: float32 program against the float32 reference, as a share of the
#: logits' standard deviation: round-off of sums of at most 128 terms
#: over 3 layers reads ~3e-6; bfloat16 anywhere reads ~1e-2
F32_OF_STD = 2e-5
#: log-probs sit near -log(512) = -6.24, where one float32 ulp is
#: 4.8e-7: two roundings (log-softmax here, and in the reference)
LOGP_ATOL = 2e-6


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "afmoe_reference",
        ROOT / "benchmark" / "configs" / "afmoe_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load_reference()


def _model(seed, config=TOY, **kw):
    RNG.set_seed(seed)
    model = AfmoeLM(config, max_len=MAX_LEN, **kw)
    model.evaluate()
    model._ensure_params()
    return model


@pytest.fixture(scope="module")
def lm():
    return _model(11)


def _tokens(seed, *shape):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"] + 1, size=shape)


_REF_FNS = {}


def _ref_logits(params, seq, config=TOY):
    """The reference's logits at every position of ``seq``: one compiled
    forward a configuration, over the sequence padded to the cache
    window (causal: what follows a position does not reach it)."""
    key = str(sorted(config.items()))
    if key not in _REF_FNS:
        _REF_FNS[key] = jax.jit(lambda p, padded: REF.logits_and_ties(
            p, padded, jnp.arange(MAX_LEN), config)[0])
    padded = np.ones((MAX_LEN,), np.int32)
    padded[:len(seq)] = seq
    return np.asarray(_REF_FNS[key](params, jnp.asarray(padded)))[:len(seq)]


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
    """The same parameters under an ``expert_bias`` that decides the
    top-k whatever the scores (experts 4 and 5, both held by share 1):
    what bfloat16 costs where no choice can flip."""
    for i in range(TOY["num_dense_layers"], TOY["num_hidden_layers"]):
        bias = -10.0 * jnp.abs(jnp.arange(16, dtype=jnp.float32) - 4.5)
        params = _with(params, ("layers", i, "moe", "router", "bias"), bias)
    return params


# ---------------------------------------------------------------- forward


def test_whole_sequence_logits_match_the_reference(lm):
    toks = _tokens(0, 2, 41)          # 41 > the window, not a block multiple
    got = np.asarray(lm.forward(toks))
    assert got.shape == (2, 41, TOY["vocab_size"])
    for row in range(2):
        want = _ref_logits(lm.params, toks[row])
        assert np.abs(got[row] - want).max() <= F32_OF_STD * want.std()


def test_queries_attend_in_blocks_over_the_span_they_can_see(monkeypatch,
                                                             lm):
    """Blocks of 8 queries over 41 positions (a padded last block, a key
    span of window + 8 on the sliding layers) give the logits of one
    block over everything."""
    toks = _tokens(1, 1, 41)
    want = _ref_logits(lm.params, toks[0])
    monkeypatch.setattr(decoder_ops, "QUERY_BLOCK", 8)
    got, _ = lm.apply(lm.params, toks)
    assert np.abs(np.asarray(got[0]) - want).max() <= F32_OF_STD * want.std()


def test_the_float32_tolerance_would_fail_bfloat16(lm):
    toks = _tokens(1, 1, 41)
    want = _ref_logits(lm.params, toks[0])
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), lm.params)
    got, _ = lm.apply(low, toks)
    err = np.abs(np.asarray(got[0], np.float32) - want).max()
    assert err > 50 * F32_OF_STD * want.std()


def test_bfloat16_forward_where_no_choice_can_flip(lm):
    """bfloat16 parameters and compute against the float32 reference ON
    THE SAME (bfloat16) PARAMETERS, the top-k pinned by the bias: every
    logit within 0.15 of the logits' standard deviation (rounding of
    activations to 8 bits over 3 layers reads ~0.05 at the worst logit
    of 512 x 41). With free routing a near-tie in the top-k flips
    between the two precisions on a few tokens in a hundred, and such a
    token's logits then differ by about the whole deviation: that is
    routing, not arithmetic, and the next test holds it."""
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                 _pinned_routing(lm.params))
    toks = _tokens(2, 1, 41)
    want = _ref_logits(low, toks[0])
    got, _ = lm.apply(low, toks)
    err = np.abs(np.asarray(got[0], np.float32) - want)
    assert err.max() <= 0.15 * want.std()


def test_bfloat16_flips_are_near_ties_of_the_reference(lm):
    """Free routing in bfloat16: wherever the program's top-k differs
    from the float32 reference's on the same input, the reference's
    k-th and (k+1)-th biased scores tie within 1% (the precision's
    resolution), never a clear choice."""
    cfg, k = lm.config, TOY["num_experts_per_tok"]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((512, 64)), jnp.float32)
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))
    router = lm.params["layers"][1]["moe"]["router"]
    sel32, _ = route_top_k(x, router["w"], router["bias"], k)
    sel16, _ = route_top_k(x.astype(jnp.bfloat16),
                           router["w"].astype(jnp.bfloat16),
                           router["bias"], k)
    scores = np.sort(np.asarray(jax.nn.sigmoid(x @ router["w"])), -1)
    margin = scores[:, -k] - scores[:, -k - 1]
    differ = (np.sort(np.asarray(sel32), -1)
              != np.sort(np.asarray(sel16), -1)).any(-1)
    assert differ.sum() < 0.1 * len(differ)
    assert (margin[differ] < 0.01).all()
    assert cfg.router_experts == 16


def test_tie_distance_is_the_logit_change_that_moves_a_held_expert():
    """The reference's tie distance of a token, by hand: the biased
    scores of 8 experts, top-2, experts 2..3 held. A selected held
    expert leaves at the runner-up's biased score, an unselected one
    enters at the 2nd's; an expert whose bias keeps it out whatever its
    score is infinitely far; the distance is in units of the logits'
    deviation."""
    logits = np.array([[2.0, 1.5, 1.4, -1.0, 0.0, 0.1, -0.3, 0.2],
                       [0.3, -0.2, 0.1, 2.5, 1.0, 1.1, 0.9, -1.0]], np.float32)
    bias = np.zeros(8, np.float32)
    bias[3] = -2.0                     # expert 3 can never be selected
    cfg = dict(TOY, num_experts=2, num_experts_per_tok=2,
               expert_share={"index": 1, "of": 4})
    eye = jnp.eye(8, dtype=jnp.float32)
    layer = {"router": {"w": eye, "bias": jnp.asarray(bias)},
             "shared": {n: jnp.zeros(s) for n, s in (
                 ("gate", (8, 4)), ("up", (8, 4)), ("down", (4, 8)))},
             "experts": {n: jnp.zeros((2,) + s) for n, s in (
                 ("gate", (8, 4)), ("up", (8, 4)), ("down", (4, 8)))}}
    _, tie = REF._moe(layer, jnp.asarray(logits), cfg)
    # token 0: experts 0, 1 selected; held expert 2 (1.4) enters at
    # expert 1's 1.5. token 1: expert 3's bias keeps it out (its biased
    # score is under 0 < every other), so only held expert 2 (0.1)
    # counts: it enters at the 2nd best's 1.0 (4 and 5 are selected)
    want = np.array([0.1 / logits[0].std(), (1.0 - 0.1) / logits[1].std()])
    np.testing.assert_allclose(np.asarray(tie), want, rtol=1e-5)


def test_a_position_under_the_tie_margin_is_not_judged(lm, monkeypatch):
    """``logits_at`` answers with zeros exactly where the tie distance
    is under ``TIE_MARGIN`` (no shortfall and no spread for the
    benchmark's comparison) and with the logits everywhere else."""
    toks = jnp.asarray(_tokens(7, MAX_LEN))
    at = jnp.arange(MAX_LEN)
    logits, tie = (np.asarray(a) for a in
                   REF.logits_and_ties(lm.params, toks, at, TOY))
    assert np.isfinite(tie).all() and (tie > 0).all()
    monkeypatch.setattr(REF, "TIE_MARGIN", float(np.median(tie)))
    got = np.asarray(REF.logits_at(lm.params, toks, at, TOY))
    left_out = tie < np.median(tie)
    assert left_out.sum() == MAX_LEN // 2
    assert (got[left_out] == 0).all()
    np.testing.assert_array_equal(got[~left_out], logits[~left_out])
    monkeypatch.setattr(REF, "TIE_MARGIN", 0.0)
    np.testing.assert_array_equal(
        np.asarray(REF.logits_at(lm.params, toks, at, TOY)), logits)


def test_bfloat16_misses_lie_under_the_tie_margin():
    """Free routing in bfloat16 against the float32 reference on the
    same parameters, at the least size where flips show (the cell's five
    layer kinds, top-4 of 256 with 32 held, hidden 128, 512 tokens):
    every position whose greedy token misses the benchmark's slack (0.06
    of the spread) has a tie distance under the reference's
    ``TIE_MARGIN``, so the positions it judges all pass, those that
    attend over flipped tokens included; and there ARE misses, so the
    margin is doing the work."""
    T = 512
    mid = dict(TOY, vocab_size=4096, hidden_size=128, intermediate_size=512,
               moe_intermediate_size=128, num_hidden_layers=5,
               num_attention_heads=8, head_dim=32, num_experts=32,
               num_experts_per_tok=4, sliding_window=128,
               layer_types=["sliding_attention"] * 3
               + ["full_attention", "sliding_attention"],
               expert_share={"index": 0, "of": 8})
    RNG.set_seed(1)
    low = AfmoeLM(mid, max_len=T, param_dtype="bfloat16")
    low.evaluate()
    low._ensure_params()
    toks = np.random.default_rng(1).integers(1, 4097, size=(1, T))
    got = np.asarray(jax.jit(low.apply)(low.params, toks)[0][0], np.float32)
    want, tie = (np.asarray(a) for a in jax.jit(
        lambda p, t: REF.logits_and_ties(p, t, jnp.arange(T), mid))(
            low.params, jnp.asarray(toks[0])))
    short = want.max(-1) - want[np.arange(T), got.argmax(-1)]
    slack = 0.06 * np.mean(want.max(-1) - np.median(want, -1))
    misses = short > slack
    judged = tie >= REF.TIE_MARGIN
    assert misses.sum() >= 3
    assert tie[misses].max() < REF.TIE_MARGIN
    assert judged.mean() > 0.4
    # as the comparison reads it: the slack shrinks with the share judged
    assert short[judged].max() < 0.5 * slack * judged.mean()


def test_parameters_are_created_in_the_stated_dtype():
    low = _model(5, param_dtype="bfloat16")
    leaves = jax.tree_util.tree_leaves(low.params)
    # expert_bias is a float32 buffer, everything else as stated
    assert {leaf.dtype.name for leaf in leaves} == {"bfloat16", "float32"}
    assert all(leaf.shape == (16,) for leaf in leaves
               if leaf.dtype == jnp.float32)
    assert low.grad_params is None        # the family does not train
    moe = low.params["layers"][1]["moe"]
    assert moe["experts"]["gate"].shape == (4, 64, 32)    # the 4 held
    assert moe["router"]["w"].shape == (64, 16)           # routes over all
    assert not np.asarray(moe["router"]["bias"]).any()
    assert "moe" not in low.params["layers"][0]           # the dense one
    # the engine takes them as they are: no second copy
    eng = ServingEngine(low, n_slots=2, compute_dtype=jnp.bfloat16)
    for mine, theirs in zip(jax.tree_util.tree_leaves(eng.params), leaves):
        assert mine is theirs


# ------------------------------------------------ what each mechanism does


def _drop_rope(monkeypatch):
    monkeypatch.setattr(afmoe, "rope", lambda x, pos, theta: x)


MECHANISMS = {
    "gate": lambda p: _with(p, ("layers", 2, "attn", "wg"),
                            jnp.zeros_like(p["layers"][2]["attn"]["wg"])),
    "q_norm": lambda p: _with(p, ("layers", 2, "attn", "q_norm"),
                              3.0 * p["layers"][2]["attn"]["q_norm"]),
    "k_norm": lambda p: _with(p, ("layers", 2, "attn", "k_norm"),
                              3.0 * p["layers"][2]["attn"]["k_norm"]),
    "input_norm": lambda p: _with(p, ("layers", 2, "input_norm"),
                                  3.0 * p["layers"][2]["input_norm"]),
    "post_attn_norm": lambda p: _with(
        p, ("layers", 2, "post_attn_norm"),
        3.0 * p["layers"][2]["post_attn_norm"]),
    "pre_mlp_norm": lambda p: _with(p, ("layers", 2, "pre_mlp_norm"),
                                    3.0 * p["layers"][2]["pre_mlp_norm"]),
    "post_mlp_norm": lambda p: _with(p, ("layers", 2, "post_mlp_norm"),
                                     3.0 * p["layers"][2]["post_mlp_norm"]),
}


@pytest.mark.parametrize("name", sorted(MECHANISMS))
def test_each_mechanism_shows_in_the_logits(lm, name):
    """The gate (a zero ``W_g`` gates by one half), the two head norms
    and the four layer norms (a weight of 3: an RMSNorm that was left
    out would not see it) each move the logits, in the program and the
    reference alike."""
    toks = _tokens(4, 1, 24)
    base = _ref_logits(lm.params, toks[0])
    changed = MECHANISMS[name](lm.params)
    want = _ref_logits(changed, toks[0])
    assert np.abs(want - base).max() > 0.05 * base.std()
    got, _ = lm.apply(changed, toks)
    assert np.abs(np.asarray(got[0]) - want).max() <= F32_OF_STD * want.std()


def test_sliding_layers_rotate_and_full_layers_do_not(monkeypatch, lm):
    """With the rotary embedding taken out of the program, a model whose
    layers are all FULL computes the same logits (they never rotate)
    and one with sliding layers does not."""
    toks = _tokens(5, 1, 24)
    full = dict(TOY, layer_types=["full_attention"] * 3)
    want_full = _ref_logits(lm.params, toks[0], full)
    want = _ref_logits(lm.params, toks[0])
    _drop_rope(monkeypatch)
    got, _ = lm.apply(lm.params, toks)
    assert np.abs(np.asarray(got[0]) - want).max() > 0.05 * want.std()
    RNG.set_seed(11)
    plain = AfmoeLM(full, max_len=MAX_LEN)
    got, _ = plain.apply(lm.params, toks)
    assert np.abs(np.asarray(got[0]) - want_full).max() \
        <= F32_OF_STD * want_full.std()
    # and a full layer sees past the window: the two models differ
    assert np.abs(want_full - want).max() > 0.05 * want.std()


# ------------------------------------------------------------------ routing


def _moe_inputs(seed, T=50, d=16, f=24, n_all=16):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    router = {"w": jnp.asarray(rng.standard_normal((d, n_all)), jnp.float32),
              "bias": jnp.asarray(0.3 * rng.standard_normal((n_all,)),
                                  jnp.float32)}
    experts = {n: jnp.asarray(0.3 * rng.standard_normal((n_all,) + s),
                              jnp.float32)
               for n, s in (("gate", (d, f)), ("up", (d, f)),
                            ("down", (f, d)))}
    return x, router, experts


def _dense_expert(experts, e, x):
    p = {n: np.asarray(v[e], np.float64) for n, v in experts.items()}
    x = np.asarray(x, np.float64)
    g = x @ p["gate"]
    return (g / (1 + np.exp(-g)) * (x @ p["up"])) @ p["down"]


def test_top_k_selects_by_biased_and_weighs_by_unbiased_scores():
    """Top-4 of 256 with ties (equal columns: the lower index wins),
    ``route_norm`` and ``route_scale``, against numpy."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    w = rng.standard_normal((32, 256)).astype(np.float32)
    w[:, 100:104] = w[:, 7:8]                 # five experts always tie
    bias = (0.2 * rng.standard_normal((256,))).astype(np.float32)
    bias[100:104] = bias[7]
    sel, weights = route_top_k(x, jnp.asarray(w), jnp.asarray(bias), 4,
                               route_norm=True, route_scale=2.448)
    scores = 1 / (1 + np.exp(-(np.asarray(x, np.float64) @ w)))
    order = np.argsort(-(scores + bias), axis=-1, kind="stable")[:, :4]
    assert (np.asarray(sel) == order).all()
    tied = np.isin(np.asarray(sel), [7, 100, 101, 102, 103])
    assert tied.any()                           # the tie was in play
    raw = np.take_along_axis(scores, order, -1)
    want = raw / (raw.sum(-1, keepdims=True) + 1e-20) * 2.448
    np.testing.assert_allclose(np.asarray(weights), want, rtol=2e-6)
    unscaled = route_top_k(x, jnp.asarray(w), jnp.asarray(bias), 4,
                           route_norm=False)[1]
    np.testing.assert_allclose(np.asarray(unscaled), raw, rtol=2e-6)


@pytest.mark.parametrize("block_rows", [None, 2, 8])
def test_held_experts_give_their_part_and_count_their_tokens(block_rows):
    """Every share of 4 held experts: the layer's result is the held
    experts' weighted outputs for the tokens that chose them, the
    counts are numpy's, padding tokens are routed nowhere."""
    x, router, experts = _moe_inputs(1)
    valid = np.ones((50,), bool)
    valid[[3, 17, 40]] = False
    sel, w = map(np.asarray, route_top_k(
        x, router["w"], router["bias"], 2, route_scale=2.448))
    for held in (0, 4, 12):
        mine = {n: v[held:held + 4] for n, v in experts.items()}
        y, counts = jax.jit(lambda x: routed_experts(
            x, router, mine, held, 2, valid=jnp.asarray(valid),
            route_scale=2.448, block_rows=block_rows))(x)
        want = np.zeros((50, 16))
        for e in range(held, held + 4):
            chose = ((sel == e) * w).sum(-1) * valid
            want += chose[:, None] * _dense_expert(experts, e, x)
        assert np.abs(np.asarray(y) - want).max() <= 1e-5 * np.abs(want).max()
        assert np.asarray(counts).tolist() == [
            int(((sel == e).any(-1) & valid).sum())
            for e in range(held, held + 4)]


def test_no_token_is_dropped_at_any_load():
    """Every token on ONE expert (a bias no score can beat): all 50 are
    computed; and under the same load the layer of a chip that holds
    none of the chosen experts gives zeros."""
    x, router, experts = _moe_inputs(2)
    router = dict(router, bias=router["bias"].at[5].set(100.0))
    mine = {n: v[4:8] for n, v in experts.items()}
    y, counts = routed_experts(x, router, mine, 4, 1)
    sel, w = route_top_k(x, router["w"], router["bias"], 1)
    assert (np.asarray(sel) == 5).all()
    assert np.asarray(counts).tolist() == [0, 50, 0, 0]
    want = np.asarray(w) * _dense_expert(experts, 5, x)
    assert np.abs(np.asarray(y) - want).max() <= 1e-5 * np.abs(want).max()
    other, none = routed_experts(
        x, router, {n: v[8:12] for n, v in experts.items()}, 8, 1)
    assert not np.asarray(other).any() and not np.asarray(none).any()


def test_the_shares_add_up_to_the_uncut_layer(lm):
    """The routed parts that all 4 shares give, plus the shared expert
    counted once, equal the uncut reference's MoE layer (a non-zero
    ``expert_bias``: selection by biased, weighting by unbiased
    scores)."""
    rng = np.random.default_rng(6)
    m = jnp.asarray(rng.standard_normal((30, 64)), jnp.float32)
    layer = lm.params["layers"][2]["moe"]
    router = dict(layer["router"], bias=jnp.asarray(
        0.2 * rng.standard_normal((16,)), jnp.float32))
    all_experts = {n: jnp.asarray(0.02 * rng.standard_normal(
        (16,) + v.shape[1:]), jnp.float32)
        for n, v in layer["experts"].items()}
    uncut = dict(TOY, num_experts=16, expert_share={"index": 0, "of": 1})
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF._moe(
            {"router": router, "shared": layer["shared"],
             "experts": all_experts}, m, uncut)[0])
        shared = np.asarray(REF._swiglu(layer["shared"], m))
    total = shared.copy()
    for index in range(4):
        mine = {n: v[4 * index:4 * index + 4]
                for n, v in all_experts.items()}
        part, _ = routed_experts(m, router, mine, 4 * index, 2,
                                 route_scale=TOY["route_scale"])
        total += np.asarray(part)
        # and each share alone is the reference's with that share
        cut = dict(TOY, expert_share={"index": index, "of": 4})
        with jax.default_matmul_precision("highest"):
            alone = np.asarray(REF._moe(
                {"router": router, "shared": layer["shared"],
                 "experts": mine}, m, cut)[0])
        assert np.abs(shared + np.asarray(part) - alone).max() \
            <= 1e-5 * np.abs(alone).max()
    assert np.abs(total - want).max() <= 1e-5 * np.abs(want).max()


# ----------------------------------------------------------- padded prefill


def _prefill(lm, toks, lengths, dtype=None):
    fam = lm.serving_family()
    logp, rows = fam.batch_prefill_step(dtype)(
        fam.params(dtype), jnp.asarray(toks - 1),
        np.asarray(lengths, np.int32))
    return np.asarray(logp), jax.tree_util.tree_map(np.asarray, rows)


def test_padded_bucket_rows_fill_rings_with_their_last_window(lm):
    """Rows of unequal length and a ballast row in a bucket of 32, twice
    the window: the last logits are the reference's; the full layer's
    leaf holds the row's keys at 0..n-1 and zeros beyond; a sliding
    layer's leaf is a ring of 16 that holds position p at p % 16 for the
    row's LAST 16 positions; each row is what it is alone in the
    bucket."""
    L, lengths = 32, [29, 5, 0, 32]
    toks = _tokens(7, len(lengths), L)
    logp, rows = _prefill(lm, toks, lengths)
    assert rows["pos"].tolist() == lengths
    assert rows["k1"].shape == (4, 32, 32) and rows["k0"].shape == (4, 16, 32)
    for r, n in enumerate(lengths):
        if n == 0:
            assert not any(leaf[r].any() for key, leaf in rows.items()
                           if leaf_kind(key) == "kv")
            continue
        want = _ref_logits(lm.params, toks[r, :n])[-1]
        assert np.abs(logp[r] - (want - np.log(np.exp(want).sum()))).max() \
            <= LOGP_ATOL
        alone = np.ones_like(toks)
        alone[0] = toks[r]
        _, own = _prefill(lm, alone, [n, 0, 0, 0])
        for key, leaf in rows.items():
            if leaf_kind(key) != "kv":
                continue
            scale = np.abs(own[key]).max()
            if leaf.shape[1] == L:                       # the full layer
                assert np.abs(leaf[r, :n] - own[key][0, :n]).max() \
                    <= 1e-5 * scale, key
                assert not leaf[r, n:].any(), key
                continue
            for p in range(max(0, n - WINDOW), n):       # the ring
                assert np.abs(leaf[r, p % WINDOW]
                              - own[key][0, p % WINDOW]).max() \
                    <= 1e-5 * scale, (key, p)


def test_a_ring_holds_the_keys_of_its_positions(lm):
    """The ring a 29-token prompt leaves in a bucket of 32 holds at p %
    16 the key that a model with a window of 64 (whose leaf is not a
    ring at this bucket) keeps at position p, for the last 16 p."""
    wide = dict(TOY, sliding_window=64)
    RNG.set_seed(11)
    flat_lm = AfmoeLM(wide, max_len=MAX_LEN)
    flat_lm.params = lm.params
    toks = _tokens(8, 4, 32)
    # layer 0 is the first sliding layer: its keys depend on no window
    _, ring = _prefill(lm, toks, [29, 0, 0, 0])
    _, flat = _prefill(flat_lm, toks, [29, 0, 0, 0])
    for p in range(13, 29):
        np.testing.assert_array_equal(ring["k0"][0, p % 16],
                                      flat["k0"][0, p])
        np.testing.assert_array_equal(ring["v0"][0, p % 16],
                                      flat["v0"][0, p])


def test_prefill_refuses_lengths_outside_the_bucket(lm):
    with pytest.raises(ValueError, match="lengths"):
        _prefill(lm, _tokens(3, 4, 8), [9, 1, 0, 0])


# ------------------------------------------------- decode against the pool


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
    """The ballast contract over leaves of two lengths, and the step's
    fourth result: the token counts of the ACTIVE rows only."""
    fam = lm.serving_family()
    step, _ = fam.decode_step()
    carry = _random_carry(lm, 4, seed=6)
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
    assert counts.shape == (2, 4)            # expert layers x held
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


def test_engine_serves_the_reference_distribution_through_wrapped_rings(lm):
    """prefill -> pool -> decode through ServingEngine with rings that
    wrap in the prefill (prompts of 23, 33 and 40 against a window of
    16) and in the decode (every row passes 16; one decodes to 60 of the
    64, past max_len / 2): greedy rows emit the reference's argmax and
    every chosen log-prob is the reference's, teacher-forced on the
    served tokens. Six requests through four slots: slots are reused."""
    jobs = [(_tokens(20, 6), 20, None), (_tokens(21, 23), 20, None),
            (_tokens(22, 40), 20, None), (_tokens(23, 1), 18, None),
            (_tokens(24, 33), 12, SamplingParams(temperature=0.8, top_k=5,
                                                 seed=4)),
            (_tokens(25, 9), 20, None)]
    eng, served = _served(lm, jobs, n_slots=4)
    _check_against_reference(lm, jobs, served)
    assert eng.pool.max_len == MAX_LEN               # not k0's 16
    assert eng.pool.carry["k0"].shape[1] == WINDOW
    assert eng.pool.free_slots == 4
    # one prefill program a bucket, four rows each (no bound reached)
    assert sorted(eng.admitter.traced_shapes) == [(4, 8), (4, 32), (4, 64)]


def test_a_long_request_decodes_past_half_the_cache_window(lm):
    jobs = [(_tokens(26, 9), 54, None)]
    _, served = _served(lm, jobs, n_slots=4)
    _check_against_reference(lm, jobs, served)


def test_bfloat16_serving_stays_within_the_served_slack_without_flips():
    """bfloat16 parameters and compute through the engine, the top-k
    pinned (see the forward's bfloat16 test): every served token's
    reference logit is within 0.03 of the logits' spread of the best
    one, half of what the benchmark's comparison allows."""
    low = _model(12, param_dtype="bfloat16")
    low.params = jax.tree_util.tree_map(
        lambda a: a, _pinned_routing(low.params))
    jobs = [(_tokens(30, 21), 24, None), (_tokens(31, 30), 20, None)]
    _, served = _served(low, jobs, n_slots=2, compute_dtype=jnp.bfloat16)
    for (prompt, _, _), (out, _) in zip(jobs, served):
        seq = list(prompt) + list(out)
        logits = _ref_logits(low.params, seq[:-1])[len(prompt) - 1:]
        spread = float(np.mean(logits.max(-1) - np.median(logits, -1)))
        short = logits.max(-1) - logits[np.arange(len(out)),
                                        np.asarray(out) - 1]
        assert short.max() <= 0.03 * spread


def test_the_expert_series_and_held_bytes_ride_the_step(lm):
    """The per-expert counts are read back with the tokens (one sample
    a consumed decode step, no step of their own), and
    ``kv_held_bytes`` counts a ring at most once round."""
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
    assert (np.asarray(m.values("serving/experts_hit")) <= 8).all()
    # bytes of a position of one layer: K and V, 2 heads x 16, float32
    row = 2 * 32 * 4
    assert eng.pool.kv_held_bytes(5) == 3 * 5 * row
    assert eng.pool.kv_held_bytes(40) == (2 * 16 + 40) * row
    assert eng.pool.kv_bytes_per_slot == (2 * 16 + 64) * row
    held = np.asarray(m.values("serving/kv_held_bytes"))
    assert held.max() <= 2 * eng.pool.kv_held_bytes(40)
    assert held.max() > eng.pool.kv_held_bytes(30)
    # the kernel's block is 128 positions here and a shorter leaf is
    # padded up to one: a decoding row fetches one block a leaf
    fetched = set(m.values("serving/kv_fetched_bytes"))
    assert fetched <= {3 * 128 * row, 2 * 3 * 128 * row} and fetched


def test_the_consume_span_notes_the_experts_hit_of_its_dispatch(
        lm, monkeypatch):
    """``serving.consume(seq=)`` notes what its dispatch read: the held
    experts that got a token, beside the bytes held and fetched, the
    values the three series gained at the same read-back."""
    import bigdl_tpu.optim.metrics as metrics_mod

    notes = []

    class _Ann:
        def __init__(self, name, **ids):
            self.ids = dict(ids)
            if name == "serving.consume":
                notes.append(self.ids)

        def set_metadata(self, **more):
            self.ids.update(more)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(metrics_mod, "TraceAnnotation", _Ann)
    monkeypatch.setattr(metrics_mod, "StepTraceAnnotation", _Ann)
    jobs = [(_tokens(27, 30), 6, None), (_tokens(28, 7), 6, None)]
    eng, _ = _served(lm, jobs, n_slots=4)
    m = eng.metrics.metrics
    kept = [n for n in notes if "experts_hit" in n]     # not pure overshoot
    assert [n["seq"] for n in notes] == list(range(1, len(notes) + 1))
    for key, series in (("experts_hit", "experts_hit"),
                        ("kv_held", "kv_held_bytes"),
                        ("kv_fetched", "kv_fetched_bytes")):
        assert [float(n[key]) for n in kept] == m.values(f"serving/{series}")


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
    ("score_func", "softmax"), ("n_group", 2), ("topk_group", 2),
    ("num_shared_experts", 2), ("rope_scaling", {"type": "yarn"}),
    ("tie_word_embeddings", True), ("hidden_act", "gelu")])
def test_the_configuration_refuses_by_name_what_is_not_built(flag, value):
    with pytest.raises(ValueError, match=f"^{flag}="):
        afmoe.AfmoeConfig.from_dict(dict(TOY, **{flag: value}))


# ------------------------------------------------------------------ the pool


def test_pool_leaves_of_two_lengths_through_free_and_readmission(lm):
    """A slot whose last occupant wrapped its rings is freed and given
    to a SHORT request: none of the old keys is visible (the ring is
    valid up to min(pos + 1, 16) entries), its tokens are the
    reference's."""
    eng = ServingEngine(lm, n_slots=4)
    long_rid = eng.submit(_tokens(40, 38).tolist(), max_new_tokens=20)
    eng.step()
    (slot,) = eng.scheduler.running
    eng.drain()
    stale = np.asarray(eng.pool.carry["k0"][slot]).copy()
    assert np.abs(stale).max(axis=-1).min() > 0       # the ring is full
    short = _tokens(41, 7)
    rid = eng.submit(short.tolist(), max_new_tokens=8)
    eng.step()
    assert list(eng.scheduler.running) == [slot]      # the same slot
    outs = eng.drain()
    _check_against_reference(lm, [(short, 8, None)],
                             [(outs[rid], eng.logprobs(rid))])
    # the old occupant's keys are still in the ring beyond the new
    # row's positions, unseen
    now = np.asarray(eng.pool.carry["k0"][slot])
    assert np.array_equal(now[15:], stale[15:])
    assert len(outs[long_rid]) == 20


def test_row_state_round_trips_leaves_of_two_lengths(lm):
    eng = ServingEngine(lm, n_slots=4)
    eng.submit(_tokens(42, 30).tolist(), max_new_tokens=30)
    for _ in range(6):
        eng.step()
    eng.flush_window()
    (slot,) = eng.scheduler.running
    payload = eng.row_state(slot)
    assert payload["carry"]["k0"].shape == (1, WINDOW, 32)
    assert payload["carry"]["k1"].shape == (1, MAX_LEN, 32)
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
    """``rows(L)`` = the largest power of two with rows x L within the
    family's bound, at most ``n_slots``; the published bound gives the
    cell's 16 / 8 / 4 / 2 rows."""
    eng = ServingEngine(lm, n_slots=16)
    assert eng.admitter.token_bound == 16384
    assert [eng.admitter.wave_rows(L) for L in
            (256, 512, 1024, 2048, 4096, 8192, 16384, 32768)] \
        == [16, 16, 16, 8, 4, 2, 1, 1]
    assert eng.admitter._zero_carry() is None


def test_families_without_a_bound_keep_n_slots_rows():
    """GPT-2's and Falcon-H1's waves: ``n_slots`` rows whatever the
    bucket, and the shared zero carry."""
    from bigdl_tpu.models.transformer import TransformerLM

    RNG.set_seed(2)
    gpt = TransformerLM(vocab_size=64, hidden_size=16, n_heads=2,
                        n_layers=1, max_len=32)
    gpt.evaluate()
    eng = ServingEngine(gpt, n_slots=4)
    assert eng.admitter.token_bound is None
    assert [eng.admitter.wave_rows(L) for L in (1, 8, 32)] == [4, 4, 4]
    assert eng.admitter._zero_carry()["pos"].shape == (4,)


def test_more_arrivals_than_rows_prefill_in_chunks_of_one_program(
        monkeypatch, lm):
    """A bound of 64 tokens: the bucket of 32 prefills 2 rows a wave,
    the bucket of 64 one; five long prompts arrive at once and every one
    matches the reference (prompts longer than the ring, prefilled in a
    2-row wave)."""
    fam = lm.serving_family()
    monkeypatch.setattr(fam, "prefill_token_bound", 64, raising=False)
    jobs = [(_tokens(50 + i, n), 6, None)
            for i, n in enumerate((30, 25, 33, 21, 40, 7))]
    eng, served = _served(lm, jobs, n_slots=4)
    _check_against_reference(lm, jobs, served)
    assert sorted(eng.admitter.traced_shapes) == [(1, 64), (2, 32), (4, 8)]
    padded = eng.metrics.metrics.values("serving/prefill_batch_padded")
    assert sorted(padded) == [1.0, 2.0, 2.0, 4.0]
