"""TransformerLM family: shape/learning/remat/sequence-parallel behavior."""

import numpy as np
import pytest

from bigdl_tpu.utils.compat import shard_map
from tests.oracle import assert_close


def test_transformer_lm_shapes_and_causality(rng):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(1)
    m = TransformerLM(vocab_size=20, hidden_size=32, n_heads=4, n_layers=2,
                      max_len=16)
    m._ensure_params()
    m.evaluate()
    ids = (rng.randint(1, 21, size=(2, 10))).astype(np.float32)
    out = np.asarray(m.forward(ids))
    assert out.shape == (2, 10, 20)
    # causality: changing a future token must not change earlier outputs
    ids2 = ids.copy()
    ids2[:, -1] = 1 + (ids2[:, -1] % 20)
    out2 = np.asarray(m.forward(ids2))
    assert_close(out[:, :-1], out2[:, :-1], atol=1e-4)
    assert np.abs(out[:, -1] - out2[:, -1]).max() > 1e-6


@pytest.mark.integration
def test_transformer_remat_identical(rng):
    """Remat(block) computes EXACTLY what the bare block computes (forward
    and gradient) — verified by sharing one block's params across both."""
    import jax

    from bigdl_tpu.models.transformer import TransformerBlock
    from bigdl_tpu.nn import Remat
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(2)
    block = TransformerBlock(32, 4)
    block._ensure_params()
    x = rng.randn(2, 8, 32).astype(np.float32)
    a = np.asarray(block.forward(x))

    rem = Remat(block)
    rem.params = {rem._child_key(0): block.params}
    rem.state = {rem._child_key(0): {}}
    rem._ensure_params()
    rem.evaluate()
    block.evaluate()
    b = np.asarray(rem.forward(x))
    assert_close(a, b, atol=1e-6)

    ga = jax.grad(lambda p: (block.apply(p, x, {})[0] ** 2).sum())(block.params)
    gb = jax.grad(lambda p: (rem.apply(p, x, {})[0] ** 2).sum())(rem.params)
    for u, v in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        assert_close(np.asarray(u), np.asarray(v), atol=1e-5)


def test_transformer_train_main():
    from bigdl_tpu.models import transformer

    model = transformer.train_main([
        "-b", "8", "--maxIteration", "12", "--synthetic", "64",
        "--seqLen", "12", "--vocab", "30", "--hidden", "32",
        "--layers", "1", "--heads", "2",
    ])
    ws, _ = model.parameters()
    assert all(np.all(np.isfinite(np.asarray(w))) for w in ws)


def test_transformer_ring_sequence_parallel(rng):
    """The same LM with ring SP over an 8-way mesh matches the local LM."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(3)
    local = TransformerLM(16, hidden_size=16, n_heads=2, n_layers=1,
                          max_len=16, causal=True)
    local._ensure_params()
    local.evaluate()
    RNG.set_seed(3)
    sp = TransformerLM(16, hidden_size=16, n_heads=2, n_layers=1,
                       max_len=16, causal=True,
                       sequence_parallel="ring", sp_axis="seq")
    sp._ensure_params()
    sp.evaluate()

    ids = (rng.randint(1, 17, size=(2, 16))).astype(np.float32)
    # share weights so the SP model is the SAME function as the local one;
    # child keys embed instance counters, so graft by tree structure
    # (index-prefixed keys sort identically in both models)
    sp.params = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(sp.params),
        jax.tree_util.tree_leaves(local.params))
    want = np.asarray(local.forward(ids))

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("seq",))
    # sequence-sharded ids; PositionEmbedding(sp_axis="seq") offsets by
    # axis_index so positions stay global, matching ring causal offsets
    fn = jax.jit(shard_map(
        lambda p, x: sp.apply(p, x, sp.state, training=False)[0],
        mesh=mesh, in_specs=(P(), P(None, "seq")), out_specs=P(None, "seq"),
    ))
    out = np.asarray(fn(sp.params, ids))
    assert_close(out, want, atol=1e-3)


@pytest.mark.parametrize("layer_scan", [False, True])
def test_transformer_serialization_roundtrip(rng, tmp_path, layer_scan):
    """Unrolled AND ScanBlocks (stacked per-layer params) stacks survive
    the structured serializer — the Container protocol carries the
    stacked tree like any other child dict."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.nn.module import AbstractModule
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(4)
    m = TransformerLM(12, hidden_size=16, n_heads=2, n_layers=3, max_len=8,
                      layer_scan=layer_scan)
    m._ensure_params()
    m.evaluate()
    ids = (rng.randint(1, 13, size=(2, 8))).astype(np.float32)
    want = np.asarray(m.forward(ids))
    path = str(tmp_path / "lm.bigdl")
    m.save_module(path)
    m2 = AbstractModule.load_module(path)
    m2.evaluate()
    assert_close(np.asarray(m2.forward(ids)), want, atol=1e-6)


@pytest.mark.integration
def test_transformer_lm_remat_wiring(rng):
    """TransformerLM(remat=True): the Sequential/Remat key plumbing trains."""
    import jax

    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(6)
    m = TransformerLM(16, hidden_size=16, n_heads=2, n_layers=2, max_len=8,
                      remat=True)
    m._ensure_params()
    ids = (rng.randint(1, 17, size=(2, 8))).astype(np.float32)
    out = np.asarray(m.forward(ids))
    assert out.shape == (2, 8, 16) and np.all(np.isfinite(out))

    g = jax.grad(lambda p: (m.apply(p, ids, m.state, training=True,
                                    rng=jax.random.PRNGKey(0))[0] ** 2).sum())(
        m.params)
    leaves = jax.tree_util.tree_leaves(g)
    assert leaves and all(np.all(np.isfinite(np.asarray(l))) for l in leaves)
    assert any(np.abs(np.asarray(l)).sum() > 0 for l in leaves)


def test_kv_cached_decode_matches_full_forward(rng):
    """Cached single-token decoding must reproduce the full-forward
    log-probs at every position (exact KV-cache correctness)."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import TransformerLM, make_decode_step

    V, T = 23, 10
    model = TransformerLM(V, hidden_size=32, n_heads=4, n_layers=2, max_len=T)
    model._ensure_params()
    model.evaluate()

    ids = rng.randint(1, V + 1, size=(1, T)).astype(np.float32)
    full = np.asarray(model.forward(ids))        # (1, T, V)

    step, init_carry = make_decode_step(model)
    carry = init_carry(1)
    for t in range(T):
        tok = jnp.asarray([int(ids[0, t]) - 1], jnp.int32)
        logp, carry = step(None, tok, carry)
        assert_close(np.asarray(logp)[0], full[0, t], atol=2e-4,
                     msg=f"position {t}")


@pytest.mark.integration
def test_kv_cached_decode_with_remat_blocks(rng):
    from bigdl_tpu.models.transformer import TransformerLM, make_decode_step

    V, T = 11, 6
    model = TransformerLM(V, hidden_size=16, n_heads=2, n_layers=2,
                          max_len=T, remat=True)
    model._ensure_params()
    model.evaluate()
    ids = rng.randint(1, V + 1, size=(1, T)).astype(np.float32)
    full = np.asarray(model.forward(ids))
    step, init_carry = make_decode_step(model)
    carry = init_carry(1)
    import jax.numpy as jnp
    for t in range(T):
        logp, carry = step(None, jnp.asarray([int(ids[0, t]) - 1]), carry)
    assert_close(np.asarray(logp)[0], full[0, -1], atol=2e-4)


def test_beam_generate_transformer(rng):
    from bigdl_tpu.models.transformer import TransformerLM, beam_generate

    V = 17
    model = TransformerLM(V, hidden_size=16, n_heads=2, n_layers=1,
                          max_len=24)
    model._ensure_params()
    model.evaluate()
    seqs, scores = beam_generate(model, [3, 7, 2], beam_size=3,
                                 decode_length=5)
    assert seqs.shape == (3, 5)
    assert ((seqs >= 1) & (seqs <= V)).all()
    assert np.isfinite(scores).all()
    # best-first ordering
    assert scores[0] >= scores[1] >= scores[2]


def test_generate_greedy_and_sampled(rng):
    from bigdl_tpu.models.transformer import TransformerLM, generate

    V = 13
    model = TransformerLM(V, hidden_size=16, n_heads=2, n_layers=1,
                          max_len=20)
    model._ensure_params()
    model.evaluate()
    g1 = generate(model, [2, 5], length=6, temperature=0.0)
    g2 = generate(model, [2, 5], length=6, temperature=0.0)
    assert (g1 == g2).all()                    # greedy is deterministic
    assert ((g1 >= 1) & (g1 <= V)).all()
    s1 = generate(model, [2, 5], length=6, temperature=1.0, top_k=4, seed=1)
    assert ((s1 >= 1) & (s1 <= V)).all()
    # greedy must follow the argmax of the cached log-probs step by step
    from bigdl_tpu.models.transformer import make_decode_step
    import jax.numpy as jnp
    step, init_carry = make_decode_step(model)
    carry = init_carry(1)
    _, carry = step(None, jnp.asarray([1]), carry)   # prompt token 2
    logp, _ = step(None, jnp.asarray([4]), carry)    # prompt token 5
    assert g1[0] == int(np.argmax(np.asarray(logp)[0])) + 1


def test_generate_rejects_overlong_decode(rng):
    """Regression: decoding past max_len must raise, not silently clamp."""
    from bigdl_tpu.models.transformer import (
        TransformerLM, beam_generate, generate,
    )

    model = TransformerLM(9, hidden_size=16, n_heads=2, n_layers=1, max_len=8)
    model._ensure_params()
    with pytest.raises(ValueError, match="max_len"):
        generate(model, [1, 2, 3], length=10)
    with pytest.raises(ValueError, match="max_len"):
        beam_generate(model, [1, 2], beam_size=2, decode_length=8)
    # exactly at the limit is fine
    out = generate(model, [1, 2, 3], length=6, temperature=0.0)
    assert out.shape == (6,)


def test_lookup_table_matmul_grad_matches_scatter(rng):
    """grad_via_matmul computes the embedding gradient as a one-hot MXU
    matmul — must match the scatter-add backward exactly (fp32)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.misc import LookupTable

    V, D = 13, 6
    ids = rng.randint(1, V + 1, size=(4, 5)).astype(np.float32)
    ids[0, 0] = 0.0   # padding id embeds to zero, must get zero grad
    w = rng.randn(V, D).astype(np.float32)

    def loss_for(flag):
        lt = LookupTable(V, D, grad_via_matmul=flag)

        def f(wv):
            out, _ = lt.apply({"weight": wv}, jnp.asarray(ids))
            return jnp.sum(out * out)

        return jax.grad(f)(jnp.asarray(w))

    g_scatter = np.asarray(loss_for(False))
    g_matmul = np.asarray(loss_for(True))
    np.testing.assert_allclose(g_matmul, g_scatter, rtol=1e-5, atol=1e-6)
    assert abs(g_matmul).sum() > 0


def test_transformer_lm_logits_output_trains_and_decodes(rng):
    """output="logits" + MaskedSoftmaxCECriterion is the fused LM-scale
    path: one train step moves the loss, and make_decode_step still
    resolves the head (no trailing LogSoftMax)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer import make_decode_step
    from bigdl_tpu.nn.criterion_more import MaskedSoftmaxCECriterion
    from bigdl_tpu.optim.optim_method import Adam
    from bigdl_tpu.optim.train_step import make_train_step
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(5)
    V, T, B = 31, 8, 4
    lm = TransformerLM(V, hidden_size=16, n_heads=2, n_layers=2, max_len=T,
                       output="logits")
    crit = MaskedSoftmaxCECriterion(padding_value=0)
    optim = Adam(learning_rate=1e-2)
    lm._ensure_params()
    step = jax.jit(make_train_step(lm, crit, optim))
    x = jnp.asarray(rng.randint(1, V + 1, size=(B, T)).astype(np.int32))
    y = jnp.asarray(rng.randint(1, V + 1, size=(B, T)).astype(np.float32))
    params, ms = lm.params, lm.state
    opt_state = optim.init_state(params)
    key = jax.random.PRNGKey(0)
    losses = []
    for _ in range(8):
        params, opt_state, ms, loss = step(params, opt_state, ms, key, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0]

    lm.params = params
    dstep, init_carry = make_decode_step(lm)
    logp, carry = dstep(None, jnp.zeros((2,), jnp.int32), init_carry(2))
    assert logp.shape == (2, V)
    # decode head emits normalized log-probs even without the LM softmax
    np.testing.assert_allclose(np.exp(np.asarray(logp)).sum(-1), 1.0,
                               rtol=1e-4)


def test_layer_scan_matches_unrolled(rng):
    """layer_scan=True (ScanBlocks lax.scan over stacked params) computes
    EXACTLY the unrolled stack — verified by transplanting the unrolled
    model's block params into the stacked layout — and the KV-cached
    decode step resolves the scan model too."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer import ScanBlocks, make_decode_step
    from bigdl_tpu.utils.random_gen import RNG

    V, T, B, L = 23, 10, 2, 3
    RNG.set_seed(11)
    unrolled = TransformerLM(V, hidden_size=32, n_heads=4, n_layers=L,
                             max_len=T)
    unrolled._ensure_params()
    RNG.set_seed(12)
    scan = TransformerLM(V, hidden_size=32, n_heads=4, n_layers=L,
                         max_len=T, layer_scan=True)
    scan._ensure_params()
    sb = scan.modules[2]
    assert isinstance(sb, ScanBlocks)

    # transplant: unrolled blocks at Sequential indices 2..2+L; module
    # names carry a global counter so child keys must be remapped by
    # POSITION onto the scan template block's keys before stacking
    tmpl = sb.modules[0]

    def rekey(i):
        bp = unrolled.params[unrolled._child_key(2 + i)]
        blk = unrolled.modules[2 + i]
        return {tmpl._child_key(j): bp[blk._child_key(j)] for j in range(5)}

    per_layer = [rekey(i) for i in range(L)]
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_layer)
    new_p = dict(scan.params)
    new_p[scan._child_key(0)] = unrolled.params[unrolled._child_key(0)]
    new_p[scan._child_key(1)] = unrolled.params[unrolled._child_key(1)]
    new_p[scan._child_key(2)] = {sb._child_key(0): stacked}
    new_p[scan._child_key(3)] = unrolled.params[unrolled._child_key(2 + L)]
    new_p[scan._child_key(4)] = unrolled.params[unrolled._child_key(3 + L)]
    scan.params = new_p

    unrolled.evaluate()
    scan.evaluate()
    ids = rng.randint(1, V + 1, size=(B, T)).astype(np.float32)
    a, b = np.asarray(unrolled.forward(ids)), np.asarray(scan.forward(ids))
    assert_close(a, b, atol=1e-5)

    # gradients agree too (scan backward == unrolled backward)
    ga = jax.grad(lambda p: (unrolled.apply(p, ids, {})[0] ** 2).sum())(
        unrolled.params)
    gb = jax.grad(lambda p: (scan.apply(p, ids, {})[0] ** 2).sum())(
        scan.params)
    def rekey_grad(i):
        bp = ga[unrolled._child_key(2 + i)]
        blk = unrolled.modules[2 + i]
        return {tmpl._child_key(j): bp[blk._child_key(j)] for j in range(5)}

    ga_stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *[rekey_grad(i) for i in range(L)])
    for u, v in zip(
            jax.tree_util.tree_leaves(ga_stacked),
            jax.tree_util.tree_leaves(gb[scan._child_key(2)][sb._child_key(0)])):
        assert_close(np.asarray(u), np.asarray(v), atol=1e-4)

    # decode parity: the scan model's cached decode matches its forward
    dstep, init_carry = make_decode_step(scan)
    toks = rng.randint(1, V + 1, size=(1, 5)).astype(np.float32)
    full = np.asarray(scan.forward(toks))
    carry = init_carry(1)
    outs = []
    for t in range(5):
        logp, carry = dstep(None, jnp.asarray([int(toks[0, t]) - 1],
                                              jnp.int32), carry)
        outs.append(np.asarray(logp)[0])
    assert_close(np.stack(outs), full[0], atol=1e-4)


def test_layer_scan_with_remat(rng):
    """ScanBlocks composes with Remat (checkpoint-inside-scan — the
    long-context memory recipe): forward matches the bare scan model."""
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.utils.random_gen import RNG

    V, T = 17, 8
    RNG.set_seed(21)
    plain = TransformerLM(V, hidden_size=16, n_heads=2, n_layers=2,
                          max_len=T, layer_scan=True)
    plain._ensure_params()
    RNG.set_seed(21)
    remat = TransformerLM(V, hidden_size=16, n_heads=2, n_layers=2,
                          max_len=T, layer_scan=True, remat=True)
    remat._ensure_params()
    ids = rng.randint(1, V + 1, size=(2, T)).astype(np.float32)
    plain.evaluate()
    remat.evaluate()
    a = np.asarray(plain.forward(ids))
    b = np.asarray(remat.forward(ids))
    # same seed, but the Remat wrapper adds a child-key level; compare
    # only shapes/finiteness here — exact parity is the unrolled test's job
    assert a.shape == b.shape and np.isfinite(b).all()


def test_flash_block_knob_validates_and_matches(rng):
    """flash_block must reject non-128-multiples and, when valid, compute
    the same attention as the dense path (interpret-mode Pallas on CPU)."""
    import pytest as _pytest

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.nn.attention import MultiHeadAttention
    from bigdl_tpu.utils.random_gen import RNG

    with _pytest.raises(ValueError, match="multiple of 128"):
        MultiHeadAttention(32, 4, flash_block=100)

    V, T = 19, 128
    RNG.set_seed(31)
    flash = TransformerLM(V, hidden_size=32, n_heads=4, n_layers=1,
                          max_len=T, use_flash="always", flash_block=128)
    flash._ensure_params()
    RNG.set_seed(31)
    dense = TransformerLM(V, hidden_size=32, n_heads=4, n_layers=1,
                          max_len=T, use_flash="never")
    dense._ensure_params()
    ids = rng.randint(1, V + 1, size=(1, T)).astype(np.float32)
    flash.evaluate()
    dense.evaluate()
    a = np.asarray(flash.forward(ids))
    b = np.asarray(dense.forward(ids))
    assert_close(a, b, atol=2e-3)


def test_decode_step_bf16_and_weight_only_int8(rng):
    """Serving paths of make_decode_step: compute_dtype=bf16 tracks the
    fp32 decode closely, and a weight_only-quantized LM decodes through
    the same step (int8 dequant projections) matching ITS full forward."""
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer import make_decode_step
    from bigdl_tpu.nn.quantized import Quantizer
    from bigdl_tpu.utils.random_gen import RNG

    V, T = 27, 12
    RNG.set_seed(41)
    lm = TransformerLM(V, hidden_size=32, n_heads=4, n_layers=2, max_len=T)
    lm._ensure_params()
    lm.evaluate()
    toks = rng.randint(1, V + 1, size=(1, 6)).astype(np.float32)

    # bf16 serving dtype ~ fp32 decode
    d32, ic32 = make_decode_step(lm)
    dbf, icbf = make_decode_step(lm, compute_dtype=jnp.bfloat16)
    c32, cbf = ic32(1), icbf(1)
    assert cbf["k0"].dtype == jnp.bfloat16
    for t in range(6):
        tok = jnp.asarray([int(toks[0, t]) - 1], jnp.int32)
        l32, c32 = d32(None, tok, c32)
        lbf, cbf = dbf(None, tok, cbf)
    assert_close(np.asarray(l32), np.asarray(lbf), atol=0.15)
    # ranking preserved at bf16 for the top token
    assert np.asarray(l32).argmax() == np.asarray(lbf).argmax()

    # weight-only int8: decode matches the quantized model's own forward
    qlm = Quantizer.quantize(lm, scheme="weight_only")
    full = np.asarray(qlm.forward(toks))
    dq, icq = make_decode_step(qlm)
    cq = icq(1)
    outs = []
    for t in range(6):
        logp, cq = dq(None, jnp.asarray([int(toks[0, t]) - 1], jnp.int32),
                      cq)
        outs.append(np.asarray(logp)[0])
    # the quantized forward emits logprobs through LogSoftMax
    assert_close(np.stack(outs), full[0], atol=2e-3)


def test_decode_step_runtime_params_match_captured(rng):
    """step(params, ...) with the serving-params tree must equal
    step(None, ...) (captured constants) — the runtime-argument mode is
    how serving avoids baking weights into the compiled program."""
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer import make_decode_step, serving_params
    from bigdl_tpu.utils.random_gen import RNG

    V, T = 21, 10
    RNG.set_seed(51)
    lm = TransformerLM(V, hidden_size=32, n_heads=4, n_layers=2, max_len=T)
    lm._ensure_params()
    step, init_carry = make_decode_step(lm, compute_dtype=jnp.bfloat16)
    P = serving_params(lm, jnp.bfloat16)
    c_none, c_p = init_carry(1), init_carry(1)
    toks = rng.randint(1, V + 1, size=(5,))
    for t in toks:
        tok = jnp.asarray([int(t) - 1], jnp.int32)
        l_none, c_none = step(None, tok, c_none)
        l_p, c_p = step(P, tok, c_p)
    np.testing.assert_array_equal(np.asarray(l_none), np.asarray(l_p))


def test_prefill_matches_sequential_decode(rng):
    """make_prefill_step must leave the carry EXACTLY where P sequential
    decode steps leave it (same K/V, same pos, same last-token logprobs)
    — for plain, bf16-serving, and weight-only-int8 models."""
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer import (
        make_decode_step, make_prefill_step, serving_params,
    )
    from bigdl_tpu.nn.quantized import Quantizer
    from bigdl_tpu.utils.random_gen import RNG

    V, T, P, B = 19, 16, 7, 2
    RNG.set_seed(71)
    lm = TransformerLM(V, hidden_size=32, n_heads=4, n_layers=2, max_len=T)
    lm._ensure_params()
    lm.evaluate()
    cases = [(lm, None, 1e-5), (lm, jnp.bfloat16, 0.1),
             (Quantizer.quantize(lm, scheme="weight_only"), None, 1e-5)]
    toks = rng.randint(0, V, size=(B, P)).astype(np.int32)
    for model, dtype, atol in cases:
        step, init_carry = make_decode_step(model, compute_dtype=dtype)
        prefill = make_prefill_step(model, compute_dtype=dtype)
        Pp = serving_params(model, dtype)

        c_seq = init_carry(B)
        for t in range(P):
            l_seq, c_seq = step(Pp, jnp.asarray(toks[:, t]), c_seq)
        l_pre, c_pre = prefill(Pp, jnp.asarray(toks), init_carry(B))

        np.testing.assert_array_equal(np.asarray(c_pre["pos"]),
                                      np.asarray(c_seq["pos"]))
        for key in c_seq:
            if key == "pos":
                continue
            assert_close(np.asarray(c_pre[key], np.float32),
                         np.asarray(c_seq[key], np.float32), atol=atol,
                         msg=f"{key} dtype={dtype}")
        assert_close(np.asarray(l_pre), np.asarray(l_seq), atol=max(atol, 1e-4))
        # and decoding CONTINUES identically from the prefilled carry
        nxt = jnp.asarray(toks[:, 0])
        l1, _ = step(Pp, nxt, c_pre)
        l2, _ = step(Pp, nxt, c_seq)
        assert_close(np.asarray(l1), np.asarray(l2), atol=max(atol, 1e-4))


@pytest.mark.parametrize("program", ["prefill", "batch_prefill", "decode",
                                     "batch_decode", "batch_verify"])
def test_serving_programs_share_one_block(program, monkeypatch):
    """Every serving program of the GPT-2 family runs its layers through
    the ONE ``transformer._block``: tracing a program of a 2-layer model
    calls it exactly twice. A factory that inlines its own
    LayerNorm → projections → MLP again calls it less often and fails
    here."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM, transformer
    from bigdl_tpu.serving.sampling import make_knob_rows

    V, N, L = 19, 3, 4
    lm = TransformerLM(V, hidden_size=32, n_heads=4, n_layers=2, max_len=16)
    calls = []
    real_block = transformer._block

    def counting_block(*args):
        calls.append(args[2])                    # the layer index
        return real_block(*args)

    monkeypatch.setattr(transformer, "_block", counting_block)
    params = transformer.serving_params(lm)
    tokens, rows = jnp.zeros((N, L), jnp.int32), jnp.ones((N,), jnp.int32)
    knobs = make_knob_rows(N)
    if program == "prefill":
        _, init_carry = transformer.make_decode_step(lm)
        fn = transformer.make_prefill_step(lm)._jitted
        args = (params, tokens, init_carry(N))
    elif program == "batch_prefill":
        _, init_carry = transformer.make_batch_decode_step(lm)
        fn = transformer.make_batch_prefill_step(lm)._jitted
        args = (params, tokens, rows, init_carry(N))
    elif program == "decode":
        fn, init_carry = transformer.make_decode_step(lm)
        args = (params, tokens[:, 0], init_carry(N))
    elif program == "batch_decode":
        fn, init_carry = transformer.make_batch_decode_step(lm,
                                                            sampling=True)
        args = (params, tokens[:, 0], rows > 0, init_carry(N), knobs)
    else:
        fn, init_carry = transformer.make_batch_verify_step(lm, width=L)
        args = (params, tokens, rows, init_carry(N), knobs)
    jax.eval_shape(fn, *args)
    assert calls == [0, 1]
