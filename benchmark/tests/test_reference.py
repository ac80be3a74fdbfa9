"""The plain GPT-2 reference against the program's own forward at a toy
size on the CPU, and the served-token check's arithmetic."""

import numpy as np

from benchmark import harness, reference


def _toy():
    import jax.numpy as jnp

    from bigdl_tpu.utils.random_gen import RNG

    cfg = harness.Cell("gpt2m-train", rehearsal=True).config
    RNG.set_seed(3)
    lm = harness.resolve(cfg["model"]["factory"])(cfg)
    lm._ensure_params()
    lm.evaluate()
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg["vocab_size"] + 1, size=(2, 64))
    return cfg, lm, jnp.asarray(tokens, jnp.int32)


def test_reference_agrees_with_the_program_in_float32():
    import jax.numpy as jnp

    from bigdl_tpu.nn.criterion_more import MaskedSoftmaxCECriterion

    cfg, lm, tokens = _toy()
    ref = reference.load_reference(cfg)
    want = lm.apply(lm.params, tokens, lm.state, training=False, rng=None)[0]
    got = ref.logits_at(lm.params, tokens[0], jnp.arange(64), cfg)
    # both are float32 on the CPU: agreement to rounding, against
    # logits of order 1
    assert np.abs(np.asarray(got) - np.asarray(want[0])).max() < 1e-5
    labels = jnp.roll(tokens, -1, axis=1)
    loss = MaskedSoftmaxCECriterion(padding_value=0).apply(
        want, labels.astype(jnp.float32))
    assert abs(float(ref.mean_cross_entropy(lm.params, tokens, labels, cfg))
               - float(loss)) < 1e-5


def test_a_token_from_the_wrong_position_fails_the_check():
    cfg, lm, tokens = _toy()
    mix = harness.Cell("gpt2m-serve-chat", rehearsal=True).traffic
    ref = reference.Reference(cfg, lm, mix)
    from benchmark.traffic import Request

    prompt = [int(t) for t in np.asarray(tokens[0, :12])]
    # the reference's own greedy continuation, teacher-forced
    out = []
    for _ in range(6):
        seq = np.ones((ref.ref_len,), np.int32)
        seq[:len(prompt) + len(out)] = prompt + out
        at = np.zeros((ref.new_max,), np.int32)
        at[0] = len(prompt) + len(out) - 1
        out.append(int(ref._logits(seq, at)[0].argmax()) + 1)
    schedule = [Request(0.0, prompt, 6, None)]
    good = ref.check(schedule, {0: np.asarray(out)}, seed=0)
    assert good["ok"] and good["worst_logit_shortfall"] == 0.0
    shifted = ref.check(schedule, {0: np.asarray(out[1:] + out[:1])}, seed=0)
    assert not shifted["ok"]
    assert shifted["worst_logit_shortfall"] > 0.5 * shifted["logit_spread"]


def test_resnet50_reference_agrees_with_the_program_in_training_mode():
    import jax.numpy as jnp

    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.utils.random_gen import RNG

    cfg = harness.Cell("resnet50-train").config
    RNG.set_seed(3)
    model = harness.resolve(cfg["model"]["factory"])(cfg)
    model._ensure_params()
    ref = reference.load_reference(cfg)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((2, 3, 224, 224)), jnp.float32)
    labels = jnp.asarray(rng.integers(1, 1001, size=(2,)), jnp.int32)
    import jax

    want = jax.jit(lambda p, x: model.apply(
        p, x, model.state, training=True, rng=None)[0])(model.params, images)
    got = jax.jit(ref.logits)(model.params, images)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    loss = CrossEntropyCriterion().apply(want, labels)
    assert abs(float(ref.mean_cross_entropy(model.params, images, labels,
                                            cfg)) - float(loss)) < 1e-4
