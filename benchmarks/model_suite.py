"""Per-config training-throughput suite on the local chip.

Measures the BASELINE.json target configs (and the TransformerLM extension)
with the same jitted-train-step methodology as `bench.py` (which stays the
driver's single-line ResNet-50 north-star). Results are recorded in
`BASELINE.md`.

    python -m benchmarks.model_suite
"""

from __future__ import annotations

import json
import time

import numpy as np


def _measure(model, criterion, optim, x, y, iters=10, compute_dtype=None):
    import jax

    from bigdl_tpu.optim.train_step import make_train_step
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(7)
    model._ensure_params()
    kw = {}
    if compute_dtype is not None:
        kw["compute_dtype"] = compute_dtype
    step = jax.jit(make_train_step(model, criterion, optim, **kw),
                   donate_argnums=(0, 1))
    params, ms = jax.device_put(model.params), model.state
    opt_state = jax.device_put(optim.init_state(params))
    rng = jax.random.PRNGKey(0)
    x, y = jax.device_put(x), jax.device_put(y)
    params, opt_state, ms, loss = step(params, opt_state, ms, rng, x, y)
    for _ in range(2):
        params, opt_state, ms, loss = step(params, opt_state, ms, rng, x, y)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, ms, loss = step(params, opt_state, ms, rng, x, y)
    float(loss)
    dt = time.perf_counter() - t0
    return x.shape[0] * iters / dt


def main() -> None:
    import jax.numpy as jnp

    from bigdl_tpu.models import (
        Inception_v1_NoAuxClassifier, LeNet5, TransformerLM, VggForCifar10,
    )
    from bigdl_tpu.nn.criterion import ClassNLLCriterion, CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    rng = np.random.default_rng(0)
    results = {}

    # config #1: LeNet-5 / MNIST-shaped
    b = 512
    results["lenet5_mnist_b512"] = _measure(
        LeNet5(10), ClassNLLCriterion(),
        SGD(learning_rate=0.05, momentum=0.9),
        rng.standard_normal((b, 28, 28)).astype(np.float32),
        rng.integers(1, 11, size=(b,)).astype(np.int32))

    # config #2: VGG-16 (CIFAR variant) bf16
    b = 256
    results["vgg_cifar10_b256_bf16"] = _measure(
        VggForCifar10(10), CrossEntropyCriterion(),
        SGD(learning_rate=0.01, momentum=0.9, weight_decay=5e-4),
        rng.standard_normal((b, 3, 32, 32)).astype(np.float32),
        rng.integers(1, 11, size=(b,)).astype(np.int32),
        compute_dtype=jnp.bfloat16)

    # config #4: Inception-v1 / ImageNet-shaped bf16
    b = 128
    results["inception_v1_imagenet_b128_bf16"] = _measure(
        Inception_v1_NoAuxClassifier(1000), ClassNLLCriterion(),
        SGD(learning_rate=0.01, momentum=0.9),
        rng.standard_normal((b, 3, 224, 224)).astype(np.float32),
        rng.integers(1, 1001, size=(b,)).astype(np.int32),
        compute_dtype=jnp.bfloat16)

    # extension: TransformerLM tokens/sec on the round-4 fused path
    # (logits output + MaskedSoftmaxCECriterion — the LM-scale default;
    # the 137M-param MFU story lives in llm_mfu_bench.py)
    from bigdl_tpu.nn.criterion_more import MaskedSoftmaxCECriterion

    b, t = 8, 2048
    lm = TransformerLM(8192, hidden_size=512, n_heads=8, n_layers=6,
                       max_len=t, output="logits")
    tok_rate = _measure(
        lm, MaskedSoftmaxCECriterion(padding_value=0),
        SGD(learning_rate=0.1),
        rng.integers(1, 8193, size=(b, t)).astype(np.int32),
        rng.integers(1, 8193, size=(b, t)).astype(np.float32),
        compute_dtype=jnp.bfloat16)
    results["transformer_lm_T2048_tokens_per_sec"] = tok_rate * t

    for k, v in results.items():
        print(json.dumps({"config": k, "value": round(v, 1),
                          "unit": "samples/sec" if "tokens" not in k
                          else "tokens/sec"}))


if __name__ == "__main__":
    main()
