"""int8 quantized inference: throughput vs bf16 + accuracy delta (r2 #8).

``nn/quantized.py`` claims the MXU's native int8 path (2× the bf16 rate on
v5e); this measures it. Two parts:

1. ResNet-50 ImageNet-shape inference img/s: fp32 vs bf16 vs
   ``Quantizer.quantize(model)`` int8 (batch 256, synthetic inputs).
2. Accuracy delta on the deterministic parity dataset: the convergence-
   parity ResNet-8 (tests/test_resnet_convergence.py recipe) is trained
   briefly, then evaluated float vs quantized on the same validation set.

Run: python benchmarks/int8_bench.py [--iters 20]
"""

from __future__ import annotations

import argparse
import json
import time


def bench_infer(model_builder, batch, iters, dtype=None, quantize=False,
                scheme="dynamic"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.nn.quantized import Quantizer
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(7)
    model = model_builder()
    model._ensure_params()
    if quantize:
        model = Quantizer.quantize(model, scheme=scheme)
        model._ensure_params()
    params, state = model.params, model.state
    if dtype is not None:
        from bigdl_tpu.optim.train_step import cast_floats

        params = cast_floats(params, dtype)

    def fwd(p, x):
        out, _ = model.apply(p, x, state, training=False, rng=None)
        return out

    jf = jax.jit(fwd)
    x = jax.device_put(jnp.zeros((batch, 3, 224, 224),
                                 dtype or jnp.float32))
    params = jax.device_put(params)
    o = jf(params, x)
    float(jnp.sum(o.astype(jnp.float32)))
    t0 = time.perf_counter()
    for _ in range(iters):
        o = jf(params, x)
    float(jnp.sum(o.astype(jnp.float32)))
    return batch * iters / (time.perf_counter() - t0)


def accuracy_delta():
    """Train the parity ResNet-8 briefly on the learnable CIFAR set, then
    compare float vs int8 top-1 on the validation split."""
    import tempfile

    import numpy as np

    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.cifar import generate_batch_dataset
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.nn.quantized import Quantizer
    from bigdl_tpu.optim import Optimizer, SGD, Top1Accuracy, Trigger
    from bigdl_tpu.optim.evaluator import Evaluator
    from bigdl_tpu.utils.random_gen import RNG

    import tests.test_resnet_convergence as T

    with tempfile.TemporaryDirectory() as d:
        generate_batch_dataset(d, n_train=1280, n_test=512, seed=5,
                               noise=180.0)
        RNG.set_seed(17)
        model = ResNet(10, {"depth": 8, "shortcutType": "A",
                            "dataSet": "cifar10"})
        model._ensure_params()
        from bigdl_tpu.optim.optim_method import Step

        batches = T._batches(d, 200)
        opt = Optimizer(model=model, dataset=DataSet.array(batches),
                        criterion=ClassNLLCriterion(),
                        end_trigger=Trigger.max_iteration(200))
        opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9,
                                 weight_decay=5e-4,
                                 learning_rate_schedule=Step(150, 0.2)))
        trained = opt.optimize()
        xs, ys = T._val_arrays(d)
        mb = list(T._as_minibatches(xs, ys))

        def top1(m):
            res = Evaluator(m).test(mb, [Top1Accuracy()], 64)[0]
            acc, n = res.result()
            assert n == len(ys)
            return float(acc)

        f32_acc = top1(trained)
        q = Quantizer.quantize(trained)
        q_acc = top1(q)
        return f32_acc, q_acc


def main():
    import jax.numpy as jnp

    from bigdl_tpu.models.resnet import ResNet

    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--batch", type=int, default=256)
    args = ap.parse_args()

    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    build = lambda: ResNet(class_num=1000,
                           opt={"depth": 50, "shortcutType": "B"})
    bf16 = bench_infer(build, args.batch, args.iters, dtype=jnp.bfloat16)
    print(f"bf16 inference : {bf16:8.1f} img/s", flush=True)
    i8 = bench_infer(build, args.batch, args.iters, quantize=True)
    print(f"int8 dynamic   : {i8:8.1f} img/s  ({i8 / bf16:.2f}x bf16)",
          flush=True)
    i8w = bench_infer(build, args.batch, args.iters, quantize=True,
                      scheme="weight_only")
    print(f"int8 weight-only: {i8w:8.1f} img/s  ({i8w / bf16:.2f}x bf16)",
          flush=True)

    f32_acc, q_acc = accuracy_delta()
    print(f"parity set top-1: float {f32_acc:.4f} -> int8 {q_acc:.4f} "
          f"(delta {q_acc - f32_acc:+.4f})", flush=True)

    print(json.dumps({
        "metric": "resnet50_int8_inference_images_per_sec",
        "value": round(i8, 1),
        "unit": "images/sec/chip",
        "vs_bf16": round(i8 / bf16, 3),
        "weight_only_images_per_sec": round(i8w, 1),
        "weight_only_vs_bf16": round(i8w / bf16, 3),
        "accuracy": {"float": round(f32_acc, 4), "int8": round(q_acc, 4)},
    }))


if __name__ == "__main__":
    main()
