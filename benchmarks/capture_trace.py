"""Capture a jax.profiler trace of the bench.py train step and print the
xprof op_profile summary — the tooling behind PERF_ANALYSIS_r2.md.

Run (on the TPU host):
    python benchmarks/capture_trace.py [--steps 3] [--out /tmp/jaxtrace]

Prints per-category device time, the top op groups with achieved
bandwidth/FLOPs, and the HBM-roofline split. ``summarize`` needs the
xprof package, which the current image does NOT have (``import xprof``
fails): the trace is still captured, and the reduction is to be redone
on ``jax.profiler.ProfileData`` (ROADMAP S0).
"""

from __future__ import annotations

import argparse
import glob
import json


def capture(out_dir: str, steps: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.train_step import make_train_step
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(7)
    batch = 256
    model = ResNet(class_num=1000, opt={"depth": 50, "shortcutType": "B"})
    model._ensure_params()
    sgd = SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4)
    step = jax.jit(make_train_step(model, CrossEntropyCriterion(), sgd,
                                   compute_dtype=jnp.bfloat16),
                   donate_argnums=(0, 1))
    params, ms = jax.device_put(model.params), model.state
    opt_state = jax.device_put(sgd.init_state(params))
    rng = jax.random.PRNGKey(0)
    x = jax.device_put(np.random.default_rng(0)
                       .standard_normal((batch, 3, 224, 224)).astype(np.float32))
    y = jax.device_put(np.random.default_rng(1)
                       .integers(1, 1001, size=(batch,)).astype(np.int32))
    params, opt_state, ms, loss = step(params, opt_state, ms, rng, x, y)
    float(loss)  # drain the warm-up before the trace window opens
    jax.profiler.start_trace(out_dir)
    for _ in range(steps):
        params, opt_state, ms, loss = step(params, opt_state, ms, rng, x, y)
    float(loss)
    jax.profiler.stop_trace()


def summarize(out_dir: str, steps: int) -> None:
    from xprof.convert import raw_to_tool_data as rtd

    files = glob.glob(f"{out_dir}/plugins/profile/*/*.xplane.pb")
    if not files:
        raise SystemExit(f"no xplane.pb under {out_dir}")
    data, _ = rtd.xspace_to_tool_data([max(files)], "op_profile", {})
    obj = json.loads(data)
    prog = obj["byProgram"]["children"][0]
    tot = prog["metrics"]["rawTime"]
    print(f"device time: {tot / 1e12 * 1000 / steps:.1f} ms/step")
    cats = sorted(((c["metrics"].get("rawTime", 0), c["name"], c)
                   for c in prog["children"]), reverse=True)
    for t, name, _ in cats:
        if t / tot > 0.003:
            print(f"  {t / tot * 100:5.1f}%  {t / 1e12 * 1000 / steps:7.2f} "
                  f"ms/step  {name}")
    hbm = 0
    t_hbm = t_mxu = 0
    rows = []
    for _, _, c in cats:
        for g in c.get("children", []):
            m = g["metrics"]
            b = m.get("rawBytesAccessedArray", [0])
            t = m["rawTime"]
            hbm += b[0]
            gbps = b[0] / (t / 1e12) / 1e9 if t else 0
            tfs = m.get("rawFlops", 0) / (t / 1e12) / 1e12 if t else 0
            rows.append((t, g["name"], gbps, tfs))
            if gbps > 400:
                t_hbm += t
            elif tfs > 100:
                t_mxu += t
    print(f"HBM bytes: {hbm / steps / 1e9:.1f} GB/step "
          f"({hbm / (tot / 1e12) / 1e9:.0f} GB/s avg)")
    print(f"time split: HBM-bound {t_hbm / tot * 100:.0f}%, "
          f"MXU-heavy {t_mxu / tot * 100:.0f}%")
    rows.sort(reverse=True)
    print("top op groups:")
    for t, name, gbps, tfs in rows[:10]:
        print(f"  {t / tot * 100:4.1f}% {gbps:5.0f} GB/s {tfs:6.1f} TF/s  "
              f"{name[:60]}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", default="/tmp/jaxtrace")
    args = ap.parse_args()

    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    capture(args.out, args.steps)
    summarize(args.out, args.steps)


if __name__ == "__main__":
    main()
