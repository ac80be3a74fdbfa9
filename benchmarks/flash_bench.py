"""Flash (Pallas) vs XLA-dense attention timing table.

Round-1 verdict weak #3: the flash kernel must beat XLA's fused dense
attention at mainstream lengths (T=4k-8k), not just win on memory at 32k.
Methodology matches PERF_ANALYSIS_r2.md: enough iterations to amortize the
transport's ~135 ms fixed host-readback cost, float() sync.

Run: python benchmarks/flash_bench.py [--dtype bf16] [--causal]
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def bench(fn, args, iters, repeats=3):
    """min-of-repeats: throughput varies run to run, and the minimum is
    the least-contended estimate of true device time."""
    import jax
    import jax.numpy as jnp

    jf = jax.jit(fn)
    o = jf(*args)
    leaf = jax.tree_util.tree_leaves(o)[0]
    float(jnp.sum(leaf.astype(jnp.float32)))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            o = jf(*args)
        leaf = jax.tree_util.tree_leaves(o)[0]
        float(jnp.sum(leaf.astype(jnp.float32)))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def main():
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.flash_attention import flash_attention
    from bigdl_tpu.parallel.ring_attention import attention as dense_attention

    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "fp32"])
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--lens", default="2048,4096,8192,16384,32768")
    ap.add_argument("--block", type=int, default=None)
    args = ap.parse_args()

    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    B, H, D = 1, 4, 64
    causal = args.causal

    print(f"B={B} H={H} D={D} dtype={args.dtype} causal={causal}")
    print(f"{'T':>6} {'mode':>7} {'dense-fwd':>10} {'flash-fwd':>10} "
          f"{'dense-f+b':>10} {'flash-f+b':>10}")
    for t in [int(x) for x in args.lens.split(",")]:
        rng = np.random.default_rng(0)
        mk = lambda: jax.device_put(
            (rng.standard_normal((B, t, H, D)) * 0.3).astype(np.float32)
        ).astype(dtype)
        q, k, v = mk(), mk(), mk()
        iters = max(6, min(50, (8192 * 30) // t))

        def d_fwd(q, k, v):
            return dense_attention(q, k, v, causal=causal)

        def f_fwd(q, k, v):
            return flash_attention(q, k, v, causal=causal, block=args.block)

        def mk_loss(fn):
            def loss(q, k, v):
                return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)
            return jax.grad(loss, argnums=(0, 1, 2))

        def run(fn, it, guard):
            """Dense may legitimately OOM at long T (guard=True shows OOM /
            the error name); flash failures must CRASH the benchmark —
            masking a kernel regression as a table cell would fake the
            'flash wins, dense OOMs' headline."""
            if not guard:
                return bench(fn, (q, k, v), it)
            try:
                return bench(fn, (q, k, v), it)
            except Exception as e:
                msg = str(e)
                if "RESOURCE_EXHAUSTED" in msg or "memory" in msg.lower():
                    return "OOM"
                return type(e).__name__[:9]

        row = [run(d_fwd, iters, True), run(f_fwd, iters, False),
               run(mk_loss(d_fwd), max(3, iters // 3), True),
               run(mk_loss(f_fwd), max(3, iters // 3), False)]
        fmt = lambda x: (f"{x*1e3:9.2f}ms" if isinstance(x, float)
                         else f"{x:>10} ")
        print(f"{t:>6} {'':>7} {fmt(row[0])} {fmt(row[1])} "
              f"{fmt(row[2])} {fmt(row[3])}", flush=True)


if __name__ == "__main__":
    main()
