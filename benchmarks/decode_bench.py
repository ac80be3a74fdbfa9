"""Serving/decode throughput bench (round-5 verdict item #3).

Ties the serving pieces together end-to-end: KV-cached
``make_decode_step`` (models/transformer.py), the ``compute_dtype``
serving knob, and weight-only int8 (``Quantizer.quantize(lm,
scheme="weight_only")``) — answering whether the 1.29× int8 win measured
at the isolated weight-bound matmul (int8_bench.py, r4) survives an
end-to-end generation loop.

Protocol per (model, batch, variant): prime the cache with a 128-token
prompt, then generate 256 tokens greedily with the WHOLE loop inside one
jitted ``lax.scan`` (one device program — the rate of the model's decode
step with no per-token host dispatch in it), and report
tokens/sec = batch * 256 / wall.

``--attention`` switches to the pooled decode-attention OP bench
(``measure_attention``): the Pallas kernel against the whole-window
folded sum over the STORED ``(N, L, G*D)`` cache, at the shapes the
serving cells bring (``ATTENTION_SHAPES``), by how full the pool is
(``--fills``: the share of ``L`` each decoding row holds) and how many
rows decode (``--active``). CPU runs execute the kernel in interpret
mode and say so in the row; run on TPU for real numbers.

``--ssm`` benches the hybrid decode step's scan-state update
(``measure_ssm``): the Pallas kernel over the decoding rows against the
jnp reference over every row, at Falcon-H1's state (32 slots x 32 heads
x 128 x 256 float32), by how many rows decode (``--active``).

    python -m benchmarks.decode_bench
    ... --models 137m --batches 1 8 --variants bf16 int8   # subset
    ... --attention --shapes trinity-ring --fills 0.15 1.0 --active 0.33
    ... --ssm --active 0.5 1.0
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

MODELS = {
    "137m": dict(vocab=32768, hidden=768, layers=12, heads=12),
    "371m": dict(vocab=32768, hidden=1024, layers=24, heads=16),
}
PROMPT, GEN = 128, 256


def build(name: str, variant: str):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.models.transformer import make_decode_step, serving_params
    from bigdl_tpu.nn.quantized import Quantizer
    from bigdl_tpu.utils.random_gen import RNG

    cfg = MODELS[name]
    RNG.set_seed(17)
    lm = TransformerLM(cfg["vocab"], hidden_size=cfg["hidden"],
                       n_heads=cfg["heads"], n_layers=cfg["layers"],
                       max_len=PROMPT + GEN, output="logits")
    lm._ensure_params()
    lm.evaluate()
    if variant == "int8":
        lm = Quantizer.quantize(lm, scheme="weight_only")
    dtype = {"fp32": None, "bf16": jnp.bfloat16,
             "int8": jnp.bfloat16}[variant]
    from bigdl_tpu.models.transformer import make_prefill_step

    step, init_carry = make_decode_step(lm, compute_dtype=dtype)
    prefill = make_prefill_step(lm, compute_dtype=dtype)
    # weights as RESIDENT device buffers in the serving dtype (passing
    # None would bake them into the program as constants — hundreds of
    # MB of literals in every compiled program)
    P = jax.device_put(serving_params(lm, dtype))
    return step, init_carry, prefill, P


def measure(name: str, variant: str, batch: int, reps: int = 3) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax

    step, init_carry, prefill, P = build(name, variant)
    rng = np.random.default_rng(0)
    vocab = MODELS[name]["vocab"]
    prompt = jnp.asarray(rng.integers(0, vocab, size=(PROMPT, batch)),
                         jnp.int32)

    def prime(params, carry, toks):
        """sequential single-token priming — kept as the prefill's
        comparison baseline (re-reads all weights per prompt token)."""
        def body(c, tok):
            _, c = step(params, tok, c)
            return c, None

        return lax.scan(body, carry, toks)[0]

    def generate(params, carry, tok0, n):
        def body(c, _):
            tok, cc = c
            logp, cc = step(params, tok, cc)
            return (jnp.argmax(logp, -1).astype(jnp.int32), cc), None

        (tok, carry), _ = lax.scan(body, (tok0, carry), None, length=n)
        return tok, carry

    prime_j = jax.jit(prime)
    gen_j = jax.jit(generate, static_argnums=3)

    carry0 = init_carry(batch)
    t0 = time.perf_counter()
    carry = prime_j(P, carry0, prompt[:-1])
    jax.block_until_ready(carry)
    prime_compile_plus_run = time.perf_counter() - t0

    # warm prime times: sequential decode-steps vs ONE prefill pass (the
    # time-to-first-token story). Amortized over AMORT in-program reps so
    # per-call dispatch does not mask the device-side difference at these
    # ms-scale programs.
    AMORT = 8
    ptoks = jnp.swapaxes(prompt[:-1], 0, 1)          # (batch, P-1)

    def _live_sum(tree):
        # consume EVERY cache buffer so no layer is dead-code-eliminated
        # from the measured program
        return sum(jnp.sum(v.astype(jnp.float32)) for k, v in tree.items()
                   if k != "pos")

    def _depend(toks, acc):
        # make each amortized rep data-dependent on the carry so XLA's
        # loop-invariant code motion cannot hoist the forward out of the
        # scan (int cast of acc*1e-30 is 0, but not provably so)
        return toks + jnp.int32(acc * 1e-30)

    def many_prime(params, toks_seq, c):
        def one(acc, _):
            cend = prime(params, c, _depend(toks_seq, acc))
            return acc + _live_sum(cend), None

        return lax.scan(one, 0.0, None, length=AMORT)[0]

    def many_prefill(params, toks, c):
        def one(acc, _):
            logp, cc = prefill(params, _depend(toks, acc), c)
            return acc + jnp.sum(logp) + _live_sum(cc), None

        return lax.scan(one, 0.0, None, length=AMORT)[0]

    def amortized_s(fn, *args):
        f = jax.jit(fn)
        float(f(*args))
        t0 = time.perf_counter()
        out = f(*args)
        float(out)
        return (time.perf_counter() - t0) / AMORT

    prime_seq_s = amortized_s(many_prime, P, prompt[:-1], carry0)
    prefill_s = amortized_s(many_prefill, P, ptoks, carry0)

    tok0 = prompt[-1]
    tok, carry1 = gen_j(P, carry, tok0, GEN)     # compile + first run
    jax.block_until_ready(tok)

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        tok, _ = gen_j(P, carry, tok0, GEN)
        jax.block_until_ready(tok)
        best = min(best, time.perf_counter() - t0)

    return {
        "model": name, "variant": variant, "batch": batch,
        "prompt": PROMPT, "gen": GEN,
        "gen_s": round(best, 3),
        "ms_per_token": round(1000 * best / GEN, 3),
        "tokens_per_sec": round(batch * GEN / best, 1),
        "prime_s_cold": round(prime_compile_plus_run, 1),
        "prime_seq_ms": round(1000 * prime_seq_s, 1),
        "prefill_ms": round(1000 * prefill_s, 1),
        "prefill_speedup": round(prime_seq_s / prefill_s, 1),
    }


#: the pooled decode attention of the serving cells, as stored: rows,
#: window, query heads, K/V heads, head width (BENCHMARK.json's configs);
#: ``v_width``: a latent cache, ONE leaf whose leading columns are the
#: values (GLM-4.7-Flash's 576-value row in its 640 stored columns)
ATTENTION_SHAPES = {
    "gpt2m": dict(n=32, L=1024, h=16, g=16, d=64),
    "falconh1": dict(n=32, L=1024, h=20, g=4, d=128),
    "trinity-ring": dict(n=16, L=4096, h=48, g=8, d=128),
    "trinity-full": dict(n=16, L=8192, h=48, g=8, d=128),
    "glm-latent": dict(n=32, L=16384, h=20, g=1, d=640, v_width=512),
}


def measure_attention(shape: str, fill: float, active_share: float,
                      variant: str = "bf16", block=None,
                      reps: int = 3) -> dict:
    """One pooled decode-attention call, the Pallas kernel against the
    folded whole-window sum (``ops/decode_attention.py``), over the
    stored ``(N, L, G*D)`` cache of ``ATTENTION_SHAPES[shape]``: every
    ``1 / active_share``-th row decodes and holds ``fill`` of ``L``.
    ``variant`` ``int8`` benches the quantized layout against the jnp
    reference (ungrouped shapes only). Each timing is one program of
    ``CALLS`` dependent calls (a call's output feeds the next one's
    query), so the launch is amortised. On a CPU host the kernel runs
    in Pallas INTERPRET mode — emulation time, not kernel speed; the
    row carries ``interpret`` so readers can tell. A latent shape
    (``v_width``) has no V array: the kernel fetches each block once
    for both products, and ``two_fetch_ms`` times the grouped kernel
    handed the leaf as keys AND as values beside it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.ops.decode_attention import (
        auto_block_l, decode_attention, decode_attention_reference,
        fetched_blocks, folded_decode_attention,
    )
    from bigdl_tpu.utils.compat import auto_interpret

    CALLS = 16
    dims = ATTENTION_SHAPES[shape]
    n, L, h, g, d = (dims[key] for key in "nLhgd")
    v_width = dims.get("v_width")
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((n, h, d)), jnp.bfloat16)
    stride = max(1, round(1 / active_share))
    active = np.arange(n) % stride == 0
    pos = np.full((n,), max(1, round(fill * L)) - 1, np.int32)
    ks = vs = None
    if variant == "int8":
        k, v = (jnp.asarray(rng.integers(-127, 128, size=(n, L, g * d)),
                            jnp.int8) for _ in range(2))
        ks, vs = (jnp.asarray(0.02 + 0.01 * rng.random((n, h)),
                              jnp.float32) for _ in range(2))
    else:
        k, v = (jnp.asarray(rng.standard_normal((n, L, g * d)),
                            jnp.bfloat16) for _ in range(2))
    if block is None:
        block = auto_block_l(L, g * d * k.dtype.itemsize)
    if v_width is not None:
        v = None
    args = (q, k, v, jnp.asarray(pos), jnp.asarray(active))

    def whole_window(q, k, v, pos, active):
        if ks is not None:
            return decode_attention_reference(q, k, v, pos, k_scale=ks,
                                              v_scale=vs)
        return folded_decode_attention(q, k, v, pos, v_width=v_width)

    def kernel(q, k, v, pos, active):
        return decode_attention(q, k, v, pos, k_scale=ks, v_scale=vs,
                                active=active, block=block, impl="kernel",
                                v_width=v_width)

    def two_fetches(q, k, v, pos, active):
        return decode_attention(q, k, k, pos, active=active, block=block,
                                impl="kernel")[..., :v_width]

    def timed(fn) -> float:
        def fed_back(qq, out):       # a context may be narrower than q
            out = (out * 1e-3).astype(qq.dtype)
            return qq + jnp.pad(out, [(0, 0), (0, 0),
                                      (0, qq.shape[-1] - out.shape[-1])])

        def many(q, *rest):
            return lax.fori_loop(
                0, CALLS, lambda _, qq: fed_back(qq, fn(qq, *rest)), q)

        many = jax.jit(many)
        jax.block_until_ready(many(*args))          # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(many(*args))
            best = min(best, time.perf_counter() - t0)
        return best / CALLS

    want = np.asarray(jax.jit(whole_window)(*args), np.float32)[active]
    got = np.asarray(jax.jit(kernel)(*args), np.float32)[active]
    whole_s = timed(whole_window)
    kern_s = timed(kernel)
    position_bytes = (1 if v_width else 2) * g * d * k.dtype.itemsize
    extra = {} if v_width is None else {
        "two_fetch_ms": round(1e3 * timed(two_fetches), 4)}
    return {
        "metric": "decode_attention_call_ms", "shape": shape, **dims,
        "variant": variant, "fill": fill, "rows_decoding": int(active.sum()),
        "block": block, "interpret": bool(auto_interpret()),
        "fetched_mb": round(1e-6 * block * position_bytes * int(
            fetched_blocks(pos, active, L, block).sum()), 2),
        "stored_mb": round(1e-6 * n * L * position_bytes, 2),
        "max_abs_diff": float(np.abs(got - want).max()),
        "whole_window_ms": round(1e3 * whole_s, 4),
        "kernel_ms": round(1e3 * kern_s, 4),
        "kernel_vs_whole_window": round(whole_s / max(kern_s, 1e-9), 3),
        **extra,
    }


#: Falcon-H1's scan state per layer (BENCHMARK.json's configuration):
#: slots, scan groups, heads a group, head width, state width
SSM_SHAPE = dict(n=32, G=2, hg=16, d=128, N=256)


def measure_ssm(active_share: float, reps: int = 3) -> dict:
    """One scan-state decode step at ``SSM_SHAPE``: the Pallas kernel
    (``ops/ssm_decode.py``) over the ``active_share`` of rows that
    decode, against the jnp reference over every row. Each timing is
    one program of ``CALLS`` calls whose state is the loop's carry
    (updated in place, as the decode step's donated leaf is); the
    kernel's includes the small operations that prepare its operands.
    ``state_gb_per_s``: the decoding rows' state, read once and written
    once, over that time. On a CPU host the kernel runs in Pallas
    INTERPRET mode and the row says so."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.ops.ssm_decode import ssm_decode
    from bigdl_tpu.utils.compat import auto_interpret

    CALLS = 16
    n, G, hg, d, N = (SSM_SHAPE[key] for key in ("n", "G", "hg", "d", "N"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, G, hg, d)), jnp.bfloat16)
    Bm, Cm = (jnp.asarray(rng.standard_normal((n, G, N)), jnp.bfloat16)
              for _ in range(2))
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (n, G, hg)), jnp.float32)
    A = -jnp.asarray(np.arange(1, G * hg + 1).reshape(G, hg), jnp.float32)
    S = jnp.asarray(rng.standard_normal((n, G * hg, d, N)), jnp.float32)
    stride = max(1, round(1 / active_share))
    active = jnp.asarray(np.arange(n) % stride == 0)

    def reference(S):
        return ssm_decode(x, Bm, Cm, dt, A, S, active, impl="reference")

    def kernel(S):
        return ssm_decode(x, Bm, Cm, dt, A, S, active, impl="kernel")

    def timed(fn) -> float:
        many = jax.jit(lambda S: lax.fori_loop(
            0, CALLS, lambda _, s: fn(s)[1], S), donate_argnums=(0,))
        state = jax.block_until_ready(many(S + 0))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            state = jax.block_until_ready(many(state))
            best = min(best, time.perf_counter() - t0)
        return best / CALLS

    y_want, s_want = jax.jit(reference)(S)
    y_got, s_got = jax.jit(kernel)(S)
    ref_s = timed(reference)
    kern_s = timed(kernel)
    rows = int(np.asarray(active).sum())
    state_bytes = 2 * rows * G * hg * d * N * 4
    return {
        "metric": "ssm_decode_call_ms", **SSM_SHAPE,
        "rows_decoding": rows,
        "interpret": bool(auto_interpret()),
        "max_abs_diff_y": float(jnp.abs(y_got - y_want).max()),
        "max_abs_diff_state": float(jnp.abs(s_got - s_want).max()),
        "reference_ms": round(1e3 * ref_s, 4),
        "kernel_ms": round(1e3 * kern_s, 4),
        "kernel_vs_reference": round(ref_s / max(kern_s, 1e-9), 3),
        "state_gb_per_s": round(1e-9 * state_bytes / max(kern_s, 1e-9), 1),
    }


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--models", nargs="+", default=["137m", "371m"],
                   choices=sorted(MODELS))
    p.add_argument("--batches", nargs="+", type=int, default=[1, 8])
    p.add_argument("--variants", nargs="+", default=["bf16", "int8"],
                   choices=["fp32", "bf16", "int8"])
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--attention", action="store_true",
                   help="bench the pooled decode-attention op (Pallas "
                        "kernel vs the whole-window sum) instead of the "
                        "full decode loop")
    p.add_argument("--shapes", nargs="+", default=sorted(ATTENTION_SHAPES),
                   choices=sorted(ATTENTION_SHAPES))
    p.add_argument("--fills", nargs="+", type=float,
                   default=[0.02, 0.15, 1.0])
    p.add_argument("--active", nargs="+", type=float, default=[1 / 3, 1.0])
    p.add_argument("--blocks", nargs="+", type=int, default=[None],
                   help="KV tile lengths to try (default: the kernel's "
                        "own choice)")
    p.add_argument("--ssm", action="store_true",
                   help="bench the hybrid decode step's scan-state update "
                        "(Pallas kernel vs the jnp reference)")
    args = p.parse_args(argv)

    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # a row that fails fails the run — a kernel the compiler refuses
    # must not end up as an "error" field under exit status 0
    if args.attention:
        for shape in args.shapes:
            dims = ATTENTION_SHAPES[shape]
            for v in args.variants:
                if v == "fp32" or (v == "int8" and dims["g"] != dims["h"]):
                    continue
                for fill in args.fills:
                    for share in args.active:
                        for block in args.blocks:
                            print(json.dumps(measure_attention(
                                shape, fill, share, v, block, args.reps)),
                                flush=True)
        return
    if args.ssm:
        for share in args.active:
            print(json.dumps(measure_ssm(share, args.reps)), flush=True)
        return

    rows = []
    for name in args.models:
        for b in args.batches:
            for v in args.variants:
                r = measure(name, v, b, args.reps)
                rows.append(r)
                print(json.dumps(r), flush=True)
    # headline ratio: int8 vs bf16 at each (model, batch)
    by = {(r["model"], r["batch"], r["variant"]): r for r in rows}
    for (m, b) in sorted({(r["model"], r["batch"]) for r in rows}):
        i8, bf = by.get((m, b, "int8")), by.get((m, b, "bf16"))
        if i8 and bf:
            print(json.dumps({
                "model": m, "batch": b,
                "int8_vs_bf16": round(
                    i8["tokens_per_sec"] / bf["tokens_per_sec"], 3)}))


if __name__ == "__main__":
    main()
