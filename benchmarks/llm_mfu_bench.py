"""LLM-scale training bench with MFU accounting (round-4 headline item).

The per-chip ResNet-50 story is closed (PERF_ANALYSIS_r3); this bench
answers the same "matching-or-beating" question for the framework's
extension surface — a GPT-2-small-class TransformerLM (~137M params,
12L/768H/12 heads, T=2048) trained with Adam, bf16 compute, fp32 masters.

MFU formula (PaLM appendix-B convention, stated so the number is
auditable):

    flops_per_token = 6 * N_matmul + 12 * L * T * H
    MFU             = tokens_per_sec * flops_per_token / peak_flops

where ``N_matmul`` counts every parameter that participates in a matmul
(block weights + the unembedding projection; the embedding GATHER and the
position-embedding ADD do no matmul FLOPs) and the attention term counts
the full (not causal-halved) score/context matmuls forward+backward —
the dense kernels execute the full matrix, and PaLM's convention makes
the number comparable to published MFU figures.

Peak: TPU v5e ≈ 197 TFLOP/s bf16 (v5p 459, v4 275 — looked up by
device_kind; a device that is not in the table is an error, so the
bench cannot report an MFU for a CPU).

    python -m benchmarks.llm_mfu_bench
    python -m benchmarks.llm_mfu_bench --sweep   # full grid
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

PEAK_BF16 = {
    "v5 lite": 197e12,   # v5e
    "v5litepod": 197e12,
    "v4": 275e12,
    "v5p": 459e12,
    "v6 lite": 918e12,   # trillium
}


def detect_peak() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    for key, peak in PEAK_BF16.items():
        if key in kind.lower():
            return peak
    raise RuntimeError(
        f"no bf16 peak known for device_kind {kind!r} (backend "
        f"{jax.default_backend()!r}) — MFU is only defined against a "
        f"listed device: {sorted(PEAK_BF16)}")


def lm_flops_per_token(vocab: int, hidden: int, layers: int, t: int,
                       mlp_ratio: int = 4) -> tuple[float, float]:
    """(flops_per_token, n_matmul_params). 6N fwd+bwd convention plus the
    PaLM attention term 12*L*T*H."""
    attn_params = 4 * hidden * hidden
    mlp_params = 2 * hidden * (mlp_ratio * hidden)
    block_params = attn_params + mlp_params
    n_matmul = layers * block_params + hidden * vocab  # + unembedding
    return 6.0 * n_matmul + 12.0 * layers * t * hidden, float(n_matmul)


def total_params(vocab: int, hidden: int, layers: int, t: int,
                 mlp_ratio: int = 4) -> float:
    _, n_matmul = lm_flops_per_token(vocab, hidden, layers, t, mlp_ratio)
    # + token embedding + position table + ln scales/biases (negligible)
    return n_matmul + vocab * hidden + t * hidden


def measure(batch: int, t: int, vocab: int, hidden: int, layers: int,
            heads: int, remat: bool, use_flash: str, iters: int = 10,
            lr: float = 1e-4, fused_ce: bool = True,
            embed_matmul: bool = False, flash_block=None,
            layer_scan: bool = False, opt_state_dtype=None,
            bf16_masters: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.nn.criterion import ClassNLLCriterion
    from bigdl_tpu.nn.criterion_more import TimeDistributedMaskCriterion
    from bigdl_tpu.optim.optim_method import Adam
    from bigdl_tpu.optim.train_step import cast_floats, make_train_step
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(7)
    lm = TransformerLM(vocab, hidden_size=hidden, n_heads=heads,
                       n_layers=layers, max_len=t, remat=remat,
                       output="logits" if fused_ce else "logprobs",
                       embed_grad_matmul=embed_matmul,
                       use_flash=use_flash, flash_block=flash_block,
                       layer_scan=layer_scan)
    if fused_ce:
        from bigdl_tpu.nn.criterion_more import MaskedSoftmaxCECriterion

        crit = MaskedSoftmaxCECriterion(padding_value=0)
    else:
        crit = TimeDistributedMaskCriterion(ClassNLLCriterion(),
                                            padding_value=0)
    optim = Adam(learning_rate=lr, state_dtype=opt_state_dtype,
                 stochastic_rounding=bf16_masters)

    lm._ensure_params()
    step = jax.jit(make_train_step(lm, crit, optim,
                                   compute_dtype=jnp.bfloat16),
                   donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    x = jax.device_put(rng.integers(1, vocab + 1,
                                    size=(batch, t)).astype(np.int32))
    y = jax.device_put(rng.integers(1, vocab + 1,
                                    size=(batch, t)).astype(np.float32))
    host_params = lm.params
    if bf16_masters:
        # the weights ARE the bf16 tensors (no fp32 master copy);
        # stochastic rounding keeps the sub-ulp Adam updates unbiased
        host_params = cast_floats(host_params, jnp.bfloat16)
    params, ms = jax.device_put(host_params), lm.state
    opt_state = jax.device_put(optim.init_state(params))
    key = jax.random.PRNGKey(0)

    c0 = time.perf_counter()
    params, opt_state, ms, loss = step(params, opt_state, ms, key, x, y)
    float(loss)
    compile_s = time.perf_counter() - c0
    for _ in range(2):
        params, opt_state, ms, loss = step(params, opt_state, ms, key, x, y)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, ms, loss = step(params, opt_state, ms, key, x, y)
    float(loss)
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * t * iters / dt
    fpt, _ = lm_flops_per_token(vocab, hidden, layers, t)
    peak = detect_peak()
    return {
        "batch": batch, "t": t, "remat": remat, "use_flash": use_flash,
        "fused_ce": fused_ce, "embed_matmul": embed_matmul,
        "flash_block": flash_block, "layer_scan": layer_scan,
        "opt_state_dtype": opt_state_dtype, "bf16_masters": bf16_masters,
        "compile_s": round(compile_s, 1),
        "step_ms": round(1000 * dt / iters, 1),
        "tokens_per_sec": round(tokens_per_sec, 0),
        "mfu": round(tokens_per_sec * fpt / peak, 4),
        "loss": float(loss),
    }


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=32768)
    p.add_argument("--hidden", type=int, default=768)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--seqLen", type=int, default=2048)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--sweep", action="store_true",
                   help="grid over batch x flash x remat")
    p.add_argument("--sweep_block", action="store_true",
                   help="in-model flash block-size sweep at the best config")
    p.add_argument("--sweep_opt", action="store_true",
                   help="optimizer-state dtype rows: fp32 / bf16 slots / "
                        "bf16 masters + stochastic rounding")
    p.add_argument("--sweep_remat_batch", action="store_true",
                   help="remat x batch frontier beyond B=8")
    p.add_argument("--layer_scan", action="store_true",
                   help="one row with the lax.scan layer stack (vs the "
                        "default unrolled row for compile + step time)")
    args = p.parse_args(argv)

    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    n = total_params(args.vocab, args.hidden, args.layers, args.seqLen)
    fpt, nm = lm_flops_per_token(args.vocab, args.hidden, args.layers,
                                 args.seqLen)
    print(json.dumps({"model_params": n, "matmul_params": nm,
                      "flops_per_token": fpt,
                      "peak_bf16": detect_peak()}))

    # every row: (extra-kwargs dict) merged onto the canonical best config
    # (flash, no remat, fused CE)
    base = dict(batch=args.batch, t=args.seqLen, vocab=args.vocab,
                hidden=args.hidden, layers=args.layers, heads=args.heads,
                remat=False, use_flash="auto", iters=args.iters)
    rows: list = []
    if args.sweep:
        # "always"/"never" (not "auto") so each sweep row's label states
        # its path unconditionally — "auto" also means flash on TPU, so
        # auto-vs-always rows would differ only by run noise
        rows += [dict(batch=b, use_flash=fl, remat=rm)
                 for b in (4, 8, 16)
                 for fl in ("never", "always")
                 for rm in (True, False)]
    if args.sweep_block:
        rows += [dict(flash_block=blk)
                 for blk in (None, 128, 256, 512, 1024)]
    if args.sweep_opt:
        rows += [dict(),                                    # fp32 baseline
                 dict(opt_state_dtype="bf16"),              # bf16 slots
                 dict(opt_state_dtype="bf16",
                      bf16_masters=True)]                   # + bf16 masters
    if args.sweep_remat_batch:
        rows += [dict(batch=b, remat=rm)
                 for rm in (False, True)
                 for b in (8, 12, 16, 24, 32)]
    if args.layer_scan:
        rows += [dict(layer_scan=False), dict(layer_scan=True)]
    if not rows:
        # the measured best single-chip operating point (PERF_ANALYSIS_r4,
        # incl. the correction note): FLASH attention, no remat, fused CE
        # + logits output (measure() defaults)
        rows = [dict()]
    # a row that fails fails the run: a sweep that wants to probe past
    # the memory limit must say so for that row, not catch everything
    for extra in rows:
        print(json.dumps(measure(**{**base, **extra})))


if __name__ == "__main__":
    main()
