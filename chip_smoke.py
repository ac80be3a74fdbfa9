"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

drives the three main paths once, in this one process, through the entry
points a user calls, at the full width and depth of the models:

* ResNet-50 training   ``Optimizer(...).optimize()``, batch 256, bf16, SGD;
* 137M LM training     ``Optimizer(...).optimize()``, T=2048, batch 8, bf16,
                       Adam, Pallas flash attention forward and backward;
* 137M LM serving      ``ServingEngine(n_slots=32, bf16)`` with mixed greedy
                       and sampled requests over four prefill buckets, then
                       a ``kv_dtype="int8"`` engine — the product path into
                       the Pallas decode-attention kernel;

after checking the compiled Pallas kernels (flash forward and gradient,
pooled decode attention in bf16 and int8) against their jnp references
at the 137M shapes. With four or more chips it also trains ResNet-50
data-parallel with partitioned parameters and serves the LM on a
DP2 x TP2 mesh, and checks that state really lands on four devices.

Weights are random from a seed; data is synthetic from a seed. A phase
that raises ends the run: nothing here catches a failure. The process
needs a TPU listed in ``DEVICE_PEAKS`` and exits non-zero, before any
model is built, when jax reports anything else. The last line of a
passing run is one JSON object naming the device.

    python3 chip_smoke.py --rehearse-cpu

runs the same code at toy sizes on whatever backend jax has, for
debugging the script where there is no chip. Every line it prints says
so, it never prints the JSON line, and it exits with status 4: a
rehearsal is not a pass.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import sys
import time

import numpy as np

#: Published peaks per chip, keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" system architecture.
#: A device that is not listed is an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

REHEARSAL_EXIT = 4

#: The sizes ISSUE 22 / ROADMAP S0 name for the first cells.
FULL = dict(
    resnet=dict(batch=256, iters=6),
    lm=dict(vocab=32768, hidden=768, layers=12, heads=12, t=2048),
    lm_train=dict(batch=8, iters=6),
    serve=dict(n_slots=32, n_requests=16, n_requests_int8=8, new_tokens=64,
               prompt_lens=(64, 100, 128, 200, 256, 300, 400, 512),
               ref_len=640),
    kernel=dict(flash=(8, 2048, 12, 64), decode=(32, 2048, 12, 64)),
    multichip=dict(batch_per_chip=256, iters=3),
)
REHEARSAL = dict(
    resnet=dict(batch=2, iters=2),
    lm=dict(vocab=512, hidden=64, layers=2, heads=4, t=128),
    lm_train=dict(batch=2, iters=4),
    serve=dict(n_slots=4, n_requests=6, n_requests_int8=3, new_tokens=6,
               prompt_lens=(8, 12, 20, 30, 40, 60), ref_len=128),
    kernel=dict(flash=(2, 256, 2, 64), decode=(4, 256, 2, 64)),
    multichip=dict(batch_per_chip=1, iters=2),
)

# Tolerances of the checks, stated once.
#: compiled kernel vs jnp reference on bf16 inputs of order 1, as
#: |got - want| <= ATOL + RTOL * |want|: the two sides round intermediate
#: products at different points, so they may differ by two bf16 ulps
#: (8 mantissa bits: an ulp is up to 2^-7 relative) plus an absolute
#: floor near zero
KERNEL_RTOL = 2.0 ** -6
KERNEL_ATOL = 1e-2
#: gradients sum bf16 products over T=2048 keys: a wider floor
KERNEL_GRAD_ATOL = 4e-2
#: a served token's logit in a cache-free full forward of the same model
#: may sit this far under the best logit its sampling allowed and still
#: be a rounding tie, as a fraction of the logits' spread (best minus
#: median over the vocabulary, 2.4 for the untrained 137M model); a token
#: from a wrong cache row or position misses by about the whole spread
SERVE_SLACK_OF_SPREAD = 0.06


TAG = ""        # main() sets it for a rehearsal: every line then says so


def say(*parts) -> None:
    print(TAG + " ".join(str(p) for p in parts), flush=True)


# ------------------------------------------------------------------ phases


def run_phase(log, name, fn, *args):
    """Run one phase and print its one line. ``compile_s`` is the time
    inside the XLA backend (compiling, or reading the compile cache);
    ``run_s`` is the rest of the wall time: tracing, host work, device."""
    import jax

    p0, h0, s0 = log.snapshot()
    t0 = time.perf_counter()
    detail = fn(*args)
    wall = time.perf_counter() - t0
    p1, h1, s1 = log.snapshot()
    stats = jax.devices()[0].memory_stats() or {}
    say("phase", json.dumps(dict(
        phase=name, compile_s=round(s1 - s0, 1),
        run_s=round(wall - (s1 - s0), 1), programs=p1 - p0,
        cache_hits=h1 - h0,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"), **detail)))


def _recording_end(n_iters: int, losses: list):
    """``Trigger.max_iteration(n_iters)`` that also keeps each
    iteration's loss: the optimizer evaluates its end trigger on the
    state table once per iteration, and ``state['loss']`` is the loss of
    the iteration just finished."""
    from bigdl_tpu.optim import Trigger

    end = Trigger.max_iteration(n_iters)

    def fn(state):
        if state.get("loss") is not None and len(losses) < state["neval"] - 1:
            losses.append(float(state["loss"]))
        return end(state)

    return Trigger(fn, end.peek)


def _train(opt, n_iters):
    """Common tail of the training phases: run, check and return the
    losses."""
    losses: list = []
    opt.set_end_when(_recording_end(n_iters, losses))
    # optimize() retries ANY exception from a checkpoint; a compiler
    # refusal must surface once, not after five recompiles
    opt.retry_times = 1
    opt.optimize()
    assert len(losses) == n_iters, (losses, n_iters)
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not go down: {losses}"
    return [round(x, 4) for x in losses]


def _resnet_samples(n, seed=0):
    from bigdl_tpu.dataset.sample import Sample

    rng = np.random.default_rng(seed)
    return [Sample(rng.standard_normal((3, 224, 224)).astype(np.float32),
                   np.int32(rng.integers(1, 1001)))       # 1-based labels
            for _ in range(n)]


def _resnet50_optimizer(dataset, batch, **distributed_kw):
    """ResNet-50, bf16 compute, SGD as in ``bench.py``."""
    from bigdl_tpu.models import ResNet
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim import SGD, Optimizer
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(7)
    opt = Optimizer(model=ResNet(1000, {"depth": 50, "shortcutType": "B"}),
                    dataset=dataset, criterion=CrossEntropyCriterion(),
                    batch_size=batch, **distributed_kw)
    opt.set_compute_dtype("bf16")
    opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9,
                             weight_decay=1e-4))
    return opt


def phase_resnet50_train(size):
    from bigdl_tpu.dataset import DataSet

    batch, iters = size["resnet"]["batch"], size["resnet"]["iters"]
    opt = _resnet50_optimizer(DataSet.array(_resnet_samples(batch)), batch)
    return dict(batch=batch, iters=iters, losses=_train(opt, iters))


def _lm(size):
    from bigdl_tpu.models import TransformerLM

    c = size["lm"]
    return TransformerLM(c["vocab"], hidden_size=c["hidden"],
                         n_heads=c["heads"], n_layers=c["layers"],
                         max_len=c["t"], output="logits")


def phase_lm_train(size):
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn.criterion_more import MaskedSoftmaxCECriterion
    from bigdl_tpu.optim import Adam, Optimizer
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(7)
    c, batch, iters = size["lm"], size["lm_train"]["batch"], \
        size["lm_train"]["iters"]
    rng = np.random.default_rng(0)
    samples = [Sample(rng.integers(1, c["vocab"] + 1, size=(c["t"],))
                      .astype(np.int32),
                      rng.integers(1, c["vocab"] + 1, size=(c["t"],))
                      .astype(np.float32))
               for _ in range(batch)]
    opt = Optimizer(model=_lm(size), dataset=DataSet.array(samples),
                    criterion=MaskedSoftmaxCECriterion(padding_value=0),
                    batch_size=batch)
    opt.set_compute_dtype("bf16")
    opt.set_optim_method(Adam(learning_rate=1e-4))
    return dict(batch=batch, t=c["t"], iters=iters,
                losses=_train(opt, iters))


# ---------------------------------------------------------------- serving


def _submit_mix(eng, size, n_requests, seed):
    """Half greedy, half seeded top-k sampling; prompt lengths cycle over
    the configured set so several prefill buckets compile."""
    from bigdl_tpu.serving import SamplingParams

    c, s = size["lm"], size["serve"]
    rng = np.random.default_rng(seed)
    reqs = {}
    for i in range(n_requests):
        plen = s["prompt_lens"][i % len(s["prompt_lens"])]
        prompt = rng.integers(1, c["vocab"] + 1, size=(plen,)).tolist()
        sampling = None if i % 2 == 0 else SamplingParams(
            temperature=0.8, top_k=50, seed=1000 + i)
        rid = eng.submit(prompt, max_new_tokens=s["new_tokens"],
                         sampling=sampling)
        reqs[rid] = (prompt, sampling)
    return reqs


def _check_served(eng, reqs, outs, size):
    """What must hold of any drained engine, whatever the numerics."""
    vocab, new = size["lm"]["vocab"], size["serve"]["new_tokens"]
    assert set(outs) == set(reqs), (sorted(outs), sorted(reqs))
    for rid, out in outs.items():
        assert out.shape == (new,), (rid, out.shape)
        assert out.min() >= 1 and out.max() <= vocab, (rid, out)
        reason = eng.request(rid).finish_reason
        assert reason in ("length", "stop"), (rid, reason)
        lps = eng.logprobs(rid)
        assert lps.shape == (new,) and np.isfinite(lps).all(), (rid, lps)
    n = len(reqs)
    m = eng.metrics.metrics
    summary = eng.metrics.summary()
    assert m.get("serving/submitted")[0] == n
    assert m.get("serving/finished")[0] == n
    assert m.get("serving/tokens_out")[0] == n * new
    finished = summary.get("serving/finish_length", 0) + \
        summary.get("serving/finish_stop", 0)
    assert finished == n, summary
    # a dispatch the engine's fault recovery had to replay would show
    # here as retried rows or requests finished with "error"
    for bad in ("serving/retries", "serving/finish_error",
                "serving/shed", "serving/preempted"):
        assert not summary.get(bad), (bad, summary)


def _reference_slack(lm, reqs, outs, size):
    """Teacher-forced check against a cache-free full forward of the
    same model: each served token's reference logit, measured from the
    best logit the request's sampling allowed (the top one for greedy
    rows, the 50th for top-k=50 rows). Asserts the worst shortfall
    against the logits' spread; returns both."""
    import jax
    import jax.numpy as jnp

    ref_len, new = size["serve"]["ref_len"], size["serve"]["new_tokens"]
    rids = sorted(reqs)
    toks = np.ones((len(rids), ref_len), np.int32)
    # the position whose logits chose each served token
    at = np.zeros((len(rids), new), np.int32)
    for r, rid in enumerate(rids):
        seq = list(reqs[rid][0]) + [int(t) for t in outs[rid]]
        assert len(seq) <= ref_len
        toks[r, :len(seq)] = seq
        at[r] = len(reqs[rid][0]) - 1 + np.arange(new)
    lm.evaluate()

    def fwd(p, x, at):
        logits = lm.apply(p, x, lm.state, training=False, rng=None)[0]
        return jnp.take_along_axis(logits, at[:, :, None], axis=1)

    logits = np.asarray(jax.jit(fwd)(jax.device_put(lm.params),
                                     jnp.asarray(toks), jnp.asarray(at)),
                        np.float32)                 # (requests, new, vocab)
    assert np.isfinite(logits).all()
    worst, spreads = 0.0, []
    for r, rid in enumerate(rids):
        sampling = reqs[rid][1]
        k = 1 if sampling is None else sampling.top_k
        for row, tok in zip(logits[r], outs[rid]):
            kth = np.partition(row, -k)[-k]
            worst = max(worst, float(kth - row[int(tok) - 1]))
            spreads.append(float(row.max() - np.median(row)))
    spread = float(np.mean(spreads))
    assert worst <= SERVE_SLACK_OF_SPREAD * spread, (worst, spread)
    return dict(worst_logit_shortfall=round(worst, 4),
                logit_spread=round(spread, 3))


def _generate_agreement(lm, reqs, outs, size, n=2):
    """Informative only: how many tokens of the engine's greedy streams
    the per-request ``generate()`` reproduces. An untrained model has
    near-tied logits, so one flipped tie diverges the rest."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import generate

    greedy = [rid for rid in sorted(reqs) if reqs[rid][1] is None][:n]
    same = total = 0
    for rid in greedy:
        ids = generate(lm, reqs[rid][0], length=size["serve"]["new_tokens"],
                       temperature=0.0, compute_dtype=jnp.bfloat16)
        same += int(np.sum(ids == outs[rid]))
        total += len(ids)
    return same / max(total, 1)


def _serve(lm, size, n_requests, seed, **engine_kw):
    """One engine, one request mix, drained and checked."""
    import jax.numpy as jnp

    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=size["serve"]["n_slots"],
                        compute_dtype=jnp.bfloat16, **engine_kw)
    reqs = _submit_mix(eng, size, n_requests, seed)
    t0 = time.perf_counter()
    outs = eng.drain()
    wall = time.perf_counter() - t0
    _check_served(eng, reqs, outs, size)
    detail = dict(requests=len(reqs), drain_s=round(wall, 1),
                  **_reference_slack(lm, reqs, outs, size))
    return eng, reqs, outs, detail


def phase_serving(size, lm):
    s = size["serve"]
    eng, reqs, outs, detail = _serve(lm, size, s["n_requests"], seed=11)
    buckets = eng.metrics.metrics.get("serving/prefill_bucket_compiles")[0]
    assert buckets >= min(3, len(set(s["prompt_lens"]))), buckets
    agree = _generate_agreement(lm, reqs, outs, size)
    return dict(prefill_buckets=int(buckets),
                generate_agreement=round(agree, 3), **detail)


def phase_serving_int8(size, lm):
    eng, _, _, detail = _serve(lm, size, size["serve"]["n_requests_int8"],
                               seed=12, kv_dtype="int8")
    return dict(kv_bytes_per_slot=int(eng.pool.kv_bytes_per_slot), **detail)


# ---------------------------------------------------------------- kernels


def phase_kernels(size, interpret):
    """Compiled Pallas kernels against their jnp references at the 137M
    shapes. ``interpret`` is passed explicitly: False on the chip, so a
    kernel the compiler refuses raises here."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.decode_attention import (
        decode_attention_reference, folded_decode_attention,
        pooled_decode_attention)
    from bigdl_tpu.ops.flash_attention import flash_attention
    from bigdl_tpu.parallel.ring_attention import attention

    def excess(got, want, atol):
        """max |got - want| / (atol + RTOL |want|); passes when <= 1."""
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        assert np.isfinite(got).all()
        return float(np.max(np.abs(got - want)
                            / (atol + KERNEL_RTOL * np.abs(want))))

    out = {}
    b, t, h, d = size["kernel"]["flash"]
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    q, k, v = (jax.random.normal(keys[i], (b, t, h, d), jnp.bfloat16)
               for i in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2) / (b * h)

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            interpret=interpret)
    dense = lambda q, k, v: attention(q, k, v, causal=True)
    out["flash_fwd"] = excess(jax.jit(flash)(q, k, v),
                              jax.jit(dense)(q, k, v), KERNEL_ATOL)
    g_got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    g_want = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
    out["flash_grad"] = max(excess(a, w, KERNEL_GRAD_ATOL)
                            for a, w in zip(g_got, g_want))

    n, L, h, d = size["kernel"]["decode"]
    q = jax.random.normal(keys[3], (n, h, d), jnp.bfloat16)
    k = jax.random.normal(keys[4], (n, L, h, d), jnp.bfloat16)
    v = jax.random.normal(keys[5], (n, L, h, d), jnp.bfloat16)
    # every interesting pos: fresh row, block edges, mid-cache, last column
    pos = jax.random.randint(keys[6], (n,), 0, L)
    pos = pos.at[0].set(0).at[1].set(L - 1).at[2].set(127).at[3].set(128)
    kernel = jax.jit(lambda *a, **kw: pooled_decode_attention(
        *a, interpret=interpret, **kw))
    ref = jax.jit(decode_attention_reference)
    # kernel and folded form read the pool as it is stored, (N, L, H*D);
    # the reference reads the 4-D view
    stored = lambda x: x.reshape(n, L, h * d)
    want = ref(q, k, v, pos)
    out["decode_bf16"] = excess(kernel(q, stored(k), stored(v), pos), want,
                                KERNEL_ATOL)
    out["decode_folded"] = excess(
        jax.jit(folded_decode_attention)(q, stored(k), stored(v), pos), want,
        KERNEL_ATOL)
    # grouped queries over a third of the K/V heads, every third row not
    # decoding: those rows cost no fetch and come back as zeros
    g, on = max(1, h // 3), np.arange(n) % 3 != 1
    kg, vg = (x[:, :, :g].reshape(n, L, g * d) for x in (k, v))
    got = np.asarray(kernel(q, kg, vg, pos, active=jnp.asarray(on)),
                     np.float32)
    out["decode_grouped"] = excess(
        got[on], np.asarray(ref(q, kg, vg, pos), np.float32)[on],
        KERNEL_ATOL)
    assert not got[~on].any()
    # per-(row, head) symmetric int8: the serving carry's layout
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    ks = jnp.max(jnp.abs(k32), axis=(1, 3)) / 127.0
    vs = jnp.max(jnp.abs(v32), axis=(1, 3)) / 127.0
    kq = jnp.round(k32 / ks[:, None, :, None]).astype(jnp.int8)
    vq = jnp.round(v32 / vs[:, None, :, None]).astype(jnp.int8)
    out["decode_int8"] = excess(
        kernel(q, stored(kq), stored(vq), pos, k_scale=ks, v_scale=vs),
        ref(q, kq, vq, pos, k_scale=ks, v_scale=vs), KERNEL_ATOL)
    for name, x in out.items():
        assert x <= 1.0, f"{name}: {x:.2f}x the stated tolerance"
    return {f"{name}_err_over_tol": round(x, 3) for name, x in out.items()}


# -------------------------------------------------------------- four chips


def _devices_of(tree):
    import jax

    return {s.device for leaf in jax.tree_util.tree_leaves(tree)
            if hasattr(leaf, "addressable_shards")
            for s in leaf.addressable_shards}


def _spy_prepare(opt, seen):
    """Keep what ``_prepare`` placed on the devices and the arguments of
    the first step call: the only way to see, from outside, where the
    optimizer put its shards and what it compiled. (The partitioned
    step donates nothing, so the kept arguments stay valid.)"""
    prepare = opt._prepare

    def spied():
        step, place_batch, params, opt_state, model_state = prepare()
        seen.update(step=step, params=params, opt_state=opt_state)

        def spied_step(*args):
            seen.setdefault("args", args)
            return step(*args)

        return spied_step, place_batch, params, opt_state, model_state

    opt._prepare = spied


def phase_multichip_train(size, n_dev):
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.utils.engine import Engine

    mesh = Engine.mesh(("data",))
    assert mesh.devices.size == n_dev, (mesh.shape, n_dev)
    batch = size["multichip"]["batch_per_chip"] * n_dev
    iters = size["multichip"]["iters"]
    opt = _resnet50_optimizer(
        DataSet.distributed(_resnet_samples(batch)), batch,
        parameter_mode="partitioned", compress="bf16", mesh=mesh)
    seen: dict = {}
    _spy_prepare(opt, seen)
    losses = _train(opt, iters)
    for name in ("params", "opt_state"):
        devs = _devices_of(seen[name])
        assert len(devs) == n_dev, (name, devs)
    devs = _devices_of(opt._final_opt_state)
    assert len(devs) == n_dev, ("final opt_state", devs)
    # what the step asks for, and what the compiler made of it: on four
    # v5e chips XLA rewrites this all-gather and reduce-scatter as
    # all-reduces over the whole parameter vector, so the compiled text
    # is only asked for a collective that spans every device. (This
    # compiles the step a second time, ~85 s on the four-chip host: the
    # ahead-of-time path does not find jit's entry in the compile cache.)
    lowered = seen["step"].lower(*seen["args"])
    asked = lowered.as_text()
    for op in ("all_gather", "reduce_scatter"):
        assert op in asked, f"partitioned step lowers no {op}"
    hlo = lowered.compile().as_text()
    everyone = "replica_groups={{" + ",".join(map(str, range(n_dev))) + "}}"
    assert any(everyone in line for line in hlo.splitlines()
               if any(op in line for op in
                      ("all-gather", "reduce-scatter", "all-reduce"))), \
        "compiled partitioned step has no collective over all devices"
    return dict(batch=batch, devices=n_dev, losses=losses)


def phase_multichip_serving(size, lm):
    import jax.numpy as jnp

    n = size["serve"]["n_slots"]
    eng, _, _, detail = _serve(lm, size, size["serve"]["n_requests"],
                               seed=11, parallelism={"data": 2, "model": 2})
    for name, tree in (("params", eng.params), ("kv carry", eng.pool.carry)):
        devs = _devices_of(tree)
        assert len(devs) == 4, (name, devs)
    # Megatron layout: one psum closes attention, one closes the MLP
    hlo = eng._step_fn.lower(
        eng.params, eng._place_rows(jnp.zeros((n,), jnp.int32)),
        eng._place_rows(jnp.zeros((n,), bool)), eng.pool.carry,
        eng._knobs_device).compile().as_text()
    assert "all-reduce" in hlo, "TP decode step has no all-reduce"
    return dict(devices=4, **detail)


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    global TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on any backend; never a pass")
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_cpu
    if rehearsal:
        TAG = "[cpu-rehearsal, NOT a chip result] "

    import jax

    t_start = time.perf_counter()
    backend = jax.default_backend()
    devices = jax.devices()
    kind = devices[0].device_kind
    say(f"jax {jax.__version__}  jaxlib "
        f"{importlib.metadata.version('jaxlib')}  libtpu "
        f"{importlib.metadata.version('libtpu')}  backend {backend}  "
        f"device_kind {kind!r}  devices {len(devices)}")
    if not rehearsal:
        if backend != "tpu":
            say(f"FAIL: jax's backend is {backend!r}, not 'tpu' — this "
                "check only means something on the chip")
            return 1
        if kind not in DEVICE_PEAKS:
            say(f"FAIL: device_kind {kind!r} is not in DEVICE_PEAKS "
                f"({sorted(DEVICE_PEAKS)}) — add its published peaks "
                "with their source before measuring on it")
            return 1
        say("peaks", json.dumps(DEVICE_PEAKS[kind]))

    from bigdl_tpu.utils.compile_cache import CompileLog, enable_compile_cache

    say("compile cache:", enable_compile_cache())
    log = CompileLog()
    size = REHEARSAL if rehearsal else FULL

    run_phase(log, "kernels", phase_kernels, size, rehearsal)
    run_phase(log, "resnet50_train", phase_resnet50_train, size)
    run_phase(log, "lm137m_train", phase_lm_train, size)
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(3)
    lm = _lm(size)
    run_phase(log, "lm137m_serving_bf16", phase_serving, size, lm)
    run_phase(log, "lm137m_serving_int8kv", phase_serving_int8, size, lm)
    if len(devices) >= 4:
        run_phase(log, "multichip_resnet50_train", phase_multichip_train,
                  size, len(devices))
        run_phase(log, "multichip_lm137m_serving", phase_multichip_serving,
                  size, lm)
    else:
        say(f"multichip: not run ({len(devices)} device)")

    programs, hits, compile_s = log.snapshot()
    say("compile totals", json.dumps(dict(
        programs=programs, from_cache=hits, compiled=programs - hits,
        compile_s=round(compile_s, 1),
        wall_s=round(time.perf_counter() - t_start, 1))))
    if rehearsal:
        say("rehearsal finished: every phase ran at toy size. This is "
            f"not a pass; exit status {REHEARSAL_EXIT}.")
        return REHEARSAL_EXIT
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
