"""Serving cells: an open loop against ONE ``ServingEngine``.

The loop is the shape of ``benchmarks/serving_bench.py:run_engine``:
submit what is due, ``eng.step()``, stamp what it returned on the loop's
own clock, sleep only when the engine is idle. A request's time to first
token counts from when it was DUE, not from when the loop got round to
submitting it, and the loop reports how late it ran. The generator runs
``ramp_s`` before the window opens, so the slots are at their steady
occupancy; the ramp is set-up.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import harness, reference, traffic
from benchmark.harness import say

TRACE_SECONDS = 2.0           # traced span, right after the window


def drive(eng, submit, schedule, mix, seconds, window, trace_dir=None):
    """The open loop. Times are seconds from the generator's start; the
    window opens ``ramp_s`` after it and lasts ``seconds``; then nothing
    more is submitted and the engine is drained under a limit."""
    import jax

    ramp_s, drain_limit_s = float(mix["ramp_s"]), float(mix["drain_limit_s"])
    horizon = ramp_s + seconds
    metrics = eng.metrics.metrics
    by_rid = {}                  # rid -> index into schedule
    stamps = [[] for _ in schedule]       # token times, per request
    submitted_at = [None] * len(schedule)
    steps = []                   # (start, end, prefill calls) in window
    tracing = None               # None, "on", "done"
    t_drain = None               # when nothing more was to be submitted
    i = 0
    t_gen = time.perf_counter()           # the schedule's zero
    while True:
        now = time.perf_counter() - t_gen
        if window.t_open is None and now >= ramp_s:
            window.open(now)
            setup_log = window.log.snapshot()
        if window.t_open is not None and window.t_close is None and \
                now >= window.t_open + seconds:
            window.close(now)
            queue_at_close = eng.queue_depth
        if window.t_close is not None and trace_dir and tracing != "done":
            # the traced span comes after the window, under the same
            # load, so the profiler's cost is in none of its numbers
            if tracing is None:
                jax.profiler.start_trace(str(trace_dir))
                tracing = "on"
            elif now >= window.t_close + TRACE_SECONDS:
                jax.profiler.stop_trace()
                tracing = "done"
        while i < len(schedule) and schedule[i].due_s <= now:
            by_rid[submit(schedule[i])] = i
            submitted_at[i] = time.perf_counter() - t_gen
            i += 1
        if window.t_close is not None and i == len(schedule) and \
                (not trace_dir or tracing == "done"):
            t_drain = now if t_drain is None else t_drain
            if eng.idle() or now >= t_drain + drain_limit_s:
                break
        n_prefill = metrics.get("serving/prefill_batch")[1]
        t_step = time.perf_counter() - t_gen
        with jax.profiler.TraceAnnotation("bench.engine.step"):
            emitted = eng.step()
        t_done = time.perf_counter() - t_gen
        if window.t_open is not None and window.t_close is None:
            steps.append((t_step, t_done,
                          metrics.get("serving/prefill_batch")[1] - n_prefill))
        for rid in emitted:
            stamps[by_rid[rid]].append(t_done)
        if not emitted and eng.idle() and window.t_close is None:
            nxt = schedule[i].due_s if i < len(schedule) else horizon
            wake = min(nxt, horizon if window.t_open is not None else ramp_s)
            time.sleep(max(0.0, wake - (time.perf_counter() - t_gen)))
    t_end = time.perf_counter() - t_gen

    return dict(by_rid=by_rid, stamps=stamps, submitted_at=submitted_at,
                steps=steps, t_gen=t_gen, t_end=t_end, n_submitted=i,
                setup_log=setup_log, queue_at_close=queue_at_close)


def summarise(eng, schedule, d, window, mix, seconds):
    """The window's numbers from the loop's stamps."""
    by_rid, stamps, submitted_at, t_end, i = d["by_rid"], d["stamps"], \
        d["submitted_at"], d["t_end"], d["n_submitted"]
    # the requests of the NOMINAL window: the same set in every run,
    # whatever step the loop was in when the window opened
    ramp_s = float(mix["ramp_s"])
    due_in = [k for k, r in enumerate(schedule)
              if ramp_s <= r.due_s < ramp_s + seconds]
    requests = {k: eng.request(rid) for rid, k in by_rid.items()}
    # eng.request() knows only finished requests
    failed = [k for k in due_in
              if requests.get(k) is None or requests[k].finish_reason
              not in ("length", "stop")]
    # a request with no first token counts as the worst: it waited to
    # the end of the drain
    ttft = [(stamps[k][0] if stamps[k] else t_end) - schedule[k].due_s
            for k in due_in]
    gaps, tokens_in = [], 0
    for ts in stamps:
        tokens_in += sum(window.t_open <= t < window.t_close for t in ts)
        gaps += [b - a for a, b in zip(ts, ts[1:])
                 if window.t_open <= b < window.t_close]
    lateness = [submitted_at[k] - schedule[k].due_s
                for k in range(i) if submitted_at[k] is not None]

    return dict(due_in=due_in, failed=failed, requests=requests, ttft=ttft,
                gaps=gaps, tokens_in=tokens_in, lateness=lateness)


def build(ctx, weight_seed: int, warm_seed: int):
    """The model, the engine and a ``submit`` for the mix's requests,
    warmed up: each prefill shape of the mix, the decode step and the
    reference forward compile here, before any ramp."""
    import jax.numpy as jnp

    from bigdl_tpu.serving import SamplingParams, ServingEngine
    from bigdl_tpu.utils.random_gen import RNG

    cfg, s, mix = ctx.cell.config, ctx.cell.settings, ctx.cell.traffic
    vocab = cfg["vocab_size"]
    RNG.set_seed(weight_seed)
    lm = harness.resolve(cfg["model"]["factory"])(cfg)
    # the reference forward compiles first, while the device is empty
    # but for the float32 weights
    t0 = time.perf_counter()
    lm._ensure_params()
    ref = reference.Reference(cfg, lm, mix)
    ref.warm_up()
    say(f"weights and reference forward in {time.perf_counter() - t0:.1f} s")
    engine_kw = dict(s["engine"])
    engine_kw["compute_dtype"] = jnp.dtype(engine_kw["compute_dtype"])
    parallel = cfg.get("parallel") or {}
    if "parallelism" in parallel:
        engine_kw["parallelism"] = parallel["parallelism"]
    t0 = time.perf_counter()
    eng = ServingEngine(lm, **engine_kw)
    say(f"engine built in {time.perf_counter() - t0:.1f} s")

    def submit(req) -> int:
        sampling = None if req.sampling_seed is None else SamplingParams(
            temperature=mix["sampling"]["temperature"],
            top_k=mix["sampling"]["top_k"], seed=req.sampling_seed)
        return eng.submit(req.prompt, max_new_tokens=req.max_new_tokens,
                          sampling=sampling)

    t0 = time.perf_counter()
    for req in traffic.warmup_requests(mix, warm_seed, vocab):
        submit(req)
    eng.drain()
    say(f"warm-up in {time.perf_counter() - t0:.1f} s; prefill shapes "
        f"{int(eng.metrics.metrics.get('serving/prefill_bucket_compiles')[0])}")
    return eng, submit, ref


def run(ctx) -> harness.Result:
    import jax

    cell, cfg, s, mix = ctx.cell, ctx.cell.config, ctx.cell.settings, \
        ctx.cell.traffic
    weight_seed, warm_seed, traffic_seed, sample_seed = \
        harness.seeds_from(ctx.seed, 4)
    vocab = cfg["vocab_size"]
    eng, submit, ref = build(ctx, weight_seed, warm_seed)
    metrics = eng.metrics.metrics
    schedule = traffic.serve_schedule(
        mix, traffic_seed, float(mix["ramp_s"]) + ctx.seconds
        + (TRACE_SECONDS if ctx.trace else 0.0), vocab,
        period_s=ctx.seconds)
    n_before = int(metrics.get("serving/submitted")[1])
    window = harness.Window(ctx.log, metrics, cell.series_names())
    d = drive(eng, submit, schedule, mix, ctx.seconds, window,
              ctx.trace_dir if ctx.trace else None)
    hbm_peak = harness.memory_peak_bytes(cell.chips)
    w = summarise(eng, schedule, d, window, mix, ctx.seconds)
    by_rid, due_in, failed, requests = \
        d["by_rid"], w["due_in"], w["failed"], w.pop("requests")

    # ---- correct, outside the window
    identities = reference.check_served(
        eng, {rid: schedule[k] for rid, k in by_rid.items()}, vocab,
        n_before)
    outs = {k: np.asarray(requests[k].output, np.int32) for k in due_in
            if k not in failed}
    kv_reserved = eng.pool.n_slots * eng.pool.kv_bytes_per_slot
    # the engine's pool and carries are ~10 GB: drop every reference
    # to it before the reference forward runs
    del eng, submit, requests
    gc.collect()
    in_use = (jax.local_devices()[0].memory_stats() or {}).get("bytes_in_use")
    slack = ref.check(schedule, outs, sample_seed)
    slack["device_bytes_in_use_before"] = in_use
    correct = (not identities and slack["ok"] and not failed
               and window.compiled_inside == 0)

    obs = harness.observations(
        ctx, d["setup_log"], series=window.series,
        spans={"steps": d["steps"], "ttft_s": w["ttft"], "gaps_s": w["gaps"]},
        counters={"kv_reserved_bytes": kv_reserved,
                  "hbm_peak_bytes": hbm_peak})
    stamps, t_end = d["stamps"], d["t_end"]
    ttft, gaps, tokens_in, lateness = w["ttft"], w["gaps"], \
        w["tokens_in"], w["lateness"]
    info = [{
        "requests_due_in_window": len(due_in), "failed": len(failed),
        "window_s": window.seconds, "tokens_in_window": tokens_in,
        "offered_requests_per_s": len(due_in) / window.seconds,
        "ttft_p50_ms": harness.percentile(ttft, 50) * 1e3,
        "ttft_p95_ms": harness.percentile(ttft, 95) * 1e3,
        "gap_p95_ms": harness.percentile(gaps, 95) * 1e3,
        "share_ttft_under_1s_and_gaps_under_200ms": float(np.mean([
            bool(stamps[k]) and stamps[k][0] - schedule[k].due_s < 1.0
            and all(b - a < 0.2 for a, b in zip(stamps[k], stamps[k][1:]))
            for k in due_in])),
        "generator_late_ms_p50": harness.percentile(lateness, 50) * 1e3,
        "generator_late_ms_max": max(lateness) * 1e3,
        "queue_at_window_close": d["queue_at_close"],
        "drain_s": t_end - window.t_close,
        "steps_in_window": len(d["steps"]),
        "compiled_in_window": window.compiled_inside,
        "setup_programs": obs["counters"]["setup_programs"],
        "setup_cache_hits": obs["counters"]["setup_cache_hits"],
        "setup_backend_s": d["setup_log"][2],
        "counter_identities_broken": identities, "reference": slack,
        "seed": ctx.seed, **ctx.device}]
    return harness.Result(
        end_to_end={
            "gap_p50_ms": harness.percentile(gaps, 50) * 1e3,
            "serve_tokens_per_s": tokens_in / window.seconds,
            "setup_s": (d["t_gen"] + window.t_open) - ctx.t_start},
        correct=correct, attempted=len(due_in), failed=len(failed),
        obs=obs, info=info)
