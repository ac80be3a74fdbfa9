"""Find a serving cell's knee: the highest rate the tree sustains
without a growing queue. Made ONCE when a cell is defined (and again by
a later benchmark PR when the rate has been overtaken); its result is
written into the mix's data file as numbers. One process, one set-up,
one engine; each rate runs the mix's ramp and then ``--seconds``.

    python3 benchmark/sweep.py --workload <name> --seed <n> --rates 3,4.5,6 --seconds 15
"""

import time

T_START = time.perf_counter()

import argparse                                            # noqa: E402
import json                                                # noqa: E402
import pathlib                                             # noqa: E402
import sys                                                 # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on any backend; never a result")
    args = ap.parse_args(argv)

    from benchmark import harness, traffic
    from benchmark.harness import say
    from benchmark.runners import serve

    if args.rehearse_cpu:
        harness.TAG = harness.REHEARSAL_TAG
    cell = harness.Cell(args.workload, rehearsal=args.rehearse_cpu)
    device, _ = harness.device_gate(cell.chips, args.rehearse_cpu)
    from bigdl_tpu.utils.compile_cache import CompileLog, enable_compile_cache

    enable_compile_cache(min_compile_time_secs=0)
    ctx = harness.Context(cell=cell, seed=args.seed, log=CompileLog())
    eng, submit, _ = serve.build(ctx, *harness.seeds_from(args.seed, 2))
    say("set-up", round(time.perf_counter() - T_START, 1), "s", device)
    metrics = eng.metrics.metrics
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic,
                   arrivals=dict(cell.traffic["arrivals"], rate_per_s=rate))
        schedule = traffic.serve_schedule(
            mix, args.seed + n, float(mix["ramp_s"]) + args.seconds,
            cell.config["vocab_size"], period_s=args.seconds)
        window = harness.Window(ctx.log, metrics, ["serving/slot_occupancy",
                                                   "serving/queue_depth"])
        d = serve.drive(eng, submit, schedule, mix, args.seconds, window)
        w = serve.summarise(eng, schedule, d, window, mix, args.seconds)
        eng.drain()
        occupancy = window.series["serving/slot_occupancy"]
        queue = window.series["serving/queue_depth"]
        say("rate", json.dumps({
            "rate_per_s": rate, "due_in_window": len(w["due_in"]),
            "tokens_per_s": w["tokens_in"] / window.seconds,
            "offered_tokens_per_s": sum(
                schedule[k].max_new_tokens for k in w["due_in"])
            / window.seconds,
            "queue_at_window_close": d["queue_at_close"],
            "queue_mean_last_third": sum(queue[-len(queue) // 3:])
            / max(1, len(queue) // 3),
            "ttft_p50_ms": harness.percentile(w["ttft"], 50) * 1e3,
            "ttft_p95_ms": harness.percentile(w["ttft"], 95) * 1e3,
            "gap_p50_ms": harness.percentile(w["gaps"], 50) * 1e3,
            "gap_p95_ms": harness.percentile(w["gaps"], 95) * 1e3,
            "slot_occupancy_mean": sum(occupancy) / len(occupancy),
            "failed": len(w["failed"]),
            "drain_s": d["t_end"] - window.t_close,
            "compiled_in_window": window.compiled_inside}))
    return harness.REHEARSAL_EXIT if args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
