"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

loads the cell's entry from ``BENCHMARK.json``, finds its configuration,
its traffic or job file and its per-layer metrics by name, checks the
device, hands the cell to the runner of its kind (``runners/train.py`` or
``runners/serve.py``), and prints as its last line one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``: the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics (and
a ``breakdown``) with ``--trace 1``. Every line before the last is
information. It needs a TPU listed in ``peaks.json`` and exits non-zero,
before any model is built, on anything else.

    python3 benchmark/run.py --workload <name> --rehearse-cpu

runs the same code at the toy sizes the files give under ``rehearsal``,
on whatever backend jax has, to debug the harness where there is no
chip. Every line says so, the last line is never printed, and the exit
status is 4: a rehearsal is not a result.
"""

import time

T_START = time.perf_counter()     # set-up is counted from here

import argparse                                            # noqa: E402
import json                                                # noqa: E402
import pathlib                                             # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on any backend; never a result")
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.harness import say

    if args.rehearse_cpu:
        harness.TAG = harness.REHEARSAL_TAG
    cell = harness.Cell(args.workload, rehearsal=args.rehearse_cpu)
    seconds = args.seconds if args.seconds is not None else (
        2.0 if args.rehearse_cpu else float(cell.bench["run_seconds"]))

    try:
        import bigdl_tpu  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"FAIL: the program under test is not in this "
                         f"checkout ({e}): nothing to measure")

    device, peaks = harness.device_gate(cell.chips, args.rehearse_cpu)
    # every line before the last names the device and the seed
    harness.TAG += (f"[{device['platform']} {device['kind']!r} "
                    f"x{device['count']} seed {args.seed}] ")
    say("cell", json.dumps({
        "workload": cell.name, "cell_kind": cell.kind, "seed": args.seed,
        "seconds": seconds, "trace": args.trace, **device}))

    from bigdl_tpu.utils.compile_cache import CompileLog, enable_compile_cache

    # threshold 0: the many small set-up programs are cached too
    say("compile cache:", enable_compile_cache(min_compile_time_secs=0))
    ctx = harness.Context(
        cell=cell, seed=args.seed, seconds=seconds, trace=bool(args.trace),
        rehearsal=args.rehearse_cpu, t_start=T_START, log=CompileLog(),
        device=device, peaks=peaks,
        trace_dir=ROOT / ".cache" / "bench_trace" / cell.name)
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)   # an older trace
    result = harness.resolve(f"benchmark.runners.{cell.kind}:run")(ctx)

    for line in result.info:
        say("info", json.dumps(line))
    if args.trace:
        metrics = harness.read_per_layer(cell, result.obs)
    else:
        metrics = {m["name"]: {"value": float(result.end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end()}
    device = dict(device, memory_peak_bytes=max(
        harness.memory_peak_bytes(cell.chips),
        result.obs["counters"]["hbm_peak_bytes"]))
    last = {"correct": bool(result.correct), "attempted": result.attempted,
            "failed": result.failed, "metrics": metrics, "device": device}
    if args.trace:
        reduced = result.obs.get("trace")
        if reduced is not None:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            last["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
        elif not args.rehearse_cpu:
            raise SystemExit("FAIL: the traced run recorded no program on "
                             "a device: no busy time to report")
    if args.rehearse_cpu:
        # names only: a number from a CPU run is never written under the
        # name of a device metric
        say("would report", json.dumps(dict(last, metrics=sorted(metrics),
                                            breakdown=None)))
        say("rehearsal finished at toy size. This is not a result; exit "
            f"status {harness.REHEARSAL_EXIT}.")
        return harness.REHEARSAL_EXIT
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
