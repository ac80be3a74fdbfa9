"""Price the blockstore parameter plane (round-4 verdict item #3).

The DCN-boundary block-store exchange (``parallel/block_store.py``) is
correctness-proven (3-process pod with injected straggler) but its COST
was unknown. This bench answers three questions on a real multi-process
pod (localhost coordinator, 2 virtual CPU devices per rank — the same
rig the multihost tests use):

1. **No-straggler price**: steady-state step time of
   ``parameter_mode="blockstore"`` vs the compiled SPMD
   ``"partitioned"`` mode on an identical model/batch — what the host
   round-trip (encode → KV store → decode, full-vector reassembly)
   costs per iteration.
2. **Where gradient-drop wins**: a gradient-PUT straggler (delayed
   transfers, the reference's slow-BlockManager-fetch scenario) of
   varying severity, blockstore with drop enabled vs disabled. Drop
   bounds the stall at the calibrated deadline instead of the full
   delay — this is the plane's actual win domain.
3. **Honest non-win**: a COMPUTE straggler (rank sleeps before its
   gradient) stalls BOTH planes — static partition ownership means
   everyone still waits for the slow rank's weight partition
   (``docs/parallelism.md``; true of the reference too).

Run:  python -m benchmarks.blockstore_bench
Emits one JSON line per scenario; the summary table lives in
``docs/parallelism.md``.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

WARMUP_ITERS = 4
TIMED_ITERS = 12


def _model(n_hidden: int = 768, n_layers: int = 3):
    from bigdl_tpu.nn import Linear, ReLU, Sequential

    m = Sequential().add(Linear(256, n_hidden)).add(ReLU())
    for _ in range(n_layers - 1):
        m.add(Linear(n_hidden, n_hidden)).add(ReLU())
    m.add(Linear(n_hidden, 10))
    return m


def worker(pid: int, port: int, n: int, mode: str, put_delay: float,
           compute_delay: float, drop: float, out_dir: str) -> None:
    # every rank is pinned to the CPU, explicitly, before jax starts. A
    # chip belongs to one process: these n ranks are started side by
    # side, so this launcher is only safe while that pin stays here.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn import ClassNLLCriterion, LogSoftMax
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random_gen import RNG

    Engine.init_distributed(coordinator_address=f"localhost:{port}",
                            num_processes=n, process_id=pid)
    RNG.set_seed(11)
    rs = np.random.RandomState(0)
    samples = [Sample(rs.rand(256).astype(np.float32),
                      np.float32(i % 10 + 1)) for i in range(64 * n)]
    ds = DataSet.distributed(samples)
    model = _model().add(LogSoftMax())

    total = WARMUP_ITERS + TIMED_ITERS

    class SlowCompute:
        """Per-iteration sleep injected through the data stream (a slow
        host/input rank — the compute-straggler scenario)."""

        def __init__(self, delay):
            self.delay = delay

        def __call__(self, it):
            for b in it:
                time.sleep(self.delay)
                yield b

    if compute_delay > 0 and pid == n - 1:
        ds = ds >> SlowCompute(compute_delay)

    kw = {}
    if mode == "blockstore":
        from bigdl_tpu.parallel.block_store import CoordServiceBlockStore

        from tests.straggler import DelayedGradientPuts

        store = CoordServiceBlockStore()
        if put_delay > 0 and pid == n - 1:
            store = DelayedGradientPuts(store, delay_s=put_delay,
                                        first_iter=WARMUP_ITERS)
        kw = dict(parameter_mode="blockstore", block_store=store)
    else:
        from jax.sharding import Mesh

        kw = dict(parameter_mode="partitioned",
                  mesh=Mesh(np.asarray(jax.devices()).reshape(-1),
                            ("data",)))

    opt = Optimizer(model=model, dataset=ds,
                    criterion=ClassNLLCriterion(), batch_size=16 * n,
                    end_trigger=Trigger.max_iteration(total), **kw)
    opt.set_optim_method(SGD(learning_rate=0.05))
    if mode == "blockstore" and drop > 0:
        opt.set_drop_module_property(drop, batch_size=20,
                                     warmup_iteration=WARMUP_ITERS + 1)

    ticks = []

    def tick(s):
        # set_end_when REPLACES the end trigger — this both times each
        # iteration boundary and ends the run
        ticks.append(time.monotonic())
        return s["neval"] > total

    opt.set_end_when(Trigger(tick, lambda s: False))
    opt.optimize()

    deltas = np.diff(np.asarray(ticks))[WARMUP_ITERS:]
    result = {
        "pid": pid,
        "median_step_s": float(np.median(deltas)),
        "p90_step_s": float(np.percentile(deltas, 90)),
        "dropped": int(getattr(opt, "_bsp", None).dropped_total
                       if getattr(opt, "_bsp", None) is not None else 0),
    }
    with open(os.path.join(out_dir, f"rank_{pid}.json"), "w") as f:
        json.dump(result, f)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def run_scenario(tag: str, n: int, mode: str, put_delay: float = 0.0,
                 compute_delay: float = 0.0, drop: float = 0.0,
                 timeout: int = 420) -> dict:
    import tempfile

    out_dir = tempfile.mkdtemp(prefix=f"bsbench_{tag}_")
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker",
         str(pid), str(port), str(n), mode, str(put_delay),
         str(compute_delay), str(drop), out_dir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for pid in range(n)]
    try:
        outs = [p.communicate(timeout=timeout)[0] for p in procs]
    finally:
        for p in procs:          # a hung rank must not orphan its peers
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(
                f"{tag}: rank {pid} rc={p.returncode}\n{out[-2000:]}")
    ranks = []
    for pid in range(n):
        with open(os.path.join(out_dir, f"rank_{pid}.json")) as f:
            ranks.append(json.load(f))
    res = {
        "scenario": tag, "n_procs": n, "mode": mode,
        "put_delay_s": put_delay, "compute_delay_s": compute_delay,
        "drop": drop,
        "median_step_s": round(max(r["median_step_s"] for r in ranks), 4),
        "p90_step_s": round(max(r["p90_step_s"] for r in ranks), 4),
        "dropped_total": sum(r["dropped"] for r in ranks),
    }
    print(json.dumps(res), flush=True)
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", nargs=8, default=None)
    ap.add_argument("--n", type=int, default=2)
    args = ap.parse_args()
    if args.worker:
        pid, port, n, mode, put_d, comp_d, drop, out_dir = args.worker
        worker(int(pid), int(port), int(n), mode, float(put_d),
               float(comp_d), float(drop), out_dir)
        return

    n = args.n
    # 1) no-straggler price
    run_scenario("price_partitioned", n, "partitioned")
    run_scenario("price_blockstore", n, "blockstore")
    # 2) put-delay straggler severity sweep: drop on vs off
    for d in (0.1, 0.3, 0.6):
        run_scenario(f"putlag{d}_nodrop", n, "blockstore", put_delay=d)
        run_scenario(f"putlag{d}_drop", n, "blockstore", put_delay=d,
                     drop=0.5)
    # 3) compute straggler hits both planes (static ownership)
    run_scenario("compute_lag_partitioned", n, "partitioned",
                 compute_delay=0.3)
    run_scenario("compute_lag_blockstore_drop", n, "blockstore",
                 compute_delay=0.3, drop=0.5)


if __name__ == "__main__":
    main()
