"""Training cells: ONE ``Optimizer.optimize()`` call, measured at its
iteration boundaries.

The optimizer evaluates its end trigger once per iteration, after the
``float(loss)`` sync of the iteration before. The trigger here stamps
each of those boundaries, opens the window after the warm-up iterations
and closes it ``--seconds`` later; a traced run then records a few
more iterations with the profiler on, outside the window. The data set handed to the
optimizer is the benchmark's own wrapper, which times every ``next()``
of the batch iterator: that is the input path's share of an iteration.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness, reference, traffic
from benchmark.harness import say

WARMUP_ITERATIONS = 3
#: the traced run records this many iterations after the window closes
TRACE_ITERATIONS = 5


def _timed_dataset(inner, waits: list):
    from bigdl_tpu.dataset.dataset import AbstractDataSet

    class TimedDataSet(AbstractDataSet):
        """Delegates to the program's data set; times ``next()`` of the
        training iterator."""

        def __init__(self, inner) -> None:
            self.inner = inner

        def size(self):
            return self.inner.size()

        def shuffle(self):
            self.inner.shuffle()

        def transform(self, transformer):
            return TimedDataSet(self.inner.transform(transformer))

        def data(self, train: bool):
            it = self.inner.data(train)
            if not train:
                return it

            def timed():
                while True:
                    t0 = time.perf_counter()
                    try:
                        batch = next(it)
                    except StopIteration:
                        return
                    waits.append(time.perf_counter() - t0)
                    yield batch

            return timed()

    return TimedDataSet(inner)


def _build_optimizer(ctx, samples, waits, data_seed):
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.optim import Optimizer

    cfg, s = ctx.cell.config, ctx.cell.settings
    parallel = cfg.get("parallel") or {}
    kw = {}
    if parallel.get("dataset") == "distributed":
        from bigdl_tpu.utils.engine import Engine

        inner = DataSet.distributed(samples, seed=data_seed)
        kw = dict(distributed=True, mesh=Engine.mesh(("data",)),
                  **{k: parallel[k] for k in ("parameter_mode", "compress")
                     if k in parallel})
    else:
        inner = DataSet.array(samples, seed=data_seed)
    model = harness.resolve(cfg["model"]["factory"])(cfg)
    criterion = harness.resolve(s["criterion"]["factory"])(
        **s["criterion"].get("args", {}))
    opt = Optimizer(model=model, dataset=_timed_dataset(inner, waits),
                    criterion=criterion,
                    batch_size=s["batch_size"] * ctx.cell.chips, **kw)
    opt.set_compute_dtype(s["compute_dtype"])
    opt.set_optim_method(harness.resolve(s["optim_method"]["factory"])(
        **s["optim_method"].get("args", {})))
    # optimize() retries ANY exception from a checkpoint; a compiler
    # refusal must surface once, not after five recompiles
    opt.retry_times = 1
    return opt


def _reference_first_loss(ctx, opt, samples, order_seed):
    """The plain reference's float32 loss on the batch and the weights
    of the optimizer's first iteration, where the configuration has a
    reference that computes one; else None. The data set's order is a
    function of its seed, so a second iterator yields the same first
    batch."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch

    ref = reference.load_reference(ctx.cell.config)
    if ref is None or not hasattr(ref, "mean_cross_entropy"):
        return None
    opt.model._ensure_params()       # the weights optimize() starts from
    batch = next(DataSet.array(samples, seed=order_seed).transform(
        SampleToMiniBatch(ctx.cell.settings["batch_size"])).data(train=True))
    loss = jax.jit(lambda p, x, y: ref.mean_cross_entropy(
        p, x, y, ctx.cell.config))(
            opt.model.params, jnp.asarray(batch.get_input()),
            jnp.asarray(batch.get_target()).astype(jnp.int32))
    return float(loss)


def run(ctx) -> harness.Result:
    from bigdl_tpu.optim import Trigger
    from bigdl_tpu.utils.random_gen import RNG

    cell, s = ctx.cell, ctx.cell.settings
    weight_seed, data_seed, order_seed = harness.seeds_from(ctx.seed, 3)
    RNG.set_seed(weight_seed)
    t0 = time.perf_counter()
    samples = traffic.train_samples(cell.traffic, data_seed, cell.config)
    say(f"data: {len(samples)} samples in {time.perf_counter() - t0:.2f} s")
    waits: list = []
    opt = _build_optimizer(ctx, samples, waits, order_seed)
    ref_loss = None if cell.chips > 1 else \
        _reference_first_loss(ctx, opt, samples, order_seed)
    window = harness.Window(ctx.log, opt.metrics, cell.series_names())
    stamps, losses = [], []          # one per finished iteration
    mark = {}                        # indices into stamps / waits

    def end_when(state) -> bool:
        now = time.perf_counter()
        done = state["neval"] - 1
        if done > len(stamps):       # an iteration finished since last call
            stamps.append(now)
            losses.append(float(state["loss"]))
        if window.t_open is None:
            if done >= WARMUP_ITERATIONS:
                window.open(now)
                mark.update(open_iter=done, open_wait=len(waits),
                            setup_log=ctx.log.snapshot())
            return False
        if window.t_close is None:
            if now - window.t_open < ctx.seconds:
                return False
            window.close(now)
            # while the step's executable is loaded: see the function
            mark.update(close_iter=done, close_wait=len(waits),
                        hbm_peak=harness.memory_peak_bytes(cell.chips))
            if ctx.trace:
                # the traced iterations come after the window, so the
                # profiler's cost is in none of the window's numbers
                mark["trace_stop"] = state["neval"] + TRACE_ITERATIONS
                opt.set_profile(str(ctx.trace_dir), state["neval"],
                                TRACE_ITERATIONS)
        return state["neval"] >= mark.get("trace_stop", 0)

    # the peek predicts "not yet": at the end one batch is prefetched
    # and dropped, which is outside the window
    opt.set_end_when(Trigger(end_when, lambda state: False))
    opt.optimize()

    n_iter = mark["close_iter"] - mark["open_iter"]
    per_sample = s["items_per_sample"]
    batch = s["batch_size"] * cell.chips
    items = n_iter * batch * per_sample
    items_per_s = items / window.seconds / cell.chips
    in_window = losses[mark["open_iter"]:mark["close_iter"]]
    finite = bool(np.isfinite(losses).all())
    learned = bool(np.mean(in_window[-10:]) < losses[0])
    # bf16 compute against the float32 reference, same batch and weights
    agrees = ref_loss is None or \
        abs(losses[0] - ref_loss) <= reference.TRAIN_LOSS_RTOL * ref_loss
    correct = finite and learned and agrees and \
        window.compiled_inside == 0

    obs = harness.observations(
        ctx, mark["setup_log"], series=window.series,
        spans={"input_wait_s": waits[mark["open_wait"]:mark["close_wait"]]},
        counters={"items_per_s_per_chip": items_per_s,
                  "hbm_peak_bytes": mark["hbm_peak"]})
    iteration_s = np.diff(stamps[mark["open_iter"] - 1:mark["close_iter"]])
    info = [{
        "iterations_in_window": n_iter, "window_s": window.seconds,
        "iteration_ms_median": harness.median(iteration_s) * 1e3,
        "iteration_ms_by_quarter": [
            float(np.mean(q)) * 1e3
            for q in np.array_split(iteration_s, 4) if len(q)],
        "compiled_in_window": window.compiled_inside,
        "setup_programs": obs["counters"]["setup_programs"],
        "setup_cache_hits": obs["counters"]["setup_cache_hits"],
        "setup_backend_s": mark["setup_log"][2],
        "loss_first": losses[0], "reference_loss_first": ref_loss,
        "loss_window_last10": float(
            np.mean(in_window[-10:])), "losses_finite": finite,
        "seed": ctx.seed, **ctx.device}]
    return harness.Result(
        end_to_end={"train_items_per_s": items_per_s,
                    "setup_s": window.t_open - ctx.t_start},
        correct=correct, attempted=n_iter,
        failed=0 if finite else int((~np.isfinite(in_window)).sum()),
        obs=obs, info=info)
