"""The training loop's batch feeder (``optim/feeder.py``): the same batches
in the same order as a loop that stacks and steps by itself, never a batch
drawn that a count-based trigger will not train on, a ring that does not
alias what was placed, no thread left behind, ``stack_samples(out=)``; and
the loop's one step in flight behind the host (section (g)): the same
trajectory as the synchronous order, the order itself, and what a trigger's
peek promises."""

import threading

import numpy as np
import pytest

from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.dataset import AbstractDataSet
from bigdl_tpu.dataset.sample import Sample, batch_buffers, stack_samples
from bigdl_tpu.dataset.transformer import SampleToMiniBatch
from bigdl_tpu.optim.feeder import DEPTH, BatchFeeder
from bigdl_tpu.optim.metrics import Metrics

JOIN_S = 20.0


def _samples(n=24, dim=6, seed=0):
    rng = np.random.RandomState(seed)
    return [Sample(rng.rand(dim).astype(np.float32), np.int32(i % 3 + 1))
            for i in range(n)]


def _optimizer(samples, batch_size=4, seed=5, dataset=None):
    from bigdl_tpu.nn import ClassNLLCriterion, Linear, LogSoftMax, Sequential
    from bigdl_tpu.optim import SGD, Optimizer
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(11)
    dim = samples[0].feature().shape[0]
    model = Sequential().add(Linear(dim, 3)).add(LogSoftMax())
    opt = Optimizer(model=model,
                    dataset=dataset or DataSet.array(samples, seed=seed),
                    criterion=ClassNLLCriterion(), batch_size=batch_size)
    opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9))
    opt.retry_times = 1
    return opt


def _feeder_threads():
    return [t for t in threading.enumerate()
            if t.name == "bigdl-batch-feeder"]


def _assert_no_feeder_thread():
    for t in _feeder_threads():
        t.join(JOIN_S)
    assert not _feeder_threads()


class _Losses:
    """An end trigger that keeps every iteration's loss and stops after
    ``n``; its peek counts like ``max_iteration``."""

    def __init__(self, n):
        self.n, self.losses = n, []

    def trigger(self):
        from bigdl_tpu.optim import Trigger

        def fn(state):
            if state["loss"] is not None and \
                    len(self.losses) < state["neval"] - 1:
                self.losses.append(state["loss"])
            return state["neval"] > self.n

        return Trigger(fn, lambda s: s["neval"] > self.n)


# -- (a) the same trajectory as a loop that stacks and steps by itself --------

def _reference(samples, batch, seed, n_iter):
    """The plain loop: the optimizer's own step program, its key schedule,
    batches stacked here from the same data set order, every step read
    before the next is launched. Returns the losses and the parameters
    after each step (host copies; index k-1 is step k)."""
    import jax

    from bigdl_tpu.utils.random_gen import RNG

    ref = _optimizer(samples, batch, seed)
    ref.model._ensure_params()
    step, _, params, opt_state, model_state = ref._prepare()
    base_key = RNG.next_key()
    it = DataSet.array(samples, seed=seed).data(train=True)
    losses, after = [], []
    for k in range(1, n_iter + 1):
        b = stack_samples([next(it) for _ in range(batch)])
        params, opt_state, model_state, loss = step(
            params, opt_state, model_state, jax.random.fold_in(base_key, k),
            b.get_input(), b.get_target())
        losses.append(float(loss))
        after.append(_host(params))
    return losses, after


def _host(tree):
    import jax

    return jax.tree_util.tree_map(np.array, tree)


def _assert_trees_equal(got, want):
    import jax

    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


class _Recorded:
    """Wraps an optimizer's step, validation and checkpoint: ``events``
    holds ``("dispatch", k)`` at step k's launch, ``("read", k)`` when
    the loop takes ``float()`` of its loss, ``("validate", neval)`` and
    ``("checkpoint", neval)`` with the parameters each was handed in
    ``held``; ``losses`` is what the loop read, in order."""

    def __init__(self, opt, first=1, on_dispatch=None):
        self.events, self.losses, self.held = [], [], {}
        rec, prepare = self, opt._prepare
        validate, checkpoint = opt._run_validation, opt._checkpoint

        class Loss:
            def __init__(self, k, value):
                self.k, self.value = k, value

            def __float__(self):
                rec.events.append(("read", self.k))
                rec.losses.append(float(self.value))
                return rec.losses[-1]

        def recorded_prepare():
            step, *rest = prepare()
            launched = [first - 1]

            def recorded(*a):
                launched[0] += 1
                rec.events.append(("dispatch", launched[0]))
                if on_dispatch is not None:
                    on_dispatch(launched[0])
                *out, loss = step(*a)
                return (*out, Loss(launched[0], loss))

            return (recorded, *rest)

        def recorded_validation(params, model_state, state):
            rec.events.append(("validate", state["neval"]))
            rec.held["validate", state["neval"]] = _host(params)
            return validate(params, model_state, state)

        def recorded_checkpoint(state, params, model_state, opt_state):
            rec.events.append(("checkpoint", state["neval"]))
            rec.held["checkpoint", state["neval"]] = _host(params)
            return checkpoint(state, params, model_state, opt_state)

        opt._prepare = recorded_prepare
        opt._run_validation = recorded_validation
        opt._checkpoint = recorded_checkpoint

    def order(self, *kinds):
        return [e for e in self.events if e[0] in (kinds or (
            "dispatch", "read"))]

    def ahead(self):
        """The steps launched before the step before was read."""
        return {k for i, (what, k) in enumerate(self.events)
                if what == "dispatch" and ("read", k - 1) in self.events[i:]}


def _pipelined(n):
    """dispatch 1, then dispatch k+1 before read k, up to n."""
    out = [("dispatch", 1)]
    for k in range(1, n):
        out += [("dispatch", k + 1), ("read", k)]
    return out + [("read", n)]


def _with_validation_and_checkpoints(opt, tmp_path):
    from bigdl_tpu.optim import Top1Accuracy, Trigger

    opt.set_validation(Trigger.every_epoch(), DataSet.array(_samples(8, seed=3)),
                       [Top1Accuracy()], batch_size=4)
    opt.set_checkpoint(str(tmp_path / "ckpt"), Trigger.several_iteration(3))
    opt.overwrite_checkpoint = False
    return opt


@pytest.mark.parametrize("case", ["hand_built_peek", "max_iteration",
                                  "max_epoch_validation_checkpoints"])
def test_losses_equal_a_synchronous_loop_bit_for_bit(case, tmp_path):
    """Losses, final parameters and state of the loop, with a step in
    flight wherever the triggers allow, against the plain loop's."""
    from bigdl_tpu.optim import Trigger

    batch, seed = 4, 5
    samples = _samples()                      # 24: six iterations an epoch
    opt = _optimizer(samples, batch, seed)
    if case == "hand_built_peek":
        n_iter, got = 9, _Losses(9)
        opt.set_end_when(got.trigger())
    elif case == "max_iteration":
        n_iter = 9
        opt.set_end_when(Trigger.max_iteration(n_iter))
    else:
        n_iter = 12
        opt.set_end_when(Trigger.max_epoch(2))
        _with_validation_and_checkpoints(opt, tmp_path)
    rec = _Recorded(opt)
    opt.optimize()

    want, after = _reference(samples, batch, seed, n_iter)
    assert rec.losses == want
    if case == "hand_built_peek":
        assert got.losses == want
    assert len(set(want)) == n_iter          # a trajectory, not a constant
    _assert_trees_equal(opt.model.params, after[-1])
    assert opt.optim_method.state["neval"] == n_iter + 1
    assert opt.optim_method.state.get("epoch", 1) == 1 + n_iter // 6
    # and the loop did run ahead: every step but those that follow a
    # validation or a checkpoint (after steps 3, 6, 9, 12) and the first
    held_back = {1} if case != "max_epoch_validation_checkpoints" \
        else {1, 4, 7, 10}
    assert rec.ahead() == set(range(1, n_iter + 1)) - held_back
    assert opt.metrics.values("launched ahead") == [
        float(k not in held_back) for k in range(1, n_iter + 1)]
    for series in ("computing time", "data fetch time", "dispatch time",
                   "loss sync time", "input ready", "records/second"):
        assert opt.metrics.get(series)[1] == n_iter, series


# -- (b) never a batch drawn that will not be trained on ----------------------

class _CountingDataSet(AbstractDataSet):
    """Yields MiniBatches itself and counts what was drawn; ``limit``
    makes the training iterator finite."""

    def __init__(self, samples, batch, limit=None):
        self.samples, self.batch, self.limit = samples, batch, limit
        self.drawn = 0

    def size(self):
        return len(self.samples)

    def data(self, train):
        def batches():
            per_epoch = len(self.samples) // self.batch
            k = 0
            while self.limit is None or k < self.limit:
                i = (k % per_epoch) * self.batch
                self.drawn += 1
                k += 1
                yield stack_samples(self.samples[i:i + self.batch])

        return batches() if train else iter(
            [stack_samples(self.samples[:self.batch])])


@pytest.mark.parametrize("n", [1, DEPTH, DEPTH + 3])
def test_max_iteration_draws_exactly_n_batches(n):
    from bigdl_tpu.optim import Trigger

    ds = _CountingDataSet(_samples(), 4)
    opt = _optimizer(ds.samples, None, dataset=ds)
    opt.set_end_when(Trigger.max_iteration(n))
    opt.optimize()
    assert ds.drawn == n
    assert opt.metrics.get("computing time")[1] == n
    _assert_no_feeder_thread()


def test_max_epoch_draws_exactly_its_batches():
    from bigdl_tpu.optim import Trigger

    ds = _CountingDataSet(_samples(24), 4)
    opt = _optimizer(ds.samples, None, dataset=ds)
    opt.set_end_when(Trigger.max_epoch(2))
    opt.optimize()
    assert ds.drawn == 12


def test_a_finite_iterator_ends_the_loop_cleanly():
    from bigdl_tpu.optim import Trigger

    ds = _CountingDataSet(_samples(), 4, limit=5)
    opt = _optimizer(ds.samples, None, dataset=ds)
    opt.set_end_when(Trigger.max_iteration(50))
    opt.optimize()
    assert ds.drawn == 5
    assert opt.metrics.get("computing time")[1] == 5
    _assert_no_feeder_thread()


def test_a_peek_that_wrongly_says_stop_only_pauses_the_feeder():
    """``peek`` says the loop ends, ``fn`` says it runs: the loop asks all
    the same and the batch is drawn then, one at a time."""
    from bigdl_tpu.optim import Trigger

    ds = _CountingDataSet(_samples(), 4)
    opt = _optimizer(ds.samples, None, dataset=ds)
    opt.set_end_when(Trigger(lambda s: s["neval"] > 4, lambda s: True))
    opt.optimize()
    assert ds.drawn == 4
    assert opt.metrics.values("input ready") == [0.0] * 4


# -- (c) the ring does not alias what was placed ------------------------------

def _feeder(samples, batch, place, metrics=None, peek=lambda s: False):
    from bigdl_tpu.optim import Trigger

    batcher = SampleToMiniBatch(batch)
    ds = DataSet.array(samples, seed=3).transform(batcher)
    state = {"neval": 1, "epoch": 1, "epoch_finished": False, "loss": None}
    f = BatchFeeder(ds, batcher, place, Trigger(lambda s: False, peek),
                    state, metrics or Metrics())
    assert batcher.staging is None           # set only around data()
    return f


def _local_place(batch):
    import jax

    return jax.device_put(batch.get_input()), \
        jax.device_put(batch.get_target())


def test_a_placed_batch_is_unchanged_after_the_ring_has_gone_round():
    samples, batch = _samples(64, dim=5), 4
    f = _feeder(samples, batch, _local_place)
    f.start(0)
    try:
        first = f.get()
        kept = np.array(first[0]), np.array(first[1])
        later = [f.get() for _ in range(2 * DEPTH + 3)]   # past the ring
    finally:
        f.close()
    np.testing.assert_array_equal(np.asarray(first[0]), kept[0])
    np.testing.assert_array_equal(np.asarray(first[1]), kept[1])
    # and they are the data set's batches, in its order
    it = DataSet.array(samples, seed=3).data(train=True)
    for inp, tgt, bsz in [first] + later:
        want = stack_samples([next(it) for _ in range(batch)])
        np.testing.assert_array_equal(np.asarray(inp), want.get_input())
        np.testing.assert_array_equal(np.asarray(tgt), want.get_target())
        assert bsz == batch


def test_the_ring_refills_a_slot_only_after_its_placement_is_done(
        monkeypatch):
    """Where placing does not copy (a device with memory of its own is
    stood in for by a placement that keeps the host arrays), a slot's
    arrays come round again after DEPTH + 1 batches, and only after
    ``block_until_ready`` of what was placed from them."""
    import jax

    samples, batch = _samples(64, dim=5), 4
    waited = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (waited.append(x), real(x))[1])
    placed = []

    def place(b):
        placed.append((b.get_input(), b.get_target()))
        return placed[-1]

    f = _feeder(samples, batch, place)
    f._aliases_host = False
    f.start(0)
    try:
        for _ in range(2 * (DEPTH + 1)):
            f.get()
    finally:
        f.close()
    n = DEPTH + 1
    assert all(placed[i][0] is placed[i + n][0] for i in range(n))
    assert len({id(p[0]) for p in placed[:n]}) == n
    assert [w[0] is placed[i][0] for i, w in enumerate(waited[:n])] == \
        [True] * n


# -- (d) no thread left behind ------------------------------------------------

def test_no_feeder_thread_after_optimize_returns():
    from bigdl_tpu.optim import Trigger

    opt = _optimizer(_samples())
    opt.set_end_when(Trigger.max_iteration(3))
    opt.optimize()
    _assert_no_feeder_thread()


def test_no_feeder_thread_after_training_preempted(tmp_path):
    from bigdl_tpu.optim import Trigger
    from bigdl_tpu.optim.optimizer import TrainingPreempted

    opt = _optimizer(_samples())
    opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(100))

    def evict(state):
        if state["neval"] > 2:
            opt._preempt_flag = True
        return False

    opt.set_end_when(Trigger(evict, lambda s: False))
    with pytest.raises(TrainingPreempted):
        opt.optimize()
    _assert_no_feeder_thread()


def test_no_feeder_thread_after_an_exception_inside_the_step():
    from bigdl_tpu.optim import Trigger

    opt = _optimizer(_samples())
    opt.set_end_when(Trigger.max_iteration(5))
    real = opt._prepare

    def prepare():
        step, *rest = real()
        calls = []

        def failing(*a):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("step failed")
            return step(*a)

        return (failing, *rest)

    opt._prepare = prepare
    with pytest.raises(RuntimeError, match="step failed"):
        opt.optimize()
    _assert_no_feeder_thread()


@pytest.mark.parametrize("end", ["runs_on", "stops_after_the_step_in_flight"])
def test_an_error_in_the_input_path_reaches_the_loop(end):
    """The fetch for step 2 fails with step 1 in flight: step 1 is booked
    first, and the error is raised even where the end trigger (its peek
    said "runs") then says stop."""
    from bigdl_tpu.optim import Trigger

    class Broken(_CountingDataSet):
        def data(self, train):
            inner = super().data(train)
            if not train:
                return inner

            def gen():
                yield next(inner)
                raise OSError("disk gone")

            return gen()

    ds = Broken(_samples(), 4)
    opt = _optimizer(ds.samples, None, dataset=ds)
    opt.set_end_when(Trigger.max_iteration(5) if end == "runs_on" else
                     Trigger(lambda s: s["neval"] > 1, lambda s: False))
    with pytest.raises(OSError, match="disk gone"):
        opt.optimize()
    assert opt.metrics.get("computing time")[1] == 1
    assert opt.optim_method.state["neval"] == 2
    _assert_no_feeder_thread()


# -- (e) stack_samples(out=) --------------------------------------------------

def _mixed_samples(n=7, seed=2):
    rng = np.random.RandomState(seed)
    return [Sample([rng.rand(3, 4).astype(np.float32),
                    rng.randint(0, 9, (5,)).astype(np.int64)],
                   [np.int32(i), rng.rand(2).astype(np.float64)])
            for i in range(n)]


@pytest.mark.parametrize("make", [_mixed_samples, _samples],
                         ids=["multi_feature", "scalar_label"])
def test_stack_samples_out_equals_fresh(make):
    samples = make()
    fresh = stack_samples(samples)
    out = batch_buffers(samples)
    built = stack_samples(samples, out=out)

    def leaves(x):
        return x if isinstance(x, list) else [x]

    for a, b, buf in zip(leaves(fresh.get_input()), leaves(built.get_input()),
                         out[0]):
        assert b is buf                       # built IN the given arrays
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    for a, b, buf in zip(leaves(fresh.get_target()),
                         leaves(built.get_target()), out[1]):
        assert b is buf
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


def test_stack_samples_without_out_returns_arrays_of_its_own():
    samples = _samples(8)
    first = stack_samples(samples[:4])
    kept = first.get_input().copy(), first.get_target().copy()
    second = stack_samples(samples[4:])
    assert not np.shares_memory(first.get_input(), second.get_input())
    np.testing.assert_array_equal(first.get_input(), kept[0])
    np.testing.assert_array_equal(first.get_target(), kept[1])
    # and so does a batcher nobody gave a staging
    a, b = list(SampleToMiniBatch(4)(iter(samples)))
    assert not np.shares_memory(a.get_input(), b.get_input())


def test_series_have_one_sample_a_batch_handed_over():
    m = Metrics()
    f = _feeder(_samples(64), 4, _local_place, metrics=m)
    f.start(0)
    try:
        for _ in range(5):
            f.get()
    finally:
        f.close()
    assert m.get("input ready")[1] == 5
    # built: the five handed over and at most DEPTH more, queued
    assert 5 <= m.get("batch build time")[1] <= 5 + DEPTH + 1
    assert all(v > 0.0 for v in m.values("batch build time"))


def test_many_hand_overs_under_a_short_switch_interval_lose_nothing():
    """The queue, the starved flag and the stop flag are shared by two
    threads: 1,500 batches through a feeder whose peek pauses it every
    third batch arrive once each, in order, and it stops when told."""
    import sys

    samples = [Sample(np.full(2, i, np.float32), np.int32(i))
               for i in range(64)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    f = _feeder(samples, 1, lambda b: (b.get_input().copy(),
                                       b.get_target().copy()),
                peek=lambda s: s["neval"] % 3 == 0)
    f._aliases_host = False                  # the placement above copies
    f.start(0)
    try:
        got = [int(f.get()[1][0]) for _ in range(1500)]
    finally:
        f.close()
        sys.setswitchinterval(old)
    f._thread.join(JOIN_S)
    assert not f._thread.is_alive()
    it = DataSet.array(samples, seed=3).data(train=True)
    assert got == [int(next(it).label()) for _ in range(1500)]


def test_nothing_is_built_between_get_and_launched():
    """The producer leaves the host to the step's launch: room in the
    queue is used only once the loop says the step is under way."""
    import time

    built = []
    f = _feeder(_samples(64), 4, lambda b: (built.append(1), _local_place(b))[1])
    f.start(0)
    try:
        deadline = time.monotonic() + JOIN_S
        while len(built) < DEPTH and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)
        assert len(built) == DEPTH           # the queue is full: it waits
        f.get()
        time.sleep(0.2)
        assert len(built) == DEPTH           # room, but the launch is on
        f.launched()
        while len(built) < DEPTH + 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(built) == DEPTH + 1
    finally:
        f.close()


# -- (g) one step in flight behind the host -----------------------------------

def test_step_k_plus_1_is_launched_before_loss_k_is_read():
    from bigdl_tpu.optim import Trigger

    opt = _optimizer(_samples())
    opt.set_end_when(Trigger.max_iteration(5))
    rec = _Recorded(opt)
    opt.optimize()
    assert rec.order() == _pipelined(5)
    _assert_no_feeder_thread()


def test_a_validation_or_checkpoint_iteration_is_read_first(tmp_path):
    """Validation (after the epoch's last step, 6) and checkpoints (after
    steps 3 and 6) get the parameters as they stand after THEIR step,
    so that step is read with nothing launched; the next one starts the
    pipeline again."""
    from bigdl_tpu.optim import Trigger

    samples = _samples()
    opt = _with_validation_and_checkpoints(_optimizer(samples), tmp_path)
    opt.set_end_when(Trigger.max_iteration(8))
    rec = _Recorded(opt)
    opt.optimize()

    d, r = (lambda k: ("dispatch", k)), (lambda k: ("read", k))
    assert rec.order("dispatch", "read", "validate", "checkpoint") == [
        d(1), d(2), r(1), d(3), r(2), r(3), ("checkpoint", 4),
        d(4), d(5), r(4), d(6), r(5), r(6), ("validate", 7),
        ("checkpoint", 7), d(7), d(8), r(7), r(8)]
    _, after = _reference(samples, 4, 5, 8)
    _assert_trees_equal(rec.held["checkpoint", 4], after[2])
    _assert_trees_equal(rec.held["validate", 7], after[5])
    _assert_trees_equal(rec.held["checkpoint", 7], after[5])


def test_resume_from_a_checkpoint_continues_the_same_trajectory(tmp_path):
    from bigdl_tpu.optim import Trigger

    samples = _samples()
    want, after = _reference(samples, 4, 5, 10)

    first = _with_validation_and_checkpoints(_optimizer(samples), tmp_path)
    first.set_end_when(Trigger.max_iteration(7))     # snapshots at 4 and 7
    first.optimize()
    mblob, oblob = first._latest_checkpoint()
    assert oblob["neval"] == 7
    _assert_trees_equal(mblob["params"], after[5])

    again = _with_validation_and_checkpoints(_optimizer(samples), tmp_path)
    again.set_end_when(Trigger.max_iteration(10))
    rec = _Recorded(again, first=7)
    again.optimize(resume=True)
    assert rec.losses == want[6:]
    assert rec.order()[:3] == [("dispatch", 7), ("dispatch", 8), ("read", 7)]
    _assert_trees_equal(again.model.params, after[-1])


@pytest.mark.parametrize("end", ["min_loss", "min_loss_or_max_iteration",
                                 "no_peek_fn"])
def test_an_end_that_reads_results_runs_the_synchronous_order(end):
    """``min_loss`` stops after the same iteration, with the same
    parameters, as a loop that reads every step before the next."""
    from bigdl_tpu.optim import Trigger

    samples, seed = _samples(), 3
    want, after = _reference(samples, 4, seed, 12)
    # the last of a run of new minima (this order's: steps 4 to 7)
    stop_at = max(k for k in range(2, 13) if want[k - 1] < min(want[:k - 1]))
    assert stop_at > 4
    limit = (want[stop_at - 1] + min(want[:stop_at - 1])) / 2
    trigger = {
        "min_loss": Trigger.min_loss(limit),
        "min_loss_or_max_iteration":
            Trigger.max_iteration(40).or_(Trigger.min_loss(limit)),
        "no_peek_fn": Trigger(lambda s: s["neval"] > stop_at),
    }[end]
    assert trigger.reads_result

    opt = _optimizer(samples, seed=seed)
    opt.set_end_when(trigger)
    rec = _Recorded(opt)
    opt.optimize()
    assert rec.order() == [e for k in range(1, stop_at + 1)
                           for e in (("dispatch", k), ("read", k))]
    assert rec.losses == want[:stop_at]
    _assert_trees_equal(opt.model.params, after[stop_at - 1])
    assert opt.optim_method.state["neval"] == stop_at + 1
    assert opt.metrics.values("launched ahead") == [0.0] * stop_at


def test_a_trigger_that_reads_results_holds_validation_back_too(tmp_path):
    from bigdl_tpu.optim import Top1Accuracy, Trigger

    opt = _optimizer(_samples())
    opt.set_validation(Trigger.min_loss(-1.0), DataSet.array(_samples(8)),
                       [Top1Accuracy()], batch_size=4)
    opt.set_end_when(Trigger.max_iteration(4))
    rec = _Recorded(opt)
    opt.optimize()
    assert rec.ahead() == set() and len(rec.losses) == 4


def test_preemption_with_a_step_in_flight_checkpoints_what_it_says(tmp_path):
    """SIGTERM lands while step 3 is being launched ahead of step 2's
    read: both are read and booked, then the snapshot is taken."""
    from bigdl_tpu.optim import Trigger
    from bigdl_tpu.optim.optimizer import TrainingPreempted

    samples = _samples()
    opt = _optimizer(samples)
    opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(100))
    opt.set_end_when(Trigger.max_iteration(50))

    def evict(k):
        if k == 3:
            opt._preempt_flag = True

    rec = _Recorded(opt, on_dispatch=evict)
    with pytest.raises(TrainingPreempted, match="iteration 4"):
        opt.optimize()
    assert rec.order("dispatch", "read", "checkpoint") == \
        _pipelined(3) + [("checkpoint", 4)]
    mblob, oblob = opt._latest_checkpoint()
    _, after = _reference(samples, 4, 5, 3)
    assert oblob["neval"] == 4 == opt.optim_method.state["neval"]
    _assert_trees_equal(mblob["params"], after[2])
    _assert_trees_equal(rec.held["checkpoint", 4], after[2])
    _assert_no_feeder_thread()


def test_an_end_peek_that_wrongly_says_runs_costs_one_more_step():
    """The peek says the loop runs on where ``fn`` stops it after step 4:
    step 5 is in flight by then and is booked like any other, so state,
    optimizer state and the written-back model agree. ``fn`` is shown
    every booked iteration, that one too; the loop ends whatever it says
    of it."""
    from bigdl_tpu.optim import Trigger

    samples, asked = _samples(), []

    def fn(state):
        asked.append(state["neval"])
        return state["neval"] == 5

    opt = _optimizer(samples)
    opt.set_end_when(Trigger(fn, lambda s: False))
    rec = _Recorded(opt)
    opt.optimize()
    want, after = _reference(samples, 4, 5, 5)
    assert rec.order() == _pipelined(5) and rec.losses == want
    assert asked == [1, 2, 3, 4, 5, 6]
    assert opt.optim_method.state["neval"] == 6
    _assert_trees_equal(opt.model.params, after[4])
    assert opt.metrics.get("computing time")[1] == 5
    _assert_no_feeder_thread()


@pytest.mark.parametrize("guard", ["checkpoint", "validation", "both"])
def test_a_hand_built_guard_fires_every_iteration(guard, tmp_path):
    """``Trigger(lambda s: True, lambda s: False)`` is the repo's "every
    iteration" idiom: as the guard of a checkpoint or of validation it
    is served after EVERY step, on that step's parameters, whatever its
    peek says, so the loop keeps the synchronous order."""
    from bigdl_tpu.optim import Top1Accuracy, Trigger

    samples = _samples()
    opt = _optimizer(samples)
    every = Trigger(lambda s: True, lambda s: False)
    assert not every.reads_result and not every.counted
    kinds = {"checkpoint": ["checkpoint"], "validation": ["validate"],
             "both": ["validate", "checkpoint"]}[guard]
    if "checkpoint" in kinds:
        opt.set_checkpoint(str(tmp_path), every)
        opt.overwrite_checkpoint = False
    if "validate" in kinds:
        opt.set_validation(every, DataSet.array(_samples(8, seed=3)),
                           [Top1Accuracy()], batch_size=4)
    opt.set_end_when(Trigger.max_iteration(6))
    rec = _Recorded(opt)
    opt.optimize()
    want, after = _reference(samples, 4, 5, 6)
    assert rec.losses == want
    assert rec.order("dispatch", "read", *kinds) == [
        e for k in range(1, 7)
        for e in [("dispatch", k), ("read", k)] + [(kind, k + 1)
                                                   for kind in kinds]]
    for kind in kinds:
        for k in range(1, 7):
            _assert_trees_equal(rec.held[kind, k + 1], after[k - 1])
    assert opt.metrics.values("launched ahead") == [0.0] * 6
    _assert_trees_equal(opt.model.params, after[-1])


@pytest.mark.parametrize("failing_call", [1, 2, 4])
def test_an_exception_inside_a_step_reaches_the_caller(failing_call):
    """The first step, the first one launched ahead, a later one."""
    from bigdl_tpu.optim import Trigger

    opt = _optimizer(_samples())
    opt.set_end_when(Trigger.max_iteration(6))

    def fail(k):
        if k == failing_call:
            raise RuntimeError(f"step {k} failed")

    _Recorded(opt, on_dispatch=fail)
    with pytest.raises(RuntimeError, match=f"step {failing_call} failed"):
        opt.optimize()
    assert opt._feeder is None
    _assert_no_feeder_thread()


@pytest.mark.parametrize("asked", ["before_the_loop", "by_the_end_trigger"])
def test_a_trace_starts_and_stops_with_no_step_in_flight(asked, monkeypatch,
                                                          tmp_path):
    """``set_profile(dir, 3, 2)`` holds steps 3 and 4 whole. Asked for by
    the end trigger when it is shown step 2 (step 3 is in flight by
    then), it holds two whole steps all the same: 4 and 5."""
    import jax

    from bigdl_tpu.optim import Trigger

    opt = _optimizer(_samples())
    if asked == "before_the_loop":
        opt.set_end_when(Trigger.max_iteration(6))
        opt.set_profile(str(tmp_path), 3, 2)
    else:
        def fn(state):
            if state["neval"] == 3 and opt._profile is None:
                opt.set_profile(str(tmp_path), state["neval"], 2)
            return state["neval"] > 6

        opt.set_end_when(Trigger(fn, lambda s: s["neval"] > 6))
    rec = _Recorded(opt)
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: rec.events.append(("trace", "on")))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: rec.events.append(("trace", "off")))
    opt.optimize()
    d, r = (lambda k: ("dispatch", k)), (lambda k: ("read", k))
    assert rec.order("dispatch", "read", "trace") == {
        "before_the_loop": [
            d(1), d(2), r(1), r(2), ("trace", "on"), d(3), d(4), r(3), r(4),
            ("trace", "off"), d(5), d(6), r(5), r(6)],
        "by_the_end_trigger": [
            d(1), d(2), r(1), d(3), r(2), r(3), ("trace", "on"), d(4), d(5),
            r(4), r(5), ("trace", "off"), d(6), r(6)]}[asked]
    assert opt._profile is None


def test_a_trace_the_loop_ends_inside_is_stopped(monkeypatch, tmp_path):
    """The benchmark's case: the end trigger asks for five steps and
    stops the loop when the fifth is booked; its peek never says so, so
    the step launched ahead is the fifth the trace holds."""
    import jax

    from bigdl_tpu.optim import Trigger

    opt = _optimizer(_samples())

    def fn(state):
        if state["neval"] == 3 and opt._profile is None:
            opt.set_profile(str(tmp_path), state["neval"], 5)
        return state["neval"] >= 3 + 5

    opt.set_end_when(Trigger(fn, lambda s: False))
    rec = _Recorded(opt)
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: rec.events.append(("trace", "on")))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: rec.events.append(("trace", "off")))
    opt.optimize()
    d, r = (lambda k: ("dispatch", k)), (lambda k: ("read", k))
    assert rec.order("dispatch", "read", "trace") == [
        d(1), d(2), r(1), d(3), r(2), r(3), ("trace", "on"),
        d(4), d(5), r(4), d(6), r(5), d(7), r(6), d(8), r(7), r(8),
        ("trace", "off")]
    assert opt._profile is None


def test_which_triggers_read_results():
    from bigdl_tpu.optim import Trigger

    counted = [Trigger.max_epoch(2), Trigger.max_iteration(3),
               Trigger.every_epoch(), Trigger.several_iteration(2),
               Trigger(lambda s: True, lambda s: False)]
    reading = [Trigger.min_loss(0.1), Trigger.max_score(0.9),
               Trigger(lambda s: s["neval"] > 3)]
    assert not any(t.reads_result for t in counted)
    assert all(t.reads_result for t in reading)
    for c in counted:
        assert not c.and_(counted[0]).reads_result
        assert not c.or_(counted[1]).reads_result
        for r in reading:
            for both in (c.and_(r), r.and_(c), c.or_(r), r.or_(c)):
                assert both.reads_result
    # counted: the factories over the counters, and joins of two such;
    # a hand-built trigger is not, with a peek_fn or without
    assert all(t.counted for t in counted[:4])
    assert not counted[4].counted and not any(t.counted for t in reading)
    for c in counted[:4]:
        assert c.and_(counted[0]).counted and c.or_(counted[3]).counted
        for other in reading + counted[4:]:
            assert not c.and_(other).counted and not other.or_(c).counted
    # may_fire, asked of a guard: the peek of a counted trigger, and
    # always for any other
    state = {"neval": 2, "epoch": 1, "epoch_finished": False, "loss": 9.0}
    assert not Trigger.max_iteration(3).may_fire(state)
    assert Trigger.max_iteration(1).may_fire(state)
    assert Trigger.min_loss(0.1).may_fire(state)
    assert Trigger.max_iteration(3).or_(Trigger.min_loss(0.1)).may_fire(state)
    assert Trigger(lambda s: True, lambda s: False).may_fire(state)


def test_the_loop_and_the_feeder_count_alike():
    """``advance`` is the one copy of the counter arithmetic."""
    from bigdl_tpu.optim.feeder import advance

    counters, seen = {"neval": 1, "epoch": 1, "epoch_finished": False}, 0
    trail = []
    for _ in range(5):
        counters, seen = advance(counters, seen, 4, 10)
        trail.append((counters["neval"], counters["epoch"],
                      counters["epoch_finished"], seen))
    assert trail == [(2, 1, False, 4), (3, 1, False, 8), (4, 2, True, 0),
                     (5, 2, False, 4), (6, 2, False, 8)]

