"""The training loop's batch feeder (``optim/feeder.py``): the same batches
in the same order as a loop that stacks and steps by itself, never a batch
drawn that a count-based trigger will not train on, a ring that does not
alias what was placed, no thread left behind, and ``stack_samples(out=)``."""

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

def test_losses_equal_a_synchronous_loop_bit_for_bit():
    import jax

    n_iter, batch, seed = 9, 4, 5
    samples = _samples()

    got = _Losses(n_iter)
    opt = _optimizer(samples, batch, seed)
    opt.set_end_when(got.trigger())
    opt.optimize()

    # the plain loop: the optimizer's own step program, its key schedule,
    # batches stacked here from the same data set order, nothing ahead
    from bigdl_tpu.utils.random_gen import RNG

    ref = _optimizer(samples, batch, seed)
    ref.model._ensure_params()
    step, _, params, opt_state, model_state = ref._prepare()
    base_key = RNG.next_key()
    it = DataSet.array(samples, seed=seed).data(train=True)
    want = []
    for k in range(1, n_iter + 1):
        b = stack_samples([next(it) for _ in range(batch)])
        params, opt_state, model_state, loss = step(
            params, opt_state, model_state, jax.random.fold_in(base_key, k),
            b.get_input(), b.get_target())
        want.append(float(loss))

    assert got.losses == want
    assert len(set(want)) == n_iter          # a trajectory, not a constant


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


def test_an_error_in_the_input_path_reaches_the_loop():
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
    opt.set_end_when(Trigger.max_iteration(5))
    with pytest.raises(OSError, match="disk gone"):
        opt.optimize()
    assert opt.metrics.get("computing time")[1] == 1
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
