"""Observability: TB histograms + summary triggers + LoggerFilter + profiler
hook (SURVEY.md §5.1/§5.5)."""

import logging
import os

import numpy as np
import pytest


def test_histogram_event_roundtrip(rng, tmp_path):
    """Histogram events parse back via tensorflow's event reader."""
    from bigdl_tpu.visualization.tensorboard import FileWriter

    w = FileWriter(str(tmp_path))
    vals = rng.randn(1000)
    w.add_histogram("Parameters/w", vals, 3)
    w.close()

    import tensorflow as tf

    events = list(tf.compat.v1.train.summary_iterator(w.path))
    histos = [e for e in events if e.summary.value
              and e.summary.value[0].HasField("histo")]
    assert len(histos) == 1
    h = histos[0].summary.value[0].histo
    assert histos[0].step == 3
    assert abs(h.num - 1000) < 1e-6
    assert abs(h.sum - vals.sum()) < 1e-3
    assert abs(h.min - vals.min()) < 1e-9


def test_parameter_histograms_during_training(rng, tmp_path):
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn import Linear, MSECriterion, Sequential
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.visualization import TrainSummary

    samples = [Sample(rng.randn(4).astype(np.float32),
                      rng.randn(2).astype(np.float32)) for _ in range(20)]
    summary = TrainSummary(str(tmp_path), "app")
    summary.set_summary_trigger("Parameters", Trigger.several_iteration(2))

    opt = Optimizer(model=Sequential().add(Linear(4, 2)),
                    dataset=DataSet.array(samples),
                    criterion=MSECriterion(), batch_size=10)
    opt.set_optim_method(SGD(learning_rate=0.01))
    opt.set_end_when(Trigger.max_iteration(5))
    opt.set_train_summary(summary)
    opt.optimize()
    summary.close()

    import tensorflow as tf

    n_histo = 0
    for name in os.listdir(summary.log_dir):
        for e in tf.compat.v1.train.summary_iterator(
                os.path.join(summary.log_dir, name)):
            for v in e.summary.value:
                if v.HasField("histo"):
                    n_histo += 1
                    assert v.tag.startswith("Parameters/")
    assert n_histo >= 2  # weight+bias at least once


def test_logger_filter(tmp_path):
    from bigdl_tpu.utils.logger_filter import LoggerFilter

    path = LoggerFilter.redirect_spark_info_logs(str(tmp_path))
    logging.getLogger("jax").info("chatty compiler message")
    logging.getLogger("tensorflow").warning("tf noise")
    with open(path) as f:
        content = f.read()
    assert "chatty compiler message" in content
    assert "tf noise" in content


def test_profiler_hook_smoke(rng, tmp_path):
    """set_profile captures a trace directory without disturbing training."""
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn import Linear, MSECriterion, Sequential
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    samples = [Sample(rng.randn(4).astype(np.float32),
                      rng.randn(2).astype(np.float32)) for _ in range(20)]
    opt = Optimizer(model=Sequential().add(Linear(4, 2)),
                    dataset=DataSet.array(samples),
                    criterion=MSECriterion(), batch_size=10)
    opt.set_optim_method(SGD(learning_rate=0.01))
    opt.set_end_when(Trigger.max_iteration(4))
    opt.set_profile(str(tmp_path / "trace"), start_iteration=2, n_iterations=1)
    opt.optimize()
    assert os.path.isdir(str(tmp_path / "trace"))


def test_orbax_checkpoint_and_resume(rng, tmp_path):
    """orbax backend: checkpoint written at trigger, resume restores state
    (SURVEY.md §5.4)."""
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn import Linear, MSECriterion, Sequential
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    samples = [Sample(rng.randn(4).astype(np.float32),
                      rng.randn(2).astype(np.float32)) for _ in range(20)]
    ckpt = str(tmp_path / "ckpt")
    opt = Optimizer(model=Sequential().add(Linear(4, 2)),
                    dataset=DataSet.array(samples),
                    criterion=MSECriterion(), batch_size=10)
    opt.set_optim_method(SGD(learning_rate=0.01))
    opt.set_end_when(Trigger.max_iteration(4))
    opt.set_checkpoint(ckpt, Trigger.several_iteration(2), backend="orbax")
    opt.optimize()
    assert any(f.startswith("orbax") for f in os.listdir(ckpt))

    snap = opt._latest_checkpoint()
    assert snap is not None
    mblob, oblob = snap
    assert "params" in mblob and oblob["neval"] >= 2
    w = np.asarray(next(iter(
        np.asarray(v) for v in _leaves(mblob["params"]))))
    assert np.all(np.isfinite(w))


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def test_orbax_async_checkpoint_and_resume(rng, tmp_path):
    """orbax_async backend: the save runs on a background thread (training
    is only gated by back-to-back saves); the written snapshot must be
    restorable and training must resume from it (SURVEY §5.4 + the
    TPU-ecosystem async-save extension)."""
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn import Linear, MSECriterion, Sequential
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    samples = [Sample(rng.randn(4).astype(np.float32),
                      rng.randn(2).astype(np.float32)) for _ in range(20)]
    ckpt = str(tmp_path / "ckpt")

    def make(n_iter):
        opt = Optimizer(model=Sequential().add(Linear(4, 2)),
                        dataset=DataSet.array(samples),
                        criterion=MSECriterion(), batch_size=10)
        opt.set_optim_method(SGD(learning_rate=0.01))
        opt.set_end_when(Trigger.max_iteration(n_iter))
        opt.set_checkpoint(ckpt, Trigger.several_iteration(1),
                           backend="orbax_async")
        return opt

    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(11)
    make(3).optimize()
    snap_files = os.listdir(ckpt)
    assert any(f.startswith("orbax") for f in snap_files)

    RNG.set_seed(11)
    opt2 = make(6)
    trained = opt2.optimize(resume=True)
    assert opt2.optim_method.state["neval"] == 7  # continued 4..6
    w = np.concatenate([np.asarray(p).ravel()
                        for p in trained.parameters()[0]])
    assert np.all(np.isfinite(w))


def test_preemption_sigterm_checkpoints_and_resumes(tmp_path):
    """handle_preemption(): SIGTERM mid-training finishes the in-flight
    iteration, writes a checkpoint, and exits with TrainingPreempted
    instead of being retried; a fresh run resumes from that snapshot."""
    import subprocess
    import sys
    import time

    script = tmp_path / "preempt_worker.py"
    script.write_text(f"""
import os, sys, time
sys.path.insert(0, {repr(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))})
import numpy as np
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.nn import Linear, MSECriterion, Sequential
from bigdl_tpu.optim import Optimizer, SGD, Trigger, TrainingPreempted
from bigdl_tpu.utils.random_gen import RNG

RNG.set_seed(5)
rs = np.random.RandomState(0)
samples = [Sample(rs.randn(4).astype(np.float32),
                  rs.randn(2).astype(np.float32)) for _ in range(40)]
opt = Optimizer(model=Sequential().add(Linear(4, 2)),
                dataset=DataSet.array(samples),
                criterion=MSECriterion(), batch_size=10)
opt.set_optim_method(SGD(learning_rate=0.01))
opt.set_end_when(Trigger.max_iteration(100000))
opt.set_checkpoint({repr(str(tmp_path / 'ckpt'))}, Trigger(lambda s: True, lambda s: False))
opt.handle_preemption()
print("READY", flush=True)

# slow the loop so the parent's SIGTERM lands mid-run; the ITER marker
# tells the parent the train loop (and the signal hook) is live
class SlowIter:
    def __init__(self, inner): self.inner = iter(inner)
    def __iter__(self): return self
    def __next__(self):
        print("ITER", flush=True)
        time.sleep(0.05)
        return next(self.inner)

_data = opt.dataset.data
opt.dataset.data = lambda train: SlowIter(_data(train=train))
try:
    opt.optimize()
    print("NOT_PREEMPTED", flush=True)
    sys.exit(1)
except TrainingPreempted as e:
    print("PREEMPTED_OK", e, flush=True)
    sys.exit(7)
""")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # hermetic: a child never takes a chip
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen([sys.executable, str(script)],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    # wait until the train loop is demonstrably iterating (two batch
    # fetches seen), then evict it — no timing guess
    deadline = time.time() + 120
    line, iters = "", 0
    while time.time() < deadline and iters < 2:
        line = proc.stdout.readline()
        if line == "" and proc.poll() is not None:
            break  # child died before the loop started — fail fast
        if "ITER" in line:
            iters += 1
    assert iters == 2, f"loop never started: {line}"
    proc.terminate()  # SIGTERM
    out, _ = proc.communicate(timeout=120)
    assert proc.returncode == 7, f"rc={proc.returncode}\n{line}{out}"
    assert "PREEMPTED_OK" in out, out
    assert os.path.isdir(str(tmp_path / "ckpt"))
    assert any(f.startswith("model")
               for f in os.listdir(str(tmp_path / "ckpt")))

    # the evicted job's replacement resumes from the snapshot
    import numpy as np

    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn import Linear, MSECriterion, Sequential
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(5)
    rs = np.random.RandomState(0)
    samples = [Sample(rs.randn(4).astype(np.float32),
                      rs.randn(2).astype(np.float32)) for _ in range(40)]
    opt = Optimizer(model=Sequential().add(Linear(4, 2)),
                    dataset=DataSet.array(samples),
                    criterion=MSECriterion(), batch_size=10)
    opt.set_optim_method(SGD(learning_rate=0.01))
    opt.set_checkpoint(str(tmp_path / "ckpt"),
                       Trigger.several_iteration(5))
    resumed_from = None
    snap = opt._latest_checkpoint()
    assert snap is not None
    resumed_from = snap[1]["neval"]
    assert resumed_from > 1  # at least one iteration ran pre-eviction
    opt.set_end_when(Trigger.max_iteration(resumed_from + 2))
    trained = opt.optimize(resume=True)
    assert opt.optim_method.state["neval"] == resumed_from + 3


def test_orbax_resume_preserves_mid_epoch_position(rng, tmp_path):
    """The orbax restore must carry the 'seen' counter so a mid-epoch
    snapshot resumes at the right stream position (not the epoch start)."""
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn import Linear, MSECriterion, Sequential
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    samples = [Sample(rng.randn(4).astype(np.float32),
                      rng.randn(2).astype(np.float32)) for _ in range(40)]
    ckpt = str(tmp_path / "ckpt")
    opt = Optimizer(model=Sequential().add(Linear(4, 2)),
                    dataset=DataSet.array(samples),
                    criterion=MSECriterion(), batch_size=10)
    opt.set_optim_method(SGD(learning_rate=0.01))
    opt.set_end_when(Trigger.max_iteration(6))  # 1.5 epochs of 4 batches
    opt.set_checkpoint(ckpt, Trigger.several_iteration(1), backend="orbax")
    opt.optimize()
    snap = opt._latest_checkpoint()
    assert snap is not None
    # 6 iterations of 10 = epoch 2, 20 records into the 40-record epoch
    assert snap[1]["epoch"] == 2
    assert snap[1]["seen"] == 20


def test_adapt_restored_tree_natural_order():
    """Rebuilt auto-names with 2-digit counters must map by construction
    order, not lexicographic order (which scrambles L1,L10,L2,...)."""
    from bigdl_tpu.optim.optimizer import _adapt_restored_tree

    # checkpoint written by modules Linear1..Linear12, live model rebuilt
    # as Linear13..Linear24 — same architecture, same construction order
    restored = {f"Linear{i}": np.full((2,), float(i))
                for i in range(1, 13)}
    template = {f"Linear{i}": np.zeros((2,))
                for i in range(13, 25)}
    out = _adapt_restored_tree(template, restored, "params")
    for pos, i in enumerate(range(13, 25)):
        np.testing.assert_array_equal(out[f"Linear{i}"],
                                      np.full((2,), float(pos + 1)))

    # tuple->list container change (orbax) is tolerated
    out2 = _adapt_restored_tree((np.zeros(2), np.zeros(3)),
                                [np.ones(2), np.ones(3)], "opt_state")
    assert isinstance(out2, tuple)

    # real mismatches still raise
    with pytest.raises(ValueError, match="different architecture"):
        _adapt_restored_tree({"Linear1": np.zeros((2,))},
                             {"Conv1": np.zeros((2,))}, "params")
    with pytest.raises(ValueError, match="different architecture"):
        _adapt_restored_tree({"Linear1": np.zeros((3,))},
                             {"Linear2": np.zeros((2,))}, "params")


def test_handle_preemption_requires_checkpoint(rng):
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn import Linear, MSECriterion, Sequential
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    samples = [Sample(rng.randn(4).astype(np.float32),
                      rng.randn(2).astype(np.float32)) for _ in range(20)]
    opt = Optimizer(model=Sequential().add(Linear(4, 2)),
                    dataset=DataSet.array(samples),
                    criterion=MSECriterion(), batch_size=10)
    opt.set_optim_method(SGD(learning_rate=0.01))
    opt.set_end_when(Trigger.max_iteration(1))
    opt.handle_preemption()
    with pytest.raises(ValueError, match="set_checkpoint"):
        opt.optimize()
