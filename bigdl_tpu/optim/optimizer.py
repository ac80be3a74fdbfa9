"""Optimizer — abstract trainer + factory + LocalOptimizer.

Reference (UNVERIFIED, SURVEY.md §0): ``.../bigdl/optim/Optimizer.scala``
(fluent config + ``object Optimizer.apply`` dispatching Local vs Distri on
dataset type — the north star keeps this API source-unchanged) and
``LocalOptimizer.scala`` (single-node trainer that clones the model across a
thread pool).

TPU-native redesign of LocalOptimizer: the ``subModelNumber`` thread-pool
data parallelism vanishes — one jitted train step uses the whole chip
(SURVEY.md §2.4 "intra-node DP vanishes"). The optimize() driver loop stays
a thin host loop: fetch host batch → device_put → compiled step, with
trigger/validation/checkpoint/summary cadence identical to the reference.
The bounded retry-from-checkpoint wrapper (§5.3) lives here too.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from bigdl_tpu.dataset.dataset import AbstractDataSet, DataSet, DistributedDataSet
from bigdl_tpu.dataset.sample import MiniBatch, Sample
from bigdl_tpu.dataset.transformer import SampleToMiniBatch
from bigdl_tpu.optim.feeder import BatchFeeder, advance
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.train_step import make_eval_step, make_train_step
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import ValidationMethod

logger = logging.getLogger("bigdl_tpu")


class TrainingPreempted(RuntimeError):
    """Raised when training stops at an iteration boundary because a
    preemption signal (SIGTERM) arrived — AFTER a final checkpoint was
    written. Deliberately not retried by the bounded-retry wrapper: the
    process is being evicted; the restarted job resumes with
    ``optimize(resume=True)``."""


def _natural_key(s: str):
    import re

    return [int(p) if p.isdigit() else p for p in re.split(r"(\d+)", str(s))]


def _digit_skeleton(s: str) -> str:
    import re

    return re.sub(r"\d+", "#", str(s))


def _adapt_restored_tree(template, restored, what: str, _path: str = ""):
    """Reconcile a restored checkpoint tree against the live structure.

    A model rebuilt in the same process gets fresh auto-name counters
    (``Linear13`` where the checkpoint says ``Linear1``), and orbax
    restores tuples as lists. Walk both trees together: dict levels whose
    key sets differ are paired in NATURAL-SORT order (numeric runs compare
    as numbers — i.e. construction order for counter-suffixed names, which
    plain sorted() would scramble across digit-count boundaries), with the
    non-digit skeleton of each paired key required to match; sequences
    pair by position; leaf shapes must agree. Anything else is a real
    architecture mismatch and raises."""
    if restored is None:
        return template
    where = f"{what}{_path}"
    if isinstance(template, dict) and isinstance(restored, dict):
        if len(template) != len(restored):
            raise ValueError(
                f"checkpoint {where} has {len(restored)} entries but the "
                f"model expects {len(template)} — different architecture")
        if set(template) == set(restored):
            return {k: _adapt_restored_tree(template[k], restored[k], what,
                                            f"{_path}/{k}")
                    for k in template}
        tk = sorted(template, key=_natural_key)
        rk = sorted(restored, key=_natural_key)
        out = {}
        for a, b in zip(tk, rk):
            if _digit_skeleton(a) != _digit_skeleton(b):
                raise ValueError(
                    f"checkpoint {where} key {b!r} does not correspond to "
                    f"the model's {a!r} — different architecture")
            out[a] = _adapt_restored_tree(template[a], restored[b], what,
                                          f"{_path}/{a}")
        logger.info(
            "resume: %s keys differ from the live model (rebuilt module "
            "auto-names); matched %s in natural order", where, list(rk))
        return out
    if isinstance(template, (list, tuple)) and \
            isinstance(restored, (list, tuple)):
        if len(template) != len(restored):
            raise ValueError(
                f"checkpoint {where} has {len(restored)} entries but the "
                f"model expects {len(template)} — different architecture")
        vals = [_adapt_restored_tree(t, r, what, f"{_path}[{i}]")
                for i, (t, r) in enumerate(zip(template, restored))]
        return type(template)(vals) if isinstance(template, tuple) else vals
    if isinstance(template, dict) or isinstance(restored, dict) or \
            isinstance(template, (list, tuple)) or \
            isinstance(restored, (list, tuple)):
        raise ValueError(
            f"checkpoint {where} container kind does not match the model "
            "— different architecture")
    if tuple(np.shape(template)) != tuple(np.shape(restored)):
        raise ValueError(
            f"checkpoint {where} has shape {np.shape(restored)} but the "
            f"model expects {np.shape(template)} — different architecture")
    return restored


def _ensure_dataset(dataset, batch_size: Optional[int],
                    drop_remainder: bool = True,
                    batcher: Optional[SampleToMiniBatch] = None
                    ) -> AbstractDataSet:
    if dataset is None:
        raise ValueError(
            "Optimizer requires a dataset (pass dataset=...; a raw Sample "
            "sequence also needs batch_size=...)"
        )
    if not isinstance(dataset, AbstractDataSet):
        # raw list of Samples → local dataset (pyspark-API convenience)
        if batch_size is None:
            raise ValueError("batch_size required when passing raw samples")
        dataset = DataSet.array(list(dataset))
    if batch_size is not None:
        # Reference semantics: Optimizer(model, sampleRDD, criterion,
        # batchSize) batches a Sample dataset itself; a dataset already
        # yielding MiniBatch (Scala-style transformer chain) passes through.
        probe = next(iter(dataset.data(train=False)), None)
        if isinstance(probe, Sample):
            dataset = dataset.transform(batcher or SampleToMiniBatch(
                batch_size, drop_remainder=drop_remainder))
    return dataset


class Optimizer:
    """Fluent training config; ``Optimizer(...)`` returns a Local or Distri
    optimizer based on the dataset type (reference factory semantics)."""

    def __new__(cls, model=None, dataset=None, criterion=None,
                batch_size: Optional[int] = None, end_trigger=None, **kw):
        if cls is Optimizer:
            # dispatch on dataset TYPE only; the side-effecting conversion
            # (list(), probe, SampleToMiniBatch) happens once, in __init__
            if isinstance(dataset, DistributedDataSet) or kw.pop("distributed", False):
                from bigdl_tpu.optim.distri_optimizer import DistriOptimizer

                inst = object.__new__(DistriOptimizer)
            else:
                inst = object.__new__(LocalOptimizer)
            return inst
        return object.__new__(cls)

    def __init__(self, model=None, dataset=None, criterion=None,
                 batch_size: Optional[int] = None, end_trigger=None, **kw):
        self.model = model
        # the batching stage is the optimizer's own, so that the loop's
        # feeder can have it build batches in the feeder's arrays
        self._batcher = None if batch_size is None else \
            SampleToMiniBatch(batch_size)
        self.dataset = _ensure_dataset(dataset, batch_size,
                                       batcher=self._batcher)
        self.criterion = criterion
        self.optim_method: OptimMethod = SGD()
        self.end_when: Trigger = end_trigger or Trigger.max_epoch(1)
        self._device_preprocess = None
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_trigger: Optional[Trigger] = None
        self.checkpoint_backend = "pickle"
        self.overwrite_checkpoint = True
        self.validation_trigger: Optional[Trigger] = None
        self.validation_dataset: Optional[AbstractDataSet] = None
        self.validation_methods: List[ValidationMethod] = []
        self.train_summary = None
        self.validation_summary = None
        self.grad_clip: Dict[str, Any] = {}
        self.compute_dtype = None
        self.loss_scale = 1.0
        self._profile: Optional[Dict[str, Any]] = None
        self.metrics = Metrics()
        self.retry_times = int(os.environ.get("BIGDL_FAILURE_RETRY_TIMES", "5"))
        self.retry_interval_s = float(
            os.environ.get("BIGDL_FAILURE_RETRY_INTERVAL", "1")
        )
        self._handle_preemption = False
        self._preempt_flag = False
        self._async_ckptr = None
        self._async_pending_marker = None
        self._feeder: Optional[BatchFeeder] = None

    # -- fluent config (reference names, snake_case) -----------------------

    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_checkpoint(self, path: str = None, trigger: Trigger = None,
                       backend: str = "pickle",
                       # pyspark keyword names
                       checkpoint_trigger: Trigger = None,
                       checkpoint_path: str = None) -> "Optimizer":
        """``backend="pickle"`` writes the reference-style model/optimMethod
        snapshot pair; ``backend="orbax"`` writes an orbax PyTree checkpoint
        (tensor-store format, the TPU-ecosystem standard — SURVEY.md §5.4).

        Accepts both reference dialects: Scala ``(path, trigger)``, pyspark
        positional ``(checkpoint_trigger, checkpoint_path)``, and the
        pyspark keyword names ``checkpoint_trigger=``/``checkpoint_path=``
        (same aliasing policy as ``set_validation``'s val_rdd/val_method).

        On a multi-process pod (``jax.process_count() > 1``) every rank
        writes/reads ``<path>/proc_<rank>`` — give all ranks the SAME
        durable path and each keeps its own shard snapshot (see
        ``_ckpt_dir``)."""
        if isinstance(path, Trigger):          # pyspark positional order
            path, trigger = trigger, path
        # keyword overrides AFTER the swap: a positional Trigger mixed with
        # checkpoint_path= (natural pyspark mix) keeps its trigger
        if checkpoint_trigger is not None:
            trigger = checkpoint_trigger
        if checkpoint_path is not None:
            path = checkpoint_path
        if path is None or trigger is None:
            raise ValueError("set_checkpoint needs both a path and a trigger")
        if backend not in ("pickle", "orbax", "orbax_async"):
            raise ValueError(f"unknown checkpoint backend {backend!r}")
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self.checkpoint_backend = backend
        return self

    def set_device_preprocess(self, fn) -> "Optimizer":
        """Jit-traced preprocessing applied to each input batch ON DEVICE
        before the forward pass — pair with a uint8-NHWC host pipeline
        (``NativeImagePipeline(output="u8_nhwc")`` +
        ``DeviceImageNormalizer``) so host→device transfers ship 4× fewer
        bytes and the normalize fuses into the first conv."""
        self._device_preprocess = fn
        return self

    def handle_preemption(self, enabled: bool = True) -> "Optimizer":
        """TPU-native extension (no reference counterpart — Spark rebuilt
        lost executors; a preempted TPU slice just dies): when enabled,
        a SIGTERM during ``optimize()`` finishes the in-flight iteration,
        writes a final checkpoint (``set_checkpoint`` must be configured),
        and raises :class:`TrainingPreempted` — which the bounded retry
        deliberately does NOT swallow. The restarted job continues with
        ``optimize(resume=True)``. On multi-process pods the scheduler
        delivers SIGTERM to every process of the slice, so each writes
        its own shard checkpoint at the same iteration boundary."""
        self._handle_preemption = bool(enabled)
        return self

    def over_write_checkpoint(self) -> "Optimizer":
        self.overwrite_checkpoint = True
        return self

    def set_validation(self, trigger, dataset=None, methods=None,
                       batch_size: Optional[int] = None,
                       # pyspark keyword names
                       val_rdd=None, val_method=None) -> "Optimizer":
        """Scala order ``(trigger, dataset, methods, batch_size)``; the
        pyspark order ``set_validation(batch_size, val_rdd, trigger,
        val_method)`` is also accepted (detected by an int first arg)."""
        if isinstance(trigger, int):            # pyspark positional order
            batch_size, dataset, trigger, methods = (
                trigger, dataset, methods, batch_size)
        if val_rdd is not None:
            dataset = val_rdd
        if val_method is not None:
            methods = val_method
        self.validation_trigger = trigger
        # keep the trailing partial batch: validation must score EVERY
        # record (reference Evaluator semantics); the mesh eval path pads
        # ragged batches to the data axis and trims the outputs
        self.validation_dataset = _ensure_dataset(dataset, batch_size,
                                                  drop_remainder=False)
        self.validation_methods = list(methods)
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self.train_summary = summary
        return self

    def set_val_summary(self, summary) -> "Optimizer":
        self.validation_summary = summary
        return self

    def set_profile(self, trace_dir: str, start_iteration: int = 5,
                    n_iterations: int = 3) -> "Optimizer":
        """Capture a ``jax.profiler`` trace for iterations
        ``[start_iteration, start_iteration + n_iterations)`` — the deep
        option on top of the reference-style Metrics counters (SURVEY.md
        §5.1); view with TensorBoard's profile plugin or Perfetto. The
        loop's phases are in it as ``train.iteration`` (a profiler step)
        holding ``train.fetch``, ``train.dispatch`` and
        ``train.loss_sync``, on the device events' timebase; Python
        frames are not recorded. The trace starts and stops with no step
        in flight (the profiler truncates a program that is running when
        it starts), so it holds ``n_iterations`` steps whole: its first
        is launched onto an idle device, the others ahead of the read
        before them. Called from inside a running loop (an end trigger's
        ``fn``) with ``start_iteration`` already launched, the trace
        starts one step later and still holds ``n_iterations`` steps."""
        self._profile = {"dir": trace_dir, "start": start_iteration,
                         "n": n_iterations}
        return self

    def set_compute_dtype(self, dtype) -> "Optimizer":
        """Mixed precision: run forward/backward in ``"bf16"``/``"fp16"``
        while master weights, optimizer state and loss stay fp32 (TPU-native
        performance knob; no reference counterpart — MKL was fp32-only).
        fp16 needs :meth:`set_loss_scale` — its ~6e-8 cotangent floor flushes
        small gradients to zero unscaled (bf16 does not)."""
        self.compute_dtype = dtype
        if dtype in ("fp16", "float16") and self.loss_scale == 1.0:
            logger.warning(
                "fp16 compute without loss scaling will underflow small "
                "gradients; call set_loss_scale(e.g. 1024.0)")
        return self

    def set_loss_scale(self, scale: float) -> "Optimizer":
        """Static loss scaling for fp16 compute (loss × scale before the
        backward pass, gradients ÷ scale after)."""
        self.loss_scale = float(scale)
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float) -> "Optimizer":
        self.grad_clip["l2_norm"] = clip_norm
        return self

    def set_constant_gradient_clipping(self, min_v: float, max_v: float) -> "Optimizer":
        self.grad_clip["constant"] = (min_v, max_v)
        return self

    def disable_gradient_clipping(self) -> "Optimizer":
        self.grad_clip = {}
        return self

    # -- shared driver helpers --------------------------------------------

    def _state0(self) -> Dict[str, Any]:
        return {
            "epoch": int(self.optim_method.state.get("epoch", 1)),
            "neval": int(self.optim_method.state.get("neval", 1)),
            "loss": None,
            "score": None,
            "epoch_finished": False,
        }

    @staticmethod
    def _pod_rank():
        """(process_count, process_index); (1, 0) when jax is unavailable
        (pure-host tooling contexts that never touch a device)."""
        try:
            import jax

            return jax.process_count(), jax.process_index()
        except Exception:
            return 1, 0

    def _ckpt_dir(self) -> Optional[str]:
        """Effective checkpoint directory: on a multi-process pod every
        rank writes its OWN subdirectory (``proc_<rank>``) under the
        configured path. Ranks given one shared/durable path (the normal
        preemption-survival setup) must not race on a single orbax target
        — and in blockstore mode ``opt_state`` is a per-rank shard of
        IDENTICAL shape, so a rank restoring another rank's slice would
        corrupt optimizer momentum silently, past any shape check."""
        if not self.checkpoint_path:
            return self.checkpoint_path
        n, rank = self._pod_rank()
        if n > 1:
            return os.path.join(self.checkpoint_path, f"proc_{rank}")
        return self.checkpoint_path

    def _write_latest_marker(self, ckpt_dir: str, neval: int) -> None:
        """Sidecar recording the newest snapshot's iteration — cheap for
        peers on a shared path to read at resume time (atomic rename;
        for async saves it may briefly run ahead of a torn final write,
        which resume already treats as absent)."""
        tmp = os.path.join(ckpt_dir, f".LATEST.tmp.{os.getpid()}")
        with open(tmp, "w") as f:
            f.write(str(int(neval)))
        os.replace(tmp, os.path.join(ckpt_dir, "LATEST"))

    def _peer_latest_markers(self, exclude_rank=None):
        """{proc dirname: LATEST iteration} for sibling ranks under the
        shared checkpoint path; unreadable/pre-sidecar entries skipped."""
        out = {}
        try:
            siblings = os.listdir(self.checkpoint_path)
        except OSError:
            return out
        for d in sorted(siblings):
            if not d.startswith("proc_") or d == f"proc_{exclude_rank}":
                continue
            try:
                with open(os.path.join(self.checkpoint_path, d,
                                       "LATEST")) as f:
                    out[d] = int(f.read().strip())
            except (OSError, ValueError):
                continue
        return out

    def _pod_common_neval(self, own_neval: int) -> int:
        """On a pod with a SHARED checkpoint path, the iteration every
        rank must resume from: the minimum of all ranks' LATEST sidecars.
        Ranks checkpoint independently, so a kill can leave them holding
        snapshots at different iterations — resuming from mismatched
        iterations would silently offset the data streams and trip the
        end trigger at different steps."""
        if self._pod_rank()[0] <= 1:
            return own_neval
        markers = self._peer_latest_markers()
        if not markers:              # path not shared — nothing visible
            return own_neval
        return min([own_neval] + list(markers.values()))

    def _checkpoint(self, state, params, model_state, opt_state) -> None:
        from bigdl_tpu.utils.file_io import File

        ckpt_dir = self._ckpt_dir()
        if not ckpt_dir:
            return
        tag = "" if self.overwrite_checkpoint else f".{state['neval']}"
        os.makedirs(ckpt_dir, exist_ok=True)
        if self.checkpoint_backend in ("orbax", "orbax_async"):
            import jax
            import orbax.checkpoint as ocp

            target = os.path.abspath(
                os.path.join(ckpt_dir, f"orbax{tag or '.0'}"))
            blob = {
                "params": jax.tree_util.tree_map(np.asarray, params),
                "model_state": jax.tree_util.tree_map(np.asarray, model_state),
                "opt_state": jax.tree_util.tree_map(np.asarray, opt_state),
                "epoch": np.int64(state["epoch"]),
                "neval": np.int64(state["neval"]),
                "seen": np.int64(state.get("seen", 0)),
            }
            if self.checkpoint_backend == "orbax_async":
                # TPU-ecosystem async save: the write happens on a
                # background thread while training continues; the only
                # sync points are back-to-back saves and loop exit
                if self._async_ckptr is None:
                    self._async_ckptr = ocp.AsyncCheckpointer(
                        ocp.PyTreeCheckpointHandler())
                self._async_ckptr.wait_until_finished()
                # previous async save is now durable — only NOW may its
                # sidecar go out (a marker ahead of a torn in-flight
                # save would make peers trust an iteration this rank
                # cannot actually restore)
                self._flush_async_marker()
                self._async_ckptr.save(target, blob, force=True)
                self._async_pending_marker = (ckpt_dir, state["neval"])
                return
            ocp.PyTreeCheckpointer().save(target, blob, force=True)
            self._write_latest_marker(ckpt_dir, state["neval"])
            return
        File.save(
            # same blob shape as Module.save, so Module.load() can open a
            # checkpoint snapshot directly (reference resume semantics)
            {"params": params, "state": model_state, "module": self.model},
            os.path.join(ckpt_dir, f"model{tag}"),
            over_write=True,
        )
        File.save(
            {
                "method": self.optim_method,
                "opt_state": opt_state,
                "epoch": state["epoch"],
                "neval": state["neval"],
                "seen": state.get("seen", 0),
            },
            os.path.join(ckpt_dir, f"optimMethod{tag}"),
            over_write=True,
        )
        self._write_latest_marker(ckpt_dir, state["neval"])

    def _flush_async_marker(self) -> None:
        """Write the sidecar for the last CONFIRMED async save. Call only
        after ``wait_until_finished`` — see ``_checkpoint``."""
        if self._async_pending_marker is not None:
            self._write_latest_marker(*self._async_pending_marker)
            self._async_pending_marker = None

    def _pod_rollback(self, own_neval: int, exists_fn, load_fn):
        """Reconcile this rank's newest restorable snapshot against the
        pod-wide common iteration: returns ``load_fn(common)`` when a
        rollback is needed, ``None`` when the own snapshot stands, and
        raises LOUDLY when ranks are skewed but the common snapshot is
        not retained — resuming skewed iterations would silently offset
        the per-rank data streams and end triggers."""
        common = self._pod_common_neval(own_neval)
        if common == own_neval:
            return None
        if self.overwrite_checkpoint or not exists_fn(common):
            raise RuntimeError(
                f"pod resume: this rank's newest checkpoint is at "
                f"iteration {own_neval} but the pod-wide common "
                f"iteration is {common}, and no snapshot for it is "
                "retained (overwrite mode keeps one). Use "
                "over-write=False checkpoints on pods, or align the "
                "per-rank checkpoints manually.")
        try:
            result = load_fn(common)
        except Exception as e:
            raise RuntimeError(
                f"pod resume: the pod-common snapshot at iteration "
                f"{common} exists but is not restorable ({e!r}) — align "
                "the per-rank checkpoints manually") from e
        logger.warning(
            "pod resume: rolled back to the pod-common snapshot at "
            "iteration %d", common)
        return result

    def _assert_pod_peers_not_ahead(self):
        """Guard for the nothing-restorable case: a rank that would start
        FRESH must not do so silently while pod peers resume from their
        snapshots (that is the same silent iteration skew `_pod_rollback`
        exists to stop, through the other door)."""
        n, rank = self._pod_rank()
        if n <= 1 or not self.checkpoint_path:
            return
        peers = self._peer_latest_markers(exclude_rank=rank)
        if peers:
            raise RuntimeError(
                f"pod resume: this rank (proc_{rank}) has no restorable "
                f"checkpoint but pod peers do ({peers}) — starting fresh "
                "would silently skew the pod. Restore this rank's "
                "snapshot or clear every rank's checkpoints.")

    def _latest_checkpoint(self):
        from bigdl_tpu.utils.file_io import File

        ckpt_dir = self._ckpt_dir()
        if not ckpt_dir or not os.path.isdir(ckpt_dir):
            self._assert_pod_peers_not_ahead()
            return None
        if self.checkpoint_backend in ("orbax", "orbax_async"):
            import orbax.checkpoint as ocp

            if self._async_ckptr is not None:
                self._async_ckptr.wait_until_finished()
                self._flush_async_marker()

            def _iteration_of(f):
                # valid snapshots are "orbax.<iter>"; anything else (orbax
                # temp dirs from a crash mid-save) must not break resume
                try:
                    return float(f[len("orbax."):] or 0)
                except ValueError:
                    return None

            snaps = sorted(
                (f for f in os.listdir(ckpt_dir)
                 if f.startswith("orbax") and _iteration_of(f) is not None),
                key=_iteration_of,
            )
            if not snaps:
                self._assert_pod_peers_not_ahead()
                return None
            blob = None
            for snap in reversed(snaps):   # newest first; skip torn ones
                try:
                    blob = ocp.PyTreeCheckpointer().restore(os.path.abspath(
                        os.path.join(ckpt_dir, snap)))
                    break
                except Exception:
                    logger.warning(
                        "resume: snapshot %s is torn — trying older", snap)
            if blob is None:
                self._assert_pod_peers_not_ahead()
                return None

            def _load(c):
                return ocp.PyTreeCheckpointer().restore(os.path.abspath(
                    os.path.join(ckpt_dir, f"orbax.{c}")))

            rb = self._pod_rollback(
                int(blob["neval"]),
                lambda c: os.path.isdir(
                    os.path.join(ckpt_dir, f"orbax.{c}")),
                _load)
            if rb is not None:
                blob = rb
            return (
                {"params": blob["params"], "model_state": blob["model_state"]},
                {"opt_state": blob["opt_state"], "epoch": int(blob["epoch"]),
                 "neval": int(blob["neval"]),
                 "seen": int(blob.get("seen", 0))},
            )
        def _snap_iter(f):
            # numeric ordering: "model.12" must outrank "model.9" (and the
            # overwrite-mode bare "model" sorts first)
            try:
                return float(f[len("model."):] or 0)
            except ValueError:
                return -1.0

        models = sorted(
            (f for f in os.listdir(ckpt_dir)
             if f.startswith("model")),
            key=_snap_iter,
        )
        if not models:
            self._assert_pod_peers_not_ahead()
            return None
        m = o = None
        for f in reversed(models):         # newest first; skip torn ones
            tag = f[len("model"):]
            try:
                m = File.load(os.path.join(ckpt_dir, f"model{tag}"))
                o = File.load(os.path.join(ckpt_dir, f"optimMethod{tag}"))
                break
            except Exception:
                logger.warning(
                    "resume: snapshot model%s is torn — trying older", tag)
                m = o = None
        if o is None:
            self._assert_pod_peers_not_ahead()
            return None

        def _load(c):
            return (File.load(os.path.join(ckpt_dir, f"model.{c}")),
                    File.load(os.path.join(ckpt_dir, f"optimMethod.{c}")))

        rb = self._pod_rollback(
            int(o["neval"]),
            lambda c: os.path.exists(os.path.join(ckpt_dir, f"model.{c}")),
            _load)
        if rb is not None:
            m, o = rb
        return m, o

    def _eval_forward(self, params, model_state, inp):
        import jax

        if not hasattr(self, "_eval_step"):
            self._eval_step = jax.jit(make_eval_step(
                self.model, self._device_preprocess))
        return self._eval_step(params, model_state, inp)

    def _run_validation(self, params, model_state, state) -> Optional[float]:
        if not (self.validation_dataset and self.validation_methods):
            return None
        totals = [None] * len(self.validation_methods)
        for batch in self.validation_dataset.data(train=False):
            inp = batch.get_input() if isinstance(batch, MiniBatch) else batch
            tgt = batch.get_target() if isinstance(batch, MiniBatch) else None
            out = self._eval_forward(params, model_state, inp)
            for i, m in enumerate(self.validation_methods):
                r = m.apply(out, tgt)
                totals[i] = r if totals[i] is None else totals[i] + r
        import jax as _jax

        multi = _jax.process_count() > 1
        score = None
        for m, r in zip(self.validation_methods, totals):
            if r is None:
                if not multi:
                    continue
                # the merge below is a COLLECTIVE: a process whose shard
                # yielded no batches must still participate or the pod
                # deadlocks — contribute a zero accumulator
                r = m.empty_result()
            # pod runs: every process scored its own validation shard;
            # merge to the GLOBAL result (reference driver-side reduce)
            r = r.merge_across_processes()
            val, n_scored = r.result()
            if multi and n_scored == 0:
                continue  # no process had data for this method
            logger.info("validation [%s] epoch %d iter %d: %s",
                        m.name, state["epoch"], state["neval"], r)
            if self.validation_summary is not None:
                self.validation_summary.add_scalar(m.name, val, state["neval"])
            if score is None:
                score = val
        # feed plateau-style schedules
        sched = getattr(self.optim_method, "learning_rate_schedule", None)
        if sched is not None and hasattr(sched, "record_score") and score is not None:
            sched.record_score(score)
        return score

    def optimize(self, resume: bool = False):
        """``resume=True`` restarts from the latest checkpoint under
        ``set_checkpoint``'s path before the first attempt — the pod
        restart-after-kill entry point (within-process failures always
        retry from checkpoint regardless)."""
        if self._handle_preemption and not self.checkpoint_path:
            # configuration error — validate BEFORE the retry loop so it
            # isn't pointlessly retried
            raise ValueError(
                "handle_preemption() needs set_checkpoint(...) configured "
                "— an eviction with nowhere to write the final snapshot "
                "would silently lose all progress")
        last_err = None
        try:
            for attempt in range(self.retry_times):
                try:
                    return self._optimize_once(resume=resume or attempt > 0)
                except (KeyboardInterrupt, SystemExit, TrainingPreempted):
                    raise  # eviction is not a transient failure — no retry
                except Exception as e:  # bounded retry from checkpoint (§5.3)
                    last_err = e
                    logger.exception(
                        "training attempt %d failed; retrying from "
                        "checkpoint", attempt)
                    time.sleep(self.retry_interval_s)
            raise last_err
        finally:
            if self._async_ckptr is not None:
                # release the background save executor (a long-lived
                # process may construct many Optimizers)
                self._async_ckptr.wait_until_finished()
                self._flush_async_marker()
                self._async_ckptr.close()
                self._async_ckptr = None
            self._teardown()

    def _teardown(self) -> None:
        """Subclass hook run when optimize() finishes or fails — drain any
        background machinery (a daemon thread mid-RPC at interpreter
        shutdown aborts the process)."""

    # -- subclass hooks ----------------------------------------------------

    def _prepare(self):
        """Returns (step, place_batch, params, opt_state, model_state).

        ``step(params, opt_state, model_state, rng, inp, tgt)`` is compiled;
        ``place_batch(batch) -> (inp, tgt)`` stages a host MiniBatch onto
        device(s) with the right sharding.
        """
        raise NotImplementedError

    def _writeback(self, params, opt_state, model_state) -> None:
        """Store final (host-layout) params back into the module facade."""
        import jax

        self.model.params = jax.tree_util.tree_map(np.asarray, params)
        self.model.state = jax.tree_util.tree_map(np.asarray, model_state)
        self._final_opt_state = opt_state

    def _ckpt_params_to_host(self, params):
        return params

    def _host_params_to_device(self, params):
        return params

    def _ckpt_opt_state_to_host(self, opt_state):
        return opt_state

    def _opt_state_to_device(self, opt_state):
        return opt_state

    def _trace_turns(self, neval: int) -> bool:
        """Whether ``set_profile``'s trace starts or stops before step
        ``neval``. It does either with no step in flight, so that it
        holds whole steps: such a step is not launched ahead. Asked for
        from inside the loop (an end trigger's ``fn``) for the iteration
        already in flight, it starts one step later."""
        p = self._profile
        if p is None:
            return False
        if "stop" in p:
            return neval >= p["stop"]
        return p["start"] <= neval <= p["start"] + 1

    def _turn_trace(self, neval: int) -> None:
        """Starts the trace before step ``neval``, to hold the
        ``n_iterations`` steps from it on, or stops the one running."""
        import jax

        if "stop" in self._profile:
            jax.profiler.stop_trace()
            self._profile = None
            return
        # the loop's spans name its phases; Python frames on the same
        # line would only rename them with every edit (file:line), at
        # several times the trace's size and cost
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self._profile["dir"],
                                 profiler_options=options)
        self._profile["stop"] = neval + self._profile["n"]

    def _host_waits_for(self, state: dict, bsz: int, epoch_size: int) -> bool:
        """Whether something on the host needs the step in flight (of
        ``bsz`` records, the one after those ``state`` has booked) before
        the next step may start: its RESULT (an end trigger that reads
        the loss or the score, or whose peek says the loop ends there) or
        the PARAMETERS as they stand after it, which a launch ahead
        donates to the next step (validation, a checkpoint, the
        ``Parameters`` summary, a preemption's snapshot, a trace's edge).
        The triggers are asked, side-effect-free, of the counters the
        step will leave (``feeder.advance``) beside the loss and score of
        the steps before it; a guard of the parameters that no factory
        built from the counters counts as firing (``Trigger.may_fire``)."""
        counters, _ = advance(state, state["seen"], bsz, epoch_size)
        after = {**state, **counters}
        return bool(
            self._preempt_flag
            or self.end_when.reads_result or self.end_when.peek(after)
            or any(t is not None and t.may_fire(after) for t in (
                self.validation_trigger, self.checkpoint_trigger))
            or (self.train_summary is not None
                and self.train_summary.may_record("Parameters", after))
            or self._trace_turns(after["neval"]))

    def _optimize_once(self, resume: bool = False):
        import jax

        self.model.training()
        self.model._ensure_params()
        prev_sigterm = None
        if self._handle_preemption:
            import signal

            self._preempt_flag = False

            def _on_sigterm(signum, frame):
                logger.warning(
                    "SIGTERM received: finishing the current iteration, "
                    "checkpointing, then stopping (TrainingPreempted)")
                self._preempt_flag = True

            try:  # signal handlers only install from the main thread
                prev_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)
            except ValueError:
                logger.warning(
                    "handle_preemption: not on the main thread, SIGTERM "
                    "hook not installed")
        try:
            return self._optimize_loop(resume)
        finally:
            if self._feeder is not None:
                # before the training iterator goes: a generator cannot be
                # closed while the feeder's thread is inside it
                self._feeder.close()
                self._feeder = None
            if prev_sigterm is not None:
                import signal

                signal.signal(signal.SIGTERM, prev_sigterm)
            if self._async_ckptr is not None:
                self._async_ckptr.wait_until_finished()
                self._flush_async_marker()

    def _optimize_loop(self, resume: bool = False):
        import jax

        step, place_batch, params, opt_state, model_state = self._prepare()
        state = self._state0()

        if resume:
            snap = self._latest_checkpoint()
            if snap is not None:
                mblob, oblob = snap
                # a model rebuilt in the same process gets fresh auto-name
                # counters ("Linear2" vs the checkpoint's "Linear1"), so
                # reconcile restored trees against the live structure by
                # position when only the key names differ
                restored_params = _adapt_restored_tree(
                    self.model.params, mblob["params"], "params")
                params = self._host_params_to_device(restored_params)
                model_state = _adapt_restored_tree(
                    model_state, mblob.get("state", mblob.get("model_state")),
                    "model_state")
                opt_state = self._opt_state_to_device(_adapt_restored_tree(
                    self._ckpt_opt_state_to_host(opt_state),
                    oblob["opt_state"], "opt_state"))
                state["epoch"] = oblob["epoch"]
                state["neval"] = oblob["neval"]
                state["seen"] = oblob.get("seen", 0)
                logger.info("resumed from checkpoint at iteration %d", state["neval"])

        from bigdl_tpu.utils.random_gen import RNG

        base_key = RNG.next_key()

        # the input path runs AHEAD of the loop on a thread of its own
        # (optim/feeder.py): batches arrive built and placed, as far ahead
        # as the end trigger's peek allows, so a finite or shared iterator
        # never loses a batch to a count-based stop. Loss-triggered stops
        # can't be predicted pre-sync and may drop what is queued.
        # _optimize_once stops it on every way out of this function.
        feeder = self._feeder = BatchFeeder(
            self.dataset, self._batcher, place_batch, self.end_when, state,
            self.metrics)
        data_iter = feeder.data_iter
        epoch_size = self.dataset.size()
        seen_this_epoch = 0
        if resume and state["neval"] > 1:
            # replay the deterministic stream up to the checkpointed
            # position so the continued trajectory consumes exactly the
            # batches an uninterrupted run would (epochs reshuffle by
            # epoch index, so full epochs must be replayed, not skipped)
            target = (state["epoch"] - 1) * epoch_size + state.get("seen", 0)
            skipped = 0
            while skipped < target:
                try:
                    skipped += next(data_iter).size()
                except StopIteration:
                    raise ValueError(
                        f"cannot resume: the data stream ended after "
                        f"{skipped} records but the checkpoint was taken "
                        f"{target} records in — the dataset is smaller (or "
                        f"differently sized) than the one that wrote the "
                        f"checkpoint") from None
            seen_this_epoch = state.get("seen", 0)
        state["seen"] = seen_this_epoch
        feeder.start(seen_this_epoch)
        epoch_start = time.time()

        # The loop keeps ONE step in flight behind the host. A pass makes
        # at most one launch and then at most one read: with step k
        # launched and not read it launches step k+1 on step k's outputs
        # (not ready yet; params and opt_state are donated and rebound, so
        # this is program order only) and THEN blocks on float(loss_k) and
        # books step k: the launch and the read-back go under the step on
        # the device. Where the host needs step k first (_host_waits_for),
        # the pass reads it with nothing launched, and the next pass
        # launches and reads its own step, the synchronous order.
        in_flight = None     # (loss, bsz) of the step launched, not read
        stop = self.end_when(state)
        while in_flight is not None or not stop:
            reading = in_flight
            if reading is None:
                # nothing in flight: `params` are those `state` describes
                if self._preempt_flag:
                    self._checkpoint(
                        state, self._ckpt_params_to_host(params), model_state,
                        self._ckpt_opt_state_to_host(opt_state),
                    )
                    if self._async_ckptr is not None:
                        self._async_ckptr.wait_until_finished()
                        self._flush_async_marker()
                    raise TrainingPreempted(
                        f"evicted at iteration {state['neval']}; checkpoint "
                        f"written to {self.checkpoint_path or '(no path set)'}")
                if self._trace_turns(state["neval"]):
                    self._turn_trace(state["neval"])
            launching = reading is None or not (
                stop or self._host_waits_for(state, reading[1], epoch_size))
            # the step this pass launches, else the one it reads
            neval = state["neval"] + (launching and reading is not None)
            fetch_error = None
            # one pass = one step of the profiler's step analysis; its
            # phases below are series and profile events at once
            # (Metrics.span). "computing time" is the whole of a pass that
            # reads a step: the wait for the batch, the dispatch,
            # float(loss); pipelined, the dispatch is the NEXT step's.
            with self.metrics.span("train.iteration", step_num=neval):
                t0 = time.perf_counter()
                in_flight = None
                if launching:
                    # the input path's share of an iteration AS THE LOOP
                    # SEES IT: the wait for the feeder, ~0 when the batch
                    # was ready (StopIteration passes through, leaving no
                    # sample)
                    try:
                        with self.metrics.span("train.fetch",
                                               "data fetch time"):
                            inp, tgt, bsz = feeder.get()
                    except Exception as e:
                        if reading is not None:
                            # the step in flight is read and booked
                            # first, as it was before this fetch on the
                            # synchronous order; the pass then ends on
                            # what the input path raised
                            fetch_error = e
                        elif isinstance(e, StopIteration):
                            logger.warning(
                                "data iterator exhausted before end_when "
                                "fired; stopping. (Possible causes: the "
                                "iterator yields fewer batches than "
                                "dataset.size() implies, or a "
                                "directly-constructed stateful Trigger "
                                "without a side-effect-free peek_fn.)")
                            break
                        else:
                            raise
                    else:
                        # the LAUNCH of the step: host time, the program's
                        # device time is the trace's jit_step. What it
                        # reads of the host is its own step's: rng of ITS
                        # neval
                        with self.metrics.span("train.dispatch",
                                               "dispatch time"):
                            rng = jax.random.fold_in(base_key, neval)
                            params, opt_state, model_state, loss = step(
                                params, opt_state, model_state, rng, inp, tgt,
                            )
                        # nothing for the host to do until float(loss): the
                        # feeder builds the next batch now, under the step,
                        # not beside the launch
                        feeder.launched()
                        self.metrics.add("launched ahead",
                                         float(reading is not None))
                        if reading is not None or not self._host_waits_for(
                                state, bsz, epoch_size):
                            in_flight = (loss, bsz)   # read a launch later
                        else:
                            reading = (loss, bsz)     # the synchronous order
                if reading is None:
                    continue
                loss, bsz = reading
                # the loop's one sync: the host BLOCKED on the step
                with self.metrics.span("train.loss_sync", "loss sync time"):
                    loss_f = float(loss)
                dt = time.perf_counter() - t0
                self.metrics.add("computing time", dt)
                self.metrics.add("records/second", bsz / max(dt, 1e-9))
                counters, _ = advance(state, state["seen"], bsz, epoch_size)
                state["loss"] = loss_f
                state["epoch_finished"] = False
                state["neval"] = counters["neval"]
                self.optim_method.state["neval"] = state["neval"]
                state["seen"] += bsz

                if self.train_summary is not None:
                    self.train_summary.add_scalar("Loss", loss_f, state["neval"] - 1)
                    self.train_summary.add_scalar(
                        "Throughput", bsz / max(dt, 1e-9), state["neval"] - 1
                    )
                    sched = getattr(self.optim_method, "learning_rate_schedule", None)
                    base_lr = getattr(self.optim_method, "learning_rate", None)
                    if sched is not None and base_lr is not None:
                        # jitted optim state's neval counts from 0, host neval
                        # from 1: the lr JUST applied was sched.lr(neval - 2)
                        self.train_summary.add_scalar(
                            "LearningRate",
                            float(sched.lr(base_lr, max(0, state["neval"] - 2))),
                            state["neval"] - 1,
                        )
                    if self.train_summary.should_record("Parameters", state):
                        assert in_flight is None, "params donated to a step"
                        host = self._ckpt_params_to_host(params)
                        for path, leaf in jax.tree_util.tree_flatten_with_path(
                                host)[0]:
                            tag = "Parameters/" + "/".join(
                                getattr(k, "key", str(k)) for k in path)
                            self.train_summary.add_histogram(
                                tag, np.asarray(leaf), state["neval"] - 1)

                if counters["epoch_finished"]:
                    logger.info(
                        "epoch %d done: %d records in %.1fs, last loss %.4f",
                        state["epoch"], state["seen"], time.time() - epoch_start, loss_f,
                    )
                    state.update(counters)
                    self.optim_method.state["epoch"] = state["epoch"]
                    state["seen"] = 0
                    epoch_start = time.time()

                # validation and a checkpoint read the parameters as they
                # stand after the step `state` describes: _host_waits_for
                # saw to it that none is in flight beyond it
                if self.validation_trigger is not None and self.validation_trigger(state):
                    assert in_flight is None, "params donated to a step"
                    # device-layout params: DistriOptimizer overrides
                    # _eval_forward to evaluate SHARDED over the mesh instead of
                    # gathering to host and wasting N-1 chips (SURVEY §3.3)
                    score = self._run_validation(params, model_state, state)
                    if score is not None:
                        state["score"] = score
                if self.checkpoint_trigger is not None and self.checkpoint_trigger(state):
                    assert in_flight is None, "params donated to a step"
                    self._checkpoint(
                        state, self._ckpt_params_to_host(params), model_state,
                        self._ckpt_opt_state_to_host(opt_state),
                    )
                # every booked iteration, in order. A step launched ahead
                # of a "stop" the peek did not announce is booked and shown
                # like any other; the loop ends whatever is said of it
                stop = self.end_when(state) or stop
                if fetch_error is not None and \
                        not isinstance(fetch_error, StopIteration):
                    raise fetch_error

        if self._profile is not None and "stop" in self._profile:
            self._turn_trace(state["neval"])  # the loop ended inside it
        self._writeback(params, opt_state, model_state)
        return self.model


class LocalOptimizer(Optimizer):
    """Single-process trainer driving the local chip(s) with one jitted step.

    Reference ``LocalOptimizer.scala``'s thread-pool model clones vanish:
    one compiled step saturates the chip (SURVEY.md §2.4).
    """

    def _prepare(self):
        import jax

        from bigdl_tpu.optim.train_step import resolve_dtype

        import jax.numpy as jnp

        # fresh device buffers: device_put would alias arrays that already
        # live on device (the module facade's own params), and donating an
        # aliased buffer would delete it out from under model.params
        params = jax.tree_util.tree_map(
            lambda a: jnp.array(a), self.model.params)
        model_state = self.model.state
        opt_state = self.optim_method.init_state(params)
        # donate params+opt_state: XLA updates them in place, halving their
        # peak HBM footprint (they are rebound to the step's outputs anyway)
        step = jax.jit(
            make_train_step(self.model, self.criterion, self.optim_method,
                            self.grad_clip, loss_scale=self.loss_scale,
                            compute_dtype=resolve_dtype(self.compute_dtype),
                            device_preprocess=self._device_preprocess),
            donate_argnums=(0, 1),
        )

        def place_batch(batch: MiniBatch):
            # on the feeder's thread: the copy to the device is done before
            # the loop launches the step, not inside the launch
            return (jax.device_put(batch.get_input()),
                    jax.device_put(batch.get_target()))

        return step, place_batch, params, opt_state, model_state
