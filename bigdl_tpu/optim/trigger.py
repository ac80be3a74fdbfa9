"""Trigger — composable stop/fire conditions.

Reference (UNVERIFIED, SURVEY.md §0): ``.../bigdl/optim/Trigger.scala`` —
``maxEpoch``, ``maxIteration``, ``everyEpoch``, ``severalIteration``,
``minLoss``, ``maxScore``, ``and``/``or``. Evaluated host-side against the
optimizer's state table each iteration, exactly like the reference.
"""

from __future__ import annotations

from typing import Callable


class Trigger:
    """``fn(state) -> bool`` decides firing; ``peek_fn`` must be a
    SIDE-EFFECT-FREE predictor of ``fn`` from the loop's counters
    (``neval``, ``epoch``, ``epoch_finished``). Two parties call ``peek``
    on states that are not real yet, so a stateful ``fn`` used as its own
    peek (the default) would consume its latch on a state that never
    comes:

    * the optimizer's batch feeder, ON ITS OWN THREAD, on states a few
      iterations ahead, to decide whether to draw another batch. A peek
      that wrongly says "runs" costs one batch drawn and dropped; one
      that wrongly says "stop" only pauses the feeder (the optimizer
      also guards the loop-top ``next()``, so a wrong peek degrades to a
      clean stop, not a crash);
    * the training loop, on the state step k will leave, to decide
      whether step k+1 may be LAUNCHED before step k's loss is read
      (``Optimizer._optimize_loop``). An END trigger's ``peek_fn`` is
      trusted there as the feeder trusts it: one whose peek says "runs"
      where ``fn`` then says "stop" costs ONE MORE TRAINED STEP. The step
      already launched is read and booked like any other (the end
      trigger is shown it too, though it can no longer answer "run on"),
      so the state table, a checkpoint and the written-back model
      describe the same parameters, and then the loop ends.

    Two facts say what the loop may do ahead of a trigger:

    * :attr:`reads_result`: the decision reads a step's RESULT
      (``state["loss"]``, ``state["score"]``), which no peek can know
      before the step has been read. ``min_loss`` and ``max_score`` do;
      ``max_epoch``, ``max_iteration``, ``every_epoch`` and
      ``several_iteration`` do not; ``and_`` / ``or_`` do if either side
      does; a directly-constructed Trigger does UNLESS it was given a
      ``peek_fn`` (passing one declares the trigger predictable from the
      counters; a stateful one must pass it anyway). Under an end trigger
      that reads results the loop reads every step before it launches
      the next (the synchronous order: launch, read, decide).
    * :attr:`counted`: a factory built the trigger from the counters
      alone (``max_epoch``, ``max_iteration``, ``every_epoch``,
      ``several_iteration``, and ``and_`` / ``or_`` of two such), so its
      peek IS its decision. Only such a trigger lets the loop launch
      ahead where it guards validation, a checkpoint or the
      ``Parameters`` summary: each needs the parameters as they stand
      after its own step, so a wrong "no" would serve it late or skip it.
      A hand-built trigger there, with a ``peek_fn`` or without, counts
      as firing every iteration (:meth:`may_fire`) and keeps the
      synchronous order, so ``Trigger(lambda s: True, lambda s: False)``
      checkpoints every iteration as it always did."""

    def __init__(self, fn: Callable[[dict], bool],
                 peek_fn: Callable[[dict], bool] = None) -> None:
        self._fn = fn
        self._peek = peek_fn or fn
        self.reads_result = peek_fn is None
        self.counted = False

    def __call__(self, state) -> bool:
        return self._fn(state)

    def peek(self, state) -> bool:
        """Side-effect-free evaluation: would the trigger fire on this
        state? Stateful triggers (every_epoch) must NOT consume their
        one-shot latch here — the optimizer's feeder peeks at speculative
        states ahead of the loop to decide whether to draw the next batch."""
        return self._peek(state)

    def may_fire(self, state) -> bool:
        """Whether the trigger, as the guard of something that needs a
        step's parameters, could fire on ``state``, the state that step
        will leave: any but a counted trigger may, whatever its peek
        says."""
        return not self.counted or self._peek(state)

    def _joined(self, other: "Trigger", fn, peek_fn) -> "Trigger":
        both = Trigger(fn, peek_fn)
        both.reads_result = self.reads_result or other.reads_result
        both.counted = self.counted and other.counted
        return both

    def and_(self, other: "Trigger") -> "Trigger":
        return self._joined(other, lambda s: self(s) and other(s),
                            lambda s: self.peek(s) and other.peek(s))

    def or_(self, other: "Trigger") -> "Trigger":
        return self._joined(other, lambda s: self(s) or other(s),
                            lambda s: self.peek(s) or other.peek(s))

    # -- factories ---------------------------------------------------------

    @staticmethod
    def max_epoch(max_e: int) -> "Trigger":
        return _counted(lambda s: s["epoch"] > max_e)

    @staticmethod
    def max_iteration(max_it: int) -> "Trigger":
        return _counted(lambda s: s["neval"] > max_it)

    @staticmethod
    def every_epoch() -> "Trigger":
        holder = {"last": None}

        def would_fire(s):
            return s["epoch"] != holder["last"] and s.get("epoch_finished", False)

        def fn(s):
            if would_fire(s):
                holder["last"] = s["epoch"]
                return True
            return False

        return _counted(fn, would_fire)

    @staticmethod
    def several_iteration(interval: int) -> "Trigger":
        return _counted(
            lambda s: (s["neval"] - 1) % interval == 0 and s["neval"] > 1)

    @staticmethod
    def min_loss(min_l: float) -> "Trigger":
        return Trigger(lambda s: s.get("loss") is not None and s["loss"] < min_l)

    @staticmethod
    def max_score(max_s: float) -> "Trigger":
        return Trigger(lambda s: s.get("score") is not None and s["score"] > max_s)


def _counted(fn: Callable[[dict], bool],
             peek_fn: Callable[[dict], bool] = None) -> Trigger:
    """A trigger over the counters alone, whose peek is exact: a
    stateless one is its own peek."""
    trigger = Trigger(fn, peek_fn or fn)
    trigger.counted = True
    return trigger


# module-level factory aliases matching the reference's Trigger.xxx style
max_epoch = Trigger.max_epoch
max_iteration = Trigger.max_iteration
every_epoch = Trigger.every_epoch
several_iteration = Trigger.several_iteration
min_loss = Trigger.min_loss
max_score = Trigger.max_score
