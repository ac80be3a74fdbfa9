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
    SIDE-EFFECT-FREE predictor of ``fn``. The optimizer's batch feeder calls
    ``peek`` ON ITS OWN THREAD, on speculative states a few iterations
    ahead, to decide whether to draw another batch, so a stateful ``fn``
    used as its own peek (the default) would consume its latch on a state
    that never becomes real. Factories below supply correct peeks;
    directly-constructed stateful Triggers must pass ``peek_fn``
    explicitly (the optimizer also guards the loop-top ``next()`` so a
    wrong peek degrades to a clean stop, not a crash)."""

    def __init__(self, fn: Callable[[dict], bool],
                 peek_fn: Callable[[dict], bool] = None) -> None:
        self._fn = fn
        self._peek = peek_fn or fn

    def __call__(self, state) -> bool:
        return self._fn(state)

    def peek(self, state) -> bool:
        """Side-effect-free evaluation: would the trigger fire on this
        state? Stateful triggers (every_epoch) must NOT consume their
        one-shot latch here — the optimizer's feeder peeks at speculative
        states ahead of the loop to decide whether to draw the next batch."""
        return self._peek(state)

    def and_(self, other: "Trigger") -> "Trigger":
        return Trigger(lambda s: self(s) and other(s),
                       lambda s: self.peek(s) and other.peek(s))

    def or_(self, other: "Trigger") -> "Trigger":
        return Trigger(lambda s: self(s) or other(s),
                       lambda s: self.peek(s) or other.peek(s))

    # -- factories ---------------------------------------------------------

    @staticmethod
    def max_epoch(max_e: int) -> "Trigger":
        return Trigger(lambda s: s["epoch"] > max_e)

    @staticmethod
    def max_iteration(max_it: int) -> "Trigger":
        return Trigger(lambda s: s["neval"] > max_it)

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

        return Trigger(fn, would_fire)

    @staticmethod
    def several_iteration(interval: int) -> "Trigger":
        return Trigger(lambda s: (s["neval"] - 1) % interval == 0 and s["neval"] > 1)

    @staticmethod
    def min_loss(min_l: float) -> "Trigger":
        return Trigger(lambda s: s.get("loss") is not None and s["loss"] < min_l)

    @staticmethod
    def max_score(max_s: float) -> "Trigger":
        return Trigger(lambda s: s.get("score") is not None and s["score"] > max_s)


# module-level factory aliases matching the reference's Trigger.xxx style
max_epoch = Trigger.max_epoch
max_iteration = Trigger.max_iteration
every_epoch = Trigger.every_epoch
several_iteration = Trigger.several_iteration
min_loss = Trigger.min_loss
max_score = Trigger.max_score
