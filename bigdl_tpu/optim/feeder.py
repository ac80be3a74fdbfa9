"""BatchFeeder — builds and places the training loop's batches ahead of it.

``Optimizer._optimize_loop`` owns one for the length of the loop. A single
producer thread draws from the training iterator (so the batches keep the
iterator's order, and every process of a pod places them in the same
order), has each batch built in host arrays that already exist, places it
on the device, and queues ``(inp, tgt, bsz)`` for the loop. An iteration
then costs the larger of the step and the build, not their sum.

Three things decide what it may do:

* **The end trigger.** Batch k+j is drawn only when ``end_when.peek`` of
  the state j iterations ahead (``advance``, the loop's own counter
  arithmetic) says the loop will run it, so a count-based trigger never
  draws a batch that is not trained on. A peek that says "stop" only
  PAUSES the producer: should the loop ask for a batch all the same (its
  own ``end_when`` disagreed), one is drawn. The loop asks the same peek
  ONE step ahead, of the same arithmetic, before it launches step k+1 on
  the outputs of a step k it has not read yet (``Trigger``'s docstring
  says what a wrong peek costs either of them).
* **The ring.** ``SampleToMiniBatch`` builds into the ring's arrays
  (``stack_samples(out=)``); a set of arrays is filled again only after
  the placement made from it is done (``block_until_ready``).
* **The backend.** A host-platform array may alias the numpy memory it was
  placed from, so there the ring's batch is copied before it is placed.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Iterator, Optional

import numpy as np

from bigdl_tpu.dataset.sample import MiniBatch, batch_buffers

#: placed batches the producer may hold ready for the loop. Chosen on the
#: chip (PERF.md section 6, PR 29): one hides a build shorter than a
#: step, the second takes up a build that ran long.
DEPTH = 2


def advance(counters: dict, seen: int, bsz: int, epoch_size: int):
    """The loop's counter arithmetic, one trained batch of ``bsz``
    records on: ``(counters, seen)`` after it, from the ``neval``,
    ``epoch`` and records ``seen`` of the epoch before it. The loop books
    a step with it, and peeks with it at the state a step not yet read
    will leave; the feeder peeks with it further ahead."""
    seen += bsz
    finished = seen >= epoch_size
    return {"neval": counters["neval"] + 1,
            "epoch": counters["epoch"] + finished,
            "epoch_finished": finished}, 0 if finished else seen


class _Slot:
    __slots__ = ("out", "placed")

    def __init__(self) -> None:
        self.out = None      # (features, labels) of stack_samples(out=)
        self.placed = None   # what was placed from them last


class _Ring:
    """``n`` sets of ``stack_samples(out=)`` arrays, handed out in turn."""

    def __init__(self, n: int) -> None:
        self._slots = [_Slot() for _ in range(n)]
        self._next = 0
        #: the slot handed out since the producer last looked
        self.taken: Optional[_Slot] = None

    def take(self, samples):
        """``SampleToMiniBatch.staging``: the arrays to build this batch
        in, once nothing reads them any more."""
        import jax

        slot = self._slots[self._next]
        self._next = (self._next + 1) % len(self._slots)
        if slot.placed is not None:
            jax.block_until_ready(slot.placed)
            slot.placed = None
        if slot.out is None or not self._fits(slot.out, samples):
            slot.out = batch_buffers(samples)
        self.taken = slot
        return slot.out

    @staticmethod
    def _fits(out, samples) -> bool:
        """A later batch may differ in size or shape (a bucketed length)."""
        first = samples[0]
        return all(
            buf.shape == (len(samples),) + col.shape and buf.dtype == col.dtype
            for bufs, cols in zip(out, (first.features, first.labels))
            for buf, col in zip(bufs, cols))


class BatchFeeder:
    """Makes ``data_iter`` (with ``batcher``, the data set's own last
    stage where the optimizer added one, building in the ring);
    ``start()`` sets the producer going from the loop's ``state``,
    ``get()`` hands the loop its next placed batch, ``launched()`` says
    the step on it is under way (the loop may call ``get()`` again at
    once, with that step still running and unread: the protocol is per
    launch, not per finished step), ``close()`` stops and joins the
    producer.
    ``state`` is the loop's live state table: its counters seed the
    producer's own, the rest of it is what a trigger may look at beside
    them."""

    def __init__(self, dataset, batcher, place_batch: Callable,
                 end_when, state: dict, metrics) -> None:
        import jax

        self._place_batch = place_batch
        self._end_when = end_when
        self._state = state
        self._metrics = metrics
        self._epoch_size = dataset.size()
        self._aliases_host = jax.default_backend() == "cpu"
        self._ring = _Ring(DEPTH + 1)
        if batcher is not None:
            batcher.staging = self._ring.take
        try:
            self.data_iter: Iterator[Any] = dataset.data(train=True)
        finally:
            if batcher is not None:
                batcher.staging = None
        self._cond = threading.Condition()
        self._ready: collections.deque = collections.deque()
        self._starved = False    # the loop waits on an empty queue
        self._launching = False  # between get() and launched()
        self._stop = False
        self._done = False       # the producer has left
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._produce, name="bigdl-batch-feeder", daemon=True)

    def start(self, seen: int) -> None:
        """``seen``: records of the current epoch already consumed."""
        self._spec = {k: self._state[k]
                      for k in ("neval", "epoch", "epoch_finished")}
        self._seen = seen
        self._thread.start()

    # -- the loop's side ---------------------------------------------------

    def get(self):
        """The next ``(inp, tgt, bsz)``; ``StopIteration`` when the
        iterator ended; whatever the producer raised."""
        with self._cond:
            was_ready = bool(self._ready)
            if not was_ready:
                # asking again says the last launch is over, said or not
                self._starved, self._launching = True, False
                self._cond.notify_all()
                while not self._ready and not self._done:
                    self._cond.wait()
                self._starved = False
            if not self._ready:
                if self._error is not None:
                    raise self._error
                raise StopIteration
            item = self._ready.popleft()
            self._launching = True
        self._metrics.add("input ready", float(was_ready))
        return item

    def launched(self) -> None:
        """The loop has launched the step on the batch ``get()`` gave it
        and will now wait for the device: the producer may build. Not
        before: a build beside the launch lengthened it by half, with the
        device idle all the while (PERF.md section 6, PR 29)."""
        with self._cond:
            self._launching = False
            self._cond.notify_all()

    def close(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread.ident is not None:
            self._thread.join()

    # -- the producer's side -----------------------------------------------

    def _loop_will_run(self) -> bool:
        """Whether the loop trains on one more batch than were drawn."""
        return not self._end_when.peek({**self._state, **self._spec})

    def _wait_for_room(self) -> bool:
        """Sleeps until a batch may be drawn; False when told to stop."""
        with self._cond:
            while not self._stop and (
                    self._launching or len(self._ready) >= DEPTH):
                self._cond.wait()
            if self._stop:
                return False
        if self._loop_will_run():
            return True
        with self._cond:
            while not self._stop and not (self._starved and not self._ready):
                self._cond.wait()
            return not self._stop

    def _build(self):
        import jax

        self._ring.taken = None
        batch = next(self.data_iter)
        slot, self._ring.taken = self._ring.taken, None
        if slot is not None and self._aliases_host:
            batch = MiniBatch(*jax.tree_util.tree_map(
                np.array, (batch.get_input(), batch.get_target())))
        inp, tgt = self._place_batch(batch)
        if slot is not None:
            slot.placed = (inp, tgt)
        return inp, tgt, batch.size()

    def _produce(self) -> None:
        try:
            while self._wait_for_room():
                t0 = time.perf_counter()
                try:
                    item = self._build()
                except StopIteration:
                    break
                self._spec, self._seen = advance(
                    self._spec, self._seen, item[2], self._epoch_size)
                self._metrics.add("batch build time",
                                  time.perf_counter() - t0)
                with self._cond:
                    self._ready.append(item)
                    self._cond.notify_all()
        except Exception as e:      # get() raises it on the loop's thread
            self._error = e
        finally:
            with self._cond:
                self._done = True
                self._cond.notify_all()
