"""Transformer — composable iterator→iterator stages.

Reference (UNVERIFIED, SURVEY.md §0): ``.../bigdl/dataset/Transformer.scala``
— a serializable ``Iterator[A] => Iterator[B]`` composed with ``->`` and
cloned per partition.

Python surface: compose with ``>>`` (or ``.and_then``); a transformer is a
callable over an iterator. ``SampleToMiniBatch`` is the batching stage.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Iterator

from bigdl_tpu.dataset.sample import MiniBatch, Sample, stack_samples


class Transformer:
    def apply(self, it: Iterator[Any]) -> Iterator[Any]:
        raise NotImplementedError

    def __call__(self, it: Iterable[Any]) -> Iterator[Any]:
        return self.apply(iter(it))

    def and_then(self, other: "Transformer") -> "ChainedTransformer":
        return ChainedTransformer(self, other)

    __rshift__ = and_then  # `a >> b` mirrors the reference's `a -> b`


class ChainedTransformer(Transformer):
    def __init__(self, first: Transformer, second: Transformer) -> None:
        self.first = first
        self.second = second

    def apply(self, it: Iterator[Any]) -> Iterator[Any]:
        return self.second(self.first(it))


class FnTransformer(Transformer):
    """Lift a per-record function into a Transformer."""

    def __init__(self, fn: Callable[[Any], Any]) -> None:
        self.fn = fn

    def apply(self, it: Iterator[Any]) -> Iterator[Any]:
        for x in it:
            yield self.fn(x)


class Identity(Transformer):
    def apply(self, it: Iterator[Any]) -> Iterator[Any]:
        return it


class SampleToMiniBatch(Transformer):
    """Group a sample stream into MiniBatches of ``batch_size``
    (reference ``SampleToMiniBatch.scala``). Drops the trailing partial
    batch when ``drop_remainder`` (static shapes keep XLA from recompiling —
    the TPU analog of the reference's fixed per-core batch)."""

    def __init__(self, batch_size: int, drop_remainder: bool = True) -> None:
        self.batch_size = batch_size
        self.drop_remainder = drop_remainder
        #: ``staging(samples) -> out`` of ``stack_samples``, read when an
        #: iterator is made: that iterator builds its batches where
        #: ``staging`` says, and a batch is good only until ``staging``
        #: hands the same arrays out again. The training loop's feeder
        #: sets it around its own ``data(train=True)`` call; left None,
        #: every batch is made of new arrays.
        self.staging = None

    def apply(self, it: Iterator[Sample]) -> Iterator[MiniBatch]:
        return self._batches(it, self.staging)

    def _batches(self, it, staging) -> Iterator[MiniBatch]:
        def stack(buf):
            return stack_samples(buf, out=staging(buf) if staging else None)

        buf = []
        for s in it:
            buf.append(s)
            if len(buf) == self.batch_size:
                yield stack(buf)
                buf = []
        if buf and not self.drop_remainder:
            yield stack(buf)


SampleToBatch = SampleToMiniBatch  # early-reference alias
