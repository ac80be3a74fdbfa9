"""DataSet — local and distributed dataset abstractions.

Reference (UNVERIFIED, SURVEY.md §0): ``.../bigdl/dataset/DataSet.scala`` —
``DataSet.array`` (local), ``DataSet.rdd`` (distributed),
``LocalDataSet``/``DistributedDataSet`` exposing ``data(train=)`` iterators
(infinite shuffled for train, one-pass for eval) and ``size()``; the
``Optimizer`` factory dispatches Local vs Distri on the dataset type.

TPU-native redesign: there is no RDD — a *distributed* dataset means "this
process loads its 1/process_count shard and batches are laid out for the
device mesh". ``DataSet.array(...)`` → ``LocalDataSet``;
``DataSet.rdd(...)`` / ``.distributed()`` → ``DistributedDataSet`` (same
host-side iterator machinery, plus shard arithmetic). Feeding 256 chips is
the real bottleneck at pod scale (SURVEY.md §7), so the iterator layer stays
thin numpy and the optimizer's batch feeder (``optim/feeder.py``) draws from
it on a thread of its own: batches are built and placed on the device ahead
of the training loop, while the step runs.
"""

from __future__ import annotations

from typing import Any, Iterator, List, Optional, Sequence

import numpy as np

from bigdl_tpu.dataset.transformer import Transformer


class AbstractDataSet:
    def data(self, train: bool) -> Iterator[Any]:
        raise NotImplementedError

    def size(self) -> int:
        raise NotImplementedError

    def shuffle(self) -> None:
        pass

    def transform(self, transformer: Transformer) -> "AbstractDataSet":
        raise NotImplementedError

    __rshift__ = None  # set below


class LocalDataSet(AbstractDataSet):
    def __init__(self, data: Sequence[Any], transformers: Optional[List[Transformer]] = None,
                 seed: int = 0) -> None:
        self._data = list(data)
        self._transformers = transformers or []
        self._seed = seed

    def size(self) -> int:
        return len(self._data)

    def transform(self, transformer: Transformer) -> "LocalDataSet":
        out = type(self)(self._data, self._transformers + [transformer], self._seed)
        return out

    __rshift__ = transform  # dataset >> transformer, mirroring `->`

    def _base_iter(self, train: bool) -> Iterator[Any]:
        if train:
            rng = np.random.RandomState(self._seed)
            n = len(self._data)
            while True:
                order = rng.permutation(n)
                for i in order:
                    yield self._data[i]
        else:
            yield from self._data

    def data(self, train: bool) -> Iterator[Any]:
        it: Iterator[Any] = self._base_iter(train)
        for t in self._transformers:
            it = t(it)
        return it


class DistributedDataSet(LocalDataSet):
    """Shard-aware dataset: holds this process's shard of the global data.

    ``partition_num`` mirrors the reference's RDD partition count; in SPMD
    terms it is the number of processes. The Optimizer factory returns a
    DistriOptimizer for this type (reference ``object Optimizer.apply``).
    """

    def __init__(self, data: Sequence[Any], transformers=None, seed: int = 0,
                 partition_num: int = 1, partition_index: int = 0) -> None:
        super().__init__(data, transformers, seed)
        self.partition_num = partition_num
        self.partition_index = partition_index

    def transform(self, transformer: Transformer) -> "DistributedDataSet":
        return DistributedDataSet(
            self._data, self._transformers + [transformer], self._seed,
            self.partition_num, self.partition_index,
        )

    __rshift__ = transform


class _DataSetFactory:
    """``DataSet.array`` / ``DataSet.rdd`` factories (reference ``object DataSet``)."""

    @staticmethod
    def array(data: Sequence[Any], seed: int = 0) -> LocalDataSet:
        return LocalDataSet(data, seed=seed)

    @staticmethod
    def distributed(data: Sequence[Any], seed: int = 0) -> DistributedDataSet:
        """Global data → this process's shard (multi-host SPMD)."""
        import jax

        n_proc = jax.process_count()
        idx = jax.process_index()
        shard = list(data)[idx::n_proc]
        return DistributedDataSet(
            shard, seed=seed, partition_num=n_proc, partition_index=idx
        )

    # reference name: DataSet.rdd(...)
    rdd = distributed

    @staticmethod
    def image_folder(path: str, **kwargs):
        from bigdl_tpu.dataset.image import image_folder_samples

        return _DataSetFactory.array(image_folder_samples(path, **kwargs))

    @staticmethod
    def seq_file_folder(path: str, decoder=None, seed: int = 0,
                        format: str = "recs"):
        """Sharded record-file ingestion (reference ``DataSet.SeqFileFolder``
        — ImageNet-as-SequenceFiles). Shards are split across processes.
        ``format="hadoop"`` streams actual Hadoop SequenceFiles (a
        reference user's existing corpus) via
        ``dataset/hadoop_seqfile.py``; the default reads this framework's
        RECS shards (convert once with ``hadoop_seqfile.convert_to_recs``
        for the native-indexer fast path). ``decoder(label, payload)``
        has the SAME signature for both formats (hadoop derives the label
        from the Text/Int/Long key and unwraps BytesWritable first), so
        one decoder survives a convert_to_recs migration."""
        import jax

        if format not in ("recs", "hadoop"):
            raise ValueError(
                f"unknown seq_file_folder format {format!r} — expected "
                "'recs' (native shards) or 'hadoop' (SequenceFiles)")
        if format == "hadoop":
            from bigdl_tpu.dataset.hadoop_seqfile import HadoopSeqFileDataSet

            return HadoopSeqFileDataSet(
                path, decoder=decoder, seed=seed,
                shard_index=jax.process_index(),
                num_shards=jax.process_count(),
            )
        from bigdl_tpu.dataset.seqfile import SeqFileDataSet

        return SeqFileDataSet(
            path, decoder=decoder, seed=seed,
            shard_index=jax.process_index(), num_shards=jax.process_count(),
        )


DataSet = _DataSetFactory()
