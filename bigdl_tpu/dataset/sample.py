"""Sample / MiniBatch — the unit records of the input pipeline.

Reference (UNVERIFIED, SURVEY.md §0): ``.../bigdl/dataset/Sample.scala``
(``ArraySample``: contiguous feature+label storage), ``MiniBatch.scala``
(``slice`` for per-thread sub-batches), ``SampleToMiniBatch.scala``.

TPU-native: numpy on the host side (pipeline runs on CPU feeding the chips);
a ``MiniBatch`` is the host-side staging buffer that the optimizer's
feeder ``device_put``s with the mesh sharding — batch slicing for "sub-models"
disappears (XLA uses the whole chip) but ``slice`` is kept for parity and for
the data-parallel shard math.
"""

from __future__ import annotations

from typing import Any, List, Sequence, Union

import numpy as np


def _to_np(x) -> np.ndarray:
    if isinstance(x, np.ndarray):
        return x
    try:
        from bigdl_tpu.tensor import Tensor

        if isinstance(x, Tensor):
            return x.to_numpy()
    except ImportError:  # pragma: no cover
        pass
    return np.asarray(x)


class Sample:
    """One training record: feature tensor(s) + label tensor(s)."""

    def __init__(self, features: Any, labels: Any) -> None:
        if isinstance(features, (list, tuple)):
            self.features = [_to_np(f) for f in features]
            self._multi_feature = True
        else:
            self.features = [_to_np(features)]
            self._multi_feature = False
        if isinstance(labels, (list, tuple)):
            self.labels = [_to_np(l) for l in labels]
        else:
            self.labels = [_to_np(labels)]

    def feature(self, i: int = 0) -> np.ndarray:
        return self.features[i]

    def label(self, i: int = 0) -> np.ndarray:
        return self.labels[i]

    def __repr__(self) -> str:
        fs = ",".join(str(f.shape) for f in self.features)
        ls = ",".join(str(l.shape) for l in self.labels)
        return f"Sample(features=[{fs}], labels=[{ls}])"


class MiniBatch:
    """A batched group of samples: stacked input + target arrays."""

    def __init__(self, input: Any, target: Any = None) -> None:
        self.input = input
        self.target = target

    def size(self) -> int:
        x = self.input[0] if isinstance(self.input, (list, tuple)) else self.input
        return x.shape[0]

    def get_input(self):
        return self.input

    def get_target(self):
        return self.target

    def slice(self, offset: int, length: int) -> "MiniBatch":
        """1-based offset, reference-style."""
        s = slice(offset - 1, offset - 1 + length)

        def cut(x):
            if isinstance(x, (list, tuple)):
                return [v[s] for v in x]
            return x[s] if x is not None else None

        return MiniBatch(cut(self.input), cut(self.target))

    def __repr__(self) -> str:
        return f"MiniBatch(size={self.size()})"


def batch_buffers(samples: Sequence[Sample]):
    """Uninitialised ``out=`` arrays for ``stack_samples(samples, out=)``:
    ``(features, labels)``, one array a feature and a label, shaped and
    typed as ``np.stack`` of that column would be."""
    def column(arrays):
        dtype = np.result_type(*{a.dtype for a in arrays})
        return np.empty((len(arrays),) + arrays[0].shape, dtype)

    first = samples[0]
    return ([column([s.features[i] for s in samples])
             for i in range(len(first.features))],
            [column([s.labels[i] for s in samples])
             for i in range(len(first.labels))])


def stack_samples(samples: Sequence[Sample], out=None) -> MiniBatch:
    """Stack samples into one MiniBatch (the SampleToMiniBatch kernel).

    Without ``out`` every array of the batch is new. With
    ``out=(features, labels)`` (``batch_buffers`` makes them) the batch is
    built in those arrays and holds them: it is the caller's to say when
    they may be filled again. Filling 154 MB that exist takes a tenth of
    the time of stacking them anew (page faults; PERF.md section 6, PR 29)."""
    n_feat = len(samples[0].features)
    n_lab = len(samples[0].labels)
    out_feats, out_labs = out or ([None] * n_feat, [None] * n_lab)
    feats = [np.stack([s.features[i] for s in samples], out=out_feats[i])
             for i in range(n_feat)]
    labs = [np.stack([s.labels[i] for s in samples], out=out_labs[i])
            for i in range(n_lab)]
    inp = feats[0] if n_feat == 1 else feats
    tgt = labs[0] if n_lab == 1 else labs
    return MiniBatch(inp, tgt)
