"""DataSet / MultiDataSet: the feature/label/mask bundle fit() consumes.

Reference parity: ``org.nd4j.linalg.dataset.DataSet`` / ``MultiDataSet``
(SURVEY.md J9). Arrays are numpy on the host (the input pipeline side);
they cross to device inside the jitted step, staged by the iterator's
prefetch (SURVEY.md section 3.1: async prefetch thread is the host
boundary).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


def _np(x):
    from deeplearning4j_tpu.ndarray.ndarray import INDArray
    if isinstance(x, INDArray):
        return x.to_numpy()
    import jax
    if isinstance(x, jax.Array):
        # keep device-resident arrays on device — np.asarray would
        # round-trip them through the host on every fit
        return x
    return np.asarray(x)


class DataSet:
    def __init__(self, features, labels, features_mask=None,
                 labels_mask=None):
        self.features = _np(features)
        self.labels = _np(labels)
        self.features_mask = _np(features_mask) \
            if features_mask is not None else None
        self.labels_mask = _np(labels_mask) \
            if labels_mask is not None else None

    def num_examples(self) -> int:
        return int(self.features.shape[0])

    def get_features(self):
        return self.features

    def get_labels(self):
        return self.labels

    # -- reference API ---------------------------------------------------
    def split_test_and_train(self, n_train: int):
        tr = DataSet(self.features[:n_train], self.labels[:n_train],
                     self.features_mask[:n_train]
                     if self.features_mask is not None else None,
                     self.labels_mask[:n_train]
                     if self.labels_mask is not None else None)
        te = DataSet(self.features[n_train:], self.labels[n_train:],
                     self.features_mask[n_train:]
                     if self.features_mask is not None else None,
                     self.labels_mask[n_train:]
                     if self.labels_mask is not None else None)
        return tr, te

    def shuffle(self, seed: Optional[int] = None):
        rng = np.random.RandomState(seed)
        perm = rng.permutation(self.num_examples())
        self.features = self.features[perm]
        self.labels = self.labels[perm]
        if self.features_mask is not None:
            self.features_mask = self.features_mask[perm]
        if self.labels_mask is not None:
            self.labels_mask = self.labels_mask[perm]

    def batch_by(self, batch_size: int) -> List["DataSet"]:
        out = []
        n = self.num_examples()
        for i in range(0, n, batch_size):
            out.append(DataSet(
                self.features[i:i + batch_size],
                self.labels[i:i + batch_size],
                self.features_mask[i:i + batch_size]
                if self.features_mask is not None else None,
                self.labels_mask[i:i + batch_size]
                if self.labels_mask is not None else None))
        return out

    @staticmethod
    def merge(datasets: Sequence["DataSet"]) -> "DataSet":
        return DataSet(
            np.concatenate([d.features for d in datasets]),
            np.concatenate([d.labels for d in datasets]),
            np.concatenate([d.features_mask for d in datasets])
            if datasets[0].features_mask is not None else None,
            np.concatenate([d.labels_mask for d in datasets])
            if datasets[0].labels_mask is not None else None)

    def __repr__(self):
        return (f"DataSet(features={self.features.shape}, "
                f"labels={self.labels.shape})")


class MultiDataSet:
    """N features / M labels (reference: org.nd4j.linalg.dataset.MultiDataSet)."""

    def __init__(self, features, labels, features_masks=None,
                 labels_masks=None):
        as_list = lambda x: [_np(a) for a in x] \
            if isinstance(x, (list, tuple)) else [_np(x)]
        self.features = as_list(features)
        self.labels = as_list(labels)
        self.features_masks = [_np(m) if m is not None else None
                               for m in features_masks] \
            if features_masks else None
        self.labels_masks = [_np(m) if m is not None else None
                             for m in labels_masks] \
            if labels_masks else None

    def num_examples(self) -> int:
        return int(self.features[0].shape[0])
