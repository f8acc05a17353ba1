"""The paper's clients (section 7): a Gaussian-prototype classification
set, sorted by label and dealt out in shards, so that each client holds two
labels. A copy of the repo's `paper_problem` data and partition, kept here
so that the yardstick does not move with the program.

Traffic keys: `n_classes`, `n_per_class`, `noise`, `shards_per_client`,
`proto_seed`; the feature width is the configuration's `d_model`.
"""
from __future__ import annotations

import numpy as np


def make_classification(n_classes: int, dim: int, n_per_class: int,
                        noise: float, seed: int, proto_seed: int = 1234):
    """(X (n, dim) f32, y (n,) int32): a Gaussian-prototype mixture with
    features scaled to about unit norm."""
    protos = np.random.default_rng(proto_seed).normal(0.0, 1.0,
                                                      (n_classes, dim))
    rng = np.random.default_rng(seed)
    X = np.empty((n_classes * n_per_class, dim), np.float32)
    y = np.empty(n_classes * n_per_class, np.int32)
    for c in range(n_classes):
        rows = slice(c * n_per_class, (c + 1) * n_per_class)
        X[rows] = protos[c] + rng.normal(0.0, noise, (n_per_class, dim))
        y[rows] = c
    X /= np.float32(np.sqrt(dim))
    perm = rng.permutation(len(y))
    return X[perm], y[perm]


def label_skew_partition(y: np.ndarray, n_clients: int, shards: int,
                         seed: int):
    """Sort by label, cut `n_clients * shards` shards, deal each client
    `shards` of them: (client index arrays, (N, 2) labels each holds)."""
    rng = np.random.default_rng(seed)
    order = np.argsort(y, kind="stable")
    parts = np.array_split(order, n_clients * shards)
    deal = rng.permutation(n_clients * shards)
    idx, labels = [], []
    for i in range(n_clients):
        sids = deal[i * shards:(i + 1) * shards]
        idx.append(np.concatenate([parts[s] for s in sids]))
        held = sorted({int(y[parts[s]][0]) for s in sids})
        labels.append((held * 2)[:2] if len(held) == 1 else held[:2])
    return idx, np.asarray(labels, np.int64)


class Data:
    """Each client holds the rows of two labels; round t's minibatches of
    client i come from `default_rng((seed, t, i))`."""

    def __init__(self, spec: dict, cfg: dict, seed: int):
        self.X, self.y = make_classification(
            spec["n_classes"], cfg["d_model"], spec["n_per_class"],
            spec["noise"], seed, spec["proto_seed"])
        self.idx, self.labels = label_skew_partition(
            self.y, cfg["n_clients"], spec["shards_per_client"], seed)
        self.seed, self.K, self.mb = seed, cfg["k_steps"], cfg["batch_size"]
        self.Xs = [self.X[i] for i in self.idx]
        self.ys = [self.y[i] for i in self.idx]

    def batches(self, t: int, ids) -> dict:
        """{'x': (len(ids), K, mb, d) f32, 'y': (len(ids), K, mb) int32}."""
        xs = np.empty((len(ids), self.K, self.mb, self.X.shape[1]),
                      np.float32)
        ys = np.empty((len(ids), self.K, self.mb), np.int32)
        for j, i in enumerate(ids):
            i = int(i)
            pick = np.random.default_rng((self.seed, t, i)).integers(
                0, len(self.ys[i]), size=(self.K, self.mb))
            xs[j], ys[j] = self.Xs[i][pick], self.ys[i][pick]
        return {"x": xs, "y": ys}


def program_batcher(data: Data, cfg: dict, seed: int):
    """The program's batcher over the same rows and partition."""
    from repro.data import ClientBatcher
    return ClientBatcher(data.X, data.y, data.idx,
                         batch_size=cfg["batch_size"],
                         k_steps=cfg["k_steps"], seed=seed)
