"""Stateless cross-device clients, a copy of the draws of the repo's
`ProceduralBatcher`: client i's features are a mean shift drawn from
(seed, i) plus noise drawn from (seed, t, i); labels come from a fixed
random linear teacher. Nothing is held per client, so a population of
millions costs nothing until a client is drawn.

Traffic keys: `shift`, `noise`; the feature width is the configuration's
`d_model`, the classes its `n_classes`.
"""
from __future__ import annotations

import numpy as np


class Data:
    def __init__(self, spec: dict, cfg: dict, seed: int):
        self.seed, self.K, self.mb = seed, cfg["k_steps"], cfg["batch_size"]
        self.dim, self.n_classes = cfg["d_model"], cfg["n_classes"]
        self.shift, self.noise = spec["shift"], spec["noise"]
        self.teacher = np.random.default_rng((seed, 0x7EAC)).normal(
            size=(self.dim, self.n_classes)).astype(np.float32)

    def batches(self, t: int, ids) -> dict:
        """{'x': (len(ids), K, mb, d) f32, 'y': (len(ids), K, mb) int32}."""
        xs = np.empty((len(ids), self.K, self.mb, self.dim), np.float32)
        ys = np.empty((len(ids), self.K, self.mb), np.int32)
        for j, i in enumerate(ids):
            i = int(i)
            mean = (self.shift * np.random.default_rng(
                (self.seed, 0xC11E27, i)).normal(size=self.dim)).astype(
                    np.float32)
            x = np.random.default_rng((self.seed, t, i)).normal(
                size=(self.K, self.mb, self.dim)).astype(np.float32) \
                * self.noise + mean
            xs[j] = x
            ys[j] = np.argmax(x @ self.teacher, axis=-1).astype(np.int32)
        return {"x": xs, "y": ys}


def program_batcher(data: Data, cfg: dict, seed: int):
    """The program's procedural batcher at the same sizes and seed."""
    from repro.data import ProceduralBatcher
    return ProceduralBatcher(
        n_clients=cfg["n_clients"], dim=cfg["d_model"],
        n_classes=cfg["n_classes"], batch_size=cfg["batch_size"],
        k_steps=cfg["k_steps"], shift=data.shift, noise=data.noise,
        seed=seed)
