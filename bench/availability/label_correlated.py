"""The paper's availability (section 7): client i is active with
probability p_i = p_min + (1 - p_min) * min(labels of i) / 9, independently
each round, and every client is active at round 0. Draws come from NumPy's
`default_rng(seed)`, one uniform per client a round, as the program's
`BernoulliParticipation` draws them.

Traffic keys: `p_min`. Needs a dataset that records each client's two
labels (`label_skew`).
"""
from __future__ import annotations

import numpy as np

from workload import Availability


def make(spec: dict, data, n: int, seed: int) -> Availability:
    m = np.minimum(data.labels[:, 0], data.labels[:, 1]).astype(np.float64)
    return Availability("label_correlated",
                        spec["p_min"] + (1.0 - spec["p_min"]) * m / 9.0,
                        None)


def new_stream(av: Availability, seed: int):
    """The reference's draw stream; visit rounds 1, 2, ... in order."""
    return np.random.default_rng(seed)


def active_ids(av: Availability, t: int, seed: int, stream) -> np.ndarray:
    """Round t's active client ids, drawn independently of the program."""
    n = len(av.probs)
    if t == 0:
        return np.arange(n)
    return np.flatnonzero(stream.random(n) < av.probs)


def program_side(av: Availability, n: int, seed: int) -> dict:
    """The program's sampler: a participation object the driver draws."""
    from repro.core import BernoulliParticipation
    return {"participation": BernoulliParticipation(av.probs, seed=seed)}
