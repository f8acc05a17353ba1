"""A returning minority: only `n_available` clients of the population are
ever active after round 0, each with probability
`mean_cohort / n_available` a round; round 0 is the first `n_initial`
clients, as in `uniform`.

The returning clients fill whole blocks of `block` consecutive ids (a
bank page each, where `block` is the bank's page size). Which blocks is
drawn once from `layout_seed` and is the same for every run seed, so every
seed pages the same number of pages in the same layout and the seed
changes only who is active when and the data. Draws and the program's
sampler are those of `uniform`.

Traffic keys: `mean_cohort`, `n_initial`, `n_available`, `block`,
`layout_seed`.
"""
from __future__ import annotations

import numpy as np

from workload import Availability, plugin

_uniform = plugin("availability", "uniform")
new_stream = _uniform.new_stream
active_ids = _uniform.active_ids
program_side = _uniform.program_side


def returning_ids(spec: dict, n: int) -> np.ndarray:
    """The returning clients: `n_available / block` whole blocks."""
    r, block = spec["n_available"], spec["block"]
    if r % block or n % block:
        raise ValueError(f"n_available {r} and the population {n} must be "
                         f"whole blocks of {block}")
    blocks = np.random.default_rng(spec["layout_seed"]).choice(
        n // block, r // block, replace=False)
    return np.sort((blocks[:, None] * block + np.arange(block)).ravel())


def make(spec: dict, data, n: int, seed: int) -> Availability:
    probs = np.zeros(n, np.float32)
    probs[returning_ids(spec, n)] = np.float32(
        spec["mean_cohort"] / spec["n_available"])
    return Availability("returning", probs, _uniform.presence(spec, n))
