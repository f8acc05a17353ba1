"""Cross-device cohorts: every client of the population is active with the
same probability `mean_cohort / N`, independently each round, once it is
present. The first `n_initial` clients are present at round 0, the rest
join at round 1. The program runs this as its `elastic` scenario over
`bernoulli`; the reference draws the same uniforms,
`jax.random.uniform(fold_in(PRNGKey(seed), t))`, on the host.

Traffic keys: `mean_cohort`, `n_initial`.
"""
from __future__ import annotations

import numpy as np

from workload import Availability


def presence(spec: dict, n: int) -> np.ndarray:
    """Join round of each client: 0 for the first `n_initial`, else 1."""
    return np.where(np.arange(n) < spec["n_initial"], 0, 1).astype(np.int64)


def make(spec: dict, data, n: int, seed: int) -> Availability:
    return Availability("uniform",
                        np.full(n, spec["mean_cohort"] / n, np.float32),
                        presence(spec, n))


def new_stream(av: Availability, seed: int):
    return None


def active_ids(av: Availability, t: int, seed: int, stream) -> np.ndarray:
    """Round t's active client ids, drawn independently of the program."""
    import jax
    n = len(av.probs)
    if t == 0:
        mask = np.ones(n, bool)
    else:
        u = np.asarray(jax.random.uniform(
            jax.random.fold_in(jax.random.PRNGKey(seed), t), (n,)),
            np.float64)
        mask = u < av.probs
    return np.flatnonzero(mask & (av.join <= t))


def program_side(av: Availability, n: int, seed: int) -> dict:
    """The program's sampler: a scenario the runner is built with."""
    from repro.scenarios import make_scenario
    return {"scenario": make_scenario(
        "elastic", n=n, seed=seed, inner="bernoulli",
        inner_kwargs={"probs": av.probs}, join=av.join)}
