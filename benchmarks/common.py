"""Shared benchmark utilities: the paper's experimental setup on synthetic
non-iid data (docs/architecture.md §6), timed-call helper, artifact IO."""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (BernoulliParticipation,  # noqa: E402
                        label_correlated_probs)
from repro.data import (ClientBatcher, label_skew_partition,  # noqa: E402
                        make_classification)
from repro.models import build_model  # noqa: E402

ARTIFACTS = os.path.join(os.path.dirname(__file__), "artifacts")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets nothing; otherwise the cache lives at the fixed `<repo>/.jax_cache`,
    so every later run from this checkout finds what earlier runs compiled.
    Entry points call this before their first compile; importing a module
    never does. Returns the cache directory in use.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def paper_problem(model_name: str = "paper_logistic", *, n_clients: int = 100,
                  p_min: float = 0.1, n_per_class: int = 500,
                  batch_size: int = 100, k_steps: int = 5, seed: int = 0):
    """The paper §7 setup: N=100 clients, 2 classes each, label-correlated
    Bernoulli availability, batch 100 (synthetic stand-in for MNIST/CIFAR)."""
    cfg = get_config(model_name).replace(fl_clients=n_clients)
    model = build_model(cfg)
    X, y = make_classification(10, cfg.d_model, n_per_class, noise=1.0,
                               seed=seed)
    Xte, yte = make_classification(10, cfg.d_model, 100, noise=1.0,
                                   seed=seed + 1000)
    idx, labels = label_skew_partition(y, n_clients, seed=seed)
    probs = label_correlated_probs(labels, p_min=p_min)
    batcher = ClientBatcher(X, y, idx, batch_size=batch_size, k_steps=k_steps,
                            seed=seed)

    def eval_fn(params):
        batch = {"x": jnp.asarray(Xte), "y": jnp.asarray(yte)}
        loss, _ = model.loss_fn(params, batch)
        return float(loss), float(model.accuracy(params, batch))

    # the raw test set and client labels, so fleet benchmarks can build a
    # vmapped eval (repro.fleet.make_fleet_eval) and sweep availability
    # parameters (label_correlated_probs) over the same problem instance
    eval_fn.eval_batch = {"x": Xte, "y": yte}
    eval_fn.client_labels = labels

    participation = lambda s: BernoulliParticipation(probs, seed=s)
    return model, batcher, probs, participation, eval_fn


def timeit_us(fn, *args, iters: int = 10, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def save_artifact(name: str, payload: dict) -> str:
    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(ARTIFACTS, name + ".json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def emit(name: str, us: float, derived) -> None:
    print(f"{name},{us:.1f},{derived}")
