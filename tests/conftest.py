"""Shared test scaffolding: import path, device pinning, tiny-problem
fixtures, and the `slow` / `mesh8` markers.

Tier-1 (`pytest -x -q`) deselects tests marked `@pytest.mark.slow`; run
them with `--runslow`. The session-scoped factories below memoise the
small synthetic FL problems that used to be copy-pasted per test file —
one construction per distinct shape, shared by every test that asks.

Multi-device tests (the `mesh8` marker)
---------------------------------------
XLA only honours `--xla_force_host_platform_device_count` if it is set
before the backend initialises, so a multi-device world cannot be opened
inside an already-running pytest process — it must be a SUBPROCESS, the
same mechanism `launch/dryrun.py` uses for its 512-device world. The
pattern:

  * tests that need 8 host devices carry `@pytest.mark.mesh8` and take the
    `mesh8_world` fixture (which builds meshes via
    `launch.mesh.make_host_mesh` and skips cleanly if JAX initialised
    before the flag landed);
  * in a normal tier-1 run (`REPRO_MESH8_WORLD` unset) those tests are
    skipped at collection, and the un-marked proxy
    `tests/test_sharded_scan.py::test_mesh8_subprocess_suite` spawns
    `pytest -m mesh8` in a subprocess with the forced-device environment —
    so tier-1 still exercises the whole multi-device suite, one world per
    run;
  * CI's mesh-smoke step runs `pytest -m mesh8` directly with the same
    environment (see .github/workflows/ci.yml).

The world also sets `JAX_THREEFRY_PARTITIONABLE=1`: the legacy threefry
lowering generates different random bits when operands are sharded, so
sharded-vs-single parity is only well-defined under the partitionable
implementation (docs/architecture.md §13).
"""
import functools
import os
import sys

# Make `src/` importable regardless of how pytest is invoked.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Smoke tests and benches must see exactly ONE device (the dry-run sets its own
# XLA_FLAGS in a subprocess); keep CPU determinism.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

MESH8_WORLD_ENV = "REPRO_MESH8_WORLD"
MESH8_ENV = {
    MESH8_WORLD_ENV: "1",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "JAX_PLATFORMS": "cpu",
    "JAX_THREEFRY_PARTITIONABLE": "1",
}

if os.environ.get(MESH8_WORLD_ENV):
    # belt-and-braces for a hand-launched world: conftest imports before
    # the test modules touch JAX, so these still land in time unless a
    # plugin initialised the backend first (mesh8_world skips then)
    for _k, _v in MESH8_ENV.items():
        os.environ.setdefault(_k, _v)

import pytest  # noqa: E402

# A vmapped fleet and K sequential runs are different programs: under vmap a
# dot with the shared (unbatched) data batch folds the trial axis into the
# matmul, and XLA may sum it in another order. Parameters and losses agree
# to the fp32 reduction-order band of tests/test_sharded_scan.py; the
# mask-derived integers (active counts) agree exactly.
FLEET_RTOL, FLEET_ATOL = 2e-5, 1e-6


def assert_fleet_trial_close(fleet, seq_run, k):
    """Trial k of a fleet result `(params, FleetHistory)` against the
    sequential run `(params, FLHistory)` with the same seed and trace."""
    import jax
    import numpy as np
    params_k = jax.tree.map(lambda leaf: leaf[k], fleet[0])
    for a, b in zip(jax.tree.leaves(params_k), jax.tree.leaves(seq_run[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=FLEET_RTOL, atol=FLEET_ATOL)
    hist_k = fleet[1].trial(k)
    np.testing.assert_allclose(hist_k.train_loss, seq_run[1].train_loss,
                               rtol=FLEET_RTOL, atol=FLEET_ATOL)
    assert hist_k.n_active == seq_run[1].n_active


def serial_procedural_round(b, t, client_ids=None) -> dict:
    """`ProceduralBatcher.sample_round` of `b` as the serial loop over
    every slot that it once was, frozen here as the oracle of its bits:
    each slot draws its own client's streams, repeats included."""
    import numpy as np
    mb, K, d = b.batch_size, b.k_steps, b.dim
    teacher = np.random.default_rng((b.seed, 0x7EAC)).normal(
        size=(d, b.n_classes)).astype(np.float32)
    ids = (np.arange(b.n_clients) if client_ids is None
           else np.asarray(client_ids, np.int64))
    xs = np.empty((len(ids), K, mb, d), np.float32)
    ys = np.empty((len(ids), K, mb), np.int32)
    for j, i in enumerate(ids):
        i = int(i)
        mean = (b.shift * np.random.default_rng(
            (b.seed, 0xC11E27, i)).normal(size=d)).astype(np.float32)
        x = np.random.default_rng((b.seed, t, i)).normal(
            size=(K, mb, d)).astype(np.float32) * b.noise + mean
        xs[j] = x
        ys[j] = np.argmax(x @ teacher, axis=-1).astype(np.int32)
    return {"x": xs, "y": ys}


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="run tests marked @pytest.mark.slow")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy test, deselected from tier-1 (enable with --runslow)")
    config.addinivalue_line(
        "markers",
        "mesh8: needs 8 forced host devices; runs inside the subprocess "
        "world (REPRO_MESH8_WORLD=1 + XLA_FLAGS, see conftest docstring)")


def pytest_collection_modifyitems(config, items):
    if not os.environ.get(MESH8_WORLD_ENV):
        skip8 = pytest.mark.skip(
            reason="mesh8: runs in the forced-8-device subprocess world "
                   "(driven by test_sharded_scan.py::"
                   "test_mesh8_subprocess_suite)")
        for item in items:
            if "mesh8" in item.keywords:
                item.add_marker(skip8)
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow: tier-1 deselects (--runslow)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def mesh8_world():
    """Gate for `mesh8` tests: asserts this process actually has the 8
    forced host devices, skipping cleanly when JAX initialised before
    XLA_FLAGS could land (e.g. an eager plugin in a hand-launched world)."""
    import jax
    n = len(jax.devices())
    if n < 8:
        pytest.skip(f"mesh8 world has only {n} device(s): JAX initialised "
                    "before --xla_force_host_platform_device_count took "
                    "effect")
    return n


@pytest.fixture(autouse=True)
def _fresh_fallback_warnings():
    """Engine-fallback warnings dedupe once-per-config per process
    (core.runner.warn_engine_fallback); tests asserting on them need each
    test to start with a clean slate."""
    from repro.core.runner import _reset_fallback_warnings
    _reset_fallback_warnings()
    yield


# --------------------------------------------------------------------------- #
# tiny-problem factories (session-scoped, memoised per shape)
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="session")
def tiny_problem():
    """Factory: tiny_problem(n_clients=10, ...) -> (model, batcher).

    The paper's logistic setup shrunk to test size. Memoised — model and
    batcher are stateless after construction, so tests share them freely.
    """
    from repro.configs import get_config
    from repro.data import (ClientBatcher, label_skew_partition,
                            make_classification)
    from repro.models import build_model

    @functools.lru_cache(maxsize=None)
    def make(n_clients=10, seed=0, n_per_class=40, batch_size=8, k_steps=2,
             model_name="paper_logistic"):
        cfg = get_config(model_name).replace(fl_clients=n_clients)
        model = build_model(cfg)
        X, y = make_classification(10, cfg.d_model, n_per_class, noise=1.0,
                                   seed=seed)
        idx, _ = label_skew_partition(y, n_clients, seed=seed)
        batcher = ClientBatcher(X, y, idx, batch_size=batch_size,
                                k_steps=k_steps, seed=seed)
        return model, batcher

    return make


@pytest.fixture(scope="session")
def tiny_runner(tiny_problem):
    """Factory: tiny_runner(algo, n_clients=10, seed=0, **problem_kw) ->
    RoundRunner on the shared tiny problem."""
    def make(algo, *, n_clients=10, seed=0, schedule=None, **problem_kw):
        from repro.core import RoundRunner
        from repro.optim import inv_t
        model, batcher = tiny_problem(n_clients=n_clients, **problem_kw)
        return RoundRunner(model=model, algo=algo, batcher=batcher,
                           schedule=schedule or inv_t(1.0),
                           weight_decay=1e-3, seed=seed)
    return make


@pytest.fixture(scope="session")
def bernoulli_part():
    """Factory: bernoulli_part(n, p=0.5, seed=0) -> BernoulliParticipation."""
    import numpy as np
    from repro.core import BernoulliParticipation

    def make(n, p=0.5, seed=0):
        return BernoulliParticipation(np.full(n, p), seed=seed)
    return make
