"""Sharded scan engine: multi-device parity, pinned in the mesh8 world.

Anchor properties for `run_fl(engine="scan", mesh=...)` (and the fleet's
trial-axis sharding) on 1x1 / 2x2 / 8x1 host-device meshes built by
`launch.mesh.make_host_mesh` under forced 8 host devices:

  * the 1x1 mesh is fp32 BIT-EXACT against the unsharded scan — placing
    the carry on a one-device mesh must not perturb a single ulp;
  * >1-device meshes match to reduction-order tolerance: the client-axis
    mean reduces per-device partial sums and all-reduces them, so fp32
    rounding GROUPS differently than the single-device sequential
    reduction — same math, different parenthesisation. Integer-derived
    quantities (availability masks, n_active, τ statistics) stay exact;
  * chunking and mesh shape are execution details: scan_chunk ∈ {1, 4, T}
    on the same mesh is bit-exact, 2x2 vs 8x1 agree to the same tolerance
    and draw identical masks (the partitionable threefry RNG the world
    enables is sharding-invariant — the legacy lowering is NOT, which is
    why the world pins JAX_THREEFRY_PARTITIONABLE=1; conftest docstring,
    docs/architecture.md §13).

Everything here except the subprocess proxy is `@pytest.mark.mesh8`: in a
plain tier-1 run those tests skip at collection and
`test_mesh8_subprocess_suite` re-runs them in the forced-device world.
"""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

N, T = 8, 9          # N divides both data extents exercised below (2 and 8)
SHAPES = [(1, 1), (2, 2), (8, 1)]

mesh8 = pytest.mark.mesh8


def _algos():
    from repro.bank import BankedMIFA, DenseBank
    from repro.core import MIFA, BiasedFedAvg
    return {
        "mifa_array": lambda: MIFA(memory="array"),
        "banked_dense": lambda: BankedMIFA(DenseBank()),
        "fedavg": lambda: BiasedFedAvg(),
    }


def _ge(seed=0):
    from repro.scenarios import GilbertElliott
    return GilbertElliott.from_rate_and_burst(0.5, 3.0, n=N, seed=100 + seed)


def _kw(tiny_problem, **over):
    model, batcher = tiny_problem(n_clients=N)
    kw = dict(model=model, batcher=batcher,
              schedule=lambda t: 0.1 / (1 + t), n_rounds=T,
              weight_decay=1e-3, seed=0, cohort_capacity=8)
    kw.update(over)
    return kw


def _assert_close(run_ref, run_got, *, exact):
    """exact=True pins bitwise equality; otherwise fp32 reduction-order
    tolerance (see module docstring). Mask-derived integers are always
    exact — a mismatch there means the RNG diverged, not the arithmetic."""
    import jax
    (pa, ha), (pb, hb) = run_ref, run_got
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        a, b = np.asarray(a), np.asarray(b)
        if exact:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    if exact:
        assert ha.train_loss == hb.train_loss
    else:
        np.testing.assert_allclose(ha.train_loss, hb.train_loss,
                                   rtol=2e-5, atol=1e-6)
    assert ha.rounds == hb.rounds
    assert ha.n_active == hb.n_active
    assert (ha.tau_bar, ha.tau_max) == (hb.tau_bar, hb.tau_max)


@pytest.fixture(scope="session")
def single_scan_runs(mesh8_world, tiny_problem):
    """Unsharded scan trajectories, one per algorithm — the parity
    reference every mesh shape is compared against."""
    from repro.core import run_fl
    return {name: run_fl(algo=mk(), engine="scan", scan_chunk=4,
                         scenario=_ge(), **_kw(tiny_problem))
            for name, mk in _algos().items()}


# --------------------------------------------------------------------------- #
# sharded-vs-single-device parity
# --------------------------------------------------------------------------- #

@mesh8
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("name", ["mifa_array", "banked_dense", "fedavg"])
def test_sharded_scan_matches_single_device(mesh8_world, tiny_problem,
                                            single_scan_runs, name, shape):
    from repro.core import run_fl
    from repro.launch.mesh import make_host_mesh
    got = run_fl(algo=_algos()[name](), engine="scan", scan_chunk=4,
                 scenario=_ge(), mesh=make_host_mesh(*shape),
                 **_kw(tiny_problem))
    _assert_close(single_scan_runs[name], got, exact=(shape == (1, 1)))


@mesh8
@pytest.mark.parametrize("chunk", [1, 4, T])
def test_chunk_invariance_on_mesh(mesh8_world, tiny_problem, chunk):
    """On ONE mesh the per-round program is identical whatever the chunk
    length, so scan_chunk stays bit-exact even sharded."""
    from repro.core import MIFA, run_fl
    from repro.launch.mesh import make_host_mesh
    kw = _kw(tiny_problem)
    ref = run_fl(algo=MIFA(memory="array"), engine="scan", scan_chunk=4,
                 scenario=_ge(), mesh=make_host_mesh(2, 2), **kw)
    got = run_fl(algo=MIFA(memory="array"), engine="scan", scan_chunk=chunk,
                 scenario=_ge(), mesh=make_host_mesh(2, 2), **kw)
    _assert_close(ref, got, exact=True)


@mesh8
def test_mesh_shape_invariance(mesh8_world, tiny_problem):
    """2x2 and 8x1 draw IDENTICAL masks (partitionable threefry) and agree
    on the trajectory to reduction-order tolerance."""
    from repro.core import MIFA, run_fl
    from repro.launch.mesh import make_host_mesh
    kw = _kw(tiny_problem)
    a = run_fl(algo=MIFA(memory="array"), engine="scan", scan_chunk=4,
               scenario=_ge(), mesh=make_host_mesh(2, 2), **kw)
    b = run_fl(algo=MIFA(memory="array"), engine="scan", scan_chunk=4,
               scenario=_ge(), mesh=make_host_mesh(8, 1), **kw)
    _assert_close(a, b, exact=False)


# --------------------------------------------------------------------------- #
# fleet trial-axis sharding
# --------------------------------------------------------------------------- #

@mesh8
def test_fleet_trial_sharding_matches_sequential(mesh8_world, tiny_problem):
    """K=8 scenario trials sharded over the 8x1 data axis reproduce the
    sequential per-seed `run_fl` trajectories (reduction-order tolerance;
    per-trial masks and n_active exact)."""
    import jax
    from repro.core import MIFA, run_fl
    from repro.fleet import Trial, run_fleet
    from repro.launch.mesh import make_host_mesh
    kw = _kw(tiny_problem)
    trials = [Trial(seed=s, scenario=_ge(s)) for s in range(8)]
    pf, hf = run_fleet(model=kw["model"], batcher=kw["batcher"],
                       schedule=kw["schedule"], n_rounds=T,
                       algo=MIFA(memory="array"), trials=trials,
                       weight_decay=kw["weight_decay"], engine="scan",
                       scan_chunk=4, mesh=make_host_mesh(8, 1))
    for k in range(8):
        ps, hs = run_fl(algo=MIFA(memory="array"), engine="scan",
                        scan_chunk=4, scenario=_ge(k), **{**kw, "seed": k})
        for a, b in zip(jax.tree.leaves(ps), jax.tree.leaves(pf)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b)[k],
                                       rtol=2e-5, atol=1e-6)
        ht = hf.trial(k)
        np.testing.assert_allclose(ht.train_loss, hs.train_loss,
                                   rtol=2e-5, atol=1e-6)
        assert ht.n_active == hs.n_active


# --------------------------------------------------------------------------- #
# bank layout + kernel safety under the mesh
# --------------------------------------------------------------------------- #

@mesh8
def test_bank_rows_pad_and_shard(mesh8_world, tiny_problem):
    """A DenseBank inheriting the run's mesh pads its rows so the client
    axis divides the data extent, lays them out row-sharded, and refuses
    the (single-device-program) Pallas kernel path even when forced."""
    import jax.numpy as jnp
    from repro.bank import DenseBank
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.rules import data_axis_size, padded_bank_rows
    mesh = make_host_mesh(8, 1)
    bank = DenseBank(use_pallas=True, mesh=mesh)
    state = bank.init({"w": jnp.zeros((4, 3))}, n_clients=N)
    assert bank.n_rows == padded_bank_rows(N, mesh) == 16
    rows = state["rows"]["w"]
    assert rows.shape[0] == 16
    assert len(rows.sharding.device_set) == data_axis_size(mesh) == 8
    assert bank._pallas() is False


@mesh8
def test_run_fl_wires_mesh_into_bank(mesh8_world, tiny_problem):
    from repro.bank import BankedMIFA, DenseBank
    from repro.core import run_fl
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(2, 2)
    algo = BankedMIFA(DenseBank())
    run_fl(algo=algo, engine="scan", scan_chunk=4, scenario=_ge(),
           mesh=mesh, **_kw(tiny_problem))
    assert algo.bank.mesh is mesh
    assert algo.bank.n_rows == 10      # N+1=9 padded up to divide d=2


@mesh8
def test_chip_smoke_mesh_phase(mesh8_world):
    """Rehearsal of `chip_smoke.py --chips 4`: its mesh phase on four of the
    world's host devices at a tiny size, under the script's own checks;
    the client-axis rows must spread evenly over the four devices."""
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    import chip_smoke
    out = chip_smoke.mesh_phase(data=4, n_clients=8, n_rounds=8,
                                scan_chunk=4, n_per_class=20, batch_size=8,
                                k_steps=2, use_pallas=True)
    for name in ("mifa_array", "banked_dense"):
        per_device = out[name]["client_rows"]
        assert len(per_device) == 4
        assert len(set(per_device.values())) == 1, per_device
        assert min(per_device.values()) > 0
        # the client rows are part of the returned state, read in full
        assert all(out[name]["state"][d] >= per_device[d]
                   for d in per_device)
        assert out[name]["agreement"]["max_rel_param_diff"] <= \
            chip_smoke.RTOL


# --------------------------------------------------------------------------- #
# the subprocess proxy — the only test here that runs in plain tier-1
# --------------------------------------------------------------------------- #

def test_mesh8_subprocess_suite():
    """Drive the whole `mesh8` suite in a forced-8-device subprocess.

    The parent pytest process owns a single-device JAX backend, so the
    multi-device world has to be a fresh interpreter with XLA_FLAGS set
    before JAX initialises (conftest docstring). `-m mesh8` deselects this
    proxy inside the world, so there is no recursion.
    """
    if os.environ.get("REPRO_MESH8_WORLD"):
        pytest.skip("already inside the mesh8 world")
    from conftest import MESH8_ENV
    repo = pathlib.Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "mesh8",
         str(pathlib.Path(__file__).resolve())],
        env={**os.environ, **MESH8_ENV}, cwd=repo,
        capture_output=True, text=True, timeout=1500)
    tail = proc.stdout[-4000:] + proc.stderr[-2000:]
    assert proc.returncode == 0, tail
    assert " passed" in proc.stdout, tail
    assert " failed" not in proc.stdout, tail
