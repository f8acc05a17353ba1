"""Competing-baseline atlas invariants (benchmarks/scenario_atlas.py).

Anchors for the FedAR / CA-Fed additions and the algorithm registry:

  * EVERY registered algorithm runs under EVERY registered scenario
    process on both engines (loop and scan) and stays finite — the atlas
    benchmark must never discover an unrunnable cell in CI;
  * the new baselines are bit-exact fleet-vs-sequential under
    `engine="scan"` (the acceptance bar MIFA already clears): vmapping a
    trial axis and scanning rounds must not change a single bit;
  * `tau_bound()` / `stationary_rate()` classifications of the atlas
    scenario axis are pinned (the Assumption 4 taxonomy the atlas's
    winner table is read against);
  * the `assumes` tags (docs/scenarios.md, "Algorithm taxonomy") are
    pinned per algorithm;
  * engine-fallback warnings dedupe once per distinct config
    (core.runner.warn_engine_fallback) — a 30-cell sweep must not print
    30 copies of the same fallback notice.
"""
import warnings

import jax
import numpy as np
import pytest

from repro.core import (algorithm_assumes, algorithm_names, make_algorithm,
                        run_fl)
from repro.core.runner import _reset_fallback_warnings, warn_engine_fallback
from repro.fleet import Trial, run_fleet
from repro.scenarios import make_scenario, scenario_names
from conftest import assert_fleet_trial_close

N = 6


def _scen(name, seed=0):
    # tiny kwargs where a scenario needs them to be interesting at N=6
    kw = {"staged_blackout": {"stage_len": 2},
          "cluster": {"n_clusters": 2}}.get(name, {})
    return make_scenario(name, n=N, seed=7 + seed, **kw)


# --------------------------------------------------------------------------- #
# every algorithm × every scenario × both engines
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("engine", ["loop", "scan"])
@pytest.mark.parametrize("scenario", scenario_names())
@pytest.mark.parametrize("algo_name", algorithm_names())
def test_every_algorithm_runs_every_scenario(tiny_problem, algo_name,
                                             scenario, engine):
    model, batcher = tiny_problem(n_clients=N)
    algo = make_algorithm(algo_name, n=N)
    params, hist = run_fl(algo=algo, model=model, batcher=batcher,
                          schedule=lambda t: 0.1 / (1 + t), n_rounds=3,
                          weight_decay=1e-3, scenario=_scen(scenario),
                          seed=0, engine=engine)
    assert all(np.isfinite(x) for x in hist.train_loss)
    assert all(np.isfinite(np.asarray(leaf)).all()
               for leaf in jax.tree.leaves(params))


# --------------------------------------------------------------------------- #
# fleet-vs-sequential agreement for the new baselines (scan engine)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("algo_name", ["fedar", "ca_fed"])
def test_new_baselines_fleet_scan_bitexact_vs_sequential(tiny_problem,
                                                         algo_name):
    """K seeds of FedAR / CA-Fed as one jit(scan(vmap)) program reproduce
    the sequential per-seed `run_fl` runs within the fp32 reduction-order
    band, active counts exact."""
    model, batcher = tiny_problem(n_clients=N)
    algo = make_algorithm(algo_name, n=N)
    kw = dict(model=model, batcher=batcher,
              schedule=lambda t: 0.1 / (1 + t), n_rounds=5,
              weight_decay=1e-3)

    def ge(k):
        return make_scenario("gilbert_elliott", n=N, seed=100 + k,
                             rate=0.5, burst=3.0)

    seq = [run_fl(algo=algo, scenario=ge(k), seed=k, engine="scan", **kw)
           for k in range(3)]
    fleet = run_fleet(algo=algo,
                      trials=[Trial(seed=k, scenario=ge(k))
                              for k in range(3)],
                      engine="scan", **kw)
    for k in range(3):
        assert_fleet_trial_close(fleet, seq[k], k)


# --------------------------------------------------------------------------- #
# atlas scenario-axis theory pins (Assumption 4 taxonomy)
# --------------------------------------------------------------------------- #

def _atlas_axis():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    from scenario_grid import scenario_axis
    return scenario_axis(stage_len=6)


def test_atlas_axis_tau_classifications():
    """The axis orders by correlation/non-stationarity; `tau_bound()` must
    agree: geometric τ under iid, growing E[τ] with burst length, a
    DETERMINISTIC bound for the staged blackout (Assumption 4 holds), and
    an unbounded/unknown τ for cluster outages."""
    tb = {label: make_scenario(name, n=8, seed=0, **kw).process.tau_bound()
          for label, name, kw in _atlas_axis()}
    assert not tb["iid"].deterministic
    assert tb["iid"].expected_tau == pytest.approx(1.0)
    assert tb["ge_burst4"].expected_tau == pytest.approx(2.0)
    assert tb["ge_burst16"].expected_tau == pytest.approx(8.0)
    assert tb["ge_burst16"].expected_tau > tb["ge_burst4"].expected_tau
    assert tb["staged_blackout"].deterministic
    assert np.isfinite(tb["staged_blackout"].t0)
    assert not tb["cluster"].deterministic
    assert np.isinf(tb["cluster"].t0)
    assert np.isnan(tb["cluster"].expected_tau)


def test_atlas_axis_calibrated_to_half_rate():
    """The stochastic cells share a ≈0.5 stationary rate — the axis varies
    correlation structure, not the participation budget."""
    for label, name, kw in _atlas_axis():
        if label == "staged_blackout":
            continue  # non-stationary by construction
        rate = make_scenario(name, n=8, seed=0,
                             **kw).process.stationary_rate().mean()
        assert rate == pytest.approx(0.5, abs=0.05), label


def test_algorithm_assumes_tags():
    """docs/scenarios.md 'Algorithm taxonomy' pins."""
    want = {"mifa": "arbitrary", "banked_mifa": "arbitrary",
            "fedar": "arbitrary", "fedavg": "none",
            "fedavg_is": "iid_known_probs", "ca_fed": "stationary_mixing"}
    got = {name: algorithm_assumes(name, n=4) for name in algorithm_names()}
    assert got == want


def test_make_algorithm_unknown_name():
    with pytest.raises(KeyError, match="fedsgd"):
        make_algorithm("fedsgd", n=4)


# --------------------------------------------------------------------------- #
# engine-fallback warning dedupe
# --------------------------------------------------------------------------- #

def test_fallback_warns_once_per_distinct_message():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        warn_engine_fallback("config A unsupported")
        warn_engine_fallback("config A unsupported")
        warn_engine_fallback("config B unsupported")
        warn_engine_fallback("config A unsupported")
    msgs = [str(x.message) for x in w]
    assert msgs == ["config A unsupported", "config B unsupported"]
    # a reset (new test, new sweep) re-arms the warning
    _reset_fallback_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        warn_engine_fallback("config A unsupported")
    assert len(w) == 1


def test_repeated_fallback_runs_warn_once(tiny_problem):
    """A sweep hitting the same unsupported scan config repeatedly emits
    ONE warning, not one per run_fl call."""
    from repro.bank import BankedMIFA, HostBank
    model, batcher = tiny_problem(n_clients=N)
    kw = dict(model=model, batcher=batcher,
              schedule=lambda t: 0.1 / (1 + t), n_rounds=2,
              weight_decay=1e-3, seed=0)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for _ in range(3):
            run_fl(algo=BankedMIFA(HostBank()), engine="scan",
                   scenario=_scen("gilbert_elliott"), **kw)
    fallback = [x for x in w if "falling back" in str(x.message)]
    assert len(fallback) == 1
    # the warning points at the caller (stacklevel through the helper),
    # not at runner.py internals
    assert fallback[0].filename == __file__
