"""Scenario subsystem: host-vs-jit equivalence, theory pins, fleet in-jit.

The anchor properties:
  * the host (NumPy) and jit-native surfaces of EVERY registered scenario
    draw bit-identical masks at a fixed seed;
  * a fleet grid over scenario trials samples availability INSIDE the
    jitted round — no host sampling, no (T, N) trace — and is bit-exact
    per trial against sequential `run_fl(scenario=...)` runs;
  * the Gilbert–Elliott τ statistics match their closed forms
    (E[τ] = p_f/(p_r·(p_f+p_r))), and `tau_bound()` classifications are
    consistent with simulated traces.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MIFA, BiasedFedAvg, run_fl, tau_matrix
from repro.core.participation import TauStats
from repro.fleet import FleetRunner, Trial, expand_grid, run_fleet
from repro.scenarios import (Bernoulli, GilbertElliott, HostSampler,
                             Scenario, make_scenario, register,
                             scenario_names)
from repro.scenarios.base import as_process
from conftest import assert_fleet_trial_close

N = 6


# --------------------------------------------------------------------------- #
# host-vs-jit equivalence, round-0 convention, rates — every scenario
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("name", scenario_names())
def test_host_vs_jit_masks_identical(name):
    proc = make_scenario(name, n=12, seed=3).process
    sample = jax.jit(proc.sample_fn())
    state = proc.init_state()
    host = proc.host_sampler()
    for t in range(50):
        mask_jit, state = sample(proc.key, jnp.int32(t), state)
        mask_host = host.sample(t)
        assert mask_host.shape == (12,) and mask_host.dtype == bool
        np.testing.assert_array_equal(np.asarray(mask_jit), mask_host,
                                      err_msg=f"{name} diverges at t={t}")


@pytest.mark.parametrize("name", scenario_names())
def test_round_zero_all_active(name):
    proc = make_scenario(name, n=9, seed=0).process
    host0 = proc.host_sampler().sample(0)
    mask, _ = proc.sample_fn()(proc.key, jnp.int32(0), proc.init_state())
    if proc.round0_all_active:
        assert host0.all()
        assert bool(np.asarray(mask).all())
    else:
        # elastic: round 0 is every PRESENT client (the documented
        # Definition 5.2(1) deviation) — and some client must be present
        present = (proc.join <= 0) & (0 < proc.leave)
        np.testing.assert_array_equal(host0, present)
        np.testing.assert_array_equal(np.asarray(mask), present)
        assert present.any() and not present.all()


@pytest.mark.parametrize("name", scenario_names())
def test_stationary_rate_matches_empirical(name):
    proc = make_scenario(name, n=24, seed=1).process
    host = proc.host_sampler()
    # trace replay is empirical over the RECORDED horizon; past the end
    # the clamp repeats the last row, which would drown the comparison
    T = proc.trace.n_rounds if hasattr(proc, "trace") else 4000
    masks = np.stack([host.sample(t) for t in range(T)])
    want = proc.stationary_rate()
    assert want.shape == (24,)
    if name == "bernoulli_drift":   # limiting rate: compare the tail only
        got = masks[T // 2:].mean(0)
    else:
        got = masks[1:].mean(0)     # drop the forced round 0
    np.testing.assert_allclose(got.mean(), want.mean(), atol=0.05)


# --------------------------------------------------------------------------- #
# τ theory pins
# --------------------------------------------------------------------------- #

def test_gilbert_elliott_tau_matches_closed_form():
    """τ̄ over a long run == p_f/(p_r(p_f+p_r)), and the τ histogram matches
    P(τ=k) = π_up·p_f·(1−p_r)^(k−1) — the Markov-scenario pin."""
    proc = GilbertElliott.from_rate_and_burst(0.5, 4.0, n=48, seed=7)
    host = proc.host_sampler()
    T = 20000
    masks = np.stack([host.sample(t) for t in range(T)])
    tm = tau_matrix(masks)
    np.testing.assert_allclose(tm.mean(), proc.expected_tau(), rtol=0.05)
    # distribution head: P(τ=k), k = 0..3
    pf = float(proc.p_fail[0])
    pr = float(proc.p_recover[0])
    pi_up = pr / (pf + pr)
    emp = [(tm == k).mean() for k in range(4)]
    want = [pi_up] + [pi_up * pf * (1 - pr) ** (k - 1) for k in (1, 2, 3)]
    np.testing.assert_allclose(emp, want, atol=0.01)


def test_gilbert_elliott_burst_parametrisation():
    proc = GilbertElliott.from_rate_and_burst(0.5, 8.0, n=4, seed=0)
    np.testing.assert_allclose(proc.stationary_rate(), 0.5, atol=1e-5)
    np.testing.assert_allclose(1.0 / proc.p_recover, 8.0, rtol=1e-5)
    assert not proc.tau_bound().deterministic
    # infeasible pairs raise instead of silently clipping the rate
    with pytest.raises(ValueError, match="infeasible"):
        GilbertElliott.from_rate_and_burst(0.2, 2.0, n=4)
    with pytest.raises(ValueError, match="burst"):
        GilbertElliott.from_rate_and_burst(0.5, 0.5, n=4)


@pytest.mark.parametrize("name,kw", [
    ("adversarial", {"periods": 8, "offs": 3}),
    ("staged_blackout", {"dark_frac": 0.5, "stage_len": 10}),
])
def test_deterministic_tau_bounds_hold_on_traces(name, kw):
    proc = make_scenario(name, n=10, seed=2, **kw).process
    tb = proc.tau_bound()
    assert tb.deterministic and np.isfinite(tb.t0)
    host = proc.host_sampler()
    masks = np.stack([host.sample(t) for t in range(400)])
    assert tau_matrix(masks).max() <= tb.t0
    assert tb.holds(tb.t0) and not tb.holds(tb.t0 - 1)


def test_stochastic_tau_bound_classification():
    assert not Bernoulli(np.full(4, 0.5)).tau_bound().deterministic
    b = Bernoulli(np.full(4, 0.5)).tau_bound()
    np.testing.assert_allclose(b.expected_tau, 1.0)  # (1-p)/p at p=0.5


# --------------------------------------------------------------------------- #
# fleet: in-jit sampling, bit-exactness, grid expansion
# --------------------------------------------------------------------------- #

def _ge(seed, burst=3.0):
    return GilbertElliott.from_rate_and_burst(0.5, burst, n=N,
                                              seed=100 + seed)


def test_fleet_bitexact_vs_sequential_jit_native(tiny_problem):
    """K trials under a jit-native Gilbert–Elliott scenario: the vmapped
    fleet reproduces sequential `run_fl(scenario=...)` within the fp32
    reduction-order band, active counts exact."""
    model, batcher = tiny_problem(n_clients=N)
    kw = dict(model=model, batcher=batcher,
              schedule=lambda t: 0.1 / (1 + t), n_rounds=4,
              weight_decay=1e-3)
    seq = [run_fl(algo=MIFA(memory="array"), scenario=_ge(k), seed=k, **kw)
           for k in range(3)]
    fleet = run_fleet(algo=MIFA(memory="array"),
                      trials=[Trial(seed=k, scenario=_ge(k))
                              for k in range(3)], **kw)
    for k in range(3):
        assert_fleet_trial_close(fleet, seq[k], k)


def test_fleet_grid_three_scenario_types_sample_in_jit(tiny_problem,
                                                       monkeypatch):
    """A FleetSpec grid over >= 3 scenario types runs with availability
    sampled inside the jitted round: the host surface is NEVER queried and
    no (T, N) trace exists anywhere (trials carry no participation)."""
    model, batcher = tiny_problem(n_clients=N)

    def boom(self, t):
        raise AssertionError("host surface queried during a dense fleet "
                             "run — sampling must happen inside jit")
    monkeypatch.setattr(HostSampler, "sample", boom)

    points = [
        ("gilbert_elliott", {"rate": 0.5, "burst": 4.0}),
        ("cluster", {"n_clusters": 3, "q_fail": 0.2, "q_recover": 0.3}),
        ("staged_blackout", {"dark_frac": 0.5, "stage_len": 2}),
        ("diurnal", {"period": 6.0}),
    ]
    for name, kw in points:
        specs = expand_grid(
            algos={"mifa": MIFA(memory="array"),
                   "fedavg": BiasedFedAvg()},
            seeds=(0, 1),
            make_scenario=lambda seed, _n=name, _kw=kw: make_scenario(
                _n, n=N, seed=seed, **_kw).process)
        for spec in specs:
            assert all(tr.participation is None for tr in spec.trials)
            _, hist = run_fleet(spec=spec, model=model, batcher=batcher,
                                schedule=lambda t: 0.1, n_rounds=3,
                                weight_decay=1e-3)
            assert len(hist.train_loss) == 3
            assert np.isfinite(np.stack(hist.train_loss)).all()


def test_fleet_rejects_mixed_scenario_types(tiny_problem):
    model, batcher = tiny_problem(n_clients=N)
    with pytest.raises(ValueError, match="share a scenario type"):
        FleetRunner(model=model, algo=MIFA(memory="array"), batcher=batcher,
                    schedule=lambda t: 0.1, seeds=[0, 1],
                    scenarios=[_ge(0),
                               Bernoulli(np.full(N, 0.5), seed=1)])


def test_cohort_algo_uses_host_surface_same_masks(tiny_problem):
    """BankedMIFA (cohort) under a scenario draws the SAME masks the dense
    in-jit path draws — n_active histories match round for round."""
    from repro.bank import BankedMIFA, DenseBank
    model, batcher = tiny_problem(n_clients=N)
    kw = dict(model=model, batcher=batcher, schedule=lambda t: 0.1,
              n_rounds=6, weight_decay=1e-3, seed=0, cohort_capacity=8)
    _, dense = run_fl(algo=MIFA(memory="array"), scenario=_ge(0), **kw)
    _, banked = run_fl(algo=BankedMIFA(DenseBank()), scenario=_ge(0), **kw)
    assert dense.n_active == banked.n_active


def test_run_fl_requires_exactly_one_availability_source(tiny_problem):
    model, batcher = tiny_problem(n_clients=N)
    with pytest.raises(ValueError, match="exactly one"):
        run_fl(model=model, algo=MIFA(memory="array"), batcher=batcher,
               schedule=lambda t: 0.1, n_rounds=1)


def test_trial_requires_exactly_one_availability_source():
    with pytest.raises(ValueError, match="exactly one"):
        Trial(seed=0)
    with pytest.raises(ValueError, match="exactly one"):
        Trial(seed=0, participation=object(), scenario=object())


# --------------------------------------------------------------------------- #
# registry, samplers, composition
# --------------------------------------------------------------------------- #

def test_registry_roundtrip_and_errors():
    assert "gilbert_elliott" in scenario_names()
    with pytest.raises(KeyError, match="unknown scenario"):
        make_scenario("nope", n=4)
    with pytest.raises(ValueError, match="already registered"):
        register("bernoulli", lambda **kw: None)
    scen = make_scenario("gilbert_elliott", n=4, seed=9, rate=0.5, burst=2.0)
    assert scen.name == "gilbert_elliott/burst=2.0,rate=0.5/seed9"
    assert scen.n == 4


def test_scenario_sim_inputs_composition():
    from repro.sim import ShiftedExponentialLatency
    proc = Bernoulli(np.full(4, 0.5), seed=0)
    lat = ShiftedExponentialLatency(0.5, 0.1, n=4, seed=0)
    part, latency = Scenario(proc, latency=lat, name="x").sim_inputs()
    assert part.sample(0).all() and latency is lat
    with pytest.raises(ValueError, match="no latency"):
        Scenario(proc, name="x").sim_inputs()
    assert as_process(Scenario(proc)) is proc and as_process(proc) is proc


def test_stateful_host_sampler_enforces_round_order():
    proc = _ge(0)
    host = proc.host_sampler()
    host.sample(0)
    with pytest.raises(ValueError, match="in order"):
        host.sample(5)
    # stateless processes accept arbitrary t
    b = Bernoulli(np.full(N, 0.5), seed=0).host_sampler()
    b.sample(7)
    b.sample(2)


# --------------------------------------------------------------------------- #
# TauStats / tau_matrix round-0 strictness (the satellite bugfix)
# --------------------------------------------------------------------------- #

def test_tau_matrix_raises_on_round0_violation():
    masks = np.ones((4, 3), bool)
    masks[0, 1] = False
    with pytest.raises(ValueError, match="round 0"):
        tau_matrix(masks)
    tm = tau_matrix(masks, strict=False)     # init convention: τ(0,i)=1
    assert tm[0, 1] == 1 and tm[0, 0] == 0


def test_tau_stats_raises_on_round0_violation():
    st = TauStats(3)
    with pytest.raises(ValueError, match="round 0"):
        st.update(np.array([True, False, True]))
    lax = TauStats(3, strict=False)
    lax.update(np.array([True, False, True]))
    assert lax.tau.tolist() == [0, 1, 0]
    # only the FIRST round is checked; later gaps are the normal case
    ok = TauStats(3)
    ok.update(np.ones(3, bool))
    ok.update(np.array([True, False, True]))
    assert ok.tau_max == 1
