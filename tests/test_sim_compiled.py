"""Compiled simulator: heap-vs-scan parity, the unified policy algebra,
buffered-async semantics, the simulated fleet, and the jit-native batcher.

The contract under test (docs/architecture.md §11): `repro.sim.compiled`
reproduces the discrete-event heap engine BIT-EXACTLY — same f32 round
close times, same applied masks, same losses — for every supported
configuration, across all five policies and both independent and
temporally-correlated availability.
"""
import warnings

import numpy as np
import pytest

from repro.core import BiasedFedAvg, FedBuffAvg, MIFA, RoundRunner, run_fl
from repro.data import JitProceduralBatcher
from repro.fleet import SimTrial, make_fleet_eval, run_sim_fleet
from repro.optim import inv_t
from repro.scenarios import Bernoulli, GilbertElliott, as_process
from repro.sim import (BufferedKofN, Deadline, FedSimEngine, Impatient,
                       SimConfig, SimScanDriver, SimSpec, WaitForAll,
                       WaitForS, sim_scan_supported,
                       tiered_shifted_exponential)
from repro.sim.compiled import run_sim_scan
from repro.sim.engine import LATE
from conftest import FLEET_ATOL, FLEET_RTOL

N, T = 9, 12
CONFIG = SimConfig(epoch_s=4.0, server_overhead_s=0.1,
                   max_lookahead_epochs=40)

POLICIES = [WaitForAll(), WaitForS(s=4), Deadline(deadline_s=3.0),
            Impatient(), BufferedKofN(k=3)]
SCENARIOS = [Bernoulli(0.6, n=N, seed=5),
             GilbertElliott(0.3, 0.4, n=N, seed=5)]


def _algo_for(policy):
    return FedBuffAvg() if getattr(policy, "stateful", False) \
        else BiasedFedAvg()


@pytest.fixture
def make_runner(tiny_problem):
    def _make(algo, scenario, seed=0):
        model, batcher = tiny_problem(n_clients=N, n_per_class=60)
        return RoundRunner(model=model, algo=algo, batcher=batcher,
                           schedule=inv_t(1.0), weight_decay=1e-3, seed=seed,
                           scenario=scenario)
    return _make


def _run_both(make_runner, policy, scenario, algo=None, n_rounds=T,
              config=CONFIG, scan_chunk=5, seed=0):
    """(heap engine record, compiled driver record) for one config."""
    algo = algo or _algo_for(policy)
    lat = tiered_shifted_exponential(N, seed=7)
    sim = SimSpec(policy=policy, latency=lat, config=config)

    r_heap = make_runner(algo, scenario, seed)
    eng = FedSimEngine(r_heap, policy, as_process(scenario).host_sampler(),
                       lat, config, seed=seed)
    eng.run(n_rounds)

    r_scan = make_runner(algo, scenario, seed)
    ok, why = sim_scan_supported(r_scan, sim)
    assert ok, why
    drv = SimScanDriver(r_scan, sim, scan_chunk=scan_chunk, emit_masks=True)
    drv.run(n_rounds)
    return (eng, r_heap), (drv, r_scan)


# --------------------------------------------------------------------------- #
# heap vs compiled parity: close times, applied masks, losses
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("scenario", SCENARIOS,
                         ids=["bernoulli", "gilbert_elliott"])
@pytest.mark.parametrize("policy", POLICIES, ids=[p.name for p in POLICIES])
def test_heap_scan_parity(make_runner, policy, scenario):
    """Bit-exact parity on every supported config: the compiled scan and
    the event heap agree on round close times, applied masks, per-round
    counters, AND the resulting training losses."""
    (eng, rh), (drv, rs) = _run_both(make_runner, policy, scenario)
    for rec_h, rec_s in zip(eng.round_log, drv.round_log):
        assert rec_h["t_close"] == rec_s["t_close"], rec_h["round"]
        assert rec_h["t_open"] == rec_s["t_open"]
        for k in ("n_dispatched", "n_applied", "n_late", "n_never"):
            assert rec_h[k] == rec_s[k], (rec_h["round"], k)
    np.testing.assert_array_equal(np.stack(eng.applied_log),
                                  np.stack(drv.applied_log))
    np.testing.assert_array_equal(rh.hist.train_loss, rs.hist.train_loss)
    np.testing.assert_array_equal(rh.hist.sim_seconds, rs.hist.sim_seconds)


def test_parity_with_mifa(make_runner):
    """MIFA's memory bank rides the compiled sim body unchanged."""
    (eng, rh), (drv, rs) = _run_both(
        make_runner, Impatient(), SCENARIOS[0], algo=MIFA(memory="array"))
    np.testing.assert_array_equal(rh.hist.train_loss, rs.hist.train_loss)
    np.testing.assert_array_equal(rh.hist.sim_seconds, rs.hist.sim_seconds)


def test_run_fl_sim_engines_agree(make_runner, tiny_problem):
    """The public entry point: run_fl(sim=..., engine='loop'|'scan') gives
    identical histories, and evals are stamped at identical sim times."""
    model, batcher = tiny_problem(n_clients=N, n_per_class=60)
    lat = tiered_shifted_exponential(N, seed=7)
    sim = SimSpec(policy=WaitForS(s=4), latency=lat, config=CONFIG)
    kw = dict(model=model, algo=BiasedFedAvg(), batcher=batcher,
              schedule=inv_t(1.0), n_rounds=T, scenario=SCENARIOS[0],
              sim=sim, seed=3, eval_every=4)
    _, h_loop = run_fl(engine="loop", **kw)
    _, h_scan = run_fl(engine="scan", **kw)
    np.testing.assert_array_equal(h_loop.train_loss, h_scan.train_loss)
    np.testing.assert_array_equal(h_loop.sim_seconds, h_scan.sim_seconds)
    np.testing.assert_array_equal(h_loop.n_active, h_scan.n_active)


# --------------------------------------------------------------------------- #
# unsupported configs: honest fallback naming the blocker
# --------------------------------------------------------------------------- #

def test_sim_scan_supported_rejects_oversized_window(make_runner):
    sim = SimSpec(policy=WaitForAll(),
                  latency=tiered_shifted_exponential(N, seed=7),
                  config=SimConfig(max_lookahead_epochs=1 << 24))
    ok, why = sim_scan_supported(make_runner(BiasedFedAvg(), SCENARIOS[0]),
                                 sim)
    assert not ok and "window" in why


def test_run_fl_sim_falls_back_with_warning(tiny_problem):
    """Legacy participation= (no scenario, so no jit-native sampler) must
    fall back to the heap engine under engine='scan', naming the blocker."""
    from repro.core import BernoulliParticipation
    model, batcher = tiny_problem(n_clients=N, n_per_class=60)
    sim = SimSpec(policy=WaitForAll(),
                  latency=tiered_shifted_exponential(N, seed=7),
                  config=CONFIG)
    kw = dict(model=model, algo=BiasedFedAvg(), batcher=batcher,
              schedule=inv_t(1.0), n_rounds=4,
              participation=BernoulliParticipation(np.full(N, 0.6), seed=5),
              sim=sim)
    with pytest.warns(UserWarning, match="scenario"):
        _, hist = run_fl(engine="scan", **kw)
    assert len(hist.sim_seconds) == 4
    with pytest.raises(ValueError, match="scan_strict"):
        run_fl(engine="scan_strict", **kw)


# --------------------------------------------------------------------------- #
# buffered-async (FedBuff-style) semantics
# --------------------------------------------------------------------------- #

def test_buffered_pending_carry_over(make_runner):
    """K-of-N closes on the kth arrival; the stragglers stay in flight and
    are merged in a LATER round with staleness-discounted weight — so some
    round must apply a device whose dispatch round differs."""
    (eng, _), (drv, _) = _run_both(make_runner, BufferedKofN(k=3),
                                   SCENARIOS[0])
    # no late drops under buffering: everything eventually merges or waits
    assert all(r["n_late"] == 0 for r in eng.round_log)
    assert all(r["n_late"] == 0 for r in drv.round_log)
    # pending arrivals from earlier rounds: some round must apply a device
    # it did NOT dispatch (the straggler merged with staleness discount)
    applied = np.stack(drv.applied_log)
    cohort = np.stack(drv.cohort_log)
    assert (applied & ~cohort).any()


def test_buffered_staleness_weights():
    """FedBuffAvg: update = Σ w·u / |contributors| with the weight vector
    passed through as `active` (weight_aware)."""
    import jax.numpy as jnp
    algo = FedBuffAvg()
    assert algo.weight_aware
    params = {"w": jnp.zeros(3)}
    updates = {"w": jnp.asarray([[3.0, 0, 0], [0, 6.0, 0], [0, 0, 9.0]])}
    w = jnp.asarray([1.0, 0.5, 0.0])         # stale device discounted, one out
    st = algo.init_state(params, 3)
    _, new_p, m = algo.round_step(st, params, updates,
                                  jnp.asarray([1.0, 2.0, 3.0]), w, 1.0)
    # contributors = 2 -> mean = (1*u0 + 0.5*u1) / 2
    np.testing.assert_allclose(np.asarray(new_p["w"]),
                               [-1.5, -1.5, 0.0], rtol=1e-6)
    assert float(m["n_active"]) == 2.0
    assert float(m["loss"]) == pytest.approx(1.5)


def test_buffered_policy_weights_match_staleness(make_runner):
    """The heap engine's buffered weights are 1/sqrt(1+staleness_rounds)."""
    policy = BufferedKofN(k=3)
    pstate = policy.init_pstate(N)
    cohort = np.ones(N, bool)
    avail = np.ones(N, bool)
    arrivals = np.full(N, np.inf, np.float32)
    arrivals[:4] = np.float32([0.5, 1.0, 1.5, 9.0])
    close, applied, w, pstate = policy.resolve_pending(
        pstate, cohort, avail, arrivals, np.float32(0.0), np.float32(4.0), 0)
    assert close == np.float32(1.5)           # kth (k=3) arrival
    assert applied.sum() == 3 and not applied[3]
    np.testing.assert_array_equal(w[:3], 1.0)  # fresh: staleness 0
    assert np.isfinite(pstate["pending"][3])   # straggler still in flight
    # straggler merges next round with discounted weight
    arrivals2 = np.full(N, np.inf, np.float32)
    close2, applied2, w2, pstate = policy.resolve_pending(
        pstate, np.zeros(N, bool), avail, arrivals2, np.float32(1.6),
        np.float32(4.0), 1)
    assert applied2[3] and w2[3] == np.float32(1.0 / np.sqrt(2.0))
    assert not np.isfinite(pstate["pending"][3])


# --------------------------------------------------------------------------- #
# heap engine satellites: LATE records, never-returning counter
# --------------------------------------------------------------------------- #

def test_late_records_preserve_arrival_and_close(make_runner):
    """LATE events are 6-tuples (arrival_t, seq, 'late', client, round,
    close_t): the true arrival time survives, close is separate."""
    algo = BiasedFedAvg()
    r = make_runner(algo, SCENARIOS[0])
    eng = FedSimEngine(r, Deadline(deadline_s=0.5),
                       as_process(SCENARIOS[0]).host_sampler(),
                       tiered_shifted_exponential(N, seed=7), CONFIG, seed=0)
    eng.run(6)
    lates = [e for e in eng.event_log if e[2] == LATE]
    assert lates, "0.5s deadline under a 2.0s-shift slow tier must drop some"
    for ev in lates:
        assert len(ev) == 6
        arrival, _, _, client, rnd, close = ev
        assert arrival > close            # late means arrived after close
        assert 0 <= client < N


def test_never_returning_counter_and_warning(make_runner):
    """A device dark past the lookahead horizon is counted in n_never and
    warned about once, naming SimConfig.max_lookahead_epochs."""
    from repro.core import TraceParticipation
    from repro.sim import TraceLatency
    trace = np.ones((2, N), bool)
    trace[1, 0] = False                       # device 0 dark from epoch 1 on
    part = TraceParticipation(trace)
    lat = TraceLatency(np.full((1, N), 0.5))
    cfg = SimConfig(epoch_s=1.0, max_lookahead_epochs=5)
    algo = BiasedFedAvg()
    r = make_runner(algo, SCENARIOS[0])
    eng = FedSimEngine(r, WaitForAll(), part, lat, cfg, seed=0)
    with pytest.warns(UserWarning, match="max_lookahead_epochs"):
        eng.run(4)
    assert eng.n_never_total > 0
    assert any(rec["n_never"] > 0 for rec in eng.round_log)
    with warnings.catch_warnings():           # warn-once: silent afterwards
        warnings.simplefilter("error")
        eng.run_round(4)


# --------------------------------------------------------------------------- #
# simulated fleet: K lanes ≡ K single runs, mixed policies in one program
# --------------------------------------------------------------------------- #

def _logistic_dim() -> int:
    from repro.configs import get_config
    return get_config("paper_logistic").d_model


def test_sim_fleet_matches_single_runs(tiny_problem):
    model, _ = tiny_problem(n_clients=N, n_per_class=60)
    batcher = JitProceduralBatcher(n_clients=N, dim=_logistic_dim(),
                                   batch_size=8, k_steps=2, seed=3)
    schedule = inv_t(1.0)
    lat = lambda: tiered_shifted_exponential(N, seed=7)
    trials = [
        SimTrial(seed=13, policy=WaitForAll(),
                 scenario=Bernoulli(0.6, n=N, seed=5), latency=lat()),
        SimTrial(seed=14, policy=Deadline(deadline_s=3.0, cohort_size=6),
                 scenario=Bernoulli(0.6, n=N, seed=6), latency=lat()),
        SimTrial(seed=15, policy=BufferedKofN(k=3),
                 scenario=Bernoulli(0.6, n=N, seed=7), latency=lat()),
    ]
    eval_fn = make_fleet_eval(model, batcher.eval_batch(128))
    _, hist = run_sim_fleet(
        model=model, algo=FedBuffAvg(), batcher=batcher, schedule=schedule,
        n_rounds=T, trials=trials, config=CONFIG, scan_chunk=5,
        eval_fn=eval_fn, eval_every=4, batch_fn=batcher.batch_fn())
    st = hist.stacked()
    assert st["sim_seconds"].shape == (3, T)
    for k, tr in enumerate(trials):
        sim = SimSpec(policy=tr.policy, latency=tr.latency, config=CONFIG)
        _, h1 = run_fl(model=model, algo=FedBuffAvg(), batcher=batcher,
                       schedule=schedule, n_rounds=T, scenario=tr.scenario,
                       sim=sim, seed=tr.seed, engine="scan_strict",
                       scan_chunk=5)
        # event times are exact; losses within the fleet's reduction-order
        # band (conftest FLEET_RTOL)
        np.testing.assert_array_equal(st["sim_seconds"][k], h1.sim_seconds)
        np.testing.assert_allclose(st["train_loss"][k], h1.train_loss,
                                   rtol=FLEET_RTOL, atol=FLEET_ATOL)


def test_sim_fleet_rejects_mixed_latency_classes(tiny_problem):
    from repro.sim import LognormalLatency
    model, _ = tiny_problem(n_clients=N, n_per_class=60)
    batcher = JitProceduralBatcher(n_clients=N, dim=_logistic_dim(),
                                   batch_size=8, k_steps=2, seed=3)
    trials = [
        SimTrial(seed=1, policy=WaitForAll(),
                 scenario=Bernoulli(0.6, n=N, seed=5),
                 latency=tiered_shifted_exponential(N, seed=7)),
        SimTrial(seed=2, policy=WaitForAll(),
                 scenario=Bernoulli(0.6, n=N, seed=5),
                 latency=LognormalLatency(0.0, 0.5, comm=0.1, n=N, seed=7)),
    ]
    with pytest.raises(ValueError, match="latency"):
        run_sim_fleet(model=model, algo=BiasedFedAvg(), batcher=batcher,
                      schedule=inv_t(1.0), n_rounds=2, trials=trials,
                      config=CONFIG)


# --------------------------------------------------------------------------- #
# jit-native batcher
# --------------------------------------------------------------------------- #

def test_jit_batcher_host_matches_program():
    import jax
    b = JitProceduralBatcher(n_clients=5, dim=4, batch_size=3, k_steps=2,
                             seed=9)
    draw = jax.jit(b.batch_fn())
    for t in (0, 7):
        host = b.sample_round(t)
        prog = {k: np.asarray(v) for k, v in draw(t).items()}
        np.testing.assert_array_equal(host["x"], prog["x"])
        np.testing.assert_array_equal(host["y"], prog["y"])
    assert host["x"].shape == (5, 2, 3, 4)
    assert host["y"].dtype == np.int32
    sub = b.sample_round(0, client_ids=[4, 1])
    np.testing.assert_array_equal(sub["x"], b.sample_round(0)["x"][[4, 1]])
    ev = b.eval_batch(64)
    assert ev["x"].shape == (64, 4) and ev["y"].shape == (64,)


# --------------------------------------------------------------------------- #
# property test: parity holds across the latency-parameter space (CI-only)
# --------------------------------------------------------------------------- #

@pytest.mark.slow
def test_parity_property_over_latency_params(make_runner):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.sim import ShiftedExponentialLatency

    @settings(max_examples=8, deadline=None)
    @given(shift=st.floats(0.01, 3.0), scale=st.floats(0.01, 2.0),
           seed=st.integers(0, 10))
    def check(shift, scale, seed):
        lat = ShiftedExponentialLatency(shift, scale, n=N, seed=seed)
        algo = BiasedFedAvg()
        sim = SimSpec(policy=Deadline(deadline_s=shift + scale),
                      latency=lat, config=CONFIG)
        r_heap = make_runner(algo, SCENARIOS[1])
        eng = FedSimEngine(r_heap, sim.policy,
                           as_process(SCENARIOS[1]).host_sampler(), lat,
                           CONFIG, seed=0)
        eng.run(6)
        r_scan = make_runner(algo, SCENARIOS[1])
        drv = SimScanDriver(r_scan, sim, scan_chunk=3, emit_masks=True)
        drv.run(6)
        assert [rec["t_close"] for rec in eng.round_log] == \
               [rec["t_close"] for rec in drv.round_log]
        np.testing.assert_array_equal(np.stack(eng.applied_log),
                                      np.stack(drv.applied_log))
        np.testing.assert_array_equal(r_heap.hist.train_loss,
                                      r_scan.hist.train_loss)

    check()
