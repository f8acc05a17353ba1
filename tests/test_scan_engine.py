"""Scan engine: whole-run lax.scan programs are bit-exact vs the loop.

The anchor properties for `core.scan_engine` / the scan-native fleet path:

  * `run_fl(engine="scan")` reproduces the per-round loop fp32 bit-for-bit
    (params, loss history, n_active, τ statistics) for dense algorithms and
    jittable banks, under both a jit-native Gilbert–Elliott scenario and a
    legacy host participation process;
  * results are invariant to the chunking (`scan_chunk` ∈ {1, 7, T}) —
    chunk boundaries are an execution detail, never a numerics knob;
  * dense scenario runs sample availability INSIDE the compiled program:
    the host surface is never queried and no (T, N) mask trace is ever
    materialised (monkeypatch-verified);
  * unsupported configurations (host banks, update-clock schedules) fall
    back to the loop with a warning — or raise under "scan_strict";
  * the fleet scan path (`run_fleet(engine="scan")`) matches the per-round
    fleet path per trial, which test_fleet already pins to sequential runs.
"""
import warnings

import jax
import numpy as np
import pytest

from repro import spans
from repro.bank import BankedMIFA, DenseBank, HostBank, PagedDeviceBank
from repro.core import (MIFA, BiasedFedAvg, FedAvgSampling, RoundRunner,
                        TraceParticipation, run_fl)
from repro.core.scan_engine import ScanDriver, chunk_bounds
from repro.data import ProceduralBatcher
from repro.fleet import Trial, run_fleet
from repro.scenarios import GilbertElliott, HostSampler

N, T = 6, 9

ALGOS = {
    "mifa_array": lambda: MIFA(memory="array"),
    "mifa_int8": lambda: MIFA(memory="int8"),
    "banked_dense": lambda: BankedMIFA(DenseBank()),
    "banked_paged": lambda: BankedMIFA(PagedDeviceBank(page_size=4)),
    "fedavg": lambda: BiasedFedAvg(),
}


def _ge(seed=0, burst=3.0):
    return GilbertElliott.from_rate_and_burst(0.5, burst, n=N,
                                              seed=100 + seed)


def _kw(tiny_problem, **over):
    model, batcher = tiny_problem(n_clients=N)
    kw = dict(model=model, batcher=batcher,
              schedule=lambda t: 0.1 / (1 + t), n_rounds=T,
              weight_decay=1e-3, seed=0, cohort_capacity=8)
    kw.update(over)
    return kw


def _assert_same(run_a, run_b):
    (pa, ha), (pb, hb) = run_a, run_b
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ha.train_loss == hb.train_loss
    assert ha.n_active == hb.n_active
    assert ha.rounds == hb.rounds
    assert (ha.tau_bar, ha.tau_max) == (hb.tau_bar, hb.tau_max)


# --------------------------------------------------------------------------- #
# bit-exact equivalence vs the per-round loop
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("name", list(ALGOS))
def test_scan_bitexact_vs_loop_scenario(tiny_problem, name):
    """Jit-native Gilbert–Elliott scenario through both engines."""
    kw = _kw(tiny_problem)
    loop = run_fl(algo=ALGOS[name](), engine="loop", scenario=_ge(), **kw)
    scan = run_fl(algo=ALGOS[name](), engine="scan", scan_chunk=4,
                  scenario=_ge(), **kw)
    _assert_same(loop, scan)


@pytest.mark.parametrize("name", list(ALGOS))
def test_scan_bitexact_vs_loop_participation(tiny_problem, name):
    """Legacy host participation (trace replay) through both engines."""
    kw = _kw(tiny_problem)
    trace = np.random.default_rng(3).random((T, N)) < 0.5
    loop = run_fl(algo=ALGOS[name](), engine="loop",
                  participation=TraceParticipation(trace), **kw)
    scan = run_fl(algo=ALGOS[name](), engine="scan", scan_chunk=4,
                  participation=TraceParticipation(trace), **kw)
    _assert_same(loop, scan)


@pytest.mark.parametrize("name", ["mifa_array", "banked_dense"])
def test_return_state_is_the_final_state(tiny_problem, name):
    """return_state=True hands back the algorithm state the last round left
    — the same from both engines — and the run's params and history as
    without it."""
    kw = _kw(tiny_problem)
    runs = {engine: run_fl(algo=ALGOS[name](), engine=engine, scan_chunk=4,
                           scenario=_ge(), return_state=True, **kw)
            for engine in ("loop", "scan")}
    for engine, (params, hist, state) in runs.items():
        _assert_same((params, hist), run_fl(algo=ALGOS[name](),
                                            engine=engine, scan_chunk=4,
                                            scenario=_ge(), **kw))
    sa, sb = runs["loop"][2], runs["scan"][2]
    assert jax.tree.structure(sa) == jax.tree.structure(sb)
    for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rows = [leaf for leaf in jax.tree.leaves(sb) if leaf.ndim
            and leaf.shape[0] in (N, N + 1)]
    assert rows and any(np.asarray(r).any() for r in rows)


@pytest.mark.parametrize("chunk", [1, 7, T])
def test_scan_chunk_boundary_invariance(tiny_problem, chunk):
    """scan_chunk is an execution detail: {1, 7, T} give identical runs."""
    kw = _kw(tiny_problem)
    ref = run_fl(algo=MIFA(memory="array"), engine="loop", scenario=_ge(),
                 **kw)
    got = run_fl(algo=MIFA(memory="array"), engine="scan", scan_chunk=chunk,
                 scenario=_ge(), **kw)
    _assert_same(ref, got)


def test_scan_eval_rounds_match_loop(tiny_problem):
    """Chunk boundaries snap to eval rounds: the eval curve is recorded at
    exactly the rounds the loop engine evaluates."""
    kw = _kw(tiny_problem)
    ev = lambda p: (0.5, 0.25)
    loop = run_fl(algo=MIFA(memory="array"), engine="loop", scenario=_ge(),
                  eval_fn=ev, eval_every=4, **kw)
    scan = run_fl(algo=MIFA(memory="array"), engine="scan", scan_chunk=5,
                  scenario=_ge(), eval_fn=ev, eval_every=4, **kw)
    assert loop[1].eval_loss == scan[1].eval_loss
    assert [t for t, _ in scan[1].eval_loss] == [0, 4, 8]


# --------------------------------------------------------------------------- #
# no (T, N) trace, no host sampling — the jit-native guarantee survives
# --------------------------------------------------------------------------- #

def test_scan_scenario_never_touches_host_surface(tiny_problem, monkeypatch):
    """Dense scenario scan: availability is sampled inside the compiled
    program; the host surface must never be queried and no (T, N) mask
    trace may be stacked anywhere on the host."""
    def boom(self, t):
        raise AssertionError("host surface queried during a dense scenario "
                             "scan — sampling must happen inside the "
                             "compiled program")
    monkeypatch.setattr(HostSampler, "sample", boom)

    stacked_shapes = []
    real_stack = np.stack

    def recording_stack(arrays, *a, **k):
        out = real_stack(arrays, *a, **k)
        stacked_shapes.append((out.shape, out.dtype))
        return out
    monkeypatch.setattr(np, "stack", recording_stack)

    kw = _kw(tiny_problem)
    _, hist = run_fl(algo=MIFA(memory="array"), engine="scan", scan_chunk=4,
                     scenario=_ge(), **kw)
    assert len(hist.train_loss) == T
    assert not any(shape == (T, N) and dtype == np.bool_
                   for shape, dtype in stacked_shapes), stacked_shapes


# --------------------------------------------------------------------------- #
# the resident row table: indices cross the bus, rows are gathered on chip
# --------------------------------------------------------------------------- #

def _chunk_xs(model, batcher, chunk=4):
    """Run T dense masked rounds with `batcher`; returns (each chunk's xs,
    the run's counters)."""
    runner = RoundRunner(model=model, algo=MIFA(memory="array"),
                         batcher=batcher, schedule=lambda t: 0.1,
                         weight_decay=1e-3, seed=0)
    drv, seen = ScanDriver(runner, scan_chunk=chunk), []
    inner = drv._chunk_fn

    def recording(carry, xs):
        seen.append(xs)
        return inner(carry, xs)

    drv._chunk_fn = recording
    trace = np.random.default_rng(3).random((T, N)) < 0.5
    drv.run(T, participation=TraceParticipation(trace))
    return seen, spans.last("run")["counts"]


def test_client_batcher_takes_the_row_table(tiny_problem):
    """A `ClientBatcher` run ships `batch_rows` (int32 row indices) and no
    `batch`, and counts every round as a table round."""
    seen, counts = _chunk_xs(*tiny_problem(n_clients=N))
    assert counts["table_rounds"] == counts["rounds"] == T
    for xs in seen:
        assert "batch" not in xs and xs["batch_rows"].dtype == np.int32
    assert sum(len(xs["batch_rows"]) for xs in seen) == T


def test_procedural_batcher_keeps_host_batches(tiny_problem):
    """A batcher without a row table keeps the host-stacked `batch` path
    and records no table rounds."""
    model, ref = tiny_problem(n_clients=N)
    batcher = ProceduralBatcher(n_clients=N, dim=ref.dim, n_classes=10,
                                batch_size=ref.batch_size,
                                k_steps=ref.k_steps, seed=0)
    seen, counts = _chunk_xs(model, batcher)
    assert counts["rounds"] == T and "table_rounds" not in counts
    for xs in seen:
        assert "batch_rows" not in xs and "batch" in xs


# --------------------------------------------------------------------------- #
# fallbacks and strictness
# --------------------------------------------------------------------------- #

def test_scan_host_bank_falls_back_to_loop(tiny_problem):
    kw = _kw(tiny_problem)
    ref = run_fl(algo=BankedMIFA(HostBank()), engine="loop",
                 scenario=_ge(), **kw)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = run_fl(algo=BankedMIFA(HostBank()), engine="scan",
                     scenario=_ge(), **kw)
        assert any("falling back" in str(x.message) for x in w)
    assert ref[1].train_loss == got[1].train_loss


def test_scan_strict_raises_on_host_bank(tiny_problem):
    with pytest.raises(ValueError, match="host-offloaded"):
        run_fl(algo=BankedMIFA(HostBank()), engine="scan_strict",
               scenario=_ge(), **_kw(tiny_problem))


def test_scan_update_clock_falls_back(tiny_problem):
    kw = _kw(tiny_problem)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        run_fl(algo=FedAvgSampling(s=3), engine="scan",
               uses_update_clock=True, scenario=_ge(), **kw)
        assert any("update-clock" in str(x.message) for x in w)
    with pytest.raises(ValueError, match="update-clock"):
        run_fl(algo=FedAvgSampling(s=3), engine="scan_strict",
               uses_update_clock=True, scenario=_ge(), **kw)


def test_unknown_engine_rejected(tiny_problem):
    with pytest.raises(ValueError, match="unknown engine"):
        run_fl(algo=MIFA(memory="array"), engine="turbo", scenario=_ge(),
               **_kw(tiny_problem))


def test_scan_cohort_capacity_overflow_raises(tiny_problem):
    """A pinned capacity smaller than a drawn cohort must raise (the scan
    program cannot widen per round the way the loop's pow-2 buckets do)."""
    kw = _kw(tiny_problem, cohort_capacity=2)
    with pytest.raises(ValueError, match="overflows the scan capacity"):
        run_fl(algo=BankedMIFA(DenseBank()), engine="scan",
               participation=TraceParticipation(np.ones((T, N), bool)),
               **kw)


# --------------------------------------------------------------------------- #
# paged device bank under scan: eviction, chunk-union residency, messages
# --------------------------------------------------------------------------- #

class _RawTrace:
    """Trace participation without TraceParticipation's forced all-active
    round 0 — eviction tests need sparse cohorts from the first round."""

    def __init__(self, trace):
        self.trace = np.asarray(trace, bool)
        self.n = self.trace.shape[1]

    def sample(self, t):
        return self.trace[t]


def _paged_trace():
    """Cohorts that, at page_size=2 / n_slots=2, fit per round but force
    evictions and refaults across the run."""
    cohorts = [[0, 1], [4, 5], [2, 3], [0, 5], [2], [1, 3], [4], [0, 2]]
    tr = np.zeros((len(cohorts), N), bool)
    for t, ids in enumerate(cohorts):
        tr[t, ids] = True
    return tr


def test_scan_paged_eviction_bitexact_vs_loop(tiny_problem):
    """With pages spilling and refaulting on different schedules, loop and
    scan still match DenseBank bit-for-bit: physical slots are invisible."""
    tr = _paged_trace()
    kw = _kw(tiny_problem, n_rounds=len(tr), cohort_capacity=2)
    paged = lambda: BankedMIFA(PagedDeviceBank(page_size=2, n_slots=2))
    ref = run_fl(algo=BankedMIFA(DenseBank()), engine="loop",
                 participation=_RawTrace(tr), **kw)
    loop = run_fl(algo=paged(), engine="loop",
                  participation=_RawTrace(tr), **kw)
    scan = run_fl(algo=paged(), engine="scan", scan_chunk=1,
                  participation=_RawTrace(tr), **kw)
    _assert_same(ref, loop)
    _assert_same(loop, scan)


def test_scan_paged_chunk_union_overflow_raises(tiny_problem):
    """Under scan, residency is prepared per *chunk union*; a union wider
    than the slot budget must fail with actionable advice, not corrupt."""
    tr = _paged_trace()
    kw = _kw(tiny_problem, n_rounds=len(tr), cohort_capacity=2)
    with pytest.raises(ValueError, match="slots"):
        run_fl(algo=BankedMIFA(PagedDeviceBank(page_size=2, n_slots=2)),
               engine="scan", scan_chunk=2,
               participation=_RawTrace(tr), **kw)


def test_scan_fallback_warning_names_capable_backends(tiny_problem):
    """The fallback warning must name the blocking backend and the banks
    that do support scan, so users know what to switch to."""
    kw = _kw(tiny_problem)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        run_fl(algo=BankedMIFA(HostBank()), engine="scan",
               scenario=_ge(), **kw)
    msg = next(str(x.message) for x in w if "falling back" in str(x.message))
    assert "HostBank" in msg
    assert "DenseBank" in msg and "PagedDeviceBank" in msg


# --------------------------------------------------------------------------- #
# fleet scan path
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("name", list(ALGOS))
def test_fleet_scan_bitexact_vs_fleet_loop(tiny_problem, name):
    """run_fleet(engine="scan") matches the per-round fleet path per trial
    (which test_fleet pins to sequential run_fl) — participation trials."""
    model, batcher = tiny_problem(n_clients=N)
    traces = np.random.default_rng(7).random((3, T, N)) < 0.5
    kw = dict(model=model, batcher=batcher,
              schedule=lambda t: 0.1 / (1 + t), n_rounds=T,
              weight_decay=1e-3, cohort_capacity=8)
    mk = lambda: [Trial(seed=k, participation=TraceParticipation(traces[k]))
                  for k in range(3)]
    loop = run_fleet(algo=ALGOS[name](), trials=mk(), engine="loop", **kw)
    scan = run_fleet(algo=ALGOS[name](), trials=mk(), engine="scan",
                     scan_chunk=4, **kw)
    for a, b in zip(jax.tree.leaves(loop[0]), jax.tree.leaves(scan[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in range(3):
        assert loop[1].trial(k).train_loss == scan[1].trial(k).train_loss
        assert loop[1].trial(k).n_active == scan[1].trial(k).n_active


def test_fleet_scan_scenario_in_jit(tiny_problem, monkeypatch):
    """Scenario fleet scan samples in-program (host surface never queried)
    and matches the per-round fleet path bit-for-bit."""
    model, batcher = tiny_problem(n_clients=N)
    kw = dict(model=model, batcher=batcher,
              schedule=lambda t: 0.1 / (1 + t), n_rounds=T,
              weight_decay=1e-3)
    mk = lambda: [Trial(seed=k, scenario=_ge(k)) for k in range(3)]
    loop = run_fleet(algo=MIFA(memory="array"), trials=mk(), engine="loop",
                     **kw)

    def boom(self, t):
        raise AssertionError("host surface queried during a scenario "
                             "fleet scan")
    monkeypatch.setattr(HostSampler, "sample", boom)
    scan = run_fleet(algo=MIFA(memory="array"), trials=mk(), engine="scan",
                     scan_chunk=4, **kw)
    for a, b in zip(jax.tree.leaves(loop[0]), jax.tree.leaves(scan[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in range(3):
        assert loop[1].trial(k).train_loss == scan[1].trial(k).train_loss


def test_fleet_scan_update_clock_falls_back(tiny_problem):
    model, batcher = tiny_problem(n_clients=N)
    traces = np.ones((2, T, N), bool)
    kw = dict(model=model, batcher=batcher, schedule=lambda t: 0.1,
              n_rounds=3, weight_decay=1e-3)
    trials = [Trial(seed=k, participation=TraceParticipation(traces[k]))
              for k in range(2)]
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        run_fleet(algo=FedAvgSampling(s=3), trials=trials,
                  uses_update_clock=True, engine="scan", **kw)
        assert any("update-clock" in str(x.message) for x in w)


# --------------------------------------------------------------------------- #
# chunking helper
# --------------------------------------------------------------------------- #

def test_chunk_bounds_snap_to_evals():
    assert chunk_bounds(10, 4, set()) == [(0, 4), (4, 8), (8, 10)]
    # eval after rounds 0 and 5 forces cuts at 1 and 6
    assert chunk_bounds(10, 4, {0, 5}) == [(0, 1), (1, 4), (4, 6), (6, 8),
                                           (8, 10)]
    assert chunk_bounds(3, 100, set()) == [(0, 3)]
    with pytest.raises(ValueError, match="scan_chunk"):
        chunk_bounds(10, 0, set())
