"""`repro.spans`: the recorder, and the spans and byte counters a scan run
leaves behind.

  * spans nest: each name's count, seconds and self seconds are summed
    under the innermost open root, counters go to that root, and `last`
    returns the newest closed root of a name;
  * a `ScanDriver` run over a paged bank small enough to evict leaves one
    `run` root whose span tree names every host step of the chunk loop,
    whose `rounds` counter is the rounds run, and whose `h2d_bytes` /
    `d2h_bytes` equal the bytes computed from the xs and ys shapes, the
    page size and each `prepare` call's faults and evictions with their
    pow-2 pads, and the batcher's row table in a driver's first run only;
  * spans never change the numbers: a run inside a profiler session, with
    the spans on its timeline, is bit-exact against one with the spans
    stubbed out.
"""
import contextlib
import time

import jax
import numpy as np
import pytest

from repro import spans
from repro.bank import BankedMIFA, PagedDeviceBank
from repro.core import MIFA, RoundRunner
from repro.core.runner import _pow2_bucket
from repro.core.scan_engine import ScanDriver
from repro.fleet import Trial, run_fleet
from repro.scenarios import GilbertElliott

N, PAGE, SLOTS, CAP = 6, 2, 2, 2
# cohorts that fit two 2-row slots per round but evict and refault
COHORTS = [[0, 1], [4, 5], [2, 3], [0, 5], [2], [1, 3], [4], [0, 2]]
LOOP_TREE = {"availability": "run", "batch_assembly": "run",
             "stack": "batch_assembly", "paging": "run",
             "victims": "paging", "spill": "paging",
             "page_upload": "paging", "dispatch": "run", "flush": "run"}


class _Trace:
    """Cohorts straight from COHORTS (round 0 not forced all-active)."""

    def __init__(self):
        self.trace = np.zeros((len(COHORTS), N), bool)
        for t, ids in enumerate(COHORTS):
            self.trace[t, ids] = True
        self.n = N

    def sample(self, t):
        return self.trace[t]


# --------------------------------------------------------------------------- #
# the recorder
# --------------------------------------------------------------------------- #

def test_nesting_self_time_and_counters():
    with spans.span("r", root=True):
        spans.count("n", 2)
        with spans.span("a"):
            time.sleep(0.02)
            with spans.span("b"):
                time.sleep(0.03)
                spans.count("n", 3)
        with spans.span("a"):
            pass
    rec = spans.last("r")
    a, b = rec["spans"]["a"], rec["spans"]["b"]
    assert rec["counts"] == {"n": 5}
    assert (a["parent"], b["parent"]) == ("r", "a")
    assert (a["count"], b["count"]) == (2, 1)
    assert b["seconds"] >= 0.03 and a["self_s"] >= 0.02
    assert a["self_s"] == pytest.approx(a["seconds"] - b["seconds"])
    assert b["self_s"] == b["seconds"]
    assert rec["self_s"] == pytest.approx(rec["seconds"] - a["seconds"])


def test_last_is_the_newest_root_and_roots_nest():
    for n in (1, 2):
        with spans.span("r", root=True):
            spans.count("n", n)
    assert spans.last("r")["counts"] == {"n": 2}
    with spans.span("outer", root=True):
        with spans.span("r", root=True):
            spans.count("n", 7)
            with spans.span("c"):
                pass
        spans.count("m", 1)
    inner, outer = spans.last("r"), spans.last("outer")
    assert inner["counts"] == {"n": 7} and set(inner["spans"]) == {"c"}
    assert outer["counts"] == {"m": 1}
    assert outer["spans"]["r"]["parent"] == "outer"
    assert "c" not in outer["spans"]
    # a copy: editing it leaves the recorder's aggregate alone
    inner["counts"]["n"] = 0
    assert spans.last("r")["counts"] == {"n": 7}


def test_outside_a_root_nothing_is_kept_and_errors_unwind():
    with spans.span("loose"):
        spans.count("n", 1)
    assert spans.last("loose") is None
    with pytest.raises(RuntimeError):
        with spans.span("r", root=True):
            with spans.span("a"):
                raise RuntimeError("x")
    assert spans.last("r")["spans"]["a"]["count"] == 1
    with spans.span("r", root=True):
        with spans.span("a"):
            pass
        with spans.span("b"):
            with spans.span("a"):
                pass
    assert spans.last("r")["spans"]["a"]["parent"] is None   # two parents


# --------------------------------------------------------------------------- #
# a scan run over an evicting paged bank
# --------------------------------------------------------------------------- #

def _paged_run(tiny_problem, scan_chunk=1):
    """A ScanDriver run; returns (record, per-prepare (faults, evictions),
    per-flush ys shapes, driver)."""
    model, batcher = tiny_problem(n_clients=N)
    bank = PagedDeviceBank(page_size=PAGE, n_slots=SLOTS)
    runner = RoundRunner(model=model, algo=BankedMIFA(bank),
                         batcher=batcher, schedule=lambda t: 0.1 / (1 + t),
                         weight_decay=1e-3, seed=0, cohort_capacity=CAP)
    drv = ScanDriver(runner, scan_chunk=scan_chunk)
    calls, flushed = [], []
    prepare, flush = bank.prepare, drv._flush

    def counted_prepare(state, ids):
        f0, e0 = bank.faults, bank.evictions
        out = prepare(state, ids)
        calls.append((bank.faults - f0, bank.evictions - e0))
        return out

    def shaped_flush(t0, t1, ys, carry):
        flushed.append([(y.shape, y.dtype) for y in jax.tree.leaves(ys)])
        return flush(t0, t1, ys, carry)

    bank.prepare, drv._flush = counted_prepare, shaped_flush
    drv.run(len(COHORTS), participation=_Trace())
    return spans.last("run"), calls, flushed, drv


def _nbytes(shape, dtype) -> int:
    return int(np.prod(shape)) * np.dtype(dtype).itemsize


def _rows_nbytes(batcher) -> int:
    return sum(v.nbytes for v in batcher.rows().values())


def test_scan_run_span_tree_and_rounds(tiny_problem):
    rec, calls, _, drv = _paged_run(tiny_problem)
    assert {k: v["parent"] for k, v in rec["spans"].items()} == LOOP_TREE
    assert rec["counts"]["rounds"] == len(COHORTS)
    n = len(COHORTS)                                 # one round a chunk
    for name in ("availability", "batch_assembly", "stack", "dispatch",
                 "flush", "paging"):
        assert rec["spans"][name]["count"] == n, name
    faulted = sum(f > 0 for f, _ in calls)
    assert rec["spans"]["page_upload"]["count"] == faulted
    assert rec["spans"]["spill"]["count"] == sum(e > 0 for _, e in calls)
    bank = drv.r.algo.bank
    assert bank.evictions > 0 and faulted > 0
    top = sum(v["seconds"] for v in rec["spans"].values()
              if v["parent"] == "run")
    assert rec["self_s"] == pytest.approx(rec["seconds"] - top)


def test_scan_run_bus_bytes_match_shapes(tiny_problem):
    rec, calls, flushed, drv = _paged_run(tiny_problem)
    r, bank = drv.r, drv.r.algo.bank
    # xs of one round: two f32 learning rates, ids (int64, sent as int32),
    # the valid mask and the cohort's int32 row indices; the batcher's row
    # table once
    per_round = (2 * 4 + CAP * 4 + CAP * 1
                 + CAP * r.batcher.k_steps * r.batcher.batch_size * 4)
    pages = jax.tree.leaves(r.state["bank"]["pages"])
    page_b = sum(leaf.nbytes // leaf.shape[0] for leaf in pages) * PAGE
    table_b = (bank.lp + 1) * 4
    h2d = len(COHORTS) * per_round + _rows_nbytes(r.batcher) + sum(
        _pow2_bucket(f) * page_b + table_b for f, _ in calls if f)
    d2h = sum(_pow2_bucket(e) * page_b for _, e in calls if e) + sum(
        _nbytes(s, d) for shapes in flushed for s, d in shapes)
    assert sum(f for f, _ in calls) == bank.faults
    assert sum(e for _, e in calls) == bank.evictions
    assert rec["counts"]["h2d_bytes"] == h2d
    assert rec["counts"]["d2h_bytes"] == d2h


def test_scenario_run_counts_the_tau_reads(tiny_problem):
    """A dense scenario run reads the carried τ state (two (N,) int32
    arrays) in every flush besides the ys; its xs are the round indices,
    learning rates and every client's row indices, after the batcher's
    row table once."""
    model, batcher = tiny_problem(n_clients=N)
    runner = RoundRunner(model=model, algo=MIFA(memory="array"),
                         batcher=batcher, schedule=lambda t: 0.1,
                         weight_decay=1e-3, seed=0,
                         scenario=GilbertElliott.from_rate_and_burst(
                             0.5, 3.0, n=N, seed=100))
    drv, flushed = ScanDriver(runner, scan_chunk=3), []
    flush = drv._flush

    def shaped_flush(t0, t1, ys, carry):
        flushed.append(sum(_nbytes(y.shape, y.dtype)
                           for y in jax.tree.leaves(ys)))
        return flush(t0, t1, ys, carry)

    drv._flush = shaped_flush
    drv.run(9)
    rec = spans.last("run")
    per_round = 3 * 4 + batcher.sample_round_rows(0).nbytes
    assert rec["counts"]["rounds"] == 9 and len(flushed) == 3
    assert rec["counts"]["h2d_bytes"] == (9 * per_round
                                          + _rows_nbytes(batcher))
    assert rec["counts"]["d2h_bytes"] == sum(flushed) + 3 * 2 * N * 4
    assert "availability" not in rec["spans"]      # sampled in the program


def test_dense_run_uploads_the_row_table_once(tiny_problem):
    """A dense masked run counts the batcher's row table into `h2d_bytes`
    in its first `run` only; a second `run` on the same driver sends only
    the rounds' row indices, masks and learning rates."""
    model, batcher = tiny_problem(n_clients=N)
    runner = RoundRunner(model=model, algo=MIFA(memory="array"),
                         batcher=batcher, schedule=lambda t: 0.1,
                         weight_decay=1e-3, seed=0)
    drv, part = ScanDriver(runner, scan_chunk=2), _Trace()
    per_round = 2 * 4 + N * 1 + batcher.sample_round_rows(0).nbytes
    drv.run(4, participation=part)
    first = spans.last("run")["counts"]
    drv.run(8, participation=part, start_round=4)
    second = spans.last("run")["counts"]
    assert first["h2d_bytes"] == 4 * per_round + _rows_nbytes(batcher)
    assert second["h2d_bytes"] == 4 * per_round
    assert first["table_rounds"] == 4 == second["table_rounds"]


def test_fleet_scan_run_is_one_root(tiny_problem):
    model, batcher = tiny_problem(n_clients=N)
    trials = [Trial(seed=s, scenario=GilbertElliott.from_rate_and_burst(
        0.5, 3.0, n=N, seed=100 + s)) for s in (0, 1)]
    run_fleet(model=model, batcher=batcher, schedule=lambda t: 0.1,
              n_rounds=5, algo=BankedMIFA(PagedDeviceBank(page_size=PAGE)),
              trials=trials, cohort_capacity=8, engine="scan",
              scan_chunk=2)
    rec = spans.last("run")
    assert rec["counts"]["rounds"] == 5
    assert {"batch_assembly", "dispatch", "flush", "paging"} <= set(
        rec["spans"])
    assert rec["counts"]["h2d_bytes"] > 0 < rec["counts"]["d2h_bytes"]


def test_spans_leave_the_trajectory_bit_exact(tiny_problem, tmp_path,
                                              monkeypatch):
    """With the spans on a profiler timeline or stubbed out, the run is the
    same to the bit."""
    with jax.profiler.trace(str(tmp_path)):
        traced = _paged_run(tiny_problem)[3].r
    monkeypatch.setattr(spans, "span",
                        lambda name, root=False: contextlib.nullcontext())
    bare = _paged_run(tiny_problem)[3].r
    for a, b in zip(jax.tree.leaves((traced.params, traced.state)),
                    jax.tree.leaves((bare.params, bare.state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert traced.hist.train_loss == bare.hist.train_loss
    pd = jax.profiler.ProfileData.from_file(
        next(tmp_path.rglob("*.xplane.pb")).as_posix())
    names = {e.name for p in pd.planes for line in p.lines
             for e in line.events}
    assert set(LOOP_TREE) | {"run"} <= names
