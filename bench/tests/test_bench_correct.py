"""`correct` at a CPU-sized copy of each cell: sound runs pass, and the
control (the reference in the program's place, in three bfloat16 passes)
and a planted fault (half of each minibatch left out) fail.

The limits in `data/limits/` separate this size's readings on the CPU
(sound up to 2e-7 on every number; the control from 3e-7 on `grad_gap`
and from 2.8e-6 on `rows_median_gap`); the cells' own limits come from the
chip.
"""
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import pytest  # noqa: E402

import calibrate  # noqa: E402
import check  # noqa: E402
import harness  # noqa: E402
import workload  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
CELLS = ["tiny_dense.skew", "tiny_paged.fresh", "tiny_paged.returning"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny(name):
    return workload.load_cell(name, bench_json=os.path.join(
        DATA, "BENCHMARK.json"), base=DATA)


def run(name, seed=2 ** 31 + 3):
    return harness.run_cell(tiny(name), seed, 0.3, False,
                            t_start=time.perf_counter(), peaks=PEAKS)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run(name)["result"]
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"rounds_per_s", "peak_hbm_gib",
                                   "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_and_half_batch_are_refused(name):
    cell = tiny(name)
    out = dict(calibrate.readings(cell, 7, control=True))
    assert check.judge(out["program"], cell.limits)[0]
    assert not check.judge(out["bf16x3"], cell.limits)[0], out["bf16x3"]
    assert not check.judge(out["half_batch"], cell.limits)[0]


def test_traced_run_reports_layers_and_breakdown():
    res = harness.run_cell(tiny("tiny_paged.fresh"), 5, 0.3, True,
                           t_start=time.perf_counter(),
                           peaks=PEAKS)["result"]
    assert res["correct"]
    assert {"mfu", "batch_host_ms", "page_faults"} <= set(res["metrics"])
    assert "rounds_per_s" not in res["metrics"]
    assert res["device"]["window_s"] > 0 and "busy_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name,rows", [("tiny_dense.skew", True),
                                       ("tiny_paged.fresh", False)])
def test_batcher_proxy_times_the_row_sampler_only_where_it_exists(name,
                                                                  rows):
    spans = harness.Spans()
    prog = harness.build(tiny(name), workload.program_seed(3), spans)
    proxy = prog["runner"].batcher
    assert isinstance(proxy, harness.BatcherProxy)
    # the driver keeps a row table exactly for a batcher with a row sampler
    assert hasattr(proxy, "sample_round_rows") is rows
    assert (prog["driver"]._rows is not None) is rows
    if rows:
        spans.on = True
        assert proxy.sample_round_rows(0, client_ids=[0, 1]).shape[0] == 2
        assert spans.seconds["batch_assembly"] > 0


def test_traced_dense_run_reads_batch_host_ms():
    res = harness.run_cell(tiny("tiny_dense.skew"), 5, 0.3, True,
                           t_start=time.perf_counter(),
                           peaks=PEAKS)["result"]
    assert res["correct"], res["checks"]
    assert res["metrics"]["batch_host_ms"]["value"] > 0
