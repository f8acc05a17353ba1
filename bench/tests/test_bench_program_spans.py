"""The per-layer readers of the program's own spans and bus counters
(`program_spans.py`): each turns the window's `run` record into its number,
and reads nothing from a record of another call, a missing span or counter,
or a program without the recorder. A traced run of a CPU-sized paged cell
reports every one of them."""
import dataclasses
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import pytest  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import harness  # noqa: E402
import workload  # noqa: E402
from repro import spans  # noqa: E402

ROUNDS = 4


def _span(parent, seconds):
    return {"parent": parent, "count": 2, "seconds": seconds,
            "self_s": seconds}


RECORD = {
    "seconds": 10.0, "self_s": 0.2,
    "counts": {"rounds": ROUNDS, "h2d_bytes": 8e6, "d2h_bytes": 2e6},
    "spans": {"batch_assembly": _span("run", 3.0),
              "stack": _span("batch_assembly", 0.8),
              "dispatch": _span("run", 1.2),
              "paging": _span("run", 4.0),
              "victims": _span("paging", 2.0),
              "page_upload": _span("paging", 1.6)}}

# reader -> its value on RECORD (ms or MB per round)
EXPECT = {"stack_host_ms": 200.0, "dispatch_host_ms": 300.0,
          "untraced_host_ms": 50.0, "h2d_mb": 2.0, "d2h_mb": 0.5,
          "page_victim_host_ms": 500.0, "page_upload_host_ms": 400.0,
          # no page was evicted: `spill` never opened inside `paging`
          "page_spill_host_ms": 0.0}
# what a record lacks where the reader reads nothing: its span (with the
# spans inside it) or its counter
PAGING = ("paging", "victims", "spill", "page_upload")
LACKS = {"stack_host_ms": ("spans", ("stack",)),
         "dispatch_host_ms": ("spans", ("dispatch",)),
         "h2d_mb": ("counts", ("h2d_bytes",)),
         "d2h_mb": ("counts", ("d2h_bytes",)),
         "page_victim_host_ms": ("spans", PAGING),
         "page_spill_host_ms": ("spans", PAGING),
         "page_upload_host_ms": ("spans", PAGING)}


def _read(name, monkeypatch, record, rounds=ROUNDS):
    monkeypatch.setattr(spans, "last",
                        lambda root="run": record if root == "run" else None)
    return workload.plugin("metrics", name).read(
        SimpleNamespace(rounds=rounds))


@pytest.mark.parametrize("name", list(EXPECT))
def test_reader_value(name, monkeypatch):
    assert _read(name, monkeypatch, RECORD) == pytest.approx(EXPECT[name])


@pytest.mark.parametrize("name", list(EXPECT))
def test_reader_refuses_another_call(name, monkeypatch):
    assert _read(name, monkeypatch, RECORD, rounds=ROUNDS + 8) is None
    assert _read(name, monkeypatch, None) is None


@pytest.mark.parametrize("name", list(LACKS))
def test_reader_without_its_span_or_counter(name, monkeypatch):
    part, keys = LACKS[name]
    rec = {**RECORD, part: {k: v for k, v in RECORD[part].items()
                            if k not in keys}}
    assert _read(name, monkeypatch, rec) is None


@pytest.mark.parametrize("name", list(EXPECT))
def test_reader_without_the_recorder(name, monkeypatch):
    """An older program has no `repro.spans`: the reader reads nothing."""
    import repro
    monkeypatch.setattr(spans, "last", lambda root="run": RECORD)
    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert workload.plugin("metrics", name).read(
        SimpleNamespace(rounds=ROUNDS)) is None


def test_traced_paged_run_reports_every_program_reader():
    data = os.path.join(BENCH, "tests", "data")
    cell = workload.load_cell("tiny_paged.fresh", bench_json=os.path.join(
        data, "BENCHMARK.json"), base=data)
    layer = [{"name": n, "unit": "x"} for n in EXPECT]
    cell = dataclasses.replace(cell, metrics_layer=layer)
    res = harness.run_cell(cell, 2 ** 31 + 9, 0.3, True,
                           t_start=time.perf_counter(),
                           peaks={"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9})["result"]
    assert res["correct"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == set(EXPECT)
    assert all(v >= 0 for v in got.values())
    assert got["h2d_mb"] > 0 and got["d2h_mb"] > 0
