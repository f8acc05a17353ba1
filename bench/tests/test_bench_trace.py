"""The reduction from a profiler trace to busy time, idle gaps and kernel
sums: on a hand-made trace with known answers, and on a small trace
recorded on the chip."""
import os
import re
import sys
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import pytest  # noqa: E402

import trace_reduce as tr  # noqa: E402

MS = 1_000_000


def ev(plane, name, start_ms, dur_ms, line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_ms * MS, "dur_ns": dur_ms * MS}


@pytest.fixture
def events():
    host = "/host:CPU"
    return [
        ev(host, "window", 10, 100, "python"),
        ev(host, "batch_assembly", 10, 30, "python"),
        ev(host, "paging", 60, 20, "python"),
        # chip 0: two overlapping ops, a kernel, one op running past the end
        ev("/device:TPU:0", "fusion.1", 5, 15),        # clipped to 10..20
        ev("/device:TPU:0", "fusion.2", 15, 10),       # 15..25 -> union 10..25
        ev("/device:TPU:0", "%while.9 = (s32[]) while(...)", 38, 20),
        ev("/device:TPU:0", "%_paged_bank_scatter.3 = (f32[]) custom-call",
           40, 10),
        ev("/device:TPU:0", "all-reduce.7", 50, 5),
        ev("/device:TPU:0", "fusion.1", 100, 20),      # clipped to 100..110
        # chip 1: busy 40 ms
        ev("/device:TPU:1", "all-reduce.7", 20, 40),
    ]


def test_window_and_devices(events):
    assert tr.window_bounds(events) == (10 * MS, 110 * MS)
    assert tr.devices(events) == [0, 1]


def test_busy_union(events):
    # chip 0: 10..25, 38..58, 100..110 = 45 ms; chip 1: 40 ms
    assert tr.busy_seconds(events) == pytest.approx(0.0425)


def test_idle_gaps_named_by_host_span(events):
    gaps = tr.idle_gaps(events, device=0)
    assert gaps == [(25 * MS, 38 * MS), (58 * MS, 100 * MS)]
    named = tr.name_gaps(events, gaps)
    # the longest gap (58..100) is mostly `paging` (60..80), the other
    # (25..38) lies inside `batch_assembly` (10..40)
    assert named == [["paging", pytest.approx(0.042)],
                     ["batch_assembly", pytest.approx(0.013)]]


def test_kernel_and_all_reduce_sums(events):
    kernel = r"^%_paged_bank_scatter(_batched)?\."
    assert tr.op_seconds(events, kernel) == pytest.approx(0.010)
    assert tr.op_seconds(events, r"all-reduce", device=0) == \
        pytest.approx(0.005)
    assert tr.op_seconds(events, r"all-reduce") == pytest.approx(0.045)
    assert tr.op_seconds(events, r"no-such-op") == 0


def test_top_ops_leave_out_control_flow(events):
    top = tr.top_ops(events, top=2)
    # all-reduce: (5 + 40) ms over 2 chips; fusion.1: (10 + 10) ms over 2;
    # the 20 ms while loop contains the kernel and is not listed
    assert top[0] == ["all-reduce.7", pytest.approx(0.0225)]
    assert top[1] == ["fusion.1", pytest.approx(0.010)]


def test_one_window_span_required(events):
    with pytest.raises(ValueError):
        tr.window_bounds([e for e in events if e["name"] != "window"])


def test_short_name():
    assert tr.short_name(
        "%fusion.108 = f32[32000]{0:T(1024)S(1)} fusion(f32[320,100,10]"
        "{1,2,0:T(8,128)S(1)} %get-tuple-element.1235), kind=kCustom") == \
        "%fusion.108 fusion"
    assert tr.short_name(
        "%_paged_bank_scatter.55 = (f32[16400,1,32768]{2,1,0:T(1,128)}, "
        "f32[1,32768]{1,0:T(1,128)S(1)}) custom-call(s32[6251]{0} %c)") == \
        "%_paged_bank_scatter.55 custom-call"
    assert tr.short_name("all-reduce.7") == "all-reduce.7"


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5e: three paged-bank scatters of 320 rows
    into a two-leaf bank (six kernel calls) inside one `window` span."""
    import json
    path = os.path.join(BENCH, "tests", "data", "trace_v5e.json")
    with open(path) as f:
        events = json.load(f)
    lo, hi = tr.window_bounds(events)
    assert tr.devices(events) == [0]
    busy = tr.busy_seconds(events)
    idle = sum(b - a for a, b in tr.idle_gaps(events)) / 1e9
    assert busy == pytest.approx(0.012852953)
    assert busy + idle == pytest.approx((hi - lo) / 1e9)
    kernel = r"^%_paged_bank_scatter(_batched)?\."
    assert sum(1 for e in tr.device_ops(events)
               if re.search(kernel, e["name"])) == 6
    assert tr.op_seconds(events, kernel) == pytest.approx(0.011850151)
    assert tr.top_ops(events, top=1)[0][0] == \
        "%_paged_bank_scatter.3 custom-call"


def _collective_events():
    host, d0, d1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
    return [
        ev(host, "window", 0, 100, "python"),
        # as a v5e trace names it: the sharded bank's cohort gather
        ev(d0, "%all-reduce.26 = (f32[320,128]{1,0:T(8,128)}, "
               "f32[320,256,128]{2,1,0:T(8,128)}) all-reduce(f32[320,128]"
               "{1,0:T(8,128)S(1)} %get-tuple-element.1418, f32[320,256,128]"
               "{2,1,0:T(8,128)} %get-tuple-element.1419)", 10, 4),
        ev(d1, "%all-reduce.26 = (f32[320,128]{1,0}) all-reduce(f32[320,"
               "128]{1,0} %get-tuple-element.1418)", 10, 4),
        ev(d0, "%all-gather-start.3 = (f32[8]{0}, f32[32]{0}) "
               "all-gather-start(f32[8]{0} %p)", 20, 1),
        ev(d0, "%all-gather-done.3 = f32[32]{0} all-gather-done((f32[8]{0}, "
               "f32[32]{0}) %all-gather-start.3)", 21, 2),
        ev(d0, "%reduce-scatter.2 = f32[8]{0} reduce-scatter(f32[32]{0} %p)",
           30, 1),
        ev(d0, "%collective-permute.1 = f32[8]{0} collective-permute(f32[8]"
               "{0} %p)", 40, 1),
        ev(d0, "%all-to-all.5 = f32[8]{0} all-to-all(f32[8]{0} %p)", 50, 1),
        # what only reads a collective's result, or is async and no
        # collective, or runs outside the window, does not count
        ev(d0, "%fusion.9 = f32[320,128]{1,0} fusion(f32[320,128]{1,0} "
               "%all-reduce.26), kind=kLoop", 60, 5),
        ev(d0, "%copy-start.2 = (f32[8]{0}, f32[8]{0}) copy-start(f32[8]{0} "
               "%p)", 70, 3),
        ev(d1, "%all-reduce.27 = f32[8]{0} all-reduce(f32[8]{0} %p)", 120, 9),
    ]


def test_collective_ms_counts_collectives_by_opcode():
    import workload
    reader = workload.plugin("metrics", "collective_ms")
    ctx = SimpleNamespace(events=_collective_events(), chips=2, rounds=2)
    # chip 0: 4 + 1 + 2 + 1 + 1 + 1 ms, chip 1: 4 ms; over 2 chips, 2 rounds
    assert reader.read(ctx) == pytest.approx(3.5)


def test_collective_ms_reads_nothing_without_collectives(events):
    import workload
    reader = workload.plugin("metrics", "collective_ms")
    no_coll = [e for e in events if "all-reduce" not in e["name"]]
    assert reader.read(SimpleNamespace(events=no_coll, chips=2,
                                       rounds=2)) is None
    assert reader.read(SimpleNamespace(events=None, chips=2,
                                       rounds=2)) is None
