"""The readings the limits of `correct` are set from, at a cell's own size.

    python bench/calibrate.py --workload <cell> --seeds 1,2,...
                              [--control-seeds 1,2,3]

For every seed it builds the program exactly as a benchmark run does, drives
its compared steps (`harness.drive_compared_steps`), frees it and runs the
plain reference: the numbers of `check.py` for the program are the lower
readings. For each control seed it also puts the reference in the
program's place computed in three bfloat16 passes (the control) and with
half of each minibatch left out (a fault), against the reference at full
precision: their numbers are the upper readings. A step that returns its
state unchanged reads 1 on ``dparam_gap`` by definition and needs no run.
One JSON line per seed and kind goes to standard output. The benchmark's
own runs never run this; it holds the chip like a benchmark run, in one
process.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import jax  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import workload  # noqa: E402


def as_snapshot(run: dict, params0) -> dict:
    """A reference run in the shape of the program's snapshot, so that it
    can stand in the program's place in `check.compare`."""
    return {"ids": dict(enumerate(run["ids"])),
            "n_active": [float(len(i)) for i in run["ids"]],
            "losses": run["losses"], "mean_g": run["mean_g"],
            "mean_g_last": run["mean_g_last"],
            "params": run["params"], "params0": params0,
            "row_ids": run["row_ids"], "rows": run["rows"],
            "step1": run["step1"]}


def readings(cell: workload.Cell, seed: int, *, control: bool) -> list:
    """[(kind, numbers)] for one seed: the program, and with `control`
    the bf16x3 control and the half-batch fault."""
    cfg = cell.config
    s = workload.program_seed(seed)
    prog = harness.build(cell, s, harness.Spans())
    with jax.default_matmul_precision(cfg["precision"]):
        snap = harness.drive_compared_steps(prog, cfg)
    data, av = prog["data"], prog["av"]
    del prog
    gc.collect()
    rounds = cfg["compare_steps"] * cfg["scan_chunk"]
    reference = workload.reference(cfg)
    run = lambda mode: reference.run(cfg, data, av, s, snap["params0"],
                                     rounds, mode=mode,
                                     snap_at=cfg["scan_chunk"] - 1)
    ref = run("highest")
    out = [("program", check.compare(snap, ref))]
    if control:
        for mode in ("bf16x3", "half_batch"):
            out.append((mode, check.compare(
                as_snapshot(run(mode), snap["params0"]), ref)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = workload.load_cell(args.workload)
    harness.use_compile_cache(os.path.dirname(BENCH))
    controls = {int(x) for x in args.control_seeds.split(",") if x}
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        for kind, numbers in readings(cell, seed,
                                      control=seed in controls):
            print(json.dumps({"cell": cell.name, "seed": seed, "kind": kind,
                              "numbers": numbers,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
