"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (stdout). Artifacts land in
benchmarks/artifacts/*.json. Pass --fast for a reduced sweep (CI-scale).

  adversarial      : non-stationary/adversarial availability (paper §1/§5)
  fig2_convergence : paper Fig. 2 (4 algorithms x p_min, convex + non-convex)
  case_study       : §5.1 rounds-to-ε vs p_min (Eq. 2 vs Eq. 3)
  tau_stats        : Thm 5.2/5.3 τ statistics validation
  agg_throughput   : MIFA fused-aggregation traffic + kernel check
  roofline_bench   : §Roofline table from the dry-run artifacts
  time_to_accuracy : simulated wall-clock to target loss, MIFA vs.
                     straggler-bound round policies (repro.sim)
  bank_scale       : memory-bank cohort rounds flat in N up to 10⁶ clients
                     (repro.bank), vs the O(N·d) dense round
  fleet_scale      : vmapped K-trial sweep (repro.fleet) vs the sequential
                     run_fl loop — same trials, one program
  scenario_grid    : algorithm × availability-scenario convergence grid
                     (repro.scenarios): MIFA-vs-FedAvg gap under
                     correlated / non-stationary availability
  scenario_atlas   : competing-baseline atlas — every registered
                     algorithm (incl. FedAR, CA-Fed) × scenario × seed
                     as jit(scan(vmap)) fleet programs, with per-scenario
                     winner table
  scan_scale       : whole-run scan engine (core.scan_engine) vs the
                     per-round dispatch loop — rounds/sec across T
  trace_replay     : recorded-trace availability (repro.scenarios
                     .trace_replay) + elastic fleets over the committed
                     fixture, and the checkpoint/kill/resume exactness
                     gate (repro.checkpoint)
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sweep for CI")
    ap.add_argument("--only", default=None,
                    help="run a single benchmark by name (see module list)")
    args = ap.parse_args()

    names = ("tau_stats", "agg_throughput", "adversarial", "case_study",
             "fig2_convergence", "roofline_bench", "time_to_accuracy",
             "bank_scale", "fleet_scale", "scenario_grid", "scenario_atlas",
             "scan_scale", "trace_replay")
    # validate BEFORE any benchmark module imports: a typo'd --only must
    # not silently run *nothing* (hollow CI smoke steps), and it must not
    # die on some unrelated module's import error either
    if args.only is not None and args.only not in names:
        print(f"unknown benchmark {args.only!r}; valid names: "
              f"{', '.join(names)}", file=sys.stderr)
        raise SystemExit(2)
    selected = names if args.only is None else (args.only,)

    import importlib

    from common import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name in selected:
        try:
            importlib.import_module(name).main(fast=args.fast)
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
