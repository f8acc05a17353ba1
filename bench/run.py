"""The benchmark of this repository: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and holds the cell's chips in this
one process. It exits with code 2, before any set-up and printing no
result, when JAX finds no TPU or fewer chips than the cell asks for. The
last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics, or with `--trace 1` its
per-layer ones), `device`, with `--trace 1` a `breakdown`, and last
`checks`, each number that decided `correct` beside its limit. The same
numbers end standard error. Cells, configurations, traffic mixes, limits
and metric readers are files under `bench/` found by the names in
`BENCHMARK.json`; see `harness.py` for what one run does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import workload  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = workload.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind}): nothing measured",
              file=sys.stderr)
        return 2
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"bench: no published peaks for {kind!r} in bench/peaks.json",
              file=sys.stderr)
        return 2
    import harness
    harness.use_compile_cache(ROOT)
    res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START, peaks=peaks[kind])
    harness.main_stderr(res["log"], res["result"]["checks"])
    print(json.dumps(res["result"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
