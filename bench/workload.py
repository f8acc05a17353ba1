"""Cells by name: `BENCHMARK.json` entries, the files they name, and the
pieces of a cell that are code, found by the names those files carry.

A cell `<config>.<traffic>` is an entry of `BENCHMARK.json`'s `workloads`.
Its configuration is `bench/configs/<config>.json`, its traffic mix
`bench/traffic/<traffic>.json` (parameters only), the limits of its
correctness check `bench/limits/<cell>.json`. The code that turns those
files into a run lives in one module per name, loaded by `plugin`:

* `bench/models/<model>.py`: a model's parameter shapes, weights drawn from
  the seed, its loss, and the program's architecture fields;
* `bench/datasets/<kind>.py`: the traffic's data, and the program's batcher
  fed from it;
* `bench/availability/<kind>.py`: the traffic's availability law, the
  active ids the reference draws, and the program's sampler;
* `bench/schedules/<kind>.py`: the server learning rate;
* `bench/reference/<name>.py`: the plain reference the check compares with;
* `bench/metrics/<metric>.py`: a per-layer metric's reader.

So a new model, data or availability kind, schedule or algorithm's
reference is a new file, and no file that is there changes. Nothing here
imports the program; the generators are the benchmark's own copies, so the
yardstick stays fixed while the program changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
KINDS = ("models", "datasets", "availability", "schedules", "reference",
         "metrics")

_LOADED: dict = {}


def plugin(kind: str, name: str):
    """The module `bench/<kind>/<name>.py`, loaded once per process."""
    if kind not in KINDS:
        raise KeyError(f"unknown plugin kind {kind!r}; known: {KINDS}")
    key = (kind, name)
    if key not in _LOADED:
        path = os.path.join(BENCH, kind, name + ".py")
        if not os.path.isfile(path):
            raise KeyError(f"no {kind} module {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[key] = mod
    return _LOADED[key]


@dataclass
class Cell:
    """One workload: its BENCHMARK.json entry and the files it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    metrics_e2e: list
    metrics_layer: list


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, *, bench_json: str | None = None,
              base: str = BENCH) -> Cell:
    """Resolve cell `name` from `bench_json` (default: the checkout's
    BENCHMARK.json) and the files under `base` that carry its names."""
    spec = _load(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load(os.path.join(base, "configs", w["config"] + ".json")),
        traffic=_load(os.path.join(base, "traffic", w["traffic"] + ".json")),
        limits=_load(os.path.join(base, "limits", name + ".json")),
        metrics_e2e=[m for m in spec["end_to_end"] if applies(m)],
        metrics_layer=[m for m in spec["per_layer"] if applies(m)])


def program_seed(seed: int) -> int:
    """A 31-bit seed for the program from the run's `--seed`.

    `--seed` may exceed 32 bits; `jax.random.PRNGKey` keeps only the low
    32 of them, so distinct seeds would share keys. Mixing through
    `SeedSequence` keeps distinct seeds distinct.
    """
    state = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)
    return int(state[0] >> 1)


@dataclass
class Availability:
    """Who may be active: the law's module name, per-client rates, join
    rounds (None: everyone present from round 0)."""

    kind: str
    probs: np.ndarray
    join: np.ndarray | None


def make_data(spec: dict, cfg: dict, seed: int):
    """The traffic's data (`bench/datasets/<spec["kind"]>.py`)."""
    return plugin("datasets", spec["kind"]).Data(spec, cfg, seed)


def make_availability(spec: dict, data, n: int, seed: int) -> Availability:
    """The traffic's availability law at population `n`
    (`bench/availability/<spec["kind"]>.py`)."""
    return plugin("availability", spec["kind"]).make(spec, data, n, seed)


def schedule(cfg: dict):
    """The configuration's server learning rate as a function of the round
    clock c = t + 1 (`bench/schedules/<kind>.py`)."""
    s = cfg["schedule"]
    return plugin("schedules", s["kind"]).make(s, cfg)


def model(cfg: dict):
    """The configuration's model module (`bench/models/<model>.py`)."""
    return plugin("models", cfg["model"])


def reference(cfg: dict):
    """The configuration's plain reference (`bench/reference/<name>.py`)."""
    return plugin("reference", cfg["reference"])
