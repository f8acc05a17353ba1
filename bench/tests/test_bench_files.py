"""Every cell, configuration, traffic mix, limit file and metric reader that
`BENCHMARK.json` names loads by its name, and the file keeps the shape the
harness reads."""
import importlib.util
import json
import os
import re
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import pytest  # noqa: E402

import check  # noqa: E402
import workload  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTHS = ("d_model", "d_ff", "n_classes", "n_params", "k_steps",
          "batch_size")
# each configuration at its source's widths: CIFAR-10's 3,072 features for
# the paper's set-up, the repo's 256 where the source publishes none
PUBLISHED = {"mlp_dense_n100": {"d_model": 3072, "d_ff": 128,
                                "n_classes": 10, "n_params": 411_146,
                                "k_steps": 5, "batch_size": 100},
             "mlp_paged_n100k": {"d_model": 256, "d_ff": 128,
                                 "n_classes": 10, "n_params": 50_698,
                                 "k_steps": 5, "batch_size": 100},
             "mlp_sharded_n100k": {"d_model": 256, "d_ff": 128,
                                   "n_classes": 10, "n_params": 50_698,
                                   "k_steps": 5, "batch_size": 100}}
CONFIG_FILES = sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "configs"))
    if f.endswith(".json"))


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert spec["command"] == ["python3", "bench/run.py"]
    assert 1 <= spec["run_seconds"] <= 51


def test_names_units_and_entries(spec):
    metric_keys = {"name", "unit", "better", "source", "bound", "layer",
                   "moves", "workloads"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[group]]
        assert len(names) == len(set(names)), group
        for e in spec[group]:
            assert NAME.match(e["name"]), e["name"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m) <= metric_keys, m
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")


def _configs(spec):
    out = []
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            out.append(json.load(f))
    return out


def _traffics(spec):
    out = []
    for w in spec["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            out.append(json.load(f))
    return out


@pytest.mark.parametrize("kind", ["configs", "traffic", "limits",
                                  "metrics", "models", "reference",
                                  "schedules", "datasets", "availability"])
def test_every_named_file_exists(spec, kind):
    if kind in ("configs", "traffic", "limits", "metrics"):
        names = {"configs": [c["name"] for c in spec["configs"]],
                 "traffic": [w["traffic"] for w in spec["workloads"]],
                 "limits": [w["name"] for w in spec["workloads"]],
                 "metrics": [m["name"] for m in spec["per_layer"]]}[kind]
    elif kind in ("models", "reference"):
        key = {"models": "model", "reference": "reference"}[kind]
        names = [c[key] for c in _configs(spec)]
    elif kind == "schedules":
        names = [c["schedule"]["kind"] for c in _configs(spec)]
    else:
        key = {"datasets": "data", "availability": "availability"}[kind]
        names = [t[key]["kind"] for t in _traffics(spec)]
    ext = ".json" if kind in ("configs", "traffic", "limits") else ".py"
    for name in names:
        assert os.path.isfile(os.path.join(BENCH, kind, name + ext)), name
        if ext == ".py":
            assert workload.plugin(kind, name) is not None


def test_configs_keep_widths_and_declare_cuts(spec):
    for c in spec["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        for key in WIDTHS:
            assert cfg[key] == PUBLISHED[c["name"]][key], (c["name"], key)
        assert not set(c["reduced"]) & set(WIDTHS)


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_every_config_file_keeps_its_published_widths(name):
    """Also a configuration that no cell runs yet: it waits at its
    source's widths, its cuts declared, its mesh null or axis sizes."""
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    assert {key: cfg[key] for key in WIDTHS} == PUBLISHED[name]
    assert not set(cfg["reduced"]) & set(WIDTHS)
    assert set(cfg["reduced"]) <= set(cfg["assumed"])
    mesh = cfg["mesh"]
    assert mesh is None or (set(mesh) <= {"data", "model"} and all(
        isinstance(v, int) and v >= 1 for v in mesh.values()))


def test_every_cell_loads_by_name(spec):
    for w in spec["workloads"]:
        cell = workload.load_cell(w["name"])
        assert cell.chips == w["chips"] in (1, 4)
        assert set(cell.limits) <= set(check.NUMBERS)
        assert cell.limits["ids_mismatch"] == 0 == cell.limits["rows_wrong"]
        assert "rows_median_gap" in cell.limits
        assert {m["name"] for m in cell.metrics_e2e} >= {"setup_s"}
        assert len(cell.metrics_e2e) >= 2 and cell.metrics_layer
        assert len(w["why"]) <= 200


def test_every_metric_reader_loads(spec):
    for m in spec["per_layer"]:
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        s = importlib.util.spec_from_file_location("reader", path)
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        assert callable(mod.read)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        workload.load_cell("no_such.cell")


def test_program_seed_keeps_large_seeds_apart():
    a, b = workload.program_seed(5), workload.program_seed(5 + 2 ** 32)
    assert a != b and 0 <= a < 2 ** 31 and 0 <= b < 2 ** 31
    assert workload.program_seed(2 ** 33 + 7) == workload.program_seed(
        2 ** 33 + 7)
