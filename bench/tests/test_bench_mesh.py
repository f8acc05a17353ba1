"""A configuration that names a mesh (`"mesh": {"data": 2}`): `harness.build`
lays the program out on it as `run_fl` does, and a run on it is `correct`.

XLA fixes its device count when it starts, so the two-device world is a
subprocess with forced host devices (this file run as a script); it prints
what it saw as one JSON line, and the tests read that. A mesh that does not
span the cell's chips, or one under the legacy threefry lowering, is
refused in this process, before any device is touched.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import jax  # noqa: E402
import pytest  # noqa: E402

import calibrate  # noqa: E402
import check  # noqa: E402
import harness  # noqa: E402
import workload  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
CELL = "tiny_sharded.fresh"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WORLD = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2",
         "JAX_PLATFORMS": "cpu"}


def tiny(name=CELL):
    return workload.load_cell(name, bench_json=os.path.join(
        DATA, "BENCHMARK.json"), base=DATA)


def _shards(tree) -> list:
    """Per leaf: the mesh axis of its first dimension and the shapes of its
    addressable shards."""
    return [[str(leaf.sharding.spec[0]) if leaf.sharding.spec else None,
             sorted({tuple(s.data.shape) for s in leaf.addressable_shards}),
             len(leaf.addressable_shards), list(leaf.shape)]
            for leaf in jax.tree.leaves(tree)]


def _world() -> dict:
    """What the two-device world sees: the bank's mesh when `init` lays out
    its rows, the driver's mesh, the rows' shards before and after the
    compared steps, a sound run, and the control and the fault."""
    from repro.bank.dense import DenseBank
    init, at_init = DenseBank.init, []

    def spy(self, params, n_clients):
        at_init.append([self.mesh is not None, self.cfg is not None])
        return init(self, params, n_clients)

    DenseBank.init = spy
    cell = tiny()
    cfg = cell.config
    prog = harness.build(cell, workload.program_seed(7), harness.Spans())
    DenseBank.init = init
    bank, drv = prog["bank"], prog["driver"]
    out = {"devices": len(jax.devices()), "at_init": at_init,
           "n_rows": bank.n_rows, "driver_mesh": drv.mesh.size,
           "same_mesh": drv.mesh is bank.mesh,
           "built": _shards(prog["runner"].state["bank"]["rows"])}
    with jax.default_matmul_precision(cfg["precision"]):
        harness.drive_compared_steps(prog, cfg)
    out["stepped"] = _shards(prog["runner"].state["bank"]["rows"])
    del prog, bank, drv
    res = harness.run_cell(cell, 2 ** 31 + 3, 0.3, False,
                           t_start=time.perf_counter(), peaks=PEAKS)
    out["sound"] = res["result"]
    kinds = dict(calibrate.readings(cell, 5, control=True))
    out["judged"] = {k: check.judge(v, cell.limits)[0]
                     for k, v in kinds.items()}
    return out


@pytest.fixture(scope="module")
def world():
    p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       env={**os.environ, **WORLD}, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_world_has_two_devices(world):
    assert world["devices"] == 2


def test_bank_takes_the_mesh_before_it_lays_out_rows(world):
    assert world["at_init"] == [[True, True]]
    # 128 clients and the dummy row, padded to divide data=2
    assert world["n_rows"] == 130


def test_driver_runs_on_the_banks_mesh(world):
    assert world["driver_mesh"] == 2 and world["same_mesh"]


@pytest.mark.parametrize("when", ["built", "stepped"])
def test_rows_are_sharded_over_data(world, when):
    for axis, shapes, count, shape in world[when]:
        assert axis == "data" and count == 2
        assert shapes == [[world["n_rows"] // 2] + shape[1:]]


def test_sound_sharded_run_is_correct(world):
    res = world["sound"]
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 2
    assert set(res["metrics"]) == {"rounds_per_s", "peak_hbm_gib",
                                   "setup_s"}


def test_control_and_half_batch_are_refused_on_the_mesh(world):
    assert world["judged"] == {"program": True, "bf16x3": False,
                               "half_batch": False}


def test_a_mesh_must_span_the_cells_chips():
    cell = tiny()
    one = dataclasses.replace(cell, config={**cell.config,
                                            "mesh": {"data": 1}})
    with pytest.raises(ValueError, match="spans 1 devices"):
        harness.make_mesh(one)


def test_a_mesh_needs_the_partitionable_threefry():
    was = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", False)
    try:
        with pytest.raises(ValueError, match="threefry"):
            harness.make_mesh(tiny())
    finally:
        jax.config.update("jax_threefry_partitionable", was)


@pytest.mark.parametrize("name", ["tiny_dense.skew", "tiny_paged.fresh"])
def test_a_config_without_a_mesh_builds_none(name):
    assert tiny(name).config["mesh"] is None
    assert harness.make_mesh(tiny(name)) is None


if __name__ == "__main__":
    print(json.dumps(_world(), default=float))
