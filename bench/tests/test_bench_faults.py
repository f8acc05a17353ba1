"""A run whose timed path is broken underneath comes out not `correct`:
a step that returns its state unchanged, and local training that leaves
half of each minibatch out. (The cells run on one chip, so no exchange
between chips can be left out; no answer is served, so none is altered.)
"""
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

import harness  # noqa: E402
import workload  # noqa: E402
from repro.core import local_update  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run(name):
    cell = workload.load_cell(name, bench_json=os.path.join(
        DATA, "BENCHMARK.json"), base=DATA)
    return harness.run_cell(cell, 11, 0.3, False,
                            t_start=time.perf_counter(),
                            peaks=PEAKS)["result"]


def _state_unchanged(monkeypatch):
    build = harness.build

    def broken(*args, **kw):
        prog = build(*args, **kw)
        chunk = prog["driver"]._chunk_fn

        def same_state(carry, xs):
            kept = jax.tree.map(jnp.copy, carry)
            _, ys = chunk(carry, xs)
            return kept, ys

        prog["driver"]._chunk_fn = same_state
        return prog

    monkeypatch.setattr(harness, "build", broken)


def _half_batch(monkeypatch):
    update = local_update.device_update

    def half(loss_fn, params, client_batch, eta, weight_decay=0.0):
        client_batch = jax.tree.map(lambda x: x[:, :x.shape[1] // 2],
                                    client_batch)
        return update(loss_fn, params, client_batch, eta, weight_decay)

    monkeypatch.setattr(local_update, "device_update", half)


@pytest.mark.parametrize("name", ["tiny_dense.skew", "tiny_paged.fresh"])
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    res = run(name)
    assert not res["correct"], res["checks"]
