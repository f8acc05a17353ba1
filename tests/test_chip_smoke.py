"""CPU rehearsal of `chip_smoke.py`.

The script's phases run here at tiny sizes (still `paper_mlp`'s published
width) with the banks' kernels forced on, so the Pallas kernels run in
interpret mode under the script's own progress and agreement checks. The
script itself must refuse a process without a TPU before any phase runs.
The four-chip mesh phase is rehearsed in the forced-host-device world of
tests/test_sharded_scan.py.
"""
import os
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402

PAPER_TINY = dict(n_clients=8, n_rounds=8, scan_chunk=4, n_per_class=20,
                  batch_size=8, k_steps=2)
CROSS_TINY = dict(n_clients=512, cohort=8, capacity=16, page_size=16,
                  n_slots=20, n_rounds=4, scan_chunk=2, batch_size=4,
                  k_steps=1)
BANK_TINY = dict(n_clients=512, cohort=4, capacity=8, page_size=16,
                 n_slots=8, n_rounds=6, scan_chunk=1)
# the cross_device dynamics at a CPU-sized N (a 200 MB bank, fully resident)
CALIBRATE = dict(n_clients=1000, cohort=240, capacity=512, n_slots=None)


def test_paper_phase_rehearsal():
    runs = chip_smoke.paper_phase(use_pallas=True, **PAPER_TINY)
    assert runs["banked_dense"]["path"] == "kernel (interpret)"
    assert runs["mifa_array"]["path"] == "jnp"
    agree = runs["agreement"]
    assert agree["max_rel_param_diff"] <= chip_smoke.RTOL
    assert runs["banked_dense"]["hist"].rounds == list(range(8))


def test_cross_device_phase_rehearsal():
    runs = chip_smoke.cross_device_phase(use_pallas=True, **CROSS_TINY)
    assert runs["kernel"]["path"] == "kernel (interpret)"
    assert runs["jnp"]["path"] == "jnp"
    assert runs["kernel"]["faults"] > 0
    h = runs["kernel"]["hist"]
    # the elastic wrapper keeps round 0 to the first cohort, not all N
    assert h.n_active[0] == CROSS_TINY["cohort"]
    assert max(h.n_active) <= CROSS_TINY["capacity"]
    assert runs["agreement"]["max_rel_param_diff"] <= chip_smoke.RTOL


def test_bank_step_phase_rehearsal():
    """Kernel and jnp banks fed the same rows through paging with
    evictions: pages bit-equal every round, G_sum within the fp32 bound
    (and, on the CPU, the interpret-mode kernel's sum equals XLA's)."""
    out = chip_smoke.bank_step_phase(use_pallas=True, **BANK_TINY)
    assert out["path"] == "kernel (interpret)"
    assert out["evictions"] > 0
    assert out["worst_err_over_bound"] <= 1.0
    assert out["g_sum_bitexact"]


@pytest.mark.slow
def test_cross_device_band_covers_a_kernel_free_order_change(monkeypatch):
    """Calibrates XDEV_RTOL: the cross_device dynamics, with no kernel and
    no eviction, grow a mere regrouping of the jnp body's delta sum past
    the tight band RTOL — and stay within XDEV_RTOL."""
    import jax
    import jax.numpy as jnp

    import repro.bank.paged_device as paged
    from repro.bank.base import broadcast_valid
    from repro.bank.dense import _scatter_jnp

    def regrouped(rows, g_sum, ids, valid, updates):
        # the same body summing even and odd cohort slots apart
        def one(r, u, gs):
            old = r[ids]
            vb = broadcast_valid(valid, u)
            delta = jnp.where(vb, u - old, 0.0)
            dsum = jnp.sum(delta[0::2], 0) + jnp.sum(delta[1::2], 0)
            return r.at[ids].set(jnp.where(vb, u, old)), gs + dsum
        out = jax.tree.map(one, rows, updates, g_sum)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1)

    params = {}
    for name, body in (("xla", _scatter_jnp), ("regrouped", regrouped)):
        monkeypatch.setattr(paged, "_scatter_jnp", body)
        params[name] = jax.tree.leaves(chip_smoke.cross_device_run(
            use_pallas=False, **CALIBRATE)["params"])
    d = max(float(abs(a - b).max())
            for a, b in zip(params["xla"], params["regrouped"]))
    scale = max(float(abs(a).max()) for a in params["xla"])
    assert chip_smoke.RTOL * scale < d <= chip_smoke.XDEV_RTOL * scale, \
        d / scale


def test_main_refuses_a_process_without_tpu(monkeypatch, capsys):
    def no_phase(**_):
        raise AssertionError("a phase ran without a TPU")

    for phase in ("paper_phase", "bank_step_phase", "cross_device_phase",
                  "mesh_phase"):
        monkeypatch.setattr(chip_smoke, phase, no_phase)
    for argv in ([], ["--chips", "4"]):
        assert chip_smoke.main(argv) != 0
        out, err = capsys.readouterr()
        assert "no TPU" in err
        assert '"ok"' not in out


def test_compile_cache_rule(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the fixed `<repo>/.jax_cache`."""
    import jax
    from common import REPO, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert use_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compare_rejects_disagreeing_runs():
    import numpy as np

    from repro.core.runner import FLHistory

    def run(w, losses):
        h = FLHistory()
        for t, v in enumerate(losses):
            h.record_round(t, {"loss": v, "n_active": 2.0})
        return {"params": {"w": np.asarray(w, np.float32)}, "hist": h}

    ref = run([1.0, 2.0], [0.5, 0.4])
    assert chip_smoke.compare(ref, run([1.0, 2.0], [0.5, 0.4]))["bitexact"]
    near = chip_smoke.compare(ref, run([1.0, 2.0 + 2e-6], [0.5, 0.4]))
    assert not near["bitexact"] and near["max_rel_param_diff"] == \
        pytest.approx(1e-6, rel=0.2)
    with pytest.raises(AssertionError):
        chip_smoke.compare(ref, run([1.0, 2.01], [0.5, 0.4]))
