"""Smoke run of MIFA's training path on a TPU.

    python chip_smoke.py             # one chip: `paper`, `cross_device`,
                                     # `bank_step`
    python chip_smoke.py --chips 4   # four chips: the mesh-sharded scan only

One process drives the system's own entry points — `run_fl` with
``engine="scan_strict"`` (no phase can fall back to the per-round loop),
`make_bank`, `BankedMIFA` and the scenario registry — at `paper_mlp`'s
published width (d=256, two hidden layers of 128, 10 classes, 50,698
parameters) with weights drawn from a seed. Every phase runs two paths that
must agree and checks that training made progress:

  * ``paper``: the paper's §7 setup (N=100, batch 100, K=5, label-correlated
    availability), 64 rounds. `MIFA(memory="array")` is the reference;
    `BankedMIFA` over the dense bank runs the compiled `bank_scatter` kernel.
  * ``cross_device``: N=10⁶ clients through `ProceduralBatcher`, a cohort of
    about 240 per round from the registered ``elastic`` scenario over
    ``bernoulli``, the `paged_device` bank; training with its compiled
    paged kernels against training with the same bank's jnp body.
  * ``bank_step``: the ``cross_device`` bank (N=10⁶, 1024 slots of 16
    rows) with the same seeded rows fed to its compiled paged kernels and
    to its jnp body under the same paging; pages bit-equal after every
    round, both G_sums within the fp32 summation bound of an f64 sum.
  * ``mesh`` (``--chips 4`` only): the ``paper`` phase on
    ``make_host_mesh(data=4)`` against the same runs on one device, with
    each device's bytes of the returned parameters and state printed.

Lines before the last are a smoke run's log (compile seconds, rounds/s with
set-up included, which path ran): not benchmark numbers. The last line is
one JSON object naming the device. The script exits non-zero, before any
phase runs, when JAX finds no TPU; it starts no other process.

The compile cache follows `benchmarks/common.use_compile_cache`.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(REPO, "src"), os.path.join(REPO, "benchmarks")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from common import paper_problem, use_compile_cache  # noqa: E402
from repro.bank import BankedMIFA, make_bank  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import MIFA, run_fl  # noqa: E402
from repro.data import ProceduralBatcher  # noqa: E402
from repro.kernels.backend import interpret_default  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import inv_t  # noqa: E402
from repro.scenarios import make_scenario  # noqa: E402

# The compared paths sum the same fp32 terms in different orders (a
# sequential kernel k-loop against an XLA reduction, or per-device partial
# sums): the reduction-order band of tests/test_sharded_scan.py.
RTOL, ATOL = 2e-5, 1e-6
# cross_device training: trajectories that differ only in the order of one
# fp32 sum part under these 16 rounds (server step 0.1·N/seen). A
# kernel-free regrouping of the jnp body's delta sum lands 3e-5 to 6.1e-5 of
# the model's largest parameter away on the CPU (tests/test_chip_smoke.py,
# slow); kernel against jnp lands 6.05e-5 on a TPU v5e. The band keeps 3x
# room. The bank itself is held to MIFA's invariant (`_g_sum_vs_rows`) and,
# in `bank_step`, to bit-equal pages.
XDEV_RTOL = 2e-4
# At default precision a TPU matmul rounds its fp32 inputs to bf16, and the
# dense and cohort programs then differ at bf16 level (up to 4.1e-5 on
# parameters of ~0.3 after the paper phase's 64 rounds, one TPU v5e); the
# phases compare fp32 arithmetic, so their matmuls run at full precision.
PRECISION = "highest"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class _CompileClock:
    """Sums XLA backend compile seconds reported through jax.monitoring."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += duration


_CLOCK = _CompileClock()
jax.monitoring.register_event_duration_secs_listener(_CLOCK)


def _timed_run(*, return_state: bool = False, **kw) -> dict:
    c0, t0 = _CLOCK.seconds, time.perf_counter()
    with jax.default_matmul_precision(PRECISION):
        params, hist, *state = run_fl(engine="scan_strict",
                                      return_state=return_state, **kw)
        jax.block_until_ready(params)
    wall = time.perf_counter() - t0
    # free the finished run's device state before the next run allocates
    gc.collect()
    compile_s = _CLOCK.seconds - c0
    run = {"params": params, "hist": hist, "wall_s": wall,
           "compile_s": compile_s,
           "rounds_per_s": kw["n_rounds"] / max(wall - compile_s, 1e-9)}
    if return_state:
        run["state"] = state[0]
    return run


def _path(bank) -> str:
    if not bank._pallas():
        return "jnp"
    return "kernel (interpret)" if interpret_default() else "kernel"


def _check_progress(name: str, hist) -> None:
    losses = hist.train_loss
    assert all(math.isfinite(v) for v in losses), (name, losses)
    assert losses[-1] < losses[0], (name, losses[0], losses[-1])


def compare(ref: dict, got: dict, *, rtol: float = RTOL,
            atol: float = ATOL) -> dict:
    """Assert two runs agree; returns the agreement numbers.

    Every parameter within rtol of the model's largest parameter magnitude
    (plus atol), each round's train loss within rtol/atol, and the
    mask-derived integers (rounds, active counts, τ statistics) exactly.
    """
    pa, pb = jax.tree.leaves(ref["params"]), jax.tree.leaves(got["params"])
    ha, hb = ref["hist"], got["hist"]
    la, lb = np.asarray(ha.train_loss), np.asarray(hb.train_loss)
    diffs = [float(np.max(np.abs(np.asarray(b) - np.asarray(a))))
             for a, b in zip(pa, pb)]
    scales = [float(np.max(np.abs(np.asarray(a)))) for a in pa]
    diff, scale = max(diffs), max(scales)
    out = {"bitexact": diff == 0.0 and np.array_equal(la, lb),
           "max_rel_param_diff": diff / max(scale, 1e-30),
           # reported, not asserted: each leaf against its own largest entry
           "max_leaf_rel_diff": max(d / max(m, 1e-30)
                                    for d, m in zip(diffs, scales)),
           "max_abs_loss_diff": float(np.max(np.abs(lb - la)))}
    print(f"[smoke]   agreement {out}")
    assert diff <= rtol * scale + atol, (diff, scale, out)
    np.testing.assert_allclose(lb, la, rtol=rtol, atol=atol)
    assert ha.rounds == hb.rounds
    assert ha.n_active == hb.n_active
    assert (ha.tau_bar, ha.tau_max) == (hb.tau_bar, hb.tau_max)
    return out


def _report(phase: str, name: str, run: dict, path: str) -> None:
    h = run["hist"]
    print(f"[smoke] {phase}/{name}: path={path} "
          f"compile_s={run['compile_s']:.3f} wall_s={run['wall_s']:.3f} "
          f"rounds_per_s={run['rounds_per_s']:.3f} "
          f"loss {h.train_loss[0]:.6f} -> {h.train_loss[-1]:.6f} "
          "(smoke run, not a benchmark number)")


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #

def paper_phase(*, n_clients: int = 100, n_rounds: int = 64,
                scan_chunk: int = 16, n_per_class: int = 500,
                batch_size: int = 100, k_steps: int = 5,
                use_pallas: bool | None = None, mesh=None,
                return_state: bool = False, seed: int = 0,
                label: str = "paper") -> dict:
    """The §7 setup at `paper_mlp` width: MIFA(array) and BankedMIFA(dense)
    through `run_fl`. Returns {algorithm: run}; asserts progress and that
    the two agree. `return_state` keeps each run's final algorithm state
    (run["state"])."""
    model, batcher, _, participation, _ = paper_problem(
        "paper_mlp", n_clients=n_clients, n_per_class=n_per_class,
        batch_size=batch_size, k_steps=k_steps, seed=seed)
    kw = dict(model=model, batcher=batcher, n_rounds=n_rounds,
              schedule=inv_t(1.0), weight_decay=1e-3, seed=seed,
              scan_chunk=scan_chunk, mesh=mesh, return_state=return_state)
    runs = {}
    for name, algo in (
            ("mifa_array", MIFA(memory="array")),
            ("banked_dense",
             BankedMIFA(make_bank("dense", use_pallas=use_pallas)))):
        runs[name] = _timed_run(algo=algo,
                                participation=participation(seed + 100),
                                **kw)
        path = _path(algo.bank) if hasattr(algo, "bank") else "jnp"
        runs[name]["path"] = path
        _report(label, name, runs[name], path)
        _check_progress(f"{label}/{name}", runs[name]["hist"])
    print(f"[smoke] {label}: banked_dense vs mifa_array")
    agree = compare(runs["mifa_array"], runs["banked_dense"])
    runs["agreement"] = agree
    return runs


def cross_device_run(*, use_pallas: bool | None, n_clients: int = 1_000_000,
                     cohort: int = 240, capacity: int = 256,
                     page_size: int = 16, n_slots: int = 1024,
                     n_rounds: int = 16, scan_chunk: int = 4,
                     batch_size: int = 100, k_steps: int = 5,
                     seed: int = 0) -> dict:
    """One cross-device training run: N clients behind a `paged_device`
    bank, cohorts of about `cohort` from the registered `elastic` scenario
    over `bernoulli`. Returns the timed run with the bank's path, paging
    counters and pool bytes."""
    cfg = get_config("paper_mlp").replace(fl_clients=n_clients)
    model = build_model(cfg)
    batcher = ProceduralBatcher(n_clients=n_clients, dim=cfg.d_model,
                                n_classes=cfg.vocab_size,
                                batch_size=batch_size, k_steps=k_steps,
                                seed=seed)
    n_params = sum(math.prod(p.shape) for p in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    # MIFA averages the memory over all N rows, most still zero this early
    # on: scale the server step by N over the clients seen so far
    seen = lambda t: min(n_clients, cohort * max(t, 1))
    bank = make_bank("paged_device", page_size=page_size, n_slots=n_slots,
                     use_pallas=use_pallas)
    # every process forces round 0 all-active; the elastic wrapper lets
    # only a first cohort be present then, the rest from round 1
    scen = make_scenario(
        "elastic", n=n_clients, seed=seed, inner="bernoulli",
        inner_kwargs={"probs": cohort / n_clients}, n_initial=cohort,
        arrive_every=1, arrive_count=n_clients - cohort)
    run = _timed_run(model=model, batcher=batcher, n_rounds=n_rounds,
                     schedule=lambda t: 0.1 * n_clients / seen(t),
                     eta_local=0.1, weight_decay=1e-3, seed=seed,
                     scan_chunk=scan_chunk, cohort_capacity=capacity,
                     algo=BankedMIFA(bank), scenario=scen,
                     return_state=True)
    run["path"] = _path(bank)
    run["faults"], run["evictions"] = bank.faults, bank.evictions
    run["page_table_entries"] = bank.lp + 1
    run["pool_bytes"] = ((bank.n_slots + 1) * bank.page_size * n_params
                         * bank.dtype.itemsize)
    run["g_sum_vs_rows"] = _g_sum_vs_rows(bank, run.pop("state")["bank"])
    gc.collect()
    return run


def _g_sum_vs_rows(bank, state) -> float:
    """MIFA's invariant after a run: G_sum against the sum of every
    client's stored row — the device pages plus the pages spilled to the
    host — as a fraction of the largest |G_sum| entry. A row read or
    written in the wrong place moves it by a whole row."""
    gs = [np.asarray(g, np.float64) for g in jax.tree.leaves(state["g_sum"])]
    rows = [np.asarray(jnp.sum(p, axis=0), np.float64)
            for p in jax.tree.leaves(state["pages"])]
    for entry in bank.host_state().get("spill", []):
        for j, block in enumerate(entry["pages"]):
            rows[j] += block.sum(0, dtype=np.float64)
    return max(float(np.abs(g - r).max()) for g, r in zip(gs, rows)) / max(
        float(np.abs(g).max()) for g in gs)


def cross_device_phase(*, use_pallas: bool | None = None, **kw) -> dict:
    """Training through the paged kernels (`use_pallas`, auto = compiled on
    a TPU) against training through the same bank's jnp body
    (`cross_device_run` sizes); asserts progress and agreement, and prints
    paging counters and device memory."""
    runs = {}
    for name, pallas in (("kernel", use_pallas), ("jnp", False)):
        run = runs[name] = cross_device_run(use_pallas=pallas, **kw)
        _report("cross_device", name, run, run["path"])
        h = run["hist"]
        print(f"[smoke] cross_device/{name}: N={kw.get('n_clients', 10**6)} "
              f"cohort={min(h.n_active):.0f}..{max(h.n_active):.0f} "
              f"page_table_entries={run['page_table_entries']} "
              f"faults={run['faults']} evictions={run['evictions']} "
              f"pool_bytes={run['pool_bytes']} |G_sum - sum of rows| = "
              f"{run['g_sum_vs_rows']:.3g} of max |G_sum|")
        _check_progress(f"cross_device/{name}", h)
        assert run["g_sum_vs_rows"] <= RTOL, (name, run["g_sum_vs_rows"])
        stats = jax.devices()[0].memory_stats() or {}
        print(f"[smoke] cross_device/{name}: bytes_in_use after the run "
              f"{stats.get('bytes_in_use', 'not reported')}")
    assert runs["kernel"]["faults"] == runs["jnp"]["faults"]
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] cross_device: peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print("[smoke] cross_device: kernel vs jnp")
    agree = compare(runs["jnp"], runs["kernel"], rtol=XDEV_RTOL)
    runs["agreement"] = agree
    return runs


def bank_step_phase(*, n_clients: int = 1_000_000, cohort: int = 240,
                    capacity: int = 256, page_size: int = 16,
                    n_slots: int = 1024, n_rounds: int = 16,
                    scan_chunk: int = 4, use_pallas: bool | None = None,
                    seed: int = 0) -> dict:
    """The cross_device bank with the same rows fed to both bodies.

    Two `paged_device` banks — the paged kernels (`use_pallas`) and the jnp
    body — take identical update rows drawn from `seed` for Bernoulli
    cohorts of about `cohort`, with pages prepared per `scan_chunk`-round
    union as the scan engine does, so the second chunk on evicts. After
    every round the banks' pages must be bit-equal, and each bank's G_sum
    must lie within the worst-case fp32 bound of the f64 sum of the same
    deltas: (capacity+2)·2⁻²⁴·Σ|δ| a round plus 2·2⁻²⁴·|G| for the running
    add. A row read or written in the wrong place breaks one or the other.
    Prints both sums' distances from the f64 sum.
    """
    shapes = jax.eval_shape(build_model(get_config("paper_mlp")).init,
                            jax.random.PRNGKey(0))
    banks = {name: make_bank("paged_device", page_size=page_size,
                             n_slots=n_slots, use_pallas=pallas)
             for name, pallas in (("kernel", use_pallas), ("jnp", False))}
    states = {name: b.init(shapes, n_clients) for name, b in banks.items()}
    g_ref = [np.zeros(s.shape) for s in jax.tree.leaves(shapes)]
    bound = [np.zeros(s.shape) for s in g_ref]
    gamma = (capacity + 2) * 2.0 ** -24
    err = {name: 0.0 for name in banks}
    worst_ratio = 0.0
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    for c0 in range(0, n_rounds, scan_chunk):
        cohorts = []
        for _ in range(c0, min(c0 + scan_chunk, n_rounds)):
            size = min(int(rng.binomial(n_clients, cohort / n_clients)),
                       capacity)
            cohorts.append(np.sort(rng.choice(n_clients, size,
                                              replace=False)))
        union = np.unique(np.concatenate(cohorts))
        for name, b in banks.items():
            # `prepare` copies the pool it pages into: finish one bank's
            # paging before the other's starts, so the copies never overlap
            states[name] = jax.block_until_ready(
                b.prepare(states[name], union))
        for ids in cohorts:
            padded = np.full(capacity, n_clients, np.int64)
            padded[:len(ids)] = ids
            valid = np.arange(capacity) < len(ids)
            key, sub = jax.random.split(key)
            # client updates share a direction, as a cohort's gradients do
            leaves, treedef = jax.tree.flatten(shapes)
            updates = jax.tree.unflatten(treedef, [
                jax.random.normal(kb, s.shape)
                + 0.5 * jax.random.normal(kr, (capacity,) + s.shape)
                for s, (kb, kr) in zip(leaves, jax.random.split(
                    sub, (len(leaves), 2)))])
            old = jax.tree.leaves(banks["jnp"].gather(states["jnp"], ids))
            for name, b in banks.items():
                states[name] = b.scatter(states[name], padded, updates,
                                         valid=valid)
            for j, (u, o) in enumerate(zip(jax.tree.leaves(updates), old)):
                delta = (np.asarray(u[:len(ids)], np.float64)
                         - np.asarray(o, np.float64))
                g_ref[j] += delta.sum(0)
                bound[j] += gamma * np.abs(delta).sum(0) \
                    + 2.0 ** -23 * np.abs(g_ref[j])
            for a, b in zip(jax.tree.leaves(states["kernel"]["pages"]),
                            jax.tree.leaves(states["jnp"]["pages"])):
                assert bool(jnp.array_equal(a, b)), "pages differ"
            for name in banks:
                for g, ref, bd in zip(
                        jax.tree.leaves(states[name]["g_sum"]), g_ref, bound):
                    e = np.abs(np.asarray(g, np.float64) - ref)
                    assert (e <= bd).all(), (name, float(e.max()))
                    err[name] = max(err[name], float(e.max()))
                    worst_ratio = max(worst_ratio, float((e / bd).max()))
    scale = max(float(np.abs(r).max()) for r in g_ref)
    out = {"path": _path(banks["kernel"]),
           "faults": banks["kernel"].faults,
           "evictions": banks["kernel"].evictions,
           "g_sum_err_kernel": err["kernel"], "g_sum_err_jnp": err["jnp"],
           "max_abs_g_sum": scale, "worst_err_over_bound": worst_ratio,
           "g_sum_bitexact": all(
               bool(jnp.array_equal(a, b)) for a, b in zip(
                   jax.tree.leaves(states["kernel"]["g_sum"]),
                   jax.tree.leaves(states["jnp"]["g_sum"])))}
    assert out["faults"] == banks["jnp"].faults
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] bank_step: path={out['path']} N={n_clients} "
          f"capacity={capacity} rounds={n_rounds} faults={out['faults']} "
          f"evictions={out['evictions']} pages bit-equal every round; "
          f"G_sum vs f64: kernel {err['kernel']:.4g}, jnp {err['jnp']:.4g} "
          f"(max |G| {scale:.4g}; worst error/bound {worst_ratio:.3g}); "
          f"kernel==jnp G_sum bitwise: {out['g_sum_bitexact']}; "
          f"{time.perf_counter() - t0:.1f} s; process peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    del states
    gc.collect()
    return out


def _device_bytes(arrays, devices) -> dict:
    """Bytes each device holds of `arrays` (its addressable shards)."""
    out = {d.id: 0 for d in devices}
    for a in arrays:
        for s in a.addressable_shards:
            out[s.device.id] += s.data.nbytes
    return out


def mesh_phase(*, data: int = 4, **paper_kw) -> dict:
    """The `paper` phase on a (data, 1) mesh against the same runs on one
    device. Prints each device's bytes of what the sharded runs hand back:
    the parameters, the algorithm state, and within it the client-axis
    rows (MIFA's update array, the bank's rows)."""
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.rules import padded_bank_rows
    mesh = make_host_mesh(data=data)
    devices = list(mesh.devices.flat)
    n_clients = paper_kw.get("n_clients", 100)
    # MIFA's update array has N rows, the dense bank N+1 padded to the mesh
    row_counts = {n_clients, padded_bank_rows(n_clients, mesh)}

    single = paper_phase(label="mesh/single", **paper_kw)
    sharded = paper_phase(mesh=mesh, return_state=True,
                          label=f"mesh/data{data}", **paper_kw)
    out = {}
    for name in ("mifa_array", "banked_dense"):
        state = jax.tree.leaves(sharded[name].pop("state"))
        rows = [a for a in state if a.ndim and a.shape[0] in row_counts]
        snap = {"params": _device_bytes(jax.tree.leaves(
                    sharded[name]["params"]), devices),
                "state": _device_bytes(state, devices),
                "client_rows": _device_bytes(rows, devices),
                "client_row_shapes": sorted({a.shape for a in rows})}
        print(f"[smoke] mesh/{name}: per-device bytes "
              f"params={snap['params']} state={snap['state']} "
              f"client_rows={snap['client_rows']} "
              f"(shapes {snap['client_row_shapes']})")
        print(f"[smoke] mesh/{name}: data={data} vs one device")
        agree = compare(single[name], sharded[name])
        out[name] = {"agreement": agree, **snap}
    print("[smoke] mesh: banked_dense took the jnp path — Pallas is off "
          "under a mesh of more than one device (kernels/backend.py "
          "pallas_partition_safe)")
    return out


# --------------------------------------------------------------------------- #

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded scan phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX's first device is {dev.platform!r} "
              f"({dev.device_kind}); this script measures nothing on a CPU",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(jax.devices())}", file=sys.stderr)
        return 2
    assert not interpret_default(), "bank kernels would run in interpret mode"
    print(f"[smoke] compile cache: {use_compile_cache()}")
    print(f"[smoke] device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}, jax {jax.__version__}, "
          f"matmul precision {PRECISION}")

    if args.chips == 4:
        mesh_phase(data=4)
    else:
        paper_phase()
        cross_device_phase()
        bank_step_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
