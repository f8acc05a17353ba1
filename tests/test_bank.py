"""Memory-bank subsystem: backend equivalence, cohort rounds, drivers.

The load-bearing property: fp32 bank cohort rounds are *the same algorithm*
as dense `MIFA(memory="array")` — same parameter trajectory, same history —
while only ever touching O(|A(t)|·d) state.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bank import (BankedMIFA, DenseBank, HostBank, Int8PagedBank,
                        MemoryBank, PagedDeviceBank, make_bank)
from repro.configs import get_config
from repro.core import MIFA, BernoulliParticipation, run_fl
from repro.core.runner import RoundRunner, _pow2_bucket
from repro.data import ProceduralBatcher

N = 8


def _tree(rng):
    k1, k2 = jax.random.split(rng)
    return {"w": jax.random.normal(k1, (4, 3)),
            "b": jax.random.normal(k2, (3,))}


def _cohort_updates(rng, ids):
    k1, k2 = jax.random.split(rng)
    c = len(ids)
    return {"w": jax.random.normal(k1, (c, 4, 3)),
            "b": jax.random.normal(k2, (c, 3))}


def _random_rounds(bank: MemoryBank, rounds=6, seed=0, needs_rng=False):
    """Drive a bank and a dense MIFA('array') with identical cohorts."""
    key = jax.random.PRNGKey(seed)
    params = _tree(key)
    mifa = MIFA(memory="array")
    sm = mifa.init_state(params, N)
    bs = bank.init(params, N)
    for t in range(rounds):
        key, k1, k2, k3 = jax.random.split(key, 4)
        active = np.array(jax.random.bernoulli(k2, 0.5, (N,)))
        if t == 0:
            active[:] = True
        ids = np.flatnonzero(active)
        cu = _cohort_updates(k1, ids)
        # dense MIFA sees the same updates scattered into an (N, ...) array
        full = jax.tree.map(
            lambda c, p: jnp.zeros((N,) + p.shape).at[ids].set(c),
            cu, params)
        sm, _, _ = mifa.round_step(sm, params, full, jnp.zeros(N),
                                   jnp.asarray(active), jnp.float32(0.1))
        bs = bank.scatter(bs, ids, cu, rng=(k3 if needs_rng else None))
    dense_mean = jax.tree.map(lambda g: jnp.mean(g.astype(jnp.float32), 0),
                              sm["G"])
    return bs, dense_mean


# --------------------------------------------------------------------------- #
# backend <-> dense MIFA equivalence
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("backend", ["dense", "host", "paged_device"])
def test_fp32_backends_match_dense_mifa_mean(backend):
    bank = make_bank(backend)
    bs, dense_mean = _random_rounds(bank)
    for a, b in zip(jax.tree.leaves(bank.mean_g(bs)),
                    jax.tree.leaves(dense_mean)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_int8_paged_close_to_dense_mifa_mean():
    bank = Int8PagedBank(page_size=4)
    bs, dense_mean = _random_rounds(bank, needs_rng=True)
    for a, b in zip(jax.tree.leaves(bank.mean_g(bs)),
                    jax.tree.leaves(dense_mean)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0.05)


@pytest.mark.parametrize("backend,kwargs",
                         [("dense", {}), ("host", {}),
                          ("int8_paged", {"page_size": 4}),
                          ("paged_device", {"page_size": 4})])
def test_gsum_is_sum_of_rows(backend, kwargs):
    """The delta identity maintains G_sum == Σ_i gather(i) exactly."""
    bank = make_bank(backend, **kwargs)
    bs, _ = _random_rounds(bank, needs_rng=(backend == "int8_paged"))
    rows = bank.gather(bs, np.arange(N))
    mean = bank.mean_g(bs)
    for r, m in zip(jax.tree.leaves(rows), jax.tree.leaves(mean)):
        np.testing.assert_allclose(np.asarray(r).sum(0) / N, np.asarray(m),
                                   rtol=1e-5, atol=1e-6)


def test_scatter_only_touches_cohort_rows():
    key = jax.random.PRNGKey(3)
    params = _tree(key)
    for bank in (DenseBank(), HostBank(), Int8PagedBank(page_size=2),
                 PagedDeviceBank(page_size=2, n_slots=3)):
        bs = bank.init(params, N)
        ids0 = np.array([1, 4])
        bs = bank.scatter(bs, ids0, _cohort_updates(key, ids0),
                          rng=jax.random.fold_in(key, 1))
        before = jax.tree.leaves(bank.gather(bs, np.array([1, 4])))
        ids1 = np.array([0, 5, 6])
        bs = bank.scatter(bs, ids1, _cohort_updates(key, ids1),
                          rng=jax.random.fold_in(key, 2))
        after = jax.tree.leaves(bank.gather(bs, np.array([1, 4])))
        for a, b in zip(before, after):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_padded_slots_are_inert():
    """valid=False slots (dummy row) change neither rows nor G_sum."""
    key = jax.random.PRNGKey(7)
    params = _tree(key)
    ids = np.array([2, 5])
    cu = _cohort_updates(key, ids)
    padded_ids = np.array([2, 5, N, N])
    padded_cu = jax.tree.map(
        lambda c: jnp.concatenate([c, 999.0 * jnp.ones((2,) + c.shape[1:])]),
        cu)
    valid = np.array([True, True, False, False])
    for backend, kwargs in (("dense", {}), ("host", {}),
                            ("int8_paged", {"page_size": 4}),
                            ("paged_device", {"page_size": 4}),
                            ("paged_device", {"page_size": 4,
                                              "dtype": "int8"})):
        rng = jax.random.fold_in(key, 1)
        b1 = make_bank(backend, **kwargs)
        s1 = b1.scatter(b1.init(params, N), ids, cu, rng=rng)
        b2 = make_bank(backend, **kwargs)
        s2 = b2.scatter(b2.init(params, N), padded_ids, padded_cu,
                        valid=valid, rng=rng)
        for a, b in zip(jax.tree.leaves(b1.gather(s1, np.arange(N))),
                        jax.tree.leaves(b2.gather(s2, np.arange(N)))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(b1.mean_g(s1)),
                        jax.tree.leaves(b2.mean_g(s2))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dense_pallas_path_matches_jnp(dtype):
    key = jax.random.PRNGKey(11)
    params = _tree(key)
    b1 = DenseBank(dtype=dtype, use_pallas=False)
    b2 = DenseBank(dtype=dtype, use_pallas=True)
    s1, s2 = b1.init(params, N), b2.init(params, N)
    for t in range(3):
        key, k = jax.random.split(key)
        ids = np.array([0, 3, 5, N])
        valid = np.array([1, 1, 1, 0], bool)
        cu = _cohort_updates(k, ids)
        s1 = b1.scatter(s1, ids, cu, valid=valid)
        s2 = b2.scatter(s2, ids, cu, valid=valid)
    for a, b in zip(jax.tree.leaves(s1["rows"]), jax.tree.leaves(s2["rows"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    for a, b in zip(jax.tree.leaves(b1.mean_g(s1)),
                    jax.tree.leaves(b2.mean_g(s2))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_int8_paged_lazy_allocation():
    key = jax.random.PRNGKey(0)
    params = _tree(key)
    bank = Int8PagedBank(page_size=2)
    bs = bank.init(params, 100)
    assert bank.n_pages(bs) == 0
    bs = bank.scatter(bs, np.array([0, 1, 50]),
                      _cohort_updates(key, np.arange(3)), rng=key)
    assert bank.n_pages(bs) == 2            # page 0 (rows 0-1) + page 25
    dense_bytes = sum(
        np.prod((100,) + np.shape(leaf)) * 4
        for leaf in jax.tree.leaves(params))
    assert bank.memory_bytes(bs)["host"] < dense_bytes / 4
    # untouched rows read as exact zeros
    for leaf in jax.tree.leaves(bank.gather(bs, np.array([7, 99]))):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)


def test_make_bank_rejects_unknown():
    with pytest.raises(ValueError, match="unknown bank backend"):
        make_bank("sqlite")


# --------------------------------------------------------------------------- #
# cohort round path through RoundRunner / run_fl
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("backend", ["dense", "host", "paged_device"])
def test_banked_run_fl_matches_dense_mifa_trajectory(backend, tiny_problem):
    """Acceptance property: same params AND same per-round history."""
    model, batcher = tiny_problem(n_clients=10)
    kw = dict(model=model, batcher=batcher, schedule=lambda t: 0.1 / (1 + t),
              n_rounds=8, seed=0)
    part = lambda: BernoulliParticipation(np.full(10, 0.5), seed=1)
    p1, h1 = run_fl(algo=MIFA(memory="array"), participation=part(), **kw)
    p2, h2 = run_fl(algo=BankedMIFA(make_bank(backend)), participation=part(),
                    **kw)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(h1.train_loss, h2.train_loss,
                               rtol=1e-4, atol=1e-6)
    assert h1.n_active == h2.n_active


def test_step_cohort_skips_mask_work(tiny_problem):
    """Direct cohort stepping: ids in, O(|A|) batch out, same math."""
    model, batcher = tiny_problem(n_clients=10)
    r1 = RoundRunner(model=model, algo=BankedMIFA(DenseBank()),
                     batcher=batcher, schedule=lambda t: 0.1, seed=0)
    r2 = RoundRunner(model=model, algo=BankedMIFA(DenseBank()),
                     batcher=batcher, schedule=lambda t: 0.1, seed=0)
    rng = np.random.default_rng(0)
    for t in range(4):
        ids = np.sort(rng.choice(10, size=4, replace=False))
        mask = np.zeros(10, bool)
        mask[ids] = True
        r1.step(t, mask)
        r2.step_cohort(t, ids)
    for a, b in zip(jax.tree.leaves(r1.params), jax.tree.leaves(r2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    assert r1.hist.train_loss == r2.hist.train_loss
    assert r2.stats.rounds == 0          # τ stats skipped on the ids path


def test_empty_round_is_noop_for_params_memory(tiny_problem):
    model, batcher = tiny_problem(n_clients=10)
    runner = RoundRunner(model=model, algo=BankedMIFA(DenseBank()),
                         batcher=batcher, schedule=lambda t: 0.1, seed=0)
    runner.step(0, np.ones(10, bool))
    p_before = jax.tree.map(lambda x: np.array(x), runner.params)
    g_before = jax.tree.map(lambda x: np.array(x),
                            runner.state["bank"]["g_sum"])
    runner.step(1, np.zeros(10, bool))   # blackout round
    # memory unchanged; params still move by the memorized mean (MIFA!)
    for a, b in zip(jax.tree.leaves(g_before),
                    jax.tree.leaves(runner.state["bank"]["g_sum"])):
        np.testing.assert_array_equal(a, np.asarray(b))
    moved = any(
        not np.allclose(a, np.asarray(b)) for a, b in
        zip(jax.tree.leaves(p_before), jax.tree.leaves(runner.params)))
    assert moved


def test_pow2_bucketing():
    assert [_pow2_bucket(c) for c in (0, 1, 2, 3, 4, 5, 9)] == \
        [1, 1, 2, 4, 4, 8, 16]


def test_cohort_capacity_bounds_traces(tiny_problem):
    model, batcher = tiny_problem(n_clients=10)
    runner = RoundRunner(model=model, algo=BankedMIFA(DenseBank()),
                         batcher=batcher, schedule=lambda t: 0.1, seed=0,
                         cohort_capacity=8)
    # k=10 overflows the configured capacity: falls back to the pow2 bucket
    # instead of crashing mid-run
    for t, k in enumerate((3, 5, 1, 8, 10)):
        runner.step_cohort(t, np.arange(k))
    assert len(runner.hist.rounds) == 5


def test_duplicate_cohort_ids_rejected(tiny_problem):
    """Duplicates would silently corrupt G_sum — every entry point refuses."""
    key = jax.random.PRNGKey(0)
    params = _tree(key)
    dup = np.array([1, 1, 4])
    cu = _cohort_updates(key, dup)
    for backend, kwargs in (("dense", {}), ("host", {}),
                            ("int8_paged", {"page_size": 4}),
                            ("paged_device", {"page_size": 4})):
        bank = make_bank(backend, **kwargs)
        bs = bank.init(params, N)
        with pytest.raises(ValueError, match="duplicate"):
            bank.scatter(bs, dup, cu, rng=key)
        # duplicates among invalid pad slots are fine (shared dummy row)
        bank.scatter(bs, np.array([1, N, N]), cu,
                     valid=np.array([True, False, False]), rng=key)
    model, batcher = tiny_problem(n_clients=10)
    runner = RoundRunner(model=model, algo=BankedMIFA(DenseBank()),
                         batcher=batcher, schedule=lambda t: 0.1, seed=0)
    with pytest.raises(ValueError, match="unique"):
        runner.step_cohort(0, np.array([2, 2]))


def test_duplicate_check_is_enforced_in_base_scatter():
    """The check lives in MemoryBank.scatter (template method) — backends
    implement `_scatter_rows` and MUST NOT override `scatter`, or they
    silently drift out from under the shared validation."""
    for cls in (DenseBank, HostBank, Int8PagedBank, PagedDeviceBank):
        assert cls.scatter is MemoryBank.scatter, cls
        assert cls._scatter_rows is not MemoryBank._scatter_rows, cls


# --------------------------------------------------------------------------- #
# batchers: compact == full slice
# --------------------------------------------------------------------------- #

def test_client_batcher_compact_matches_full(tiny_problem):
    _, batcher = tiny_problem(n_clients=10)
    full = batcher.sample_round(3)
    ids = np.array([7, 0, 4])
    compact = batcher.sample_round(3, client_ids=ids)
    for k in full:
        np.testing.assert_array_equal(compact[k], full[k][ids])


def test_procedural_batcher_compact_matches_full():
    b = ProceduralBatcher(n_clients=20, dim=6, n_classes=3, batch_size=4,
                          k_steps=2, seed=5)
    full = b.sample_round(2)
    ids = np.array([19, 3, 3, 11])
    compact = b.sample_round(2, client_ids=ids)
    for k in full:
        np.testing.assert_array_equal(compact[k], full[k][ids])
    # labels come from the shared teacher: learnable, multi-class
    assert set(np.unique(full["y"])) <= set(range(3))


def test_procedural_batcher_noniid_shift():
    b = ProceduralBatcher(n_clients=4, dim=8, batch_size=64, k_steps=1,
                          shift=3.0, noise=0.1, seed=0)
    batch = b.sample_round(0)
    means = batch["x"].mean(axis=(1, 2))         # (N, dim) per-client mean
    gaps = np.linalg.norm(means[:, None] - means[None, :], axis=-1)
    assert gaps[np.triu_indices(4, 1)].min() > 1.0


# --------------------------------------------------------------------------- #
# sharded bank rows
# --------------------------------------------------------------------------- #

def test_sharded_dense_bank_smoke():
    from repro.launch.mesh import data_parallel_size, make_host_mesh
    from repro.sharding.rules import padded_bank_rows
    mesh = make_host_mesh(1, 1)
    cfg = get_config("paper_logistic")
    key = jax.random.PRNGKey(0)
    params = _tree(key)
    bank = DenseBank(mesh=mesh, cfg=cfg)
    bs = bank.init(params, N)
    assert bank.n_rows == padded_bank_rows(N, mesh) >= N + 1
    assert bank.n_rows % data_parallel_size(mesh) == 0
    ids = np.array([1, 6])
    bs = bank.scatter(bs, ids, _cohort_updates(key, ids))
    ref = DenseBank()
    rs = ref.scatter(ref.init(params, N), ids, _cohort_updates(key, ids))
    for a, b in zip(jax.tree.leaves(bank.mean_g(bs)),
                    jax.tree.leaves(ref.mean_g(rs))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# --------------------------------------------------------------------------- #
# paged device bank: eviction, determinism, page-table invariants
# --------------------------------------------------------------------------- #

# Cohorts chosen so that, at page_size=2 / n_slots=2, every round fits in the
# slot budget but the sequence as a whole forces evictions and refaults.
_EVICT_COHORTS = [[0, 1], [4, 5], [2, 3], [0, 5], [6, 7], [1, 2], [4], [0, 7]]


def _drive_cohorts(bank, cohorts, seed=3, needs_rng=False):
    """Scatter a fixed cohort sequence; return (state, per-round mean_g)."""
    key = jax.random.PRNGKey(seed)
    params = _tree(key)
    bs = bank.init(params, N)
    means = []
    for t, ids in enumerate(cohorts):
        ids = np.array(ids)
        k = jax.random.fold_in(key, t)
        rng = jax.random.fold_in(k, 1) if needs_rng else None
        bs = bank.scatter(bs, ids, _cohort_updates(k, ids), rng=rng)
        means.append(bank.mean_g(bs))
    return bs, means


def test_paged_eviction_matches_dense():
    """Evicting paged bank is bit-exact vs DenseBank: physical placement is
    invisible because reductions run over the cohort axis, never slots."""
    paged = PagedDeviceBank(page_size=2, n_slots=2)
    dense = DenseBank()
    ps, pm = _drive_cohorts(paged, _EVICT_COHORTS)
    ds, dm = _drive_cohorts(dense, _EVICT_COHORTS)
    assert paged.faults > 0 and paged.evictions > 0
    for a, b in zip(jax.tree.leaves(pm), jax.tree.leaves(dm)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(paged.gather(ps, np.arange(N))),
                    jax.tree.leaves(dense.gather(ds, np.arange(N)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_eviction_refault_determinism():
    """Same cohort sequence twice => identical trajectory AND identical
    fault/eviction counters (deterministic LRU, no tie-break wobble)."""
    runs = []
    for _ in range(2):
        bank = PagedDeviceBank(page_size=2, n_slots=2)
        bs, means = _drive_cohorts(bank, _EVICT_COHORTS)
        runs.append((bank, bank.gather(bs, np.arange(N)), means))
    (b1, g1, m1), (b2, g2, m2) = runs
    assert (b1.faults, b1.evictions) == (b2.faults, b2.evictions)
    assert b1.faults > 0 and b1.evictions > 0
    for a, b in zip(jax.tree.leaves((g1, m1)), jax.tree.leaves((g2, m2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_page_table_invariants_after_eviction():
    """No aliased slots, free-list conservation, dummy page pinned at zero —
    checked after an eviction-heavy sequence."""
    bank = PagedDeviceBank(page_size=2, n_slots=2)
    bs, _ = _drive_cohorts(bank, _EVICT_COHORTS)
    bank.check_invariants(bs)
    assert bank.n_resident() <= 2


def test_paged_working_set_overflow_raises():
    bank = PagedDeviceBank(page_size=2, n_slots=2)
    key = jax.random.PRNGKey(0)
    bs = bank.init(_tree(key), N)
    ids = np.array([0, 2, 4])        # spans 3 pages, only 2 slots
    with pytest.raises(ValueError, match="slots"):
        bank.scatter(bs, ids, _cohort_updates(key, ids))


def test_paged_device_bytes_bounded_by_slots():
    """Device page-pool bytes depend on n_slots, not on n_clients."""
    key = jax.random.PRNGKey(0)
    params = _tree(key)
    small = PagedDeviceBank(page_size=2, n_slots=2)
    big = PagedDeviceBank(page_size=2, n_slots=2)
    ss = small.init(params, N)
    sb = big.init(params, 64 * N)
    assert (small.memory_bytes(ss)["device_pages"]
            == big.memory_bytes(sb)["device_pages"])


def test_paged_pallas_path_matches_jnp():
    b1 = PagedDeviceBank(page_size=2, n_slots=2, use_pallas=False)
    b2 = PagedDeviceBank(page_size=2, n_slots=2, use_pallas=True)
    s1, m1 = _drive_cohorts(b1, _EVICT_COHORTS)
    s2, m2 = _drive_cohorts(b2, _EVICT_COHORTS)
    for a, b in zip(jax.tree.leaves(m1), jax.tree.leaves(m2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(b1.gather(s1, np.arange(N))),
                    jax.tree.leaves(b2.gather(s2, np.arange(N)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_page_table_beyond_smem_raises_naming_page_size():
    """A page table the compiled kernels could not hold in the TPU's scalar
    memory is refused when the bank is built, naming page_size — not
    inside the compiler. The jnp path and interpret mode have no bound."""
    from repro.kernels.backend import use_pallas
    from repro.kernels.bank_scatter import SMEM_PAGE_TABLE_ENTRIES
    params = _tree(jax.random.PRNGKey(0))
    too_many = SMEM_PAGE_TABLE_ENTRIES * 2        # page_size=2: entries = N/2
    use_pallas(True)                              # as on a TPU: compiled
    try:
        with pytest.raises(ValueError, match="page_size"):
            PagedDeviceBank(page_size=2, n_slots=2).init(params, too_many)
        PagedDeviceBank(page_size=4, n_slots=2).init(params, too_many)
        PagedDeviceBank(page_size=2, n_slots=2,
                        use_pallas=False).init(params, too_many)
    finally:
        use_pallas(None)
    PagedDeviceBank(page_size=2, n_slots=2).init(params, too_many)
