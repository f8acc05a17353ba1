"""Fleet executor: vmapped K-trial runs match sequential runs per trial.

The anchor property for `repro.fleet`: for every algorithm on the vmapped
path, running K trials as one jitted program yields the parameters and loss
history that K sequential `run_fl` calls produce, within the fp32
reduction-order band (vmap may regroup a dot's sum; conftest
`assert_fleet_trial_close`), with the active counts exact — so sweep
results never depend on which execution path ran them.

The property is enforced twice: on deterministic trace sets covering the
degenerate shapes (all-dark trials, full cohorts, mixed cohort sizes
sharing one padded capacity) which always run, and on hypothesis-generated
traces when hypothesis is installed (CI installs requirements-dev.txt).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bank import BankedMIFA, DenseBank, HostBank, PagedDeviceBank
from repro.core import (MIFA, BiasedFedAvg, FedAvgSampling,
                        TraceParticipation, run_fl)
from repro.fleet import (FleetRunner, Trial, expand_grid, make_fleet_eval,
                         run_fleet)
from conftest import assert_fleet_trial_close

try:
    from hypothesis import given, settings
    from hypothesis.extra import numpy as hnp
    HAVE_HYPOTHESIS = True
except ImportError:            # tier-1 containers without dev extras
    HAVE_HYPOTHESIS = False

N, T, K = 6, 4, 3

ALGOS = {
    "mifa_array": (lambda: MIFA(memory="array"), False),
    "banked_dense": (lambda: BankedMIFA(DenseBank()), False),
    "banked_paged": (lambda: BankedMIFA(PagedDeviceBank(page_size=2)), False),
    "fedavg": (lambda: BiasedFedAvg(), False),
    "wait_for_s": (lambda: FedAvgSampling(s=3), True),
}


def _run_pair(tiny_problem, algo_factory, traces, clock):
    """(sequential per-trial results, fleet results) for identical trials.

    The cohort capacity is pinned to one shared value on BOTH paths: pad
    slots are mathematically inert, but fp32 reduction grouping depends on
    the padded length, so matching pad widths keep the two paths' sums
    grouped alike (run_fl's docstring spells this out).
    """
    model, batcher = tiny_problem(n_clients=N)
    kw = dict(model=model, batcher=batcher, schedule=lambda t: 0.1 / (1 + t),
              n_rounds=traces.shape[1], weight_decay=1e-3,
              cohort_capacity=8)
    seq = [run_fl(algo=algo_factory(),
                  participation=TraceParticipation(traces[k]), seed=k,
                  uses_update_clock=clock, **kw)
           for k in range(len(traces))]
    trials = [Trial(seed=k, participation=TraceParticipation(traces[k]))
              for k in range(len(traces))]
    fleet = run_fleet(algo=algo_factory(), trials=trials,
                      uses_update_clock=clock, **kw)
    return seq, fleet


def _assert_trial_exact(seq, fleet, k):
    assert_fleet_trial_close(fleet, seq[k], k)


# --------------------------------------------------------------------------- #
# equivalence — deterministic traces, always run
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("name", list(ALGOS))
def test_fleet_bitexact_vs_sequential(tiny_problem, name):
    algo_factory, clock = ALGOS[name]
    traces = np.random.default_rng(7).random((K, T, N)) < 0.5
    seq, fleet = _run_pair(tiny_problem, algo_factory, traces, clock)
    for k in range(K):
        _assert_trial_exact(seq, fleet, k)


def test_fleet_mixed_cohort_sizes_share_capacity(tiny_problem):
    """Trials with very different |A(t)| (empty / singleton / full) pad to
    one shared capacity; the padding must stay inert per trial."""
    traces = np.zeros((3, T, N), bool)
    traces[0] = True                      # full participation
    traces[1, :, 0] = True                # a single stalwart client
    # trial 2: all dark after round 0 (TraceParticipation forces round 0)
    seq, fleet = _run_pair(tiny_problem, ALGOS["banked_dense"][0], traces,
                           False)
    for k in range(3):
        _assert_trial_exact(seq, fleet, k)


# --------------------------------------------------------------------------- #
# equivalence — hypothesis-generated traces (CI)
# --------------------------------------------------------------------------- #

if HAVE_HYPOTHESIS:

    @pytest.mark.parametrize("name", ["mifa_array", "banked_dense", "fedavg"])
    @settings(max_examples=3, deadline=None)
    @given(traces=hnp.arrays(np.bool_, (K, T, N)))
    def test_fleet_bitexact_hypothesis(tiny_problem, name, traces):
        algo_factory, clock = ALGOS[name]
        seq, fleet = _run_pair(tiny_problem, algo_factory, traces, clock)
        for k in range(K):
            _assert_trial_exact(seq, fleet, k)


# --------------------------------------------------------------------------- #
# slow: the non-convex model through the same harness
# --------------------------------------------------------------------------- #

@pytest.mark.slow
def test_fleet_bitexact_mlp(tiny_problem):
    """paper_mlp init is rng-dependent — vmapped init must also match."""
    traces = np.random.default_rng(3).random((2, 3, N)) < 0.5
    seq, fleet = _run_pair(
        lambda **kw: tiny_problem(model_name="paper_mlp", **kw),
        ALGOS["mifa_array"][0], traces, False)
    for k in range(2):
        _assert_trial_exact(seq, fleet, k)


# --------------------------------------------------------------------------- #
# eval, history views, spec expansion, exclusions
# --------------------------------------------------------------------------- #

def test_fleet_eval_matches_sequential(tiny_problem):
    model, batcher = tiny_problem(n_clients=N)
    batch = {"x": np.asarray(batcher.Xs[0][:8]),
             "y": np.asarray(batcher.ys[0][:8])}

    def seq_eval(params):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        loss, _ = model.loss_fn(params, b)
        return float(loss), float(model.accuracy(params, b))

    traces = np.ones((2, T, N), bool)
    kw = dict(model=model, batcher=batcher, schedule=lambda t: 0.1,
              n_rounds=T, weight_decay=1e-3)
    seq = [run_fl(algo=MIFA(memory="array"),
                  participation=TraceParticipation(traces[k]), seed=k,
                  eval_fn=seq_eval, eval_every=2, **kw) for k in range(2)]
    trials = [Trial(seed=k, participation=TraceParticipation(traces[k]))
              for k in range(2)]
    _, hist = run_fleet(algo=MIFA(memory="array"), trials=trials,
                        eval_fn=make_fleet_eval(model, batch), eval_every=2,
                        **kw)
    for k in range(2):
        hk = hist.trial(k)
        assert [t for t, _ in hk.eval_loss] == \
            [t for t, _ in seq[k][1].eval_loss]
        np.testing.assert_allclose(
            [v for _, v in hk.eval_loss],
            [v for _, v in seq[k][1].eval_loss], rtol=1e-6, atol=1e-7)
    stacked = hist.stacked()
    assert stacked["train_loss"].shape == (2, T)
    assert stacked["eval_loss"].shape[0] == 2


def test_expand_grid_groups_and_labels():
    part = lambda seed, p=0.5: TraceParticipation(np.ones((2, N), bool))
    specs = expand_grid(
        algos={"mifa": MIFA(memory="array"),
               "is": lambda p: BiasedFedAvg()},     # callable: per-point
        seeds=(0, 1), avail_grid=({"p": 0.1}, {"p": 0.3}),
        make_participation=part, clock=())
    by_name = {s.name: s for s in specs}
    assert by_name["mifa"].n_trials == 4             # seeds x points batch
    assert "is/p0.1" in by_name and by_name["is/p0.1"].n_trials == 2
    assert by_name["mifa"].labels[0] == "mifa/p0.1/seed0"
    assert by_name["mifa"].seeds == (0, 1, 0, 1)


def test_fleet_rejects_host_offloaded_banks(tiny_problem):
    model, batcher = tiny_problem(n_clients=N)
    trials = [Trial(seed=0,
                    participation=TraceParticipation(np.ones((2, N), bool)))]
    with pytest.raises(NotImplementedError, match="host-offloaded|jittable"):
        run_fleet(model=model, batcher=batcher, schedule=lambda t: 0.1,
                  n_rounds=1, algo=BankedMIFA(HostBank()), trials=trials)


def test_fleet_duplicate_cohort_ids_rejected(tiny_problem):
    model, batcher = tiny_problem(n_clients=N)
    runner = FleetRunner(model=model, algo=BankedMIFA(DenseBank()),
                         batcher=batcher, schedule=lambda t: 0.1,
                         seeds=[0, 1])
    with pytest.raises(ValueError, match="duplicate|unique"):
        runner.step_cohort(0, [np.array([1, 1]), np.array([0, 2])])


def test_batched_bank_scatter_kernel_matches_jnp():
    """The grid-axis batched Pallas kernel == vmapped jnp scatter body."""
    from repro.bank.dense import _scatter_jnp
    from repro.kernels.ops import fleet_bank_update_tree
    key = jax.random.PRNGKey(5)
    Kt, R, C, M = 3, 7, 4, 6
    rows = jax.random.normal(key, (Kt, R, M))
    g_sum = jnp.zeros((Kt, M))
    ids = jnp.array([[0, 3, 6, 6], [1, 2, 5, 6], [6, 6, 6, 6]], jnp.int32)
    valid = jnp.array([[1, 1, 0, 0], [1, 1, 1, 0], [0, 0, 0, 0]], bool)
    upd = jax.random.normal(jax.random.fold_in(key, 1), (Kt, C, M))
    r_ref, g_ref = jax.vmap(_scatter_jnp)(rows, g_sum, ids, valid, upd)
    r_ker, ds = fleet_bank_update_tree(rows, upd, ids, valid)
    np.testing.assert_allclose(np.asarray(r_ker), np.asarray(r_ref),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(ds), np.asarray(g_ref), atol=1e-6)


def test_bank_fleet_surface():
    """gather_fleet == per-trial gathers; host banks refuse the fleet."""
    key = jax.random.PRNGKey(2)
    params = {"w": jax.random.normal(key, (4, 3))}
    bank = DenseBank()
    single = bank.init(params, 5)
    stacked = jax.tree.map(lambda l: jnp.stack([l, l + 1.0]), single)
    ids = jnp.array([[0, 2], [1, 4]], jnp.int32)
    got = bank.gather_fleet(stacked, ids)
    for k in range(2):
        want = bank.gather(jax.tree.map(lambda l: l[k], stacked), ids[k])
        np.testing.assert_array_equal(np.asarray(got["w"][k]),
                                      np.asarray(want["w"]))
    host = HostBank()
    hs = host.init(params, 5)
    with pytest.raises(NotImplementedError, match="host-offloaded"):
        host.scatter_fleet(jax.tree.map(lambda l: np.stack([l, l]), hs),
                           np.array([[0], [1]]),
                           {"w": np.zeros((2, 1, 4, 3), np.float32)})


def test_fleet_trial_axis_sharding_smoke(tiny_problem):
    """Trial axis lands on the mesh data axes and the run still matches."""
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.rules import fleet_axis_specs, fleet_trial_specs
    mesh = make_host_mesh(1, 1)
    cfg = get_config("paper_logistic")
    model, batcher = tiny_problem(n_clients=N)
    traces = np.ones((2, 3, N), bool)
    kw = dict(model=model, batcher=batcher, schedule=lambda t: 0.1,
              n_rounds=3, weight_decay=1e-3)
    ref = run_fleet(algo=MIFA(memory="array"),
                    trials=[Trial(seed=k,
                                  participation=TraceParticipation(traces[k]))
                            for k in range(2)], **kw)
    sh = run_fleet(algo=MIFA(memory="array"),
                   trials=[Trial(seed=k,
                                 participation=TraceParticipation(traces[k]))
                           for k in range(2)], mesh=mesh, cfg=cfg, **kw)
    for a, b in zip(jax.tree.leaves(ref[0]), jax.tree.leaves(sh[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # spec shapes match the stacked trees
    specs = fleet_trial_specs(ref[0], cfg, mesh)
    assert jax.tree.structure(specs) == jax.tree.structure(ref[0])
    gen = fleet_axis_specs({"g": jnp.zeros((4, N, 3))}, mesh)
    assert len(gen["g"]) == 3
