"""Vmapped fleet executor — K independent FL trials as ONE jitted program.

The paper's headline results are statistical claims over seeds ×
participation scenarios × algorithms, but a Python loop over `run_fl` pays
per-trial dispatch, per-trial retracing, and per-trial host→device traffic.
The fleet executor stacks K trials along a leading *trial axis* and runs
each round as a single `jit(vmap(...))` call:

    params : (K, *shape)      state : per-algo leaves with a (K,) prefix
    rngs   : (K, 2)           masks : (K, N) from K host-side processes

Reuse, not reimplementation: the vmapped round is `jax.vmap` of the SAME
pure functions `RoundRunner` jits (`core.runner.make_dense_round_fn`,
`make_cohort_update_fn`, `apply_mean`), and the banked cohort path goes
through the same `DenseBank` scatter body (vmapped jnp, or the grid-axis
batched Pallas kernel `kernels.bank_scatter_batched`). Per trial the fleet
is therefore bit-exactly the trajectory `run_fl` produces — property-tested
in tests/test_fleet.py.

What is and is not vmappable (docs/architecture.md §7):
  * dense algorithms (MIFA array/delta/int8, FedAvg baselines)   — yes
  * BankedMIFA over DenseBank (jittable)                         — yes
  * BankedMIFA over PagedDeviceBank (jittable; one residency map
    shared across trials, paged in per round / chunk union)      — yes
  * BankedMIFA over HostBank / Int8PagedBank (host-offloaded)    — no; these
    live outside jit by design, run those trials sequentially.

The availability environment comes in two flavours. Legacy participation
processes stay per-trial and un-vmapped: each trial's (N,) mask is drawn on
the host exactly as `run_fl` would draw it. `repro.scenarios` trials
instead carry a jit-native process whose state (Markov chains, drifting
rates — parameters included) stacks along the trial axis, and the mask is
sampled INSIDE the vmapped round function (`step_scenario`): sweeping
`seed × scenario × algorithm` never materialises a (T, N) trace or loops
over trials on the host. Cohort batches are assembled per trial then
stacked. The trial axis can be sharded over the mesh's data axes
(`sharding.rules.fleet_trial_specs`).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.runner import (FLHistory, _pow2_bucket, apply_mean,
                               make_cohort_update_fn, make_dense_round_fn,
                               make_scenario_round_fn, warn_engine_fallback)
from repro.fleet.spec import FleetSpec, Trial


@dataclass
class FleetHistory:
    """Per-round metrics with a leading (K,) trial axis.

    `trial(k)` materialises one trial's view as a plain `FLHistory`, so
    downstream plotting/analysis written for `run_fl` works unchanged.
    """

    n_trials: int
    labels: list[str] = field(default_factory=list)
    rounds: list = field(default_factory=list)
    train_loss: list = field(default_factory=list)     # (K,) per round
    n_active: list = field(default_factory=list)       # (K,) per round
    global_updates: list = field(default_factory=list)
    eval_loss: list = field(default_factory=list)      # (t, (K,)) per eval
    eval_acc: list = field(default_factory=list)
    sim_seconds: list = field(default_factory=list)    # (K,) close per round
    eval_seconds: list = field(default_factory=list)   # (t, (K,)) per eval
    wall_time: float = 0.0

    def record_round(self, t: int, metrics: dict, sim_time=None) -> None:
        """Append round t's (K,) metric vectors (loss, n_active, ...);
        `sim_time` stamps the round with per-trial simulated seconds
        (simulated-fleet runs, `repro.fleet.sim`)."""
        self.rounds.append(t)
        self.train_loss.append(np.asarray(metrics["loss"], np.float64))
        self.n_active.append(np.asarray(metrics["n_active"], np.float64))
        if "global_updates" in metrics:
            self.global_updates.append(
                np.asarray(metrics["global_updates"], np.float64))
        if sim_time is not None:
            self.sim_seconds.append(np.asarray(sim_time, np.float64))

    def record_eval(self, t: int, eval_loss, eval_acc,
                    sim_time=None) -> None:
        """Append an eval point: (round, (K,) losses) and (round, (K,) accs);
        `sim_time` additionally stamps it on the per-trial simulated-seconds
        axis (eval_seconds)."""
        self.eval_loss.append((t, np.asarray(eval_loss, np.float64)))
        self.eval_acc.append((t, np.asarray(eval_acc, np.float64)))
        if sim_time is not None:
            self.eval_seconds.append((t, np.asarray(sim_time, np.float64)))

    def stacked(self) -> dict:
        """{'train_loss': (K, T), 'n_active': (K, T), ...} arrays."""
        out = {"rounds": np.asarray(self.rounds),
               "train_loss": np.stack(self.train_loss, axis=1)
               if self.train_loss else np.zeros((self.n_trials, 0)),
               "n_active": np.stack(self.n_active, axis=1)
               if self.n_active else np.zeros((self.n_trials, 0))}
        if self.global_updates:
            out["global_updates"] = np.stack(self.global_updates, axis=1)
        if self.eval_loss:
            out["eval_rounds"] = np.asarray([t for t, _ in self.eval_loss])
            out["eval_loss"] = np.stack([v for _, v in self.eval_loss], 1)
            out["eval_acc"] = np.stack([v for _, v in self.eval_acc], 1)
        if self.sim_seconds:
            out["sim_seconds"] = np.stack(self.sim_seconds, axis=1)
        if self.eval_seconds:
            out["eval_seconds"] = np.stack(
                [v for _, v in self.eval_seconds], 1)
        return out

    def trial(self, k: int) -> FLHistory:
        """Trial k's view as a plain `FLHistory` (scalars, not (K,) rows)."""
        h = FLHistory()
        h.rounds = list(self.rounds)
        h.train_loss = [float(v[k]) for v in self.train_loss]
        h.n_active = [float(v[k]) for v in self.n_active]
        h.global_updates = [float(v[k]) for v in self.global_updates]
        h.eval_loss = [(t, float(v[k])) for t, v in self.eval_loss]
        h.eval_acc = [(t, float(v[k])) for t, v in self.eval_acc]
        h.sim_seconds = [float(v[k]) for v in self.sim_seconds]
        h.eval_seconds = [(t, float(v[k])) for t, v in self.eval_seconds]
        h.wall_time = self.wall_time
        return h


class FleetRunner:
    """K-trial counterpart of `core.runner.RoundRunner`.

    The driver feeds `step(t, masks)` a (K, N) availability matrix — one
    row per trial, drawn by that trial's own participation process — and
    every round executes as one jitted, vmapped program. τ statistics are
    not tracked (they are host-side O(K·N) bookkeeping; run the trial
    sequentially if you need them).
    """

    def __init__(self, *, model, algo, batcher, schedule: Callable,
                 seeds: Sequence[int], eta_local: Callable | float | None = None,
                 weight_decay: float = 0.0, uses_update_clock: bool = False,
                 cohort_capacity: int | None = None,
                 labels: Sequence[str] | None = None, mesh=None, cfg=None,
                 scenarios: Sequence | None = None):
        self.model = model
        self.algo = algo
        self.batcher = batcher
        self.schedule = schedule
        self.eta_local = eta_local
        self.weight_decay = weight_decay
        self.uses_update_clock = uses_update_clock
        self.cohort_capacity = cohort_capacity
        self.n_trials = len(seeds)
        self.n_clients = batcher.n_clients
        # one PRNG stream per trial, identical to RoundRunner(seed=s):
        # the key inits the params, then splits once per round
        self.rngs = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
        self.params = jax.vmap(model.init)(self.rngs)
        self.state = jax.vmap(
            lambda p: algo.init_state(p, self.n_clients))(self.params)
        self.hist = FleetHistory(self.n_trials,
                                 labels=list(labels or
                                             [f"seed{s}" for s in seeds]))
        self.cohort_mode = getattr(algo, "cohort_based", False)

        if self.cohort_mode:
            if not getattr(algo.bank, "jittable", False):
                raise NotImplementedError(
                    f"{type(algo.bank).__name__} is host-offloaded "
                    "(jittable=False); the vmapped fleet path needs a "
                    "jittable bank — DenseBank ('dense') or PagedDeviceBank "
                    "('paged_device') — otherwise run trials sequentially")
            updates_fn = make_cohort_update_fn(model, batcher.k_steps,
                                               weight_decay)

            def cohort_round(state, params, ubatch, idx, ids, valid,
                             eta_loc, eta_srv, rngs):
                # each distinct client's batch crosses host->device ONCE;
                # trials gather their (cap, ...) slices on device
                batch = jax.tree.map(lambda l: l[idx], ubatch)
                updates, losses = jax.vmap(updates_fn)(params, batch,
                                                       eta_loc)
                with jax.named_scope("server_memory"):
                    state, mean_g, metrics = algo.round_step_cohort_fleet(
                        state, ids, valid, updates, losses, rng=rngs)
                    params = jax.vmap(apply_mean)(params, mean_g, eta_srv)
                return state, params, metrics

            self.cohort_round_fn = jax.jit(cohort_round,
                                           donate_argnums=(0,))
            self.round_fn = None
        else:
            base = make_dense_round_fn(model, algo, batcher.k_steps,
                                       weight_decay)
            # batch is shared across trials (the data is the environment):
            # in_axes=None broadcasts it, everything else carries the K axis
            self.round_fn = jax.jit(
                jax.vmap(base, in_axes=(0, 0, None, 0, 0, 0, 0)),
                donate_argnums=(0,))
            self.cohort_round_fn = None

        self.mesh = mesh
        self.cfg = cfg
        self._init_scenarios(scenarios, weight_decay)
        if mesh is not None:
            self._shard_trial_axis(mesh, cfg)

    def _init_scenarios(self, scenarios, weight_decay: float) -> None:
        """Wire per-trial `repro.scenarios` processes into the fleet.

        Dense groups sample availability INSIDE the vmapped round: each
        trial's scenario state (chain state + parameters) stacks along the
        trial axis and the shared pure sample function runs under the same
        jit as the round — no (T, N) trace, no per-trial host loop. Cohort
        groups (compact batches need the mask on the host) fall back to the
        scenarios' host surfaces, which draw identical masks.
        """
        self.scen_round_fn = None
        self._scen_fn = None
        self._scen_samplers = None
        self._scen_procs = None
        self._scen_win_start = None
        if scenarios is None:
            return
        from repro.scenarios.base import as_process
        procs = [as_process(s) for s in scenarios]
        self._scen_procs = procs
        assert len(procs) == self.n_trials, (len(procs), self.n_trials)
        if any(type(p) is not type(procs[0]) for p in procs):
            raise ValueError(
                "all trials in one fleet group must share a scenario type "
                "(one pure sample function per vmapped program); got "
                f"{sorted({type(p).__name__ for p in procs})} — split the "
                "sweep into one FleetSpec per type")
        for p in procs:
            assert p.n == self.n_clients, (p.n, self.n_clients)
        if self.cohort_mode:
            self._scen_samplers = [p.host_sampler() for p in procs]
            return
        self._scen_fn = procs[0].sample_fn()
        scen_round = make_scenario_round_fn(
            self.model, self.algo, self.batcher.k_steps, weight_decay,
            self._scen_fn)
        self.scen_round_fn = jax.jit(
            jax.vmap(scen_round,
                     in_axes=(0, 0, None, 0, None, 0, 0, 0, 0)),
            donate_argnums=(0,))
        self.scen_state = jax.tree.map(lambda *xs: jnp.stack(xs),
                                       *[p.init_state() for p in procs])
        self.scen_keys = jnp.stack([p.key for p in procs])
        # windowed processes (trace replay): every trial's window must be
        # the same length so the stacked (K, W, N) leaf is rectangular
        ws = {getattr(p, "scan_window", None) for p in procs}
        if len(ws) > 1:
            raise ValueError(
                "all trials in one fleet group must share the scenario "
                f"window length, got {sorted(map(str, ws))}")
        self._scen_win_start = 0 if ws != {None} else None

    def _shard_trial_axis(self, mesh, cfg) -> None:
        """Place every (K, ...)-leading trial structure — params, algorithm
        state, per-trial RNG streams, and (scenario fleets) the stacked
        chain state and scenario keys — with the trial axis over the mesh's
        data axes, so the vmapped/scanned programs run K-way data parallel."""
        from jax.sharding import NamedSharding
        from repro.core.runner import warn_legacy_threefry
        from repro.sharding.rules import fleet_axis_specs, fleet_trial_specs
        warn_legacy_threefry(mesh)
        put = lambda tree, specs: jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, specs)
        if cfg is not None:
            self.params = put(self.params,
                              fleet_trial_specs(self.params, cfg, mesh))
        else:
            self.params = put(self.params,
                              fleet_axis_specs(self.params, mesh))
        self.state = put(self.state, fleet_axis_specs(self.state, mesh))
        self.rngs = put(self.rngs, fleet_axis_specs(self.rngs, mesh))
        if getattr(self, "scen_round_fn", None) is not None:
            self.scen_state = put(self.scen_state,
                                  fleet_axis_specs(self.scen_state, mesh))
            self.scen_keys = put(self.scen_keys,
                                 fleet_axis_specs(self.scen_keys, mesh))

    # ------------------------------------------------------------------ #
    def _split(self):
        out = jax.vmap(jax.random.split)(self.rngs)      # (K, 2, 2)
        return out[:, 0], out[:, 1]

    def learning_rates(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        """(η_local (K,), η_server (K,)) f32 — per-trial update clocks."""
        if self.uses_update_clock and "t_updates" in self.state:
            clocks = np.asarray(self.state["t_updates"], np.int64) + 1
        else:
            clocks = np.full(self.n_trials, t + 1, np.int64)
        eta_srv = np.array([float(self.schedule(int(c))) for c in clocks],
                           np.float32)
        if self.eta_local is None:
            eta_loc = eta_srv
        elif callable(self.eta_local):
            eta_loc = np.array(
                [float(self.eta_local(int(c))) for c in clocks], np.float32)
        else:
            eta_loc = np.full(self.n_trials, float(self.eta_local),
                              np.float32)
        return eta_loc, eta_srv

    # ------------------------------------------------------------------ #
    def step(self, t: int, masks: np.ndarray) -> dict:
        """Apply round t to all trials; masks (K, N) bool applied-updates."""
        masks = np.asarray(masks, bool)
        assert masks.shape == (self.n_trials, self.n_clients), masks.shape
        if self.cohort_mode:
            return self.step_cohort(
                t, [np.flatnonzero(m) for m in masks])
        batch = self.batcher.sample_round(t)
        eta_loc, eta_srv = self.learning_rates(t)
        self.rngs, subs = self._split()
        self.state, self.params, metrics = self.round_fn(
            self.state, self.params, batch, jnp.asarray(masks),
            jnp.asarray(eta_loc), jnp.asarray(eta_srv), subs)
        self.hist.record_round(t, metrics)
        return metrics

    def step_scenario(self, t: int) -> dict:
        """Apply round t with availability drawn BY each trial's scenario.

        Dense groups: masks are sampled inside the jitted, vmapped round —
        one program computes K masks, K cohorts of local updates, and K
        server steps. Cohort groups: the scenarios' host surfaces draw the
        same (K, N) masks and the round goes through `step` unchanged.
        """
        if self._scen_samplers is not None:        # cohort: host surface
            masks = np.stack([s.sample(t) for s in self._scen_samplers])
            return self.step(t, masks)
        assert self.scen_round_fn is not None, \
            "construct FleetRunner(scenarios=...) to use step_scenario"
        procs = self._scen_procs
        w = getattr(procs[0], "scan_window", None)
        if w is not None:
            ws = self._scen_win_start
            if ws is None or not ws <= t < ws + w:
                t0 = (t // w) * w
                self.scen_state = procs[0].load_window_fleet(
                    self.scen_state, procs, t0)
                self._scen_win_start = t0
        batch = self.batcher.sample_round(t)
        eta_loc, eta_srv = self.learning_rates(t)
        self.rngs, subs = self._split()
        (self.state, self.params, metrics, self.scen_state,
         _masks) = self.scen_round_fn(
            self.state, self.params, batch, self.scen_state, jnp.int32(t),
            self.scen_keys, jnp.asarray(eta_loc), jnp.asarray(eta_srv),
            subs)
        self.hist.record_round(t, metrics)
        return metrics

    def step_cohort(self, t: int, ids_per_trial: Sequence[np.ndarray]) -> dict:
        """Cohort round for all trials; ids_per_trial[k] are trial k's
        active rows. All trials pad to one shared capacity (the pow-2
        bucket of the largest cohort, or `cohort_capacity`) — pad slots are
        inert, so per-trial results are unchanged by the shared padding."""
        assert self.cohort_mode
        from repro.bank.base import check_unique_ids
        K = self.n_trials
        ids_per_trial = [np.asarray(i, np.int64) for i in ids_per_trial]
        for ids in ids_per_trial:
            check_unique_ids(ids)
        cmax = max((len(i) for i in ids_per_trial), default=0)
        cap = self.cohort_capacity or _pow2_bucket(cmax)
        if cmax > cap:
            # widening is shared by ALL trials (vmap needs one shape), so a
            # pinned capacity no longer matches what per-trial run_fl pads
            # non-overflowing trials to — warn instead of silently breaking
            # the bit-exact cross-path comparison the pin exists for
            import warnings
            warnings.warn(
                f"cohort of {cmax} overflows pinned cohort_capacity="
                f"{self.cohort_capacity}; widening ALL trials to "
                f"{_pow2_bucket(cmax)} — fleet trajectories may no longer "
                "be bit-exact vs sequential runs pinned to the original "
                "capacity", stacklevel=2)
            cap = _pow2_bucket(cmax)
        padded = np.full((K, cap), self.n_clients, np.int64)
        valid = np.zeros((K, cap), bool)
        for k, ids in enumerate(ids_per_trial):
            padded[k, :len(ids)] = ids
            valid[k, :len(ids)] = True
        # pad slots sample client 0's batch (computed then masked), exactly
        # like RoundRunner.step_cohort. Trials share the batcher and the
        # round index, so each distinct client is sampled ONCE for the whole
        # fleet (same (seed, t, i) streams as per-trial sampling), uploaded
        # once, and every trial gathers its (cap, ...) slice on device. The
        # union is padded to a pow-2 bucket so jit traces are reused.
        wanted = np.where(valid, padded, 0)                # (K, cap)
        uniq, inv = np.unique(wanted, return_inverse=True)
        u_pad = _pow2_bucket(len(uniq))
        uniq = np.concatenate([uniq, np.full(u_pad - len(uniq), uniq[0])])
        ubatch = self.batcher.sample_round(t, client_ids=uniq)
        idx = inv.reshape(K, cap).astype(np.int32)
        eta_loc, eta_srv = self.learning_rates(t)
        self.rngs, subs = self._split()
        # paged banks fault the cross-trial union in before the program
        # runs (one residency map shared by all trials); identity otherwise
        prep = getattr(self.algo, "prepare_cohort", None)
        if prep is not None:
            self.state = prep(self.state, padded[valid])
        self.state, self.params, metrics = self.cohort_round_fn(
            self.state, self.params, ubatch, jnp.asarray(idx),
            jnp.asarray(padded), jnp.asarray(valid), jnp.asarray(eta_loc),
            jnp.asarray(eta_srv), subs)
        self.hist.record_round(t, metrics)
        return metrics

    def evaluate(self, t: int, eval_fn: Callable) -> tuple[Any, Any]:
        """eval_fn consumes stacked params -> ((K,) losses, (K,) accs)."""
        el, ea = eval_fn(self.params)
        self.hist.record_eval(t, el, ea)
        return el, ea

    def finalize(self) -> tuple[Any, FleetHistory]:
        """Returns (stacked (K, ...) params, fleet history)."""
        return self.params, self.hist


def fleet_scan_supported(runner: FleetRunner) -> tuple[bool, str]:
    """Can this fleet group execute on the scan-native path? (ok, reason)."""
    if runner.uses_update_clock:
        return False, ("update-clock schedules read per-trial device-side "
                       "counters between rounds; the host cannot precompute "
                       "a chunk of learning rates")
    return True, ""


class FleetScanDriver:
    """Scan-native fleet execution: K trials × T rounds as one program.

    The per-trial scan body (`core.runner.make_scan_round_fn`) is vmapped
    over the trial axis and the result scanned over a chunk of rounds, so
    one `jit(scan(vmap(round)))` launch advances the whole sweep by
    `scan_chunk` rounds — per trial bit-exact against both the per-round
    fleet path and sequential `run_fl` (the body IS the same pure round
    function; tests/test_scan_engine.py). Chunk boundaries snap to eval
    rounds exactly like the sequential scan driver
    (`core.scan_engine.ScanDriver`); τ statistics are not tracked, matching
    the per-round fleet path.
    """

    def __init__(self, runner: FleetRunner, *, scan_chunk: int = 64):
        from repro.core.runner import make_scan_round_fn
        if scan_chunk < 1:
            raise ValueError(f"scan_chunk must be >= 1, got {scan_chunk}")
        self.r = r = runner
        self.scan_chunk = scan_chunk
        self.scenario_mode = r._scen_fn is not None
        # windowed scenarios (trace replay): the stacked (K, W, N) window
        # is re-paged at chunk boundaries via the pre_chunk hook, exactly
        # like the sequential ScanDriver
        self._scan_window = (getattr(r._scen_procs[0], "scan_window", None)
                             if self.scenario_mode else None)
        if self._scan_window is not None and scan_chunk > self._scan_window:
            raise ValueError(
                f"scan_chunk={scan_chunk} exceeds the scenario's carried "
                f"availability window ({self._scan_window} rounds): a chunk "
                "must be coverable by one window. Raise the scenario's "
                "window= or lower scan_chunk")
        self._seg = None
        self._win_start = None
        body = make_scan_round_fn(
            r.model, r.algo, r.batcher.k_steps, r.weight_decay,
            scen_fn=r._scen_fn, cohort=r.cohort_mode)
        if r.cohort_mode:
            self.cap = r.cohort_capacity or _pow2_bucket(r.n_clients)
            # each distinct client's batch crosses host->device ONCE per
            # round (ubatch, shared across trials); trials gather their
            # (cap, ...) slices inside the program — the same dedup the
            # per-round fleet path performs in `cohort_round`
            base = body

            def body(carry, x):
                batch = jax.tree.map(lambda l: l[x["idx"]], x["ubatch"])
                return base(carry, {"batch": batch, "ids": x["ids"],
                                    "valid": x["valid"],
                                    "eta_loc": x["eta_loc"],
                                    "eta_srv": x["eta_srv"]})

            xs_axes = {"ubatch": None, "idx": 0, "ids": 0, "valid": 0,
                       "eta_loc": 0, "eta_srv": 0}
        elif self.scenario_mode:
            xs_axes = {"batch": None, "t": None, "eta_loc": 0, "eta_srv": 0}
        else:
            xs_axes = {"batch": None, "active": 0, "eta_loc": 0,
                       "eta_srv": 0}
        vbody = jax.vmap(body, in_axes=(0, xs_axes))
        # NamedSharding tree matching the carry, set by `_init_carry`
        # before the first `_chunk_fn` trace reads it
        self._carry_shardings = None
        if getattr(r, "mesh", None) is not None:
            # pin the trial-axis placement after every vmapped round, so
            # the donated carry keeps one layout across chunk boundaries
            inner = vbody

            def vbody(carry, x):
                carry, ys = inner(carry, x)
                return (jax.lax.with_sharding_constraint(
                    carry, self._carry_shardings), ys)

        self._chunk_fn = jax.jit(
            lambda carry, xs: jax.lax.scan(vbody, carry, xs),
            donate_argnums=(0,))
        # the upcoming chunk's cross-trial cohort union, stashed by
        # _build_xs for the paged-bank pre_chunk residency hook
        self._last_union = None

    # ------------------------------------------------------------------ #
    def _init_carry(self) -> dict:
        r = self.r
        carry = {"state": r.state, "params": r.params, "rng": r.rngs}
        if self.scenario_mode:
            carry["scen_state"] = r.scen_state
            carry["scen_key"] = r.scen_keys
        if getattr(r, "mesh", None) is not None:
            from jax.sharding import NamedSharding
            from repro.sharding.rules import fleet_carry_specs
            specs = fleet_carry_specs(carry, r.mesh, cfg=r.cfg)
            self._carry_shardings = jax.tree.map(
                lambda s: NamedSharding(r.mesh, s), specs,
                is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
            carry = jax.tree.map(jax.device_put, carry,
                                 self._carry_shardings)
        return carry

    def _writeback(self, carry: dict) -> None:
        r = self.r
        r.state, r.params, r.rngs = (carry["state"], carry["params"],
                                     carry["rng"])
        if self.scenario_mode:
            r.scen_state = carry["scen_state"]

    def _etas(self, t0: int, t1: int) -> tuple[np.ndarray, np.ndarray]:
        pairs = [self.r.learning_rates(t) for t in range(t0, t1)]
        return (np.stack([p[0] for p in pairs]),
                np.stack([p[1] for p in pairs]))     # (L, K) f32

    def _build_xs(self, t0: int, t1: int, parts) -> dict:
        r = self.r
        self._seg = (t0, t1)
        eta_loc, eta_srv = self._etas(t0, t1)
        xs = {"eta_loc": eta_loc, "eta_srv": eta_srv}
        if self.scenario_mode:
            xs["t"] = np.arange(t0, t1, dtype=np.int32)
            xs["batch"] = jax.tree.map(
                lambda *ls: np.stack(ls),
                *[r.batcher.sample_round(t) for t in range(t0, t1)])
            return xs
        samplers = parts if parts is not None else r._scen_samplers
        masks = np.stack([
            np.stack([np.asarray(p.sample(t), bool) for p in samplers])
            for t in range(t0, t1)])                 # (L, K, N)
        if not r.cohort_mode:
            xs["active"] = masks
            xs["batch"] = jax.tree.map(
                lambda *ls: np.stack(ls),
                *[r.batcher.sample_round(t) for t in range(t0, t1)])
            return xs
        from repro.core.scan_engine import pad_cohort
        K, cap = r.n_trials, self.cap
        ids_l, valid_l, uniq_l, idx_l = [], [], [], []
        for j in range(t1 - t0):
            padded = np.empty((K, cap), np.int64)
            valid = np.empty((K, cap), bool)
            for k in range(K):
                padded[k], valid[k] = pad_cohort(
                    np.flatnonzero(masks[j, k]), cap, r.n_clients, t0 + j)
            # pad slots sample client 0's batch, exactly like the per-round
            # paths. Each distinct client is sampled once per round; every
            # trial's (cap, ...) slice is gathered on device inside the
            # scan body (same (seed, t, i) streams as per-trial sampling).
            wanted = np.where(valid, padded, 0)
            uniq, inv = np.unique(wanted, return_inverse=True)
            ids_l.append(padded)
            valid_l.append(valid)
            uniq_l.append(uniq)
            idx_l.append(inv.reshape(K, cap).astype(np.int32))
        # one shared pow-2 width per chunk so the stacked ubatch leaves are
        # rectangular and jit traces are reused across chunks
        u_pad = _pow2_bucket(max(len(u) for u in uniq_l))
        batch_l = []
        for j, uniq in enumerate(uniq_l):
            uniq = np.concatenate(
                [uniq, np.full(u_pad - len(uniq), uniq[0])])
            batch_l.append(r.batcher.sample_round(t0 + j, client_ids=uniq))
        xs["ids"] = np.stack(ids_l)
        xs["valid"] = np.stack(valid_l)
        xs["idx"] = np.stack(idx_l)
        xs["ubatch"] = jax.tree.map(lambda *ls: np.stack(ls), *batch_l)
        self._last_union = np.concatenate(
            [p[v] for p, v in zip(ids_l, valid_l)])
        return xs

    def _pre_chunk(self, carry: dict) -> dict:
        """Host-side streaming between chunks: page the chunk's cross-trial
        union in (cohort mode, paged banks) or re-point the trials' stacked
        availability window at the upcoming chunk (windowed scenarios)."""
        if self.r.cohort_mode:
            prep = getattr(self.r.algo, "prepare_cohort", None)
            if prep is None or self._last_union is None:
                return carry
            return {**carry, "state": prep(carry["state"], self._last_union)}
        w, (t0, t1) = self._scan_window, self._seg
        if (self._win_start is not None and self._win_start <= t0
                and t1 <= self._win_start + w):
            return carry
        procs = self.r._scen_procs
        carry = {**carry, "scen_state": procs[0].load_window_fleet(
            carry["scen_state"], procs, t0)}
        self._win_start = t0
        return carry

    # ------------------------------------------------------------------ #
    def run(self, n_rounds: int, *, parts=None,
            eval_fn: Callable | None = None, eval_every: int = 10,
            verbose: bool = False) -> None:
        """Execute `n_rounds` rounds for all trials, mutating the runner."""
        from repro.core.scan_engine import (_eval_rounds, chunk_bounds,
                                            run_pipelined_chunks)
        r = self.r
        evals = _eval_rounds(n_rounds, eval_every, eval_fn is not None)

        def flush(t0, t1, ys, _carry):
            ys = {k: np.asarray(v) for k, v in ys.items()}
            for j, t in enumerate(range(t0, t1)):
                r.hist.record_round(t, {k: v[j] for k, v in ys.items()})

        def on_sync(t):
            el, ea = r.evaluate(t, eval_fn)
            if verbose:
                print(f"  round {t:5d} loss={np.asarray(el).mean():.4f} "
                      f"acc={np.asarray(ea).mean():.4f}")

        def build_xs(t0, t1):
            # one span: the trials' availability draws are part of it
            with spans.span("batch_assembly"):
                return self._build_xs(t0, t1, parts)

        run_pipelined_chunks(
            self._init_carry(),
            chunk_bounds(n_rounds, self.scan_chunk, evals),
            chunk_fn=self._chunk_fn, build_xs=build_xs,
            writeback=self._writeback, flush=flush,
            sync_rounds=evals, on_sync=on_sync,
            pre_chunk=self._pre_chunk
            if (r.cohort_mode or self._scan_window is not None) else None)


def make_fleet_eval(model, eval_batch: dict) -> Callable:
    """Vmapped eval: stacked params (K, ...) -> (losses (K,), accs (K,))."""
    batch = {k: jnp.asarray(v) for k, v in eval_batch.items()}

    @jax.jit
    def ev(params_stack):
        def one(p):
            loss, _ = model.loss_fn(p, batch)
            return loss, model.accuracy(p, batch)
        return jax.vmap(one)(params_stack)

    return ev


def run_fleet(*, model, batcher, schedule: Callable, n_rounds: int,
              spec: FleetSpec | None = None, algo=None,
              trials: Sequence[Trial] | None = None,
              eta_local: Callable | float | None = None,
              weight_decay: float = 0.0, eval_fn: Callable | None = None,
              eval_every: int = 10, uses_update_clock: bool = False,
              cohort_capacity: int | None = None, mesh=None, cfg=None,
              engine: str = "loop", scan_chunk: int | None = None,
              verbose: bool = False) -> tuple[Any, FleetHistory]:
    """Run T rounds of K independent trials as one vmapped program.

    The K-trial counterpart of `core.runner.run_fl`: pass a `FleetSpec`
    (algo + trials + clock flag), or `algo` + `trials` explicitly.

    Args:
      model, batcher, schedule: shared problem — batcher.sample_round(t)
        yields the round's batch pytree; schedule(t) the server LR for
        each of the `n_rounds` rounds (`eta_local` overrides the client
        rate; `weight_decay` applies to the local steps;
        `uses_update_clock` drives schedules off applied global updates;
        `cohort_capacity` pins the cohort pad width).
      spec: FleetSpec carrying algo/trials/clock/capacity (or pass `algo`
        and `trials` explicitly).
      trials: `Trial` list. Trials with `participation` draw each round's
        (N,) mask on the host exactly as `run_fl` would; trials with
        `scenario` sample availability INSIDE the jitted round for dense
        algorithms (cohort algorithms use the scenario's host surface) —
        no (T, N) trace is ever materialised. One group must be all-
        participation or all-scenario.
      eval_fn: consumes stacked (K, ...) params, returns ((K,) losses,
        (K,) accs) — see `make_fleet_eval`. Runs every `eval_every` rounds.
      mesh, cfg: optional mesh to shard the trial axis over
        (`sharding.rules.fleet_trial_specs`).
      engine: "loop" (default) dispatches one vmapped program per round;
        "scan" compiles `scan_chunk`-round blocks of the whole sweep into
        single `lax.scan` programs (`FleetScanDriver`,
        docs/architecture.md §9) — bit-exact per trial, falling back to
        the loop (with a warning) for update-clock schedules;
        "scan_strict" raises instead of falling back.
      scan_chunk: rounds per compiled scan block (None: the spec's
        `scan_chunk`, else 64).

    Returns:
      (stacked params with leading (K,) axis, `FleetHistory`).
    """
    if spec is not None:
        algo = spec.algo
        trials = spec.trials
        uses_update_clock = spec.uses_update_clock
        cohort_capacity = spec.cohort_capacity or cohort_capacity
        if scan_chunk is None:
            scan_chunk = spec.scan_chunk
    assert algo is not None and trials, "need a FleetSpec or algo + trials"
    if engine not in ("loop", "scan", "scan_strict"):
        raise ValueError(f"unknown engine {engine!r}: expected 'loop', "
                         "'scan', or 'scan_strict'")
    n_scen = sum(tr.scenario is not None for tr in trials)
    if n_scen not in (0, len(trials)):
        raise ValueError("mixing scenario and participation trials in one "
                         "fleet group is not supported")
    runner = FleetRunner(
        model=model, algo=algo, batcher=batcher, schedule=schedule,
        seeds=[tr.seed for tr in trials], eta_local=eta_local,
        weight_decay=weight_decay, uses_update_clock=uses_update_clock,
        cohort_capacity=cohort_capacity,
        labels=[tr.label or f"seed{tr.seed}" for tr in trials],
        mesh=mesh, cfg=cfg,
        scenarios=[tr.scenario for tr in trials] if n_scen else None)
    parts = [tr.participation for tr in trials]
    if engine != "loop":
        ok, why = fleet_scan_supported(runner)
        if ok:
            t0 = time.time()
            FleetScanDriver(
                runner,
                scan_chunk=64 if scan_chunk is None else scan_chunk).run(
                n_rounds, parts=None if n_scen else parts, eval_fn=eval_fn,
                eval_every=eval_every, verbose=verbose)
            runner.hist.wall_time = time.time() - t0
            return runner.finalize()
        if engine == "scan_strict":
            raise ValueError(f"engine='scan_strict': {why}")
        warn_engine_fallback(
            f"engine='scan' unsupported for this fleet ({why}); "
            "falling back to the per-round loop")
    t0 = time.time()
    for t in range(n_rounds):
        if n_scen:
            runner.step_scenario(t)
        else:
            masks = np.stack([np.asarray(p.sample(t), bool) for p in parts])
            runner.step(t, masks)
        if eval_fn is not None and (t % eval_every == 0 or t == n_rounds - 1):
            el, ea = runner.evaluate(t, eval_fn)
            if verbose:
                print(f"  round {t:5d} "
                      f"loss={np.asarray(el).mean():.4f} "
                      f"acc={np.asarray(ea).mean():.4f}")
    runner.hist.wall_time = time.time() - t0
    return runner.finalize()
