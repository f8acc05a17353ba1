"""Host-side FL training loop: participation process + data + algorithm.

The per-round computation (local K-step SGD on every client + algorithm
aggregation) is a single jitted function; the availability mask and minibatch
indices stream in from the host (they are the *environment*, not the model).

`RoundRunner` owns the jitted round step and all history bookkeeping so that
two drivers can share it unchanged:

  * `run_fl`            — the paper's round-synchronous loop (one availability
                          draw per round, no notion of time), and
  * `repro.sim.engine`  — the discrete-event runtime simulator, which decides
                          *when* each round closes and which updates arrived,
                          and stamps every round with simulated seconds.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.local_update import client_updates
from repro.core.participation import TauStats

_FALLBACK_WARNED: set[str] = set()


def warn_engine_fallback(msg: str, *, stacklevel: int = 3) -> None:
    """Emit an engine-fallback warning ONCE per distinct message.

    Sweeps (the scenario atlas, fleet grids, repeated run_fl calls) hit the
    same unsupported configuration hundreds of times; the first warning per
    config is signal, the rest is noise — and `simplefilter("always")`
    environments defeat the stdlib's own per-location dedup. The message
    embeds the config-specific reason, so distinct configs still warn.
    `stacklevel` defaults to 3: one frame for this helper plus the
    stacklevel=2 the inline warnings used, so the warning still points at
    the run_fl / run_fleet caller.
    """
    if msg in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(msg)
    warnings.warn(msg, stacklevel=stacklevel)


def _reset_fallback_warnings() -> None:
    """Forget which fallback warnings fired (test isolation hook)."""
    _FALLBACK_WARNED.clear()


def warn_legacy_threefry(mesh) -> None:
    """Warn once when a >1-device mesh runs under the legacy threefry RNG.

    JAX's default (non-partitionable) threefry lowering generates DIFFERENT
    random bits when its operands are sharded — a `jax.random.uniform`
    inside the round function draws different values on a 2x2 mesh than on
    one device, so jit-native scenario masks and any in-program randomness
    silently depend on the mesh shape. `jax_threefry_partitionable=True`
    makes the bits sharding-invariant (at the cost of differing from the
    legacy single-device stream). The mesh test/benchmark worlds set it
    (tests/conftest.py, docs/architecture.md §13).
    """
    n = getattr(mesh, "size", 1)
    if n <= 1 or getattr(jax.config, "jax_threefry_partitionable", True):
        return
    warn_engine_fallback(
        "mesh= with the legacy threefry RNG: in-program random draws "
        "(jit-native scenario masks, algorithm rng) depend on the mesh "
        "shape; set jax.config.update('jax_threefry_partitionable', True) "
        "for sharding-invariant trajectories")


@dataclass
class FLHistory:
    rounds: list = field(default_factory=list)
    train_loss: list = field(default_factory=list)
    eval_loss: list = field(default_factory=list)
    eval_acc: list = field(default_factory=list)
    n_active: list = field(default_factory=list)
    global_updates: list = field(default_factory=list)
    sim_seconds: list = field(default_factory=list)   # per-round close time
    eval_seconds: list = field(default_factory=list)  # (round, sim_t) per eval
    wall_time: float = 0.0
    tau_bar: float = 0.0
    tau_max: int = 0

    def as_dict(self) -> dict:
        """Plain-dict view of every history field (JSON-serialisable)."""
        return {k: getattr(self, k) for k in
                ("rounds", "train_loss", "eval_loss", "eval_acc", "n_active",
                 "global_updates", "sim_seconds", "eval_seconds", "wall_time",
                 "tau_bar", "tau_max")}

    def record_round(self, t: int, metrics: dict,
                     sim_time: float | None = None) -> None:
        """Append round t's metrics dict (loss, n_active, optional
        global_updates); `sim_time` stamps it with simulated seconds."""
        self.rounds.append(t)
        self.train_loss.append(float(metrics["loss"]))
        self.n_active.append(float(metrics["n_active"]))
        if "global_updates" in metrics:
            self.global_updates.append(float(metrics["global_updates"]))
        if sim_time is not None:
            self.sim_seconds.append(float(sim_time))

    def record_eval(self, t: int, eval_loss: float, eval_acc: float,
                    sim_time: float | None = None) -> None:
        """Append an (round, value) eval point; `sim_time` additionally
        stamps it on the simulated-seconds axis (eval_seconds)."""
        self.eval_loss.append((t, float(eval_loss)))
        self.eval_acc.append((t, float(eval_acc)))
        if sim_time is not None:
            self.eval_seconds.append((t, float(sim_time)))

    def eval_curve(self) -> list[tuple[float, float, float]]:
        """Time-stamped view: (sim_seconds, eval_loss, eval_acc) triples.

        Only meaningful for simulator-driven runs (sim_seconds populated);
        round-synchronous runs fall back to the round index as the time axis.
        """
        times = dict(self.eval_seconds)
        out = []
        for (t, el), (_, ea) in zip(self.eval_loss, self.eval_acc):
            out.append((times.get(t, float(t)), el, ea))
        return out


def _pow2_bucket(c: int) -> int:
    """Smallest power of two >= c — pads cohorts into few jit traces."""
    return 1 << max(int(np.ceil(np.log2(max(c, 1)))), 0)


# --------------------------------------------------------------------------- #
# pure round functions, shared by RoundRunner (jit) and repro.fleet (jit∘vmap)
# --------------------------------------------------------------------------- #

def make_dense_round_fn(model, algo, k_steps: int, weight_decay: float):
    """One dense federated round as a pure function.

    (state, params, batch, active, eta_loc, eta_srv, rng) ->
    (state, params, metrics). RoundRunner jits it; the fleet executor vmaps
    it over a leading trial axis — the SAME function, so the two paths can
    never drift apart.
    """
    def round_fn(state, params, batch, active, eta_loc, eta_srv, rng):
        with jax.named_scope("local_update"):
            updates, losses = client_updates(model.loss_fn, params, batch,
                                             eta_loc, K=k_steps,
                                             weight_decay=weight_decay)
        with jax.named_scope("server_memory"):
            return algo.round_step(state, params, updates, losses, active,
                                   eta_srv, rng)
    return round_fn


def make_cohort_update_fn(model, k_steps: int, weight_decay: float):
    """Compact cohort local updates: (params, batch (C, ...), eta_loc) ->
    (updates (C, ...), losses (C,)). Pure; shared with the fleet executor."""
    def cohort_updates_fn(params, batch, eta_loc):
        with jax.named_scope("local_update"):
            return client_updates(model.loss_fn, params, batch, eta_loc,
                                  K=k_steps, weight_decay=weight_decay)
    return cohort_updates_fn


def apply_mean(params, mean_g, eta_srv):
    """Server step w <- w - η·mean_G (pure; shared with the fleet executor)."""
    return jax.tree.map(
        lambda w, g: (w - eta_srv * g).astype(w.dtype), params, mean_g)


def make_scenario_round_fn(model, algo, k_steps: int, weight_decay: float,
                           scen_fn):
    """One dense round with availability sampled INSIDE the program.

    Wraps `make_dense_round_fn` so the (N,) mask comes from a scenario's
    jit-native surface (`scenarios.AvailabilityProcess.sample_fn`) instead
    of the host: (state, params, batch, scen_state, t, scen_key, eta_loc,
    eta_srv, rng) -> (state, params, metrics, scen_state, mask). `t` is a
    traced int32 scalar (no retrace per round); the returned mask feeds τ
    statistics on the host. The fleet executor vmaps the same composition
    over the trial axis — availability sweeps never materialise a (T, N)
    trace.
    """
    base = make_dense_round_fn(model, algo, k_steps, weight_decay)

    def round_fn(state, params, batch, scen_state, t, scen_key, eta_loc,
                 eta_srv, rng):
        with jax.named_scope("availability"):
            mask, scen_state = scen_fn(scen_key, t, scen_state)
        state, params, metrics = base(state, params, batch, mask, eta_loc,
                                      eta_srv, rng)
        return state, params, metrics, scen_state, mask

    return round_fn


def make_scan_round_fn(model, algo, k_steps: int, weight_decay: float, *,
                       scen_fn=None, cohort: bool = False,
                       track_tau: bool = False):
    """Lift the pure round functions into a `lax.scan` body.

    The body computes ONE federated round and has the scan signature
    ``(carry, xs) -> (carry, ys)``; `repro.core.scan_engine` scans it over a
    chunk of rounds so T rounds compile into one XLA program, and the fleet
    executor vmaps the SAME body over a leading trial axis before scanning —
    per round it is exactly `make_dense_round_fn` / `make_scenario_round_fn`
    / `make_cohort_round_fn`, so scan trajectories are fp32 bit-exact
    against the per-round dispatch loop (tests/test_scan_engine.py).

    Three modes (exactly one):
      * dense mask (default)   — xs carries the host-drawn ``active`` (N,)
        mask per round (legacy participation processes).
      * scenario (`scen_fn`)   — availability is sampled INSIDE the body
        from the jit-native scenario surface; the scenario state threads
        through the carry and xs carries only the round index ``t``. With
        `track_tau`, τ statistics accumulate in the carry ((N,) int32
        current/max τ) and per-round int32 sums ride the ys — no (T, N)
        mask trace is ever materialised.
      * cohort (`cohort=True`) — xs carries the padded cohort (``ids``,
        ``valid``, compact batch); jittable banks only.

    Carry layout: ``{"state", "params", "rng"}`` plus ``{"scen_state",
    "scen_key"}`` in scenario mode and ``{"tau", "tau_max"}`` when
    `track_tau`. ys are the round's metrics dict (plus ``tau_sum`` /
    ``tau_sq_sum``, exact while Σ τ² per round < 2^31).
    """
    assert not (cohort and scen_fn is not None), \
        "cohort scan bodies take host-assembled cohorts, not a scen_fn"
    assert not (track_tau and scen_fn is None), \
        "track_tau is for scenario bodies (mask-mode τ runs on the host)"

    if cohort:
        cohort_round = make_cohort_round_fn(model, algo, k_steps,
                                            weight_decay)

        def body(carry, x):
            rng, sub = jax.random.split(carry["rng"])
            state, params, metrics = cohort_round(
                carry["state"], carry["params"], x["batch"], x["ids"],
                x["valid"], x["eta_loc"], x["eta_srv"], sub)
            return ({"state": state, "params": params, "rng": rng}, metrics)

        return body

    if scen_fn is not None:
        scen_round = make_scenario_round_fn(model, algo, k_steps,
                                            weight_decay, scen_fn)

        def body(carry, x):
            rng, sub = jax.random.split(carry["rng"])
            state, params, metrics, scen_state, mask = scen_round(
                carry["state"], carry["params"], x["batch"],
                carry["scen_state"], x["t"], carry["scen_key"],
                x["eta_loc"], x["eta_srv"], sub)
            out = {"state": state, "params": params, "rng": rng,
                   "scen_state": scen_state, "scen_key": carry["scen_key"]}
            if track_tau:
                tau = jnp.where(mask, 0, carry["tau"] + 1)
                out["tau"] = tau
                out["tau_max"] = jnp.maximum(carry["tau_max"], tau)
                metrics = dict(metrics, tau_sum=jnp.sum(tau),
                               tau_sq_sum=jnp.sum(tau * tau))
            return out, metrics

        return body

    base = make_dense_round_fn(model, algo, k_steps, weight_decay)

    def body(carry, x):
        rng, sub = jax.random.split(carry["rng"])
        state, params, metrics = base(
            carry["state"], carry["params"], x["batch"], x["active"],
            x["eta_loc"], x["eta_srv"], sub)
        return ({"state": state, "params": params, "rng": rng}, metrics)

    return body


def make_cohort_round_fn(model, algo, k_steps: int, weight_decay: float):
    """One whole cohort round (local updates + bank scatter + server step)
    as a pure function — jittable banks only.

    RoundRunner jits it; the fleet executor runs the structurally identical
    batched composition. Keeping BOTH paths single fused programs is what
    makes them bit-identical per trial: XLA's fp32 fusion decisions depend
    on jit boundaries, so the sequential path must not split the round into
    separate dispatches the vmapped path fuses.
    """
    updates_fn = make_cohort_update_fn(model, k_steps, weight_decay)

    def cohort_round(state, params, batch, padded, valid, eta_loc, eta_srv,
                     rng):
        updates, losses = updates_fn(params, batch, eta_loc)
        with jax.named_scope("server_memory"):
            state, mean_g, metrics = algo.round_step_cohort(
                state, padded, valid, updates, losses, rng=rng)
            params = apply_mean(params, mean_g, eta_srv)
        return state, params, metrics

    return cohort_round


class RoundRunner:
    """One jitted federated round + bookkeeping, shared across drivers.

    The driver decides which mask of client updates is applied each round
    (availability in the synchronous loop; arrivals in the simulator) and may
    stamp each round with a simulated-seconds timestamp.

    Two round paths, selected by the algorithm:

      * dense (default)             — `client_updates` vmaps over ALL N
        clients and `algo.round_step` consumes the (N, ...) update array;
      * cohort (`algo.cohort_based`) — only the active cohort's batches are
        sampled and updated: compact (C, ...) leaves where C is |A(t)| padded
        to a power-of-two bucket (or `cohort_capacity`), then applied through
        the algorithm's memory bank. Pad slots carry valid=False and point at
        the bank's dummy row `n_clients`. O(|A|·d) per round instead of
        O(N·d); both `run_fl` and `sim.engine` drive it unchanged via
        `step(t, mask)`, and million-client drivers can call
        `step_cohort(t, ids)` directly to skip O(N) mask work entirely.
    """

    def __init__(self, *, model, algo, batcher, schedule: Callable,
                 eta_local: Callable | float | None = None,
                 weight_decay: float = 0.0, seed: int = 0,
                 params=None, uses_update_clock: bool = False,
                 cohort_capacity: int | None = None, scenario=None):
        self.model = model
        self.algo = algo
        self.batcher = batcher
        self.schedule = schedule
        self.eta_local = eta_local
        self.weight_decay = weight_decay
        self.uses_update_clock = uses_update_clock
        self.cohort_capacity = cohort_capacity
        self.rng = jax.random.PRNGKey(seed)
        self.params = model.init(self.rng) if params is None else params
        self.n_clients = batcher.n_clients
        self.state = algo.init_state(self.params, self.n_clients)
        # strict=False: simulator round policies (Deadline) legitimately
        # drop round-0 responders — the init convention applies there
        self.stats = TauStats(self.n_clients, strict=False)
        self.hist = FLHistory()
        self.cohort_mode = getattr(algo, "cohort_based", False)
        self._init_scenario(scenario, weight_decay)

        if self.cohort_mode:
            self.cohort_updates_fn = jax.jit(make_cohort_update_fn(
                model, batcher.k_steps, weight_decay))
            self.apply_mean_fn = jax.jit(apply_mean)
            self.round_fn = None
            # jittable banks get the whole round as ONE program (fewer
            # dispatches, and bit-identical to the vmapped fleet path);
            # host-offloaded banks keep the split updates/scatter/apply path
            if getattr(getattr(algo, "bank", None), "jittable", False):
                self.cohort_round_fn = jax.jit(
                    make_cohort_round_fn(model, algo, batcher.k_steps,
                                         weight_decay),
                    donate_argnums=(0,))
            else:
                self.cohort_round_fn = None
        else:
            self.round_fn = jax.jit(make_dense_round_fn(
                model, algo, batcher.k_steps, weight_decay))

    def _init_scenario(self, scenario, weight_decay: float) -> None:
        """Wire a `repro.scenarios` scenario (or bare process) in.

        Dense algorithms get the jit-native surface: availability is
        sampled inside the jitted round (`make_scenario_round_fn`), keyed
        by the scenario's own PRNG stream. Cohort algorithms need the mask
        on the host to assemble compact batches, so they fall back to the
        scenario's host surface — identical masks either way.
        """
        self.scenario_round_fn = None
        self._scen_sampler = None
        if scenario is None:
            self.scen_process = None
            return
        from repro.scenarios.base import as_process
        proc = as_process(scenario)
        assert proc.n == self.n_clients, (proc.n, self.n_clients)
        self.scen_process = proc
        if self.cohort_mode:
            self._scen_sampler = proc.host_sampler()
        else:
            self.scenario_round_fn = jax.jit(
                make_scenario_round_fn(self.model, self.algo,
                                       self.batcher.k_steps, weight_decay,
                                       proc.sample_fn()),
                donate_argnums=(0,))
            self.scen_state = proc.init_state()
            self.scen_key = proc.key
            # windowed processes (trace replay) carry only `window` rounds
            # of masks in scen_state; the loop engine re-pages between
            # rounds (the scan engine uses its pre_chunk hook instead).
            # None origin = unknown coverage, load before first use.
            self._scen_win_start = (
                0 if getattr(proc, "scan_window", None) is not None
                else None)

    def learning_rates(self, t: int) -> tuple[float, float]:
        """η_local, η_server for round t (update-clock aware)."""
        if self.uses_update_clock and "t_updates" in self.state:
            clock = int(self.state["t_updates"]) + 1
        else:
            clock = t + 1
        eta_srv = float(self.schedule(clock))
        if self.eta_local is None:
            eta_loc = eta_srv
        elif callable(self.eta_local):
            eta_loc = float(self.eta_local(clock))
        else:
            eta_loc = float(self.eta_local)
        return eta_loc, eta_srv

    def step(self, t: int, active: np.ndarray,
             sim_time: float | None = None) -> dict:
        """Apply one round with `active` (N,) bool as the applied-update
        mask; `sim_time` stamps it with simulated seconds. Returns the
        round's metrics dict."""
        self.stats.update(np.asarray(active, bool), sim_time=sim_time)
        if self.cohort_mode:
            ids = np.flatnonzero(np.asarray(active, bool))
            return self.step_cohort(t, ids, sim_time=sim_time)
        batch = self.batcher.sample_round(t)
        eta_loc, eta_srv = self.learning_rates(t)
        self.rng, sub = jax.random.split(self.rng)
        self.state, self.params, metrics = self.round_fn(
            self.state, self.params, batch, jnp.asarray(active),
            jnp.float32(eta_loc), jnp.float32(eta_srv), sub)
        self.hist.record_round(t, metrics, sim_time=sim_time)
        return metrics

    def step_scenario(self, t: int, sim_time: float | None = None) -> dict:
        """Apply one round with availability drawn BY the scenario.

        Dense path: the mask is sampled inside the jitted round function
        (device-side, no host trace) and returned only for τ statistics.
        Cohort path: the scenario's host surface draws the same mask and
        the round goes through `step` unchanged.
        """
        assert self.scen_process is not None, \
            "construct RoundRunner(scenario=...) to use step_scenario"
        if self.scenario_round_fn is None:        # cohort: host surface
            return self.step(t, self._scen_sampler.sample(t),
                             sim_time=sim_time)
        w = getattr(self.scen_process, "scan_window", None)
        if w is not None:
            ws = self._scen_win_start
            if ws is None or not ws <= t < ws + w:
                t0 = (t // w) * w
                self.scen_state = self.scen_process.load_window(
                    self.scen_state, t0)
                self._scen_win_start = t0
        batch = self.batcher.sample_round(t)
        eta_loc, eta_srv = self.learning_rates(t)
        self.rng, sub = jax.random.split(self.rng)
        (self.state, self.params, metrics, self.scen_state,
         mask) = self.scenario_round_fn(
            self.state, self.params, batch, self.scen_state, jnp.int32(t),
            self.scen_key, jnp.float32(eta_loc), jnp.float32(eta_srv), sub)
        self.stats.update(np.asarray(mask, bool), sim_time=sim_time)
        self.hist.record_round(t, metrics, sim_time=sim_time)
        return metrics

    def step_cohort(self, t: int, ids: np.ndarray,
                    sim_time: float | None = None) -> dict:
        """Apply one O(|A|·d) cohort round; `ids` are the active client
        rows, `sim_time` the optional simulated-seconds stamp.

        Called directly (million-client drivers), τ statistics are skipped —
        TauStats is itself O(N) per round. `step` keeps them.
        """
        assert self.cohort_mode, "step_cohort needs a cohort_based algorithm"
        from repro.bank.base import check_unique_ids
        ids = np.asarray(ids, np.int64)
        check_unique_ids(ids)    # duplicates would corrupt the bank's G_sum
        c = len(ids)
        cap = self.cohort_capacity or _pow2_bucket(c)
        if c > cap:          # stochastic overflow past the configured capacity
            cap = _pow2_bucket(c)
        padded = np.full(cap, self.n_clients, np.int64)   # pad -> dummy row
        padded[:c] = ids
        valid = np.zeros(cap, bool)
        valid[:c] = True
        # pad slots still need *some* real client's batch shape; row 0's
        # content is computed then discarded by the valid mask
        batch = self.batcher.sample_round(
            t, client_ids=np.where(valid, padded, 0))
        eta_loc, eta_srv = self.learning_rates(t)
        self.rng, sub = jax.random.split(self.rng)
        # paged banks fault this round's rows in before the jitted program
        # runs (identity for every other backend)
        prep = getattr(self.algo, "prepare_cohort", None)
        if prep is not None:
            self.state = prep(self.state, padded[valid])
        if self.cohort_round_fn is not None:
            self.state, self.params, metrics = self.cohort_round_fn(
                self.state, self.params, batch, jnp.asarray(padded),
                jnp.asarray(valid), jnp.float32(eta_loc),
                jnp.float32(eta_srv), sub)
        else:
            updates, losses = self.cohort_updates_fn(self.params, batch,
                                                     jnp.float32(eta_loc))
            self.state, mean_g, metrics = self.algo.round_step_cohort(
                self.state, padded, valid, updates, losses, rng=sub)
            self.params = self.apply_mean_fn(self.params, mean_g,
                                             jnp.float32(eta_srv))
        self.hist.record_round(t, metrics, sim_time=sim_time)
        return metrics

    def evaluate(self, t: int, eval_fn: Callable,
                 sim_time: float | None = None) -> tuple[float, float]:
        """Run `eval_fn(params) -> (loss, acc)` and record it at round t."""
        el, ea = eval_fn(self.params)
        self.hist.record_eval(t, el, ea, sim_time=sim_time)
        return float(el), float(ea)

    def finalize(self) -> tuple[Any, FLHistory]:
        """Seal τ statistics into the history; returns (params, history)."""
        self.hist.tau_bar = self.stats.tau_bar
        self.hist.tau_max = self.stats.tau_max
        return self.params, self.hist


def run_fl(*, model, algo, batcher, schedule: Callable, n_rounds: int,
           participation=None, scenario=None, sim=None,
           eta_local: Callable | float | None = None,
           weight_decay: float = 0.0, seed: int = 0,
           eval_fn: Callable | None = None, eval_every: int = 10,
           params=None, uses_update_clock: bool = False,
           cohort_capacity: int | None = None, engine: str = "loop",
           scan_chunk: int = 64, checkpoint=None, mesh=None, cfg=None,
           return_state: bool = False,
           verbose: bool = False) -> tuple[Any, FLHistory]:
    """Run T round-synchronous rounds of federated training.

    Availability comes from exactly one of:
      * participation — legacy host process (``.sample(t) -> (N,) bool``);
        one draw per round on the host, mask streamed into the jitted round.
      * scenario — a `repro.scenarios` Scenario/process; dense algorithms
        sample the mask INSIDE the jitted round (jit-native surface),
        cohort algorithms use the scenario's host surface (same masks).

    `sim` switches the run onto the simulated wall clock: pass a
    `repro.sim.compiled.SimSpec` (server policy + latency model + temporal
    config) and rounds open/close in simulated seconds under that policy —
    the applied-update mask becomes the policy's arrival decision instead
    of the raw availability draw. Under ``engine="scan"`` the compiled
    simulator (`repro.sim.compiled.SimScanDriver`) runs the whole event
    flow in-program when `sim_scan_supported` says yes; otherwise (and
    always under ``engine="loop"``) the discrete-event heap engine
    (`repro.sim.engine.FedSimEngine`) drives it, with a warning naming the
    blocker under ``engine="scan"`` and a raise under ``"scan_strict"``.

    `model` supplies init/loss/accuracy; batcher.sample_round(t) -> batch
    pytree with leaves (N, K, mb, ...); schedule(t) -> server learning rate
    η_t for each of the `n_rounds` rounds (`eta_local` overrides the
    client-side rate; the paper uses the same for both). `seed` keys model
    init and the round RNG (or pass `params` to skip init);
    `weight_decay` applies to the K local SGD steps. `eval_fn(params) ->
    (loss, acc)` runs every `eval_every` rounds; `uses_update_clock` drives
    schedules off applied global updates instead of rounds
    (FedAvgSampling-style). cohort_capacity pins the cohort-path pad width
    (default: per-round pow-2 buckets). Pad slots are mathematically inert
    either way, but fp32 reduction *grouping* depends on the padded
    length — pin the capacity when comparing trajectories bit-for-bit
    across drivers (see tests/test_fleet).

    `engine` selects the execution strategy (docs/architecture.md §9):
      * "loop" — one jitted dispatch per round (the historical path).
      * "scan" — `repro.core.scan_engine`: rounds are compiled into
        `lax.scan` programs of up to `scan_chunk` rounds each, fp32
        bit-exact against the loop. Configurations the scan cannot express
        (update-clock schedules, host-offloaded banks) fall back to the
        loop with a warning.
      * "scan_strict" — like "scan" but unsupported configurations raise.

    `checkpoint` (a `repro.checkpoint.CheckpointSpec`) wires long-horizon
    durability: the scan engine snapshots the FULL run state (params,
    algorithm state incl. bank pages + host residency bookkeeping, round
    RNG, scenario/trace cursor, τ stats, history) through
    `checkpoint.run_state.save_run` after every `checkpoint.every`
    completed rounds, atomically. With ``checkpoint.resume=True`` the
    latest snapshot in ``checkpoint.dir`` is restored and the run
    continues from its round — fp32 bit-exact against the uninterrupted
    run (docs/operations.md runbook, pinned in tests/test_trace_replay).
    Scan engines only: snapshots ride chunk boundaries, so ``engine``
    must not be "loop", and a configuration the scan cannot express
    raises rather than silently dropping durability.

    `mesh` (scan engines only) places the scan carry under explicit
    shardings (`sharding.rules.scan_carry_specs`): params by the model
    rules when `cfg` (an `ArchConfig`) is given, MIFA's update array /
    bank rows / scenario chain state with the client axis over the mesh's
    data axes — one compiled program, data-parallel over clients and
    model-parallel over d (docs/architecture.md §13). A `DenseBank`
    constructed without its own mesh inherits `mesh`/`cfg` so its rows
    pad to divide the data extent (`sharding.rules.padded_bank_rows`).
    Sharded client-axis reductions group partial sums per device, so
    trajectories match single-device runs to fp32 reduction-order
    tolerance, not bitwise (tests/test_sharded_scan.py pins both).

    `return_state=True` returns ``(params, history, state)``: the
    algorithm's state as the last round left it (MIFA's update array, a
    bank's rows and G_sum), where the run placed it — under `mesh`, with
    the carry's shardings. Not for simulated runs.
    """
    if (participation is None) == (scenario is None):
        raise ValueError("pass exactly one of participation= or scenario=")
    if return_state and sim is not None:
        raise ValueError("return_state= is not supported for simulated runs")
    if engine not in ("loop", "scan", "scan_strict"):
        raise ValueError(f"unknown engine {engine!r}: expected 'loop', "
                         "'scan', or 'scan_strict'")
    if checkpoint is not None:
        if sim is not None:
            raise ValueError("checkpoint= is not supported for simulated "
                             "runs (the compiled simulator carry holds "
                             "event-queue state with no snapshot schema)")
        if engine == "loop":
            raise ValueError("checkpoint= rides the scan engine's chunk "
                             "boundaries; pass engine='scan' (or "
                             "'scan_strict')")
    if mesh is not None:
        if engine == "loop":
            raise ValueError("mesh= places the scan carry; it has no effect "
                             "under engine='loop' — pass engine='scan'")
        if sim is not None:
            raise ValueError("mesh= is not supported for simulated runs "
                             "(the compiled simulator carry has no "
                             "sharding rules yet)")
        warn_legacy_threefry(mesh)
        # banks build their rows inside RoundRunner.__init__ (algo.init_state
        # -> bank.init), so a mesh-less bank inherits the run's mesh here
        bank = getattr(algo, "bank", None)
        if (bank is not None and hasattr(bank, "mesh")
                and bank.mesh is None):
            bank.mesh = mesh
            bank.cfg = cfg if getattr(bank, "cfg", None) is None else bank.cfg
    runner = RoundRunner(model=model, algo=algo, batcher=batcher,
                         schedule=schedule, eta_local=eta_local,
                         weight_decay=weight_decay, seed=seed, params=params,
                         uses_update_clock=uses_update_clock,
                         cohort_capacity=cohort_capacity, scenario=scenario)

    def finish():
        out = runner.finalize()
        return out + (runner.state,) if return_state else out

    if sim is not None:
        from repro.sim.compiled import run_sim_scan, sim_scan_supported
        from repro.sim.engine import FedSimEngine
        if engine != "loop":
            ok, why = sim_scan_supported(runner, sim)
            if ok:
                return run_sim_scan(runner, sim, n_rounds,
                                    scan_chunk=scan_chunk, eval_fn=eval_fn,
                                    eval_every=eval_every, verbose=verbose)
            if engine == "scan_strict":
                raise ValueError(f"engine='scan_strict': {why}")
            warn_engine_fallback(
                f"engine='scan' unsupported for this simulated "
                f"configuration ({why}); falling back to the "
                "discrete-event heap engine")
        part = participation if participation is not None \
            else runner.scen_process.host_sampler()
        eng = FedSimEngine(runner, sim.policy, part, sim.latency, sim.config,
                           seed=seed)
        t0 = time.time()
        params, hist = eng.run(n_rounds, eval_fn=eval_fn,
                               eval_every=eval_every)
        hist.wall_time = time.time() - t0
        return params, hist
    start_round = 0
    if checkpoint is not None and checkpoint.resume:
        from repro.checkpoint.run_state import (fast_forward_sampler,
                                                restore_run)
        start_round = restore_run(runner, checkpoint)
        if start_round:
            # host availability streams are not in the snapshot; replay
            # them through the restored rounds so the remaining rounds
            # draw exactly the uninterrupted run's masks
            fast_forward_sampler(participation, start_round)
            fast_forward_sampler(runner._scen_sampler, start_round)
        if start_round >= n_rounds:
            return finish()
    if engine != "loop":
        from repro.core.scan_engine import ScanDriver, scan_supported
        ok, why = scan_supported(runner)
        if ok:
            t0 = time.time()
            ScanDriver(runner, scan_chunk=scan_chunk, mesh=mesh,
                       cfg=cfg).run(
                n_rounds, participation=participation, eval_fn=eval_fn,
                eval_every=eval_every, verbose=verbose,
                checkpoint=checkpoint, start_round=start_round)
            runner.hist.wall_time = time.time() - t0
            return finish()
        if engine == "scan_strict":
            raise ValueError(f"engine='scan_strict': {why}")
        if checkpoint is not None:
            raise ValueError(
                f"checkpoint= needs the scan engine, but this "
                f"configuration cannot scan ({why}); refusing to fall "
                "back and silently drop durability")
        if mesh is not None:
            raise ValueError(f"engine='scan' with mesh= cannot fall back "
                             f"to the per-round loop (the loop ignores "
                             f"mesh); blocker: {why}")
        warn_engine_fallback(
            f"engine='scan' unsupported for this configuration "
            f"({why}); falling back to the per-round loop")
    t0 = time.time()
    for t in range(n_rounds):
        if scenario is not None:
            metrics = runner.step_scenario(t)
            n_active = int(metrics["n_active"])
        else:
            active = participation.sample(t)
            runner.step(t, active)
            n_active = int(active.sum())
        if eval_fn is not None and (t % eval_every == 0 or t == n_rounds - 1):
            el, ea = runner.evaluate(t, eval_fn)
            if verbose:
                print(f"  round {t:5d} train={runner.hist.train_loss[-1]:.4f} "
                      f"eval={el:.4f} acc={ea:.4f} active={n_active}")
    runner.hist.wall_time = time.time() - t0
    return finish()
