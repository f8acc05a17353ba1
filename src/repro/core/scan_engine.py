"""Whole-run scan engine: compile T federated rounds into one XLA program.

The per-round loop (`run_fl`'s historical path) pays one jitted dispatch,
one host→device batch upload, and one Python iteration per round. On the
tiny models where availability studies actually run (the paper's Fig. 2,
correlated-availability grids), that dispatch overhead dominates compute by
an order of magnitude. This module fuses the run itself: the pure round
functions of `core.runner` become a `lax.scan` body
(`runner.make_scan_round_fn`) and T rounds execute as ⌈T/scan_chunk⌉
compiled programs.

Chunking (`scan_chunk`): the scan consumes stacked per-round inputs
(batches, masks, learning rates), so an unchunked T-round program would
hold T rounds of batches on device at once and could only report history
at the very end. A batcher that holds its client rows in one table
(`ClientBatcher.rows()`) ships only row indices instead: the table is
uploaded once per driver and each scan step gathers its round's batch
from it on the device. Chunks bound that memory by the chunk length, flush
`FLHistory` every chunk boundary, and give eval/logging host points — and
the chunk carry is donated, so params/state buffers are reused in place
across chunks. Chunk boundaries additionally snap to eval rounds so
`eval_fn` runs at exactly the rounds the loop engine would evaluate.

Carry / ys layout (see `make_scan_round_fn`): the carry is
``{"state", "params", "rng"}`` plus the scenario's ``{"scen_state",
"scen_key"}`` and the τ accumulators ``{"tau", "tau_max"}``; the stacked
ys are the per-round metrics `FLHistory` records, plus per-round τ sums so
`TauStats` can be reconstructed without materialising a (T, N) mask trace.

What falls back to the loop (`scan_supported`): update-clock schedules
(the host schedule callable would need the device-side applied-update
counter every round) and host-offloaded banks (`HostBank`,
`Int8PagedBank` — their rows live outside jit by design). `run_fl`
warns and loops for these under ``engine="scan"`` and raises under
``engine="scan_strict"``. `PagedDeviceBank` is NOT excluded: its page
table is a jnp array in the scan carry, and its host↔device page
streaming runs at chunk boundaries through the ``pre_chunk`` hook of
`run_pipelined_chunks` — each chunk's cohort union is paged in while
the host still owns the carry, so N=10⁶ runs scan with bounded device
bytes.

Bit-exactness: per round the scan body IS the loop's jitted round function,
and `jax.random.split` / `fold_in` are deterministic bitwise, so scan
trajectories are fp32 bit-exact against the loop for dense algorithms and
for jittable banks with a pinned `cohort_capacity` (the loop's per-round
pow-2 cohort buckets vary with |A(t)|; a scan program has one shape, so the
engine pins unpinned cohort runs to the N-client bucket — pin the capacity
on both paths when comparing, per `run_fl`'s docstring).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.runner import RoundRunner, _pow2_bucket, make_scan_round_fn


def scan_supported(runner: RoundRunner) -> tuple[bool, str]:
    """Can this runner's configuration execute as a scan? (ok, reason)."""
    if runner.uses_update_clock:
        return False, ("update-clock schedules read the device-side "
                       "applied-update counter between rounds; the host "
                       "cannot precompute a chunk of learning rates")
    bank = getattr(runner.algo, "bank", None)
    if runner.cohort_mode and not getattr(bank, "jittable", False):
        return False, (
            f"{type(bank).__name__} is host-offloaded: its rows live "
            "outside jit by design and cannot ride a scan carry; scan-"
            "capable banks are DenseBank ('dense') and PagedDeviceBank "
            "('paged_device', bounded device bytes via a jit-native page "
            "table)")
    return True, ""


def _eval_rounds(n_rounds: int, eval_every: int, has_eval: bool) -> set:
    """The rounds after which the loop engine would run eval_fn."""
    if not has_eval:
        return set()
    pts = {t for t in range(n_rounds) if t % eval_every == 0}
    pts.add(n_rounds - 1)
    return pts


def chunk_bounds(n_rounds: int, scan_chunk: int, eval_rounds: set,
                 start: int = 0) -> list[tuple[int, int]]:
    """[t0, t1) segments over rounds [start, n_rounds): cut every
    `scan_chunk` rounds AND after each eval/sync round, so evals land
    exactly where the loop engine runs them. `start` > 0 is the resume
    case (checkpoint restore): the chunk grid stays anchored at round 0,
    so a resumed run shares every boundary past `start` with the
    uninterrupted run — and by chunk-boundary invariance the extra cut at
    `start` itself does not perturb the trajectory."""
    if scan_chunk < 1:
        raise ValueError(f"scan_chunk must be >= 1, got {scan_chunk}")
    cuts = {start, n_rounds}
    cuts.update(range(0, n_rounds, scan_chunk))
    cuts.update(t + 1 for t in eval_rounds if t < n_rounds)
    edges = sorted(c for c in cuts if start <= c <= n_rounds)
    return list(zip(edges[:-1], edges[1:]))


def _stack(trees: list) -> dict:
    """Stack a list of per-round pytrees along a new leading axis."""
    with spans.span("stack"):
        return jax.tree.map(lambda *xs: np.stack(xs), *trees)


def _host_bytes(tree) -> int:
    """Bytes the host arrays of `tree` take on the device (64-bit values
    narrowed as JAX narrows them); arrays already on a device count 0."""
    return sum(x.size * jax.dtypes.canonicalize_dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree) if isinstance(x, np.ndarray))


def _row_table(batcher, mesh) -> dict | None:
    """The batcher's client-row table where the scan can keep it on the
    device: a batcher with a row surface (`sample_round_rows`), and a table
    under a quarter of the device's memory where the backend reports it."""
    if not hasattr(batcher, "sample_round_rows"):
        return None
    rows = batcher.rows()
    dev = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit is not None and 4 * _host_bytes(rows) >= limit:
        return None
    return rows


def _gather_round(x: dict, rows: dict) -> dict:
    """One round's xs with its batch gathered from the resident row table
    at the round's (clients, K, mb) `batch_rows`.

    The gather writes step-major (K, clients, mb) rows, the order the
    local update's scan over K reads them; the round function gets the
    (clients, K, mb) view, and XLA cancels the two transposes, so the
    batch is written once."""
    x = dict(x)
    idx = jnp.swapaxes(x.pop("batch_rows"), 0, 1)
    x["batch"] = jax.tree.map(lambda col: jnp.swapaxes(col[idx], 0, 1),
                              rows)
    return x


def pad_cohort(ids: np.ndarray, cap: int, n_clients: int,
               round_t: int) -> tuple[np.ndarray, np.ndarray]:
    """Pad one cohort's ids to the scan capacity: (padded, valid).

    Pad slots point at the bank's dummy row `n_clients` with valid=False,
    exactly like `RoundRunner.step_cohort`. A scan program has ONE static
    shape, so a cohort overflowing `cap` raises instead of widening per
    round the way the loop engine's pow-2 buckets do.
    """
    if len(ids) > cap:
        raise ValueError(
            f"round {round_t}: cohort of {len(ids)} overflows the scan "
            f"capacity {cap}; raise cohort_capacity (a scan program cannot "
            "widen per round the way the loop engine's pow-2 buckets do)")
    padded = np.full(cap, n_clients, np.int64)
    padded[:len(ids)] = ids
    valid = np.zeros(cap, bool)
    valid[:len(ids)] = True
    return padded, valid


def run_pipelined_chunks(carry, segments, *, chunk_fn, build_xs, writeback,
                         flush, sync_rounds=frozenset(), on_sync=None,
                         pre_chunk=None):
    """Software-pipelined chunk execution, shared by `ScanDriver` and
    `fleet.FleetScanDriver`.

    Each chunk dispatches asynchronously and is flushed one iteration
    late, so the NEXT chunk's host-side xs assembly overlaps the device
    executing the current one; the pending flush always completes before
    the pending carry is donated back into `chunk_fn`. Rounds in
    `sync_rounds` (eval boundaries) force the flush and then call
    `on_sync(t)` with the chunk's results on the host.

    Callback contract: ``build_xs(t0, t1)`` assembles a chunk's stacked
    inputs; ``chunk_fn(carry, xs) -> (carry, ys)`` is the jitted scan;
    ``writeback(carry)`` publishes the (not-yet-materialised) carry to the
    runner; ``flush(t0, t1, ys, carry)`` blocks on the chunk's results and
    records history. ``pre_chunk(carry) -> carry``, when given, runs after
    ``build_xs`` (which knows the upcoming chunk's working set) and right
    before the chunk dispatches — the streaming hook paged banks use to
    fault the chunk union's pages in while the host still owns the carry;
    its device reads block on the previous chunk only when pages actually
    move. Returns the final carry.

    The loop is the root span ``run`` (`repro.spans`): it counts
    ``rounds``, ``dispatch`` times the ``chunk_fn`` call (with NumPy xs,
    handing them to the device, and the enqueue; a `ScanDriver`'s first
    call also uploads its row table) and adds the xs bytes to
    ``h2d_bytes``, ``flush`` times each flush (the wait on the device
    included) and adds the ys bytes it reads to ``d2h_bytes``.
    """
    def flush_spanned(pending):
        with spans.span("flush"):
            spans.count("d2h_bytes",
                        sum(y.nbytes for y in jax.tree.leaves(pending[2])))
            flush(*pending)

    pending = None
    with spans.span("run", root=True):
        for t0, t1 in segments:
            xs = build_xs(t0, t1)
            if pending is not None:
                flush_spanned(pending)
            if pre_chunk is not None:
                carry = pre_chunk(carry)
            with spans.span("dispatch"):
                spans.count("h2d_bytes", _host_bytes(xs))
                carry, ys = chunk_fn(carry, xs)
            spans.count("rounds", t1 - t0)
            writeback(carry)
            pending = (t0, t1, ys, carry)
            if (t1 - 1) in sync_rounds:
                flush_spanned(pending)
                pending = None
                on_sync(t1 - 1)
        if pending is not None:
            flush_spanned(pending)
    return carry


class ScanDriver:
    """Drives a `RoundRunner` through T rounds as chunked scan programs.

    Constructed by `run_fl(engine="scan")` after `scan_supported` says yes.
    Reuses the runner's init (params, algorithm state, scenario wiring,
    RNG stream) so the trajectory is the one the loop engine would produce;
    on `run` completion the runner's state/params/history/τ stats are
    written back, and `runner.finalize()` works unchanged.
    """

    def __init__(self, runner: RoundRunner, *, scan_chunk: int = 64,
                 mesh=None, cfg=None):
        if scan_chunk < 1:
            raise ValueError(f"scan_chunk must be >= 1, got {scan_chunk}")
        self.r = runner
        self.scan_chunk = scan_chunk
        self.mesh = mesh
        self.cfg = cfg
        # NamedSharding tree matching the carry, set by `_init_carry`
        # (which runs before the first `_chunk_fn` trace — the closure
        # below reads it at trace time, not at definition time)
        self._carry_shardings = None
        r = runner
        self.scenario_mode = (r.scen_process is not None
                              and not r.cohort_mode)
        scen_fn = r.scen_process.sample_fn() if self.scenario_mode else None
        body = make_scan_round_fn(
            r.model, r.algo, r.batcher.k_steps, r.weight_decay,
            scen_fn=scen_fn, cohort=r.cohort_mode,
            track_tau=self.scenario_mode)
        if mesh is not None:
            # re-pin the carry's placement after every round: without the
            # constraint XLA is free to resharded intermediates, and the
            # donated carry must keep one layout across chunk boundaries
            inner = body

            def body(carry, x):
                carry, ys = inner(carry, x)
                return (jax.lax.with_sharding_constraint(
                    carry, self._carry_shardings), ys)

        # a batcher with a row table ships only row indices a round; the
        # table rides as a third, non-donated argument (never a jit
        # constant), and each scan step gathers its own round from it
        self._rows = _row_table(r.batcher, mesh)
        self._rows_dev = None

        def chunk(carry, xs, rows):
            if rows is None:
                return jax.lax.scan(body, carry, xs)
            return jax.lax.scan(
                lambda c, x: body(c, _gather_round(x, rows)), carry, xs)

        self._scan = jax.jit(chunk, donate_argnums=(0,))
        self._chunk_fn = lambda carry, xs: self._scan(carry, xs,
                                                      self._device_rows())
        if r.cohort_mode:
            # one static shape for the whole program: unpinned runs pad to
            # the N-client bucket (the loop's per-round buckets vary)
            self.cap = r.cohort_capacity or _pow2_bucket(r.n_clients)
        # the union of the upcoming chunk's cohorts, stashed by _build_xs
        # for the paged-bank pre_chunk residency hook
        self._last_union = None
        # windowed scenarios (trace replay): the carried availability
        # window is re-paged by the same pre_chunk hook; _seg is the
        # upcoming chunk's [t0, t1), _win_start the host-tracked origin of
        # the window currently in the carry (None = force a load — also
        # the resume case, where the restored carry's window is opaque)
        self._scan_window = (getattr(r.scen_process, "scan_window", None)
                             if self.scenario_mode else None)
        if self._scan_window is not None and scan_chunk > self._scan_window:
            raise ValueError(
                f"scan_chunk={scan_chunk} exceeds the scenario's carried "
                f"availability window ({self._scan_window} rounds): a chunk "
                "must be coverable by one window. Raise the scenario's "
                "window= or lower scan_chunk")
        self._seg = None
        self._win_start = None

    # ------------------------------------------------------------------ #
    def _init_carry(self) -> dict:
        r = self.r
        # copy params: the chunk call donates the whole carry, and the
        # initial params may be a caller-passed array (run_fl(params=...))
        # that the loop engine would never invalidate — donation must only
        # ever consume engine-owned buffers. One O(d) copy per run; every
        # later chunk donates the previous chunk's own output.
        params = jax.tree.map(jnp.array, r.params)
        carry = {"state": r.state, "params": params, "rng": r.rng}
        if self.scenario_mode:
            carry["scen_state"] = r.scen_state
            carry["scen_key"] = r.scen_key
            carry["tau"] = jnp.asarray(r.stats.tau, jnp.int32)
            carry["tau_max"] = jnp.asarray(r.stats.tau_max_per_dev,
                                           jnp.int32)
        if self.mesh is not None:
            carry = self._shard_carry(carry)
        return carry

    def _shard_carry(self, carry: dict) -> dict:
        """Place the initial carry under `sharding.rules.scan_carry_specs`
        and remember the shardings — the scan body re-pins them every
        round via `with_sharding_constraint`."""
        from jax.sharding import NamedSharding
        from repro.sharding.rules import scan_carry_specs
        bank = getattr(self.r.algo, "bank", None)
        rows = getattr(bank, "n_rows", 0)
        specs = scan_carry_specs(carry, self.mesh, cfg=self.cfg,
                                 n_clients=self.r.n_clients,
                                 row_counts=(rows,) if rows else ())
        self._carry_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
        return jax.tree.map(jax.device_put, carry, self._carry_shardings)

    def _device_rows(self) -> dict | None:
        """The row table on the device, uploaded (and counted into
        `h2d_bytes`) the first time a chunk needs it; replicated under a
        mesh. None without a table."""
        if self._rows is not None and self._rows_dev is None:
            spans.count("h2d_bytes", _host_bytes(self._rows))
            where = None
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                where = NamedSharding(self.mesh, PartitionSpec())
            self._rows_dev = jax.device_put(self._rows, where)
        return self._rows_dev

    def _batches(self, rounds, ids=None) -> dict:
        """{'batch_rows': row indices} of the listed rounds when the table
        is resident, else {'batch': the stacked host batches}; `ids` are
        each round's client ids (cohort mode)."""
        ids = ids if ids is not None else [None] * len(rounds)
        b = self.r.batcher
        if self._rows is None:
            return {"batch": _stack([b.sample_round(t, client_ids=i)
                                     for t, i in zip(rounds, ids)])}
        spans.count("table_rounds", len(rounds))
        return {"batch_rows": _stack([b.sample_round_rows(t, client_ids=i)
                                      for t, i in zip(rounds, ids)])}

    def _writeback(self, carry: dict) -> None:
        r = self.r
        r.state, r.params, r.rng = (carry["state"], carry["params"],
                                    carry["rng"])
        if self.scenario_mode:
            r.scen_state = carry["scen_state"]
            # the key is carried through unchanged, but the INPUT buffer
            # was donated — keep the runner pointing at the live output
            # (checkpointing reads runner.scen_key between chunks)
            r.scen_key = carry["scen_key"]

    def _etas(self, t0: int, t1: int) -> tuple[np.ndarray, np.ndarray]:
        pairs = [self.r.learning_rates(t) for t in range(t0, t1)]
        return (np.asarray([p[0] for p in pairs], np.float32),
                np.asarray([p[1] for p in pairs], np.float32))

    def _host_masks(self, t0: int, t1: int, participation) -> np.ndarray:
        """(L, N) masks from the host surface, τ stats updated per round
        exactly as the loop engine's `step` would."""
        sampler = participation if participation is not None \
            else self.r._scen_sampler
        with spans.span("availability"):
            if hasattr(sampler, "sample_block"):
                masks = sampler.sample_block(t0, t1 - t0)
            else:
                masks = np.stack([np.asarray(sampler.sample(t), bool)
                                  for t in range(t0, t1)])
            for row in masks:
                self.r.stats.update(np.asarray(row, bool))
            return np.asarray(masks, bool)

    def _build_xs(self, t0: int, t1: int, participation) -> dict:
        """The chunk's stacked inputs. The masks come first (span
        ``availability``); the learning rates, batches and cohort padding
        make up span ``batch_assembly``."""
        r = self.r
        self._seg = (t0, t1)
        masks = (None if self.scenario_mode
                 else self._host_masks(t0, t1, participation))
        with spans.span("batch_assembly"):
            eta_loc, eta_srv = self._etas(t0, t1)
            xs = {"eta_loc": eta_loc, "eta_srv": eta_srv}
            if self.scenario_mode:
                xs["t"] = np.arange(t0, t1, dtype=np.int32)
                xs.update(self._batches(range(t0, t1)))
                return xs
            if not r.cohort_mode:
                xs["active"] = masks
                xs.update(self._batches(range(t0, t1)))
                return xs
            # cohort: reduce each mask to a padded id list + compact batch,
            # exactly as RoundRunner.step_cohort assembles a single round
            ids_l, valid_l = [], []
            for j, row in enumerate(masks):
                padded, valid = pad_cohort(np.flatnonzero(row), self.cap,
                                           r.n_clients, t0 + j)
                ids_l.append(padded)
                valid_l.append(valid)
            xs["ids"] = np.stack(ids_l)
            xs["valid"] = np.stack(valid_l)
            # pad slots draw client 0's rows; the per-round batches die
            # with the call, inside the span
            xs.update(self._batches(
                range(t0, t1),
                [np.where(v, p, 0) for p, v in zip(ids_l, valid_l)]))
            self._last_union = np.concatenate(
                [p[v] for p, v in zip(ids_l, valid_l)])
            return xs

    def _pre_chunk(self, carry: dict) -> dict:
        """Host-side streaming between chunks, while the device still owns
        the previous chunk: page the upcoming chunk union's bank rows in
        (cohort mode, paged banks) or re-point a windowed scenario's
        carried availability window at the chunk (trace replay). Both only
        *replace* carry leaves with host-built arrays — no traced reads —
        so the pipeline never stalls here."""
        if self.r.cohort_mode:
            prep = getattr(self.r.algo, "prepare_cohort", None)
            if prep is None or self._last_union is None:
                return carry
            return {**carry, "state": prep(carry["state"], self._last_union)}
        w, (t0, t1) = self._scan_window, self._seg
        if (self._win_start is not None and self._win_start <= t0
                and t1 <= self._win_start + w):
            return carry                       # chunk already covered
        carry = {**carry, "scen_state": self.r.scen_process.load_window(
            carry["scen_state"], t0)}
        self._win_start = t0
        return carry

    def _flush(self, t0: int, t1: int, ys: dict, carry: dict) -> None:
        """Reconstruct per-round history (and τ stats) from the stacked ys.

        Blocks on the chunk's results — `run` calls it one chunk late so
        the next chunk's host-side xs assembly overlaps device compute.
        """
        if self.scenario_mode:
            spans.count("d2h_bytes",
                        carry["tau"].nbytes + carry["tau_max"].nbytes)
            self.r.stats.absorb_scan(carry["tau"], carry["tau_max"],
                                     ys["tau_sum"], ys["tau_sq_sum"])
        ys = {k: np.asarray(v) for k, v in ys.items()}
        tau_keys = ("tau_sum", "tau_sq_sum")
        for j, t in enumerate(range(t0, t1)):
            self.r.hist.record_round(
                t, {k: v[j] for k, v in ys.items() if k not in tau_keys})

    # ------------------------------------------------------------------ #
    def run(self, n_rounds: int, *, participation=None,
            eval_fn: Callable | None = None, eval_every: int = 10,
            verbose: bool = False, checkpoint=None,
            start_round: int = 0) -> None:
        """Execute rounds [start_round, n_rounds), mutating the runner in
        place. `checkpoint` (a `repro.checkpoint.CheckpointSpec`) snapshots
        the full run state at every `checkpoint.every`-round boundary —
        the boundaries become chunk cuts like eval rounds, and the save
        happens after the chunk flushed, so stats/history are current;
        `start_round` > 0 continues a restored run (`run_fl` handles the
        restore itself)."""
        r = self.r
        if (participation is None and r.scen_process is None):
            raise ValueError("ScanDriver.run needs participation= or a "
                             "runner constructed with scenario=")
        evals = _eval_rounds(n_rounds, eval_every, eval_fn is not None)
        ckpts = set()
        if checkpoint is not None:
            ckpts = {t for t in range(start_round, n_rounds)
                     if (t + 1) % checkpoint.every == 0}

        def on_sync(t):
            if t in evals:
                el, ea = r.evaluate(t, eval_fn)
                if verbose:
                    print(f"  round {t:5d} "
                          f"train={r.hist.train_loss[-1]:.4f} "
                          f"eval={el:.4f} acc={ea:.4f} "
                          f"active={int(r.hist.n_active[-1])}")
            if t in ckpts:
                from repro.checkpoint.run_state import save_run
                save_run(r, checkpoint, t + 1)

        use_pre = self.r.cohort_mode or self._scan_window is not None
        run_pipelined_chunks(
            self._init_carry(),
            chunk_bounds(n_rounds, self.scan_chunk, evals | ckpts,
                         start=start_round),
            chunk_fn=self._chunk_fn,
            build_xs=lambda t0, t1: self._build_xs(t0, t1, participation),
            writeback=self._writeback, flush=self._flush,
            sync_rounds=evals | ckpts, on_sync=on_sync,
            pre_chunk=self._pre_chunk if use_pre else None)
