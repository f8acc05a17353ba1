"""One run of one cell: set-up, warm-up, the timed window, the check.

Set-up builds the program's `RoundRunner` the way `run_fl` builds it and one
`ScanDriver` on it, on the mesh the configuration names if it names one,
from data, weights and availability the benchmark draws from the seed. The
driver's first `compare_steps` chunks (the compared steps) are snapshotted
for the check: chunk 0 alone, the rest in one pipelined driver call as in
the window; further chunks warm it up until the cell is steady. The window
is one `ScanDriver.run` call over whole chunks, closed by
`block_until_ready` on the parameters: the program's own pipelined chunk
flow, with no sync of the benchmark's inside it. After the window the peak
memory is read, the program's state freed, and the plain reference the
configuration names (`reference/<name>.py`) replays the compared steps from
the same seed to decide `correct` (`check.py`).

The benchmark's host spans wrap the program's calls from outside (a proxy
around the batcher, the availability sampler, the paged bank's `prepare`,
the chunk dispatch and flush); each span is a `TraceAnnotation` and a host
clock. A `--trace 1` run profiles the window and reads the per-layer
metrics from the spans, the program's counters and the device trace.
"""
from __future__ import annotations

import contextlib
import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

import jax
import numpy as np

import check
import trace_reduce
import workload

SPAN_NAMES = ("window", "batch_assembly", "availability", "paging",
              "dispatch", "flush")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def use_compile_cache(root: str) -> str:
    """Keep JAX's persistent compilation cache at the fixed `<root>/.jax_cache`
    and cache every program there, so that only a cell's first run in a
    checkout compiles. Returns the directory."""
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts and sums XLA backend compiles reported by jax.monitoring."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, event: str, duration: float, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += duration
            self.count += 1


class Spans:
    """Host spans: a `TraceAnnotation` each, and their summed host seconds
    while `on` (the timed window)."""

    def __init__(self):
        self.on = False
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                if self.on:
                    self.seconds[name] = (self.seconds.get(name, 0.0)
                                          + time.perf_counter() - t0)


class BatcherProxy:
    """The program's batcher, its `sample_round` inside a span, and its
    `sample_round_rows` too where it has one. That one is wrapped on lookup,
    so the proxy has it exactly when the batcher does: `ScanDriver` keeps a
    row table for a batcher that has it."""

    def __init__(self, inner, spans: Spans):
        self._inner, self._spans = inner, spans

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name == "sample_round_rows":
            return _wrap(attr, self._spans, "batch_assembly")
        return attr

    def sample_round(self, t, client_ids=None):
        with self._spans("batch_assembly"):
            return self._inner.sample_round(t, client_ids=client_ids)


class SamplerProxy:
    """The program's availability sampler inside a span; records the
    active ids it draws for rounds below `keep`."""

    def __init__(self, inner, spans: Spans, keep: int):
        self._inner, self._spans, self._keep = inner, spans, keep
        self.n = inner.n
        self.ids: dict[int, np.ndarray] = {}
        if hasattr(inner, "sample_block"):
            self.sample_block = self._sample_block

    def _record(self, t, mask):
        if t < self._keep:
            self.ids[t] = np.flatnonzero(np.asarray(mask, bool))

    def sample(self, t):
        with self._spans("availability"):
            mask = self._inner.sample(t)
        self._record(t, mask)
        return mask

    def _sample_block(self, t0, length):
        with self._spans("availability"):
            masks = self._inner.sample_block(t0, length)
        for j, mask in enumerate(masks):
            self._record(t0 + j, mask)
        return masks


def make_mesh(cell: workload.Cell):
    """The mesh the cell's configuration names (`"mesh"`: axis sizes for
    `make_host_mesh`, or null for none) over its first `chips` devices."""
    axes = cell.config.get("mesh")
    if axes is None:
        return None
    if not jax.config.jax_threefry_partitionable:
        # the program's in-round draws would depend on the mesh, and the
        # reference's availability draws would no longer match them
        raise ValueError("a mesh needs jax_threefry_partitionable")
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(**axes)
    if mesh.size != cell.chips:
        raise ValueError(f"{cell.name}: mesh {axes} spans {mesh.size} "
                         f"devices, the cell holds {cell.chips} chips")
    return mesh


def _paged(bank) -> bool:
    """A bank that pages rows between host and device (counts faults)."""
    return bank is not None and hasattr(bank, "faults")


def _wrap(fn, spans: Spans, name: str):
    def call(*args, **kw):
        with spans(name):
            return fn(*args, **kw)
    return call


# --------------------------------------------------------------------------- #
# set-up
# --------------------------------------------------------------------------- #

def build(cell: workload.Cell, seed: int, spans: Spans):
    """The program's runner and scan driver for `cell`, from data, weights
    and availability drawn from `seed` (already a `program_seed`).

    Every piece is found by a name in the cell's files: the model
    (`configs/<config>.json` `model`, program architecture and
    `bench/models/<model>.py`), the algorithm (`algorithm`, by the program's
    algorithm registry), the data and availability (`traffic/<mix>.json`,
    `bench/datasets/<kind>.py`, `bench/availability/<kind>.py`)."""
    from repro.configs import get_config
    from repro.core.algorithms import make_algorithm
    from repro.core.runner import RoundRunner
    from repro.core.scan_engine import ScanDriver, scan_supported
    from repro.models import build_model

    cfg, traffic = cell.config, cell.traffic
    n = cfg["n_clients"]
    model = workload.model(cfg)
    arch = get_config(cfg["model"]).replace(fl_clients=n,
                                            **model.arch_fields(cfg))
    spec = traffic["data"]
    data = workload.make_data(spec, cfg, seed)
    batcher = workload.plugin("datasets", spec["kind"]).program_batcher(
        data, cfg, seed)
    av = workload.make_availability(traffic["availability"], data, n, seed)
    side = workload.plugin("availability", av.kind).program_side(av, n, seed)
    a = dict(cfg["algorithm"])
    algo = make_algorithm(a.pop("name"), n=n, **a)
    params0 = model.init_params(jax.random.PRNGKey(seed), cfg)
    mesh = make_mesh(cell)
    bank = getattr(algo, "bank", None)
    if mesh is not None and hasattr(bank, "mesh") and bank.mesh is None:
        # as `run_fl` does: a mesh-less bank takes the run's mesh before
        # `RoundRunner` has it lay out its rows
        bank.mesh, bank.cfg = mesh, arch
    runner = RoundRunner(
        model=build_model(arch), algo=algo,
        batcher=BatcherProxy(batcher, spans), schedule=workload.schedule(cfg),
        eta_local=cfg["eta_local"], weight_decay=cfg["weight_decay"],
        seed=seed, params=params0, cohort_capacity=cfg["cohort_capacity"],
        scenario=side.get("scenario"))
    ok, why = scan_supported(runner)
    if not ok:
        raise ValueError(f"the scan engine refuses this configuration: {why}")
    keep = cfg["compare_steps"] * cfg["scan_chunk"]
    participation = side.get("participation")
    if participation is not None:
        participation = SamplerProxy(participation, spans, keep)
        sampler = participation
    else:
        runner._scen_sampler = sampler = SamplerProxy(
            runner._scen_sampler, spans, keep)
    if _paged(bank):
        bank.prepare = _wrap(bank.prepare, spans, "paging")
    driver = ScanDriver(runner, scan_chunk=cfg["scan_chunk"], mesh=mesh,
                        cfg=arch)
    driver._chunk_fn = _wrap(driver._chunk_fn, spans, "dispatch")
    driver._flush = _wrap(driver._flush, spans, "flush")
    return {"runner": runner, "driver": driver, "bank": bank,
            "participation": participation, "sampler": sampler,
            "data": data, "av": av,
            "params0": [np.asarray(x) for x in jax.tree.leaves(params0)]}


# --------------------------------------------------------------------------- #
# snapshots of the compared steps
# --------------------------------------------------------------------------- #

def _mean_g(prog: dict) -> list:
    state, bank = prog["runner"].state, prog["bank"]
    if bank is not None:
        return [np.asarray(g, np.float64)
                for g in jax.tree.leaves(bank.mean_g(state["bank"]))]
    return [np.asarray(g, np.float64).mean(0)
            for g in jax.tree.leaves(state["G"])]


def _rows(prog: dict, ids: np.ndarray, pad_to: int) -> list:
    state, bank = prog["runner"].state, prog["bank"]
    if bank is None:
        return [np.asarray(g)[ids] for g in jax.tree.leaves(state["G"])]
    # one gather shape for every seed: pad with the bank's dummy row
    n = prog["runner"].n_clients
    padded = np.full(max(pad_to, len(ids)), n, np.int64)
    padded[:len(ids)] = ids
    rows = bank.gather(state["bank"], padded)
    return [np.asarray(r)[:len(ids)] for r in jax.tree.leaves(rows)]


def _snapshot_rows(prog: dict, upto: int, pad_to: int) -> dict:
    """The rows held for every client active in rounds below `upto`."""
    ids = np.unique(np.concatenate(
        [prog["sampler"].ids.get(t, np.zeros(0, np.int64))
         for t in range(upto)]))
    return {"row_ids": ids, "rows": _rows(prog, ids, pad_to)}


def drive_compared_steps(prog: dict, cfg: dict) -> dict:
    """Run the first `compare_steps` chunks and snapshot what the check
    compares. Chunk 0 runs alone, so that the memory after the first step
    can be read; the remaining compared chunks run in one driver call, as
    the window runs them: each chunk's host work (batches, availability,
    the paged bank's faults and evictions) overlaps the previous chunk on
    the device, and the carry passes from chunk to chunk. Also returns
    that call's seconds per chunk (`chunk_s`) and whether it faulted pages
    in (`last_faulted`)."""
    L, S = cfg["scan_chunk"], cfg["compare_steps"]
    drv, part, r = prog["driver"], prog["participation"], prog["runner"]
    bank = prog["bank"]
    paged = _paged(bank)
    cap = cfg["cohort_capacity"] or r.n_clients
    snap = {"params0": prog["params0"]}
    drv.run(L, participation=part, start_round=0)
    t0 = time.perf_counter()
    snap["mean_g"] = _mean_g(prog)
    snap["step1"] = _snapshot_rows(prog, L, L * cap)
    check_s = time.perf_counter() - t0
    faults0 = bank.faults if paged else 0
    t0 = time.perf_counter()
    drv.run(S * L, participation=part, start_round=L)
    snap["chunk_s"] = (time.perf_counter() - t0) / max(S - 1, 1)
    snap["last_faulted"] = paged and bank.faults > faults0
    t0 = time.perf_counter()
    snap["params"] = [np.asarray(x) for x in jax.tree.leaves(r.params)]
    snap["mean_g_last"] = _mean_g(prog)
    snap.update(_snapshot_rows(prog, S * L, S * L * cap))
    snap["losses"] = list(r.hist.train_loss[:S * L])
    snap["n_active"] = list(r.hist.n_active[:S * L])
    snap["ids"] = dict(prog["sampler"].ids)
    snap["check_s"] = check_s + time.perf_counter() - t0
    return snap


# --------------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------------- #

def _warm(prog: dict, cfg: dict, start: int, per_chunk: float,
          faulted: bool, max_calls: int = 8):
    """Two-chunk driver calls from round `start` until the cell is steady:
    no paging, or a call that faulted no page, or evictions under way.
    `per_chunk` and `faulted` describe the last compared step. Returns
    (next round, seconds per chunk of the last call)."""
    L, drv, bank = cfg["scan_chunk"], prog["driver"], prog["bank"]
    for _ in range(max_calls):
        if not _paged(bank) or not faulted or bank.evictions > 0:
            break
        faults0 = bank.faults
        t0 = time.perf_counter()
        drv.run(start + 2 * L, participation=prog["participation"],
                start_round=start)
        jax.block_until_ready(prog["runner"].params)
        per_chunk = (time.perf_counter() - t0) / 2
        start += 2 * L
        faulted = bank.faults > faults0
    return start, per_chunk


def _peak_bytes(chips: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def _read_trace(tmp: str) -> list:
    paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    return trace_reduce.load_xspace(paths[0], SPAN_NAMES)


def run_cell(cell: workload.Cell, seed: int, seconds: float, trace: bool,
             *, t_start: float, peaks: dict | None = None) -> dict:
    """One whole run; returns the result line's object and the lines for
    standard error."""
    cfg = cell.config
    s = workload.program_seed(seed)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    spans = Spans()
    parts = {"imports_s": time.perf_counter() - t_start}
    t0 = time.perf_counter()
    prog = build(cell, s, spans)
    parts["build_s"] = time.perf_counter() - t0
    L = cfg["scan_chunk"]
    with jax.default_matmul_precision(cfg["precision"]):
        t0, c0 = time.perf_counter(), clock.seconds
        snap = drive_compared_steps(prog, cfg)
        start, per_chunk = _warm(prog, cfg, cfg["compare_steps"] * L,
                                 snap["chunk_s"], snap["last_faulted"])
        parts["compile_s"] = clock.seconds - c0
        parts["warm_s"] = (time.perf_counter() - t0 - snap["check_s"]
                           - parts["compile_s"])
        parts["check_snapshots_s"] = snap["check_s"]
        n_chunks = max(2, round(seconds / per_chunk))
        end = start + n_chunks * L
        bank, runner = prog["bank"], prog["runner"]
        paged = _paged(bank)
        faults0 = bank.faults if paged else None
        tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tmp, profiler_options=opts)
        compiles0 = clock.count
        spans.on = True
        with jax.profiler.TraceAnnotation("window"):
            tw0 = time.perf_counter()
            prog["driver"].run(end, participation=prog["participation"],
                               start_round=start)
            jax.block_until_ready(runner.params)
            tw1 = time.perf_counter()
        spans.on = False
        if trace:
            jax.profiler.stop_trace()
    window_s, rounds = tw1 - tw0, end - start
    compiles = clock.count - compiles0
    setup_s = tw0 - t_start - snap["check_s"]
    peak = _peak_bytes(cell.chips)
    n_active = [float(x) for x in runner.hist.n_active[start:end]]
    faults = (bank.faults - faults0) if paged else None
    av, data = prog["av"], prog["data"]
    del prog, runner, bank
    gc.collect()

    events = None
    if trace:
        events = _read_trace(tmp)
        shutil.rmtree(tmp, ignore_errors=True)

    t0 = time.perf_counter()
    ref = workload.reference(cfg).run(cfg, data, av, s, snap["params0"],
                                      cfg["compare_steps"] * L,
                                      snap_at=L - 1)
    numbers = check.compare(snap, ref)
    correct, checks = check.judge(numbers, cell.limits)
    ref_s = time.perf_counter() - t0

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": rounds, "failed": 0}
    if not trace:
        values = {"rounds_per_s": rounds / window_s,
                  "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.metrics_e2e}
    else:
        lo, hi = trace_reduce.window_bounds(events)
        busy = trace_reduce.busy_seconds(events)
        device.update(busy_s=busy, window_s=(hi - lo) / 1e9)
        # what a per-layer metric's reader may read (bench/metrics/)
        ctx = SimpleNamespace(
            cfg=cfg, chips=cell.chips, peaks=peaks, window_s=window_s,
            trace_window_s=(hi - lo) / 1e9, busy_s=busy, rounds=rounds,
            n_active=n_active, span_s=dict(spans.seconds), faults=faults,
            events=events)
        metrics = {}
        for m in cell.metrics_layer:
            v = workload.plugin("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["breakdown"] = {
            "device_ops": trace_reduce.top_ops(events),
            "idle_gaps": trace_reduce.name_gaps(
                events, trace_reduce.idle_gaps(events))}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = checks
    log = {"setup_parts": parts, "window_s": window_s, "rounds": rounds,
           "chunks": n_chunks, "compiles_in_window": compiles,
           "reference_s": ref_s, "numbers": numbers}
    return {"result": out, "log": log}


def main_stderr(log: dict, checks: dict) -> None:
    """The run's log, then each compared number beside its limit, as the
    last lines on standard error."""
    print(json.dumps(log, default=float), file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
