"""PagedDeviceBank — device-resident pages behind a jit-native page table.

The missing bridge between the two big bank ideas: DenseBank is jittable (so
the scan engine and the fleet can trace it) but holds all N rows on device;
the host backends stay flat to N=10⁶ but live outside jit and force the
per-round dispatch loop. This backend keeps a *bounded* number of rows on
device — `n_slots` fixed-size pages plus one dummy page — and addresses them
through a page-table indirection that is a plain int32 jnp array riding the
scan carry:

    phys_row(lid) = page_table[lid // page_size] * page_size + lid % page_size

Everything on the hot path (gather, the fused gather/delta/scatter, the
G_sum delta identity) is pure jnp / Pallas over `phys_row`, so it traces
cleanly inside `lax.scan` bodies and under `vmap` for fleets. Residency is
managed *between* jitted programs by `prepare(state, ids)` — an eager,
host-side step that pages the cohort's (or chunk union's) logical pages in,
spilling deterministic-LRU victims to host RAM. The scan engine calls it at
chunk boundaries through the pipelined-flush hook; the per-round loop and the
fleet executor call it before each round.

Why paging never changes the numbers: a gather returns the same values no
matter which physical slot a row occupies, and every reduction (delta sum,
loss) runs over the *cohort* axis, never over physical rows. So trajectories
are fp32 bit-exact against DenseBank — even when the loop and the scan page
on different schedules — as long as every row a round touches is resident
when it executes (which `prepare` guarantees, and raises loudly when it
can't).

State layout (all jnp, scan-carry safe):
    pages      : pytree, leaves ((n_slots+1)·page_size, *shape) `dtype`;
                 the last page is the dummy page — always exact zeros —
                 that pad slots and non-resident reads resolve to.
    page_table : (logical_pages+1,) int32; sentinel (= n_slots, the dummy
                 slot) marks non-resident pages; the last entry is the
                 dummy logical page, pinned to the dummy slot.
    g_sum      : pytree, leaves (*shape,) f32 — running Σ_i G^i (over
                 dequantized values when dtype="int8", as Int8PagedBank).
    scales     : (dtype="int8" only) pytree, leaves (n_rows,) f32 absmax
                 scales per physical row.

Host-side bookkeeping (never traced): a numpy mirror of the page table, a
slot→logical-page reverse map, a free list, LRU timestamps, and the spill
store `{logical_page: per-leaf numpy blocks}` for evicted pages.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.bank.base import MemoryBank, broadcast_valid, check_unique_ids
from repro.bank.dense import _scatter_jnp, _traced
from repro.core import quantized_memory as qm
from repro.core.runner import _pow2_bucket


def _phys_rows(page_table, lids, page_size: int):
    return page_table[lids // page_size] * page_size + lids % page_size


def _scatter_pure(pages, scales, g_sum, page_table, ids, valid, updates, rng,
                  *, page_size: int, n_clients: int, dummy_lrow: int,
                  quantized: bool, use_pallas: bool):
    """Paged gather/delta/scatter body — trace-safe (scan/vmap/jit).

    Assumes every valid id's logical page is resident (`prepare` ran).
    Pad ids (>= n_clients) are remapped to the dummy logical row, whose
    writes are masked out by `valid` — so they never touch G_sum or a page.
    """
    lids = jnp.where(ids >= n_clients, dummy_lrow, ids).astype(jnp.int32)
    if quantized:
        leaves, treedef = jax.tree.flatten(pages)
        sc_leaves = treedef.flatten_up_to(scales)
        gs_leaves = treedef.flatten_up_to(g_sum)
        u_leaves = treedef.flatten_up_to(updates)
        rngs = jax.random.split(rng, len(leaves))
        phys = _phys_rows(page_table, lids, page_size)
        new_p, new_s, new_g = [], [], []
        for r, sc, gs, u, key in zip(leaves, sc_leaves, gs_leaves, u_leaves,
                                     rngs):
            # key rounding noise by logical id, not cohort slot, so pad
            # slots never shift the draws of the real rows
            row_keys = jax.vmap(jax.random.fold_in, (None, 0))(key, lids)
            q, qs = jax.vmap(
                lambda k, x: jax.tree.map(
                    lambda a: a[0], qm.quantize_leaf(k, x[None]))
            )(row_keys, u.astype(jnp.float32))
            u_eff = qm.dequantize_leaf(q, qs)        # what the bank stores
            old = qm.dequantize_leaf(r[phys], sc[phys])
            vb = broadcast_valid(valid, u_eff)
            delta = jnp.where(vb, u_eff - old, 0.0)
            new_p.append(r.at[phys].set(jnp.where(vb, q, r[phys])))
            new_s.append(sc.at[phys].set(jnp.where(valid, qs, sc[phys])))
            new_g.append(gs + jnp.sum(delta, axis=0))
        return (jax.tree.unflatten(treedef, new_p),
                jax.tree.unflatten(treedef, new_s),
                jax.tree.unflatten(treedef, new_g))
    if use_pallas:
        from repro.kernels.ops import paged_bank_update_tree_pure
        pages_new, dsum = paged_bank_update_tree_pure(
            pages, updates, page_table, lids, valid, page_size=page_size)
        g_sum = jax.tree.map(jnp.add, g_sum, dsum)
        return pages_new, scales, g_sum
    phys = _phys_rows(page_table, lids, page_size)
    pages_new, g_new = _scatter_jnp(pages, g_sum, phys, valid, updates)
    return pages_new, scales, g_new


_STATIC = ("page_size", "n_clients", "dummy_lrow", "quantized", "use_pallas")

_scatter = partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=_STATIC)(_scatter_pure)


def _scatter_fleet_pure(pages, scales, g_sum, page_table, ids, valid,
                        updates, rng, *, page_size: int, n_clients: int,
                        dummy_lrow: int, quantized: bool, use_pallas: bool):
    """Batched (K-trial) paged scatter: pages (K, R, ...), page_table (K, P),
    ids/valid (K, C), rng (K, 2) — per trial bit-identical to
    `_scatter_pure`. The Pallas fp path uses the grid-axis batched kernel;
    everything else vmaps the per-trial body."""
    if use_pallas and not quantized:
        lids = jnp.where(ids >= n_clients, dummy_lrow, ids).astype(jnp.int32)
        from repro.kernels.ops import fleet_paged_bank_update_tree_pure
        pages_new, dsum = fleet_paged_bank_update_tree_pure(
            pages, updates, page_table, lids, valid, page_size=page_size)
        g_sum = jax.tree.map(jnp.add, g_sum, dsum)
        return pages_new, scales, g_sum
    body = partial(_scatter_pure, page_size=page_size, n_clients=n_clients,
                   dummy_lrow=dummy_lrow, quantized=quantized,
                   use_pallas=False)
    return jax.vmap(body)(pages, scales, g_sum, page_table, ids, valid,
                          updates, rng)


_scatter_fleet = partial(jax.jit, donate_argnums=(0, 1, 2),
                         static_argnames=_STATIC)(_scatter_fleet_pure)


class PagedDeviceBank(MemoryBank):
    """Bounded device memory, jit-native addressing; see module docstring.

    page_size : rows per page (power of two — the same capacity-bucket
                discipline the cohort padding uses, so page row ranges stay
                aligned for the kernels' index maps).
    n_slots   : device pages resident at once (None => enough for all of
                N, i.e. fully resident — still useful: the page table rides
                the carry and the scan path works unchanged).
    dtype     : "float32" | "bfloat16" | "int8". int8 reuses the stochastic
                rounding quantizer (per-physical-row absmax scales) and
                maintains G_sum over dequantized values, like Int8PagedBank.
    """

    jittable = True

    def __init__(self, *, page_size: int = 64, n_slots: int | None = None,
                 dtype: str = "float32", use_pallas: bool | None = None):
        if page_size <= 0 or page_size & (page_size - 1):
            raise ValueError(f"page_size must be a power of two, got "
                             f"{page_size}")
        if n_slots is not None and n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.page_size = page_size
        self._n_slots_cfg = n_slots
        self.quantized = dtype == "int8"
        self.dtype = jnp.dtype(dtype)
        self._use_pallas = use_pallas
        self.n = 0
        self.n_slots = 0
        self.lp = 0            # logical pages holding real rows
        self.dummy_lrow = 0    # sanitized logical row for pad slots
        self.sentinel = 0      # page-table value meaning "not resident"
        # residency bookkeeping (host side, never traced)
        self._pt = np.zeros(0, np.int32)     # mirror of state["page_table"]
        self._slot_lp = np.zeros(0, np.int64)
        self._free: list[int] = []
        self._lru: dict[int, int] = {}
        self._clock = 0
        self._spill: dict[int, dict] = {}    # lp -> {"pages": [...], ...}
        self.faults = 0
        self.evictions = 0

    # ------------------------------------------------------------------ #
    def _pallas(self) -> bool:
        if self.quantized:
            return False                     # quantizer path is jnp-only
        if self._use_pallas is not None:
            return self._use_pallas
        from repro.kernels.backend import interpret_default
        return not interpret_default()

    def init(self, params, n_clients: int) -> dict:
        ps = self.page_size
        self.n = n_clients
        self.lp = -(-n_clients // ps)
        self.n_slots = (self.lp if self._n_slots_cfg is None
                        else self._n_slots_cfg)
        from repro.kernels.backend import interpret_default
        if self._pallas() and not interpret_default():
            # refuse at build time what one compiled kernel call could not
            # hold; a fleet's K stacked tables are checked at the call
            from repro.kernels.bank_scatter import check_page_table_fits
            check_page_table_fits(self.lp + 1, ps)
        self.dummy_lrow = self.lp * ps
        self.sentinel = self.n_slots         # the dummy slot doubles as it
        n_rows = (self.n_slots + 1) * ps
        self._pt = np.full(self.lp + 1, self.sentinel, np.int32)
        self._pt[self.lp] = self.n_slots     # dummy logical page, pinned
        self._slot_lp = np.full(self.n_slots, -1, np.int64)
        self._free = list(range(self.n_slots - 1, -1, -1))   # pop() -> 0,1,..
        self._lru = {}
        self._clock = 0
        self._spill = {}
        self.faults = 0
        self.evictions = 0
        state = {
            "pages": jax.tree.map(
                lambda p: jnp.zeros((n_rows,) + p.shape, self.dtype), params),
            "page_table": jnp.asarray(self._pt),
            "g_sum": jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params),
        }
        if self.quantized:
            state["scales"] = jax.tree.map(
                lambda p: jnp.zeros((n_rows,), jnp.float32), params)
        return state

    # ------------------------------------------------------------------ #
    # residency management — eager only, between jitted programs
    # ------------------------------------------------------------------ #

    def _is_fleet(self, state: dict) -> bool:
        return state["page_table"].ndim == 2

    def prepare(self, state: dict, ids) -> dict:
        """Make every logical page that `ids` touches device-resident.

        Eager (host-side): evicts deterministic-LRU victims to the spill
        store and uploads faulted pages (spilled data, or zeros for pages
        never written) in one batched device write per leaf. Returns the
        new state; a no-op (same state object) when everything is already
        resident. Raises when the working set cannot fit in `n_slots`.
        """
        ps = self.page_size
        ids = np.asarray(ids).reshape(-1)
        ids = ids[(ids >= 0) & (ids < self.n)]
        need = np.unique(ids // ps).astype(np.int64)
        if len(need) > self.n_slots:
            raise ValueError(
                f"cohort working set spans {len(need)} pages but "
                f"PagedDeviceBank has only {self.n_slots} slots "
                f"(page_size={ps}); raise n_slots, lower page_size, or — "
                "under engine='scan', where residency is per chunk union — "
                "lower scan_chunk")
        self._clock += 1
        for l in need:
            self._lru[int(l)] = self._clock
        missing = [int(l) for l in need if self._pt[l] == self.sentinel]
        if not missing:
            return state
        self.faults += len(missing)
        fleet = self._is_fleet(state)

        # 1) host bookkeeping: pick a slot per faulted page, evicting
        #    deterministic-LRU victims (oldest timestamp, ties by page id)
        needset = {int(l) for l in need}
        assign: list[tuple[int, int]] = []   # (lp, slot)
        evict: list[tuple[int, int]] = []    # (victim_lp, slot)
        for l in missing:
            if self._free:
                slot = self._free.pop()
            else:
                cands = [(t, lp_) for lp_, t in self._lru.items()
                         if self._pt[lp_] != self.sentinel
                         and lp_ not in needset]
                if not cands:
                    raise ValueError(
                        "no evictable page — all resident pages are in the "
                        "current working set (internal invariant violation)")
                _, victim = min(cands)
                slot = int(self._pt[victim])
                evict.append((victim, slot))
                self._pt[victim] = self.sentinel
                self._slot_lp[slot] = -1
                del self._lru[victim]
                self.evictions += 1
            assign.append((l, slot))

        pages_leaves, treedef = jax.tree.flatten(state["pages"])
        sc_leaves = (treedef.flatten_up_to(state["scales"])
                     if self.quantized else None)

        # 2) one batched device->host read for all evicted slots; the row
        #    batch is padded to a pow-2 page count with dummy-page reads
        #    (discarded below) so XLA sees few distinct gather shapes
        if evict:
            ev_rows = np.concatenate(
                [np.arange(s * ps, (s + 1) * ps) for _, s in evict]
                + [np.arange(self.n_slots * ps, (self.n_slots + 1) * ps)]
                * (_pow2_bucket(len(evict)) - len(evict)))
            ev_pages = [np.asarray(leaf[:, ev_rows] if fleet
                                   else leaf[ev_rows])
                        for leaf in pages_leaves]
            ev_scales = ([np.asarray(sc[:, ev_rows] if fleet else sc[ev_rows])
                          for sc in sc_leaves] if self.quantized else None)
            for k, (victim, _) in enumerate(evict):
                sl = (slice(None), slice(k * ps, (k + 1) * ps))
                blk = sl if fleet else sl[1]
                entry = {"pages": [p[blk].copy() for p in ev_pages]}
                if self.quantized:
                    entry["scales"] = [s[blk].copy() for s in ev_scales]
                self._spill[victim] = entry

        # 3) one batched host->device write for all faulted pages; pages
        #    with no spill entry (never written, or written only as zeros)
        #    upload zeros — REQUIRED, the slot may hold stale evicted data.
        #    The batch is padded to a pow-2 page count with zero writes to
        #    the dummy page (which is pinned to zero, so they are no-ops)
        #    to keep the number of distinct scatter shapes XLA compiles low.
        n_pad = _pow2_bucket(len(assign)) - len(assign)
        up_rows = np.concatenate(
            [np.arange(s * ps, (s + 1) * ps) for _, s in assign]
            + [np.arange(self.n_slots * ps, (self.n_slots + 1) * ps)] * n_pad)
        spilled = {l: self._spill.pop(l) for l, _ in assign
                   if l in self._spill}

        def upload(leaf, j, kind):
            blocks = []
            shape = ((leaf.shape[0], ps) + leaf.shape[2:] if fleet
                     else (ps,) + leaf.shape[1:])
            for l, _ in assign:
                sp = spilled.get(l)
                blocks.append(np.zeros(shape, leaf.dtype) if sp is None
                              else sp[kind][j])
            blocks += [np.zeros(shape, leaf.dtype)] * n_pad
            vals = np.concatenate(blocks, axis=1 if fleet else 0)
            idx = (slice(None), up_rows) if fleet else up_rows
            return leaf.at[idx].set(jnp.asarray(vals))

        new_pages = [upload(leaf, j, "pages")
                     for j, leaf in enumerate(pages_leaves)]
        new_state = dict(state)
        new_state["pages"] = jax.tree.unflatten(treedef, new_pages)
        if self.quantized:
            new_sc = [upload(sc, j, "scales")
                      for j, sc in enumerate(sc_leaves)]
            new_state["scales"] = jax.tree.unflatten(treedef, new_sc)

        for l, slot in assign:
            self._pt[l] = slot
            self._slot_lp[slot] = l
        pt_dev = jnp.asarray(self._pt)
        if fleet:
            pt_dev = jnp.broadcast_to(pt_dev, state["page_table"].shape)
        new_state["page_table"] = pt_dev
        return new_state

    # ------------------------------------------------------------------ #
    def gather(self, state: dict, ids):
        ids = jnp.asarray(ids, jnp.int32)
        lids = jnp.where(ids >= self.n, self.dummy_lrow, ids)
        phys = _phys_rows(state["page_table"], lids, self.page_size)
        if self.quantized:
            out = jax.tree.map(
                lambda r, sc: qm.dequantize_leaf(r[phys], sc[phys]),
                state["pages"], state["scales"])
        else:
            out = jax.tree.map(lambda r: r[phys].astype(jnp.float32),
                               state["pages"])
        if _traced((state, ids)) or self._is_fleet(state):
            # inside a trace `prepare` has already made the rows resident;
            # fleet states keep one shared residency map, same argument
            return out
        # eager: patch rows whose page currently lives in the spill store
        ids_np = np.asarray(ids)
        patch = [(c, int(i)) for c, i in enumerate(ids_np)
                 if 0 <= i < self.n and (i // self.page_size) in self._spill]
        if not patch:
            return out
        leaves, treedef = jax.tree.flatten(out)
        leaves = [np.array(leaf) for leaf in leaves]   # writable copies
        for c, i in patch:
            l, off = divmod(i, self.page_size)
            sp = self._spill[l]
            for j in range(len(leaves)):
                row = sp["pages"][j][off]
                if self.quantized:
                    row = row.astype(np.float32) * sp["scales"][j][off]
                leaves[j][c] = row
        return jax.tree.unflatten(treedef,
                                  [jnp.asarray(leaf) for leaf in leaves])

    def _scatter_rows(self, state: dict, ids, updates, *, valid,
                      rng=None) -> dict:
        if self.quantized:
            assert rng is not None, "int8 pages need an rng for rounding"
        traced = _traced((state, ids, updates))
        if not traced:
            ids_np = np.asarray(ids)
            valid_np = (np.ones(ids_np.shape, bool) if valid is None
                        else np.asarray(valid, bool))
            state = self.prepare(state, ids_np[valid_np])
        ids = jnp.asarray(ids, jnp.int32)
        valid = (jnp.ones(ids.shape, bool) if valid is None
                 else jnp.asarray(valid, bool))
        if rng is None:
            rng = jax.random.PRNGKey(0)      # unused on the fp paths
        fn = _scatter_pure if traced else _scatter
        pages, scales, g_sum = fn(
            state["pages"], state.get("scales"), state["g_sum"],
            state["page_table"], ids, valid, updates, rng,
            page_size=self.page_size, n_clients=self.n,
            dummy_lrow=self.dummy_lrow, quantized=self.quantized,
            use_pallas=self._pallas())
        new = {"pages": pages, "page_table": state["page_table"],
               "g_sum": g_sum}
        if self.quantized:
            new["scales"] = scales
        return new

    def scatter_fleet(self, state: dict, ids, updates, *, valid=None,
                      rng=None) -> dict:
        """Stacked-trial paged scatter: leaves (K, R, ...), page_table
        (K, P) — identical per-trial copies, one shared residency map (the
        union of all trials' cohorts is paged in together)."""
        if self.quantized:
            assert rng is not None, "int8 pages need an rng for rounding"
        traced = _traced((state, ids, updates))
        if not traced:
            ids_np = np.asarray(ids)
            valid_np = (np.ones(ids_np.shape, bool) if valid is None
                        else np.asarray(valid, bool))
            for k in range(ids_np.shape[0]):
                check_unique_ids(ids_np[k], valid_np[k])
            state = self.prepare(state, ids_np[valid_np])
        ids = jnp.asarray(ids, jnp.int32)
        valid = (jnp.ones(ids.shape, bool) if valid is None
                 else jnp.asarray(valid, bool))
        K = ids.shape[0]
        if rng is None:
            rngs = jnp.zeros((K, 2), jnp.uint32)   # unused on the fp paths
        else:
            rng = jnp.asarray(rng)
            # the fleet passes per-trial keys (K, 2); a single key is split
            rngs = rng if rng.ndim == 2 else jax.random.split(rng, K)
        fn = _scatter_fleet_pure if traced else _scatter_fleet
        pages, scales, g_sum = fn(
            state["pages"], state.get("scales"), state["g_sum"],
            state["page_table"], ids, valid, updates, rngs,
            page_size=self.page_size, n_clients=self.n,
            dummy_lrow=self.dummy_lrow, quantized=self.quantized,
            use_pallas=self._pallas())
        new = {"pages": pages, "page_table": state["page_table"],
               "g_sum": g_sum}
        if self.quantized:
            new["scales"] = scales
        return new

    def mean_g(self, state: dict):
        return jax.tree.map(lambda g: g / self.n, state["g_sum"])

    # ------------------------------------------------------------------ #
    def host_state(self) -> dict:
        """Serialise the host-side residency bookkeeping for a snapshot.

        The jit state (`pages` / `page_table` / `g_sum`) rides the run
        snapshot through `runner.state`; this captures its host mirrors —
        page-table mirror, slot ownership, the free list IN ORDER (slot
        assignment order is part of the trajectory), LRU stamps, fault
        counters, and every spilled page's bytes — so a resumed run pages
        exactly like the uninterrupted one.
        """
        lps = sorted(self._spill)
        tree = {
            "pt": self._pt, "slot_lp": self._slot_lp,
            "free": np.asarray(self._free, np.int64),
            "lru_keys": np.asarray(sorted(self._lru), np.int64),
            "lru_vals": np.asarray([self._lru[k] for k in sorted(self._lru)],
                                   np.int64),
            "clock": np.int64(self._clock),
            "faults": np.int64(self.faults),
            "evictions": np.int64(self.evictions),
            "spill_lp": np.asarray(lps, np.int64),
        }
        if lps:
            tree["spill"] = [self._spill[lp] for lp in lps]
        return tree

    def load_host_state(self, tree: dict) -> None:
        """Restore `host_state` bookkeeping (after `init`, before rounds)."""
        if not tree:
            return
        self._pt = np.asarray(tree["pt"], np.int32).copy()
        self._slot_lp = np.asarray(tree["slot_lp"], np.int64).copy()
        self._free = [int(s) for s in np.asarray(tree["free"])]
        self._lru = {int(k): int(v) for k, v in
                     zip(np.asarray(tree["lru_keys"]),
                         np.asarray(tree["lru_vals"]))}
        self._clock = int(tree["clock"])
        self.faults = int(tree["faults"])
        self.evictions = int(tree["evictions"])
        self._spill = {}
        for lp, entry in zip(np.asarray(tree["spill_lp"], np.int64),
                             tree.get("spill", [])):
            e = {"pages": [np.asarray(p) for p in entry["pages"]]}
            if "scales" in entry:
                e["scales"] = [np.asarray(s) for s in entry["scales"]]
            self._spill[int(lp)] = e

    def n_resident(self) -> int:
        return int((self._pt[:self.lp] != self.sentinel).sum())

    def memory_bytes(self, state: dict) -> dict:
        pages_b = sum(leaf.nbytes for leaf in jax.tree.leaves(state["pages"]))
        if self.quantized:
            pages_b += sum(leaf.nbytes
                           for leaf in jax.tree.leaves(state["scales"]))
        dev = pages_b + state["page_table"].nbytes
        dev += sum(leaf.nbytes for leaf in jax.tree.leaves(state["g_sum"]))
        host = sum(a.nbytes for e in self._spill.values()
                   for arrs in e.values() for a in arrs)
        # device_pages isolates the bounded allocation the paging bound is
        # stated over: (n_slots+1) pages x page_size x d, independent of N
        return {"device": dev, "host": host, "device_pages": pages_b}

    def check_invariants(self, state: dict | None = None) -> None:
        """Page-table invariants: no aliased slots, free-list conservation,
        mirror consistency, no page both resident and spilled; with `state`,
        also that the device table matches the mirror and the dummy page is
        exact zeros."""
        resident = {int(l): int(s) for l, s in enumerate(self._pt[:self.lp])
                    if s != self.sentinel}
        slots = list(resident.values())
        assert len(slots) == len(set(slots)), "aliased physical slots"
        assert all(0 <= s < self.n_slots for s in slots), "slot out of range"
        assert int(self._pt[self.lp]) == self.n_slots, "dummy page unpinned"
        assert len(self._free) + len(resident) == self.n_slots, \
            "free-list conservation violated"
        assert set(self._free).isdisjoint(slots), "slot both free and mapped"
        for l, s in resident.items():
            assert int(self._slot_lp[s]) == l, "slot->page mirror drifted"
        for s in self._free:
            assert int(self._slot_lp[s]) == -1, "free slot still mapped"
        assert set(self._spill).isdisjoint(resident), \
            "page both resident and spilled"
        if state is not None:
            pt = np.asarray(state["page_table"])
            fleet = pt.ndim == 2
            if fleet:
                assert (pt == pt[0]).all(), "fleet page tables diverged"
                pt = pt[0]
            assert (pt == self._pt).all(), "device page table != host mirror"
            start = self.n_slots * self.page_size
            for leaf in jax.tree.leaves(state["pages"]):
                dummy = np.asarray(leaf[:, start:] if fleet
                                   else leaf[start:])
                assert (dummy == 0).all(), "dummy page not zero"
