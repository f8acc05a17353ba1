"""Fused row-gather / delta / scatter Pallas kernel for the memory bank.

The bank update touched by a cohort round is

    old_a      = bank[ids[a]]                      (gather)
    delta_sum += Σ_a valid_a · (u_a − old_a)       (running-sum maintenance)
    bank[ids[a]] = u_a        if valid_a           (scatter)

Done naively with jnp this is three passes over the cohort rows (gather,
delta reduction, `.at[ids].set`) plus a full-array copy for the scatter.
The kernel streams each active row's column tile through VMEM exactly once
— read old, accumulate the delta, write the fresh update back in place
(`input_output_aliases` donates the bank buffer, so untouched rows are
never copied). HBM traffic is O(|A|·d) regardless of the bank's N.

Grid: (column tiles, cohort rows) — the cohort axis is innermost so the
delta-sum output tile stays resident in VMEM and accumulates across rows
(the classic k-loop pattern). Row ids arrive via scalar prefetch
(`PrefetchScalarGridSpec`), so the BlockSpec index map can address
`bank[ids[a]]` before the body runs — the canonical dynamic-gather idiom.

Padded cohort slots (valid=0) must point `ids` at a dedicated dummy row
(the caller uses row index N of an (N+1)-row bank): the kernel writes the
row's own old value back (a no-op, deterministic even when every pad slot
aliases the same dummy row) and contributes zero to the delta sum.

Row layout: the kernels see a bank of R rows as an (R, 1, M) array and
move one row per step in a (None, 1, block_m) block. The TPU compiler
takes a block whose last two dims are (1, block_m) of a (…, 1, M) array —
the second-minor dim equals the array's — but refuses a (1, block_m)
block of an (R, M) array (its second-minor 1 neither divides by 8 nor
equals R). The public wrappers keep the 2-D (R, M) interface and reshape,
which is free whenever R's tiled layout needs no padding. One row per
step is below the (8, 128) sublane optimum — acceptable for a DMA-bound
gather (the same trade embedding-lookup kernels make).

Page tables and row ids reach the index maps through scalar memory
(SMEM); `check_page_table_fits` bounds the page-table size the compiled
paged kernels accept.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

# Scalar prefetch copies page tables into SMEM. The bound is the TPU v5e's
# (1 MiB of SMEM; other generations differ): a 3·2**16-entry int32 table
# (768 KiB) compiles beside 256 cohort ids and masks; 2**18 entries (the
# whole 1 MiB) is refused by the compiler.
SMEM_PAGE_TABLE_ENTRIES = 3 << 16


def check_page_table_fits(n_entries: int, page_size: int) -> None:
    """Raise when a compiled paged kernel's page table cannot fit SMEM.

    `n_entries` counts every table entry the kernel prefetches (K tables of
    P entries for the fleet kernel). Fails here, naming the remedy, rather
    than inside the TPU compiler.
    """
    if n_entries > SMEM_PAGE_TABLE_ENTRIES:
        raise ValueError(
            f"page table of {n_entries} entries exceeds the "
            f"{SMEM_PAGE_TABLE_ENTRIES} that fit the TPU's scalar memory "
            f"(SMEM) beside the cohort ids; raise page_size (now "
            f"{page_size}) so that N / page_size fits, or build the bank "
            "with use_pallas=False")


def _paged_interpret(page_table, page_size: int, interpret) -> bool:
    """Resolve interpret mode for a paged kernel call; a compiled call
    first checks that every table entry it prefetches fits SMEM."""
    interpret = resolve_interpret(interpret)
    if not interpret:
        check_page_table_fits(page_table.size, page_size)
    return interpret


def _update_row(valid, first, u_ref, old_ref, out_ref, dsum_ref):
    """One cohort row's column tile: masked delta into the resident
    delta-sum tile, fresh (or old, when invalid) value written back."""
    old = old_ref[...]                                    # (1, bm) bank dtype
    u = u_ref[...]                                        # (1, bm) f32

    @pl.when(first)
    def _init():
        dsum_ref[...] = jnp.zeros_like(dsum_ref)

    # delta uses the *stored* (dtype-cast) value, not the raw f32 update —
    # keeps G_sum == Σ rows exact for bf16 banks (same as the jnp path)
    u_st = u.astype(old_ref.dtype)
    dsum_ref[...] += jnp.where(
        valid, u_st.astype(jnp.float32) - old.astype(jnp.float32), 0.0)
    out_ref[...] = jnp.where(valid, u_st, old)


def _rows3(x):
    """(..., R, M) -> (..., R, 1, M): the row layout the kernels block."""
    return x.reshape(x.shape[:-1] + (1, x.shape[-1]))


def _kernel(ids_ref, valid_ref, u_ref, bank_ref, bank_out_ref, dsum_ref):
    a = pl.program_id(1)
    _update_row(valid_ref[a] > 0, a == 0, u_ref, bank_ref, bank_out_ref,
                dsum_ref)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _bank_scatter(bank, updates, ids, valid, *, block_m, interpret):
    r, m = bank.shape
    c = updates.shape[0]
    bm = min(block_m, m)
    assert m % bm == 0, (m, bm)
    assert updates.shape == (c, m), (updates.shape, (c, m))
    assert ids.shape == valid.shape == (c,), (ids.shape, valid.shape)

    row = (None, 1, bm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                            # ids, valid
        grid=(m // bm, c),
        in_specs=[
            pl.BlockSpec(row, lambda j, a, ids, valid: (a, 0, j)),
            pl.BlockSpec(row, lambda j, a, ids, valid: (ids[a], 0, j)),
        ],
        out_specs=[
            pl.BlockSpec(row, lambda j, a, ids, valid: (ids[a], 0, j)),
            pl.BlockSpec((1, bm), lambda j, a, ids, valid: (0, j)),
        ],
    )
    new_bank, dsum = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, 1, m), bank.dtype),
                   jax.ShapeDtypeStruct((1, m), jnp.float32)],
        input_output_aliases={3: 0},                      # bank updated in place
        interpret=interpret,
    )(ids, valid, _rows3(updates), _rows3(bank))
    return new_bank.reshape(r, m), dsum


def bank_scatter(bank: jnp.ndarray, updates: jnp.ndarray, ids: jnp.ndarray,
                 valid: jnp.ndarray, *, block_m: int = 512,
                 interpret: bool | None = None):
    """bank (R, M); updates (C, M) f32; ids (C,) int32 < R; valid (C,) bool.

    Returns (new_bank (R, M) [bank.dtype], delta_sum (M,) f32) where
    delta_sum = Σ_{valid a} (updates[a] − bank[ids[a]]). Duplicate ids are
    only allowed when at most one of them is valid (pad slots share the
    dummy row). M must be a multiple of block_m (ops.py pads).
    """
    new_bank, dsum = _bank_scatter(
        bank, updates.astype(jnp.float32), ids.astype(jnp.int32),
        valid.astype(jnp.int32), block_m=block_m,
        interpret=resolve_interpret(interpret))
    return new_bank, dsum[0]


# --------------------------------------------------------------------------- #
# batched (fleet) variant: K independent banks in one launch
# --------------------------------------------------------------------------- #

def _kernel_batched(ids_ref, valid_ref, u_ref, bank_ref, bank_out_ref,
                    dsum_ref):
    k = pl.program_id(0)
    a = pl.program_id(2)
    _update_row(valid_ref[k, a] > 0, a == 0, u_ref, bank_ref, bank_out_ref,
                dsum_ref)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _bank_scatter_batched(banks, updates, ids, valid, *, block_m, interpret):
    K, r, m = banks.shape
    c = updates.shape[1]
    bm = min(block_m, m)
    assert m % bm == 0, (m, bm)
    assert updates.shape == (K, c, m), (updates.shape, (K, c, m))
    assert ids.shape == valid.shape == (K, c), (ids.shape, valid.shape)

    # trial axis outermost, cohort rows innermost: the (k, j) delta-sum tile
    # stays resident in VMEM and accumulates across that trial's cohort,
    # exactly like the single-trial kernel's k-loop
    row = (None, None, 1, bm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                            # ids, valid (K, C)
        grid=(K, m // bm, c),
        in_specs=[
            pl.BlockSpec(row, lambda k, j, a, ids, valid: (k, a, 0, j)),
            pl.BlockSpec(row,
                         lambda k, j, a, ids, valid: (k, ids[k, a], 0, j)),
        ],
        out_specs=[
            pl.BlockSpec(row,
                         lambda k, j, a, ids, valid: (k, ids[k, a], 0, j)),
            pl.BlockSpec((None, 1, bm),
                         lambda k, j, a, ids, valid: (k, 0, j)),
        ],
    )
    new_banks, dsum = pl.pallas_call(
        _kernel_batched,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((K, r, 1, m), banks.dtype),
                   jax.ShapeDtypeStruct((K, 1, m), jnp.float32)],
        input_output_aliases={3: 0},                      # banks in place
        interpret=interpret,
    )(ids, valid, _rows3(updates), _rows3(banks))
    return new_banks.reshape(K, r, m), dsum


def bank_scatter_batched(banks: jnp.ndarray, updates: jnp.ndarray,
                         ids: jnp.ndarray, valid: jnp.ndarray, *,
                         block_m: int = 512,
                         interpret: bool | None = None):
    """Grid-axis batched `bank_scatter` for the fleet executor.

    banks (K, R, M); updates (K, C, M) f32; ids (K, C) int32 < R;
    valid (K, C) bool. Returns (new_banks (K, R, M), delta_sum (K, M) f32) —
    per trial k exactly what `bank_scatter(banks[k], ...)` returns. The K
    trials share one kernel launch: the trial index is the outermost grid
    dimension, so each trial's cohort streams through VMEM back-to-back with
    no host round-trips between trials.
    """
    new_banks, dsum = _bank_scatter_batched(
        banks, updates.astype(jnp.float32), ids.astype(jnp.int32),
        valid.astype(jnp.int32), block_m=block_m,
        interpret=resolve_interpret(interpret))
    return new_banks, dsum[:, 0]


# --------------------------------------------------------------------------- #
# paged variants: rows addressed through a page-table indirection
# --------------------------------------------------------------------------- #
#
# The paged device bank (bank/paged_device.py) stores rows in fixed-size
# physical pages: logical row `lid` lives at physical row
#
#     page_table[lid // page_size] * page_size + lid % page_size
#
# The page table rides the scan carry as a plain int32 array, so it arrives
# here via scalar prefetch exactly like the row ids — the page LOOKUP happens
# inside the BlockSpec index map, before the kernel body runs. Non-resident
# logical pages map to the dedicated dummy slot (the caller's sentinel), so a
# stray access reads zeros and writes are no-ops; the bank's `prepare` hook
# guarantees every *valid* row is resident before a round executes.
#
# The kernel bodies are the flat kernels' `_update_row` (read old,
# accumulate the masked delta, write the fresh update back in place) — only
# the addressing differs, which is exactly why paged trajectories stay
# fp32 bit-exact against the flat bank: reductions run over the cohort axis,
# never over physical rows, so slot placement can never change a value.


def _paged_kernel(pt_ref, lids_ref, valid_ref, u_ref, pages_ref,
                  pages_out_ref, dsum_ref):
    a = pl.program_id(1)
    _update_row(valid_ref[a] > 0, a == 0, u_ref, pages_ref, pages_out_ref,
                dsum_ref)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "block_m", "interpret"))
def _paged_bank_scatter(pages, updates, page_table, lids, valid, *,
                        page_size, block_m, interpret):
    r, m = pages.shape
    c = updates.shape[0]
    ps = page_size
    bm = min(block_m, m)
    assert m % bm == 0, (m, bm)
    assert updates.shape == (c, m), (updates.shape, (c, m))
    assert lids.shape == valid.shape == (c,), (lids.shape, valid.shape)

    def _prow(a, pt, lids):
        return pt[lids[a] // ps] * ps + lids[a] % ps

    # the page lookup IS the index map: scalar-prefetched page_table + lids
    # resolve each cohort slot to its physical row before the body runs
    row = (None, 1, bm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                            # pt, lids, valid
        grid=(m // bm, c),
        in_specs=[
            pl.BlockSpec(row, lambda j, a, pt, lids, valid: (a, 0, j)),
            pl.BlockSpec(row, lambda j, a, pt, lids, valid:
                         (_prow(a, pt, lids), 0, j)),
        ],
        out_specs=[
            pl.BlockSpec(row, lambda j, a, pt, lids, valid:
                         (_prow(a, pt, lids), 0, j)),
            pl.BlockSpec((1, bm), lambda j, a, pt, lids, valid: (0, j)),
        ],
    )
    new_pages, dsum = pl.pallas_call(
        _paged_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, 1, m), pages.dtype),
                   jax.ShapeDtypeStruct((1, m), jnp.float32)],
        input_output_aliases={4: 0},                      # pages in place
        interpret=interpret,
    )(page_table, lids, valid, _rows3(updates), _rows3(pages))
    return new_pages.reshape(r, m), dsum


def paged_bank_scatter(pages: jnp.ndarray, updates: jnp.ndarray,
                       page_table: jnp.ndarray, lids: jnp.ndarray,
                       valid: jnp.ndarray, *, page_size: int,
                       block_m: int = 512, interpret: bool | None = None):
    """Fused gather/delta/scatter through a page-table indirection.

    pages (R, M) with R = (slots+1)·page_size; updates (C, M) f32;
    page_table (P,) int32 slot per logical page (sentinel -> dummy slot);
    lids (C,) int32 *sanitized* logical rows (pad slots already remapped to
    the dummy logical page by the caller); valid (C,) bool. Returns
    (new_pages, delta_sum (M,) f32) — per slot exactly `bank_scatter` on the
    physically-addressed rows.
    """
    interpret = _paged_interpret(page_table, page_size, interpret)
    new_pages, dsum = _paged_bank_scatter(
        pages, updates.astype(jnp.float32), page_table.astype(jnp.int32),
        lids.astype(jnp.int32), valid.astype(jnp.int32),
        page_size=page_size, block_m=block_m, interpret=interpret)
    return new_pages, dsum[0]


def _paged_gather_kernel(pt_ref, lids_ref, pages_ref, out_ref):
    del pt_ref, lids_ref
    out_ref[...] = pages_ref[...].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "block_m", "interpret"))
def _paged_bank_gather(pages, page_table, lids, *, page_size, block_m,
                       interpret):
    r, m = pages.shape
    c = lids.shape[0]
    ps = page_size
    bm = min(block_m, m)
    assert m % bm == 0, (m, bm)

    row = (None, 1, bm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                            # pt, lids
        grid=(m // bm, c),
        in_specs=[
            pl.BlockSpec(row, lambda j, a, pt, lids:
                         (pt[lids[a] // ps] * ps + lids[a] % ps, 0, j)),
        ],
        out_specs=[pl.BlockSpec(row, lambda j, a, pt, lids: (a, 0, j))],
    )
    (out,) = pl.pallas_call(
        _paged_gather_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((c, 1, m), jnp.float32)],
        interpret=interpret,
    )(page_table, lids, _rows3(pages))
    return out.reshape(c, m)


def paged_bank_gather(pages: jnp.ndarray, page_table: jnp.ndarray,
                      lids: jnp.ndarray, *, page_size: int,
                      block_m: int = 512, interpret: bool | None = None):
    """Row gather through the page table: (C, M) f32 rows for `lids`.

    Non-resident logical pages read the dummy slot (exact zeros by the
    bank's invariant); the caller masks or `prepare`s as needed.
    """
    interpret = _paged_interpret(page_table, page_size, interpret)
    return _paged_bank_gather(
        pages, page_table.astype(jnp.int32), lids.astype(jnp.int32),
        page_size=page_size, block_m=block_m, interpret=interpret)


def _paged_kernel_batched(pt_ref, lids_ref, valid_ref, u_ref, pages_ref,
                          pages_out_ref, dsum_ref):
    k = pl.program_id(0)
    a = pl.program_id(2)
    _update_row(valid_ref[k, a] > 0, a == 0, u_ref, pages_ref,
                pages_out_ref, dsum_ref)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "block_m", "interpret"))
def _paged_bank_scatter_batched(pages, updates, page_table, lids, valid, *,
                                page_size, block_m, interpret):
    K, r, m = pages.shape
    c = updates.shape[1]
    ps = page_size
    bm = min(block_m, m)
    assert m % bm == 0, (m, bm)
    assert updates.shape == (K, c, m), (updates.shape, (K, c, m))
    assert lids.shape == valid.shape == (K, c), (lids.shape, valid.shape)

    def _prow(k, a, pt, lids):
        return pt[k, lids[k, a] // ps] * ps + lids[k, a] % ps

    row = (None, None, 1, bm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                            # pt, lids, valid
        grid=(K, m // bm, c),
        in_specs=[
            pl.BlockSpec(row, lambda k, j, a, pt, lids, valid: (k, a, 0, j)),
            pl.BlockSpec(row, lambda k, j, a, pt, lids, valid:
                         (k, _prow(k, a, pt, lids), 0, j)),
        ],
        out_specs=[
            pl.BlockSpec(row, lambda k, j, a, pt, lids, valid:
                         (k, _prow(k, a, pt, lids), 0, j)),
            pl.BlockSpec((None, 1, bm),
                         lambda k, j, a, pt, lids, valid: (k, 0, j)),
        ],
    )
    new_pages, dsum = pl.pallas_call(
        _paged_kernel_batched,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((K, r, 1, m), pages.dtype),
                   jax.ShapeDtypeStruct((K, 1, m), jnp.float32)],
        input_output_aliases={4: 0},                      # pages in place
        interpret=interpret,
    )(page_table, lids, valid, _rows3(updates), _rows3(pages))
    return new_pages.reshape(K, r, m), dsum


def paged_bank_scatter_batched(pages: jnp.ndarray, updates: jnp.ndarray,
                               page_table: jnp.ndarray, lids: jnp.ndarray,
                               valid: jnp.ndarray, *, page_size: int,
                               block_m: int = 512,
                               interpret: bool | None = None):
    """Grid-axis batched `paged_bank_scatter` for the fleet executor.

    pages (K, R, M); updates (K, C, M) f32; page_table (K, P) int32 (the
    fleet keeps identical per-trial copies — one shared residency mapping);
    lids/valid (K, C). Returns (new_pages (K, R, M), delta_sum (K, M) f32),
    per trial k exactly `paged_bank_scatter(pages[k], ...)`.
    """
    interpret = _paged_interpret(page_table, page_size, interpret)
    new_pages, dsum = _paged_bank_scatter_batched(
        pages, updates.astype(jnp.float32), page_table.astype(jnp.int32),
        lids.astype(jnp.int32), valid.astype(jnp.int32),
        page_size=page_size, block_m=block_m, interpret=interpret)
    return new_pages, dsum[:, 0]
