"""Compile-only checks that the bank kernels build for a TPU v5e.

Interpret mode (how every other test runs the kernels) accepts block
shapes the TPU compiler refuses, so each kernel is compiled here for a
described, not attached, `v5e:2x2` chip — at `paper_mlp`'s leaf widths and,
for the paged kernels, at `chip_smoke.py`'s cross-device page table — and
must come out as a Mosaic kernel (`tpu_custom_call`). Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and pytest's workers import every test file.
"""
import os

import pytest

# paper_mlp leaves: W1 (256, 128), W2 (128, 128), W3 (128, 10), b1/b2 (128,),
# b3 (10,) — flattened widths 32768, 16384, 1280, 128, 10
LEAVES = {32768: (256, 128), 16384: (128, 128), 1280: (128, 10),
          128: (128,), 10: (10,)}
DENSE_ROWS, COHORT = 101, 128               # N=100 plus the dummy row
# chip_smoke.py cross_device: N=10^6, page_size=16, n_slots=1024, cap 256
PAGE_SIZE, N_SLOTS, CROSS_COHORT = 16, 1024, 256
PAGE_TABLE = 1_000_000 // PAGE_SIZE + 1
TRIALS = 2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shape(one_chip, shape, dtype="float32"):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)


def _assert_kernel(fn, *args):
    import jax
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _tree(one_chip, lead, leaf):
    return {"w": _shape(one_chip, lead + leaf)}


@pytest.mark.parametrize("width", list(LEAVES))
def test_bank_scatter_compiles(one_chip, width):
    from repro.kernels.ops import bank_update_tree_pure
    leaf = LEAVES[width]
    _assert_kernel(
        lambda r, u, i, v: bank_update_tree_pure(r, u, i, v,
                                                 interpret=False),
        _tree(one_chip, (DENSE_ROWS,), leaf),
        _tree(one_chip, (COHORT,), leaf),
        _shape(one_chip, (COHORT,), "int32"),
        _shape(one_chip, (COHORT,), "bool"))


@pytest.mark.parametrize("width", list(LEAVES))
def test_bank_scatter_batched_compiles(one_chip, width):
    from repro.kernels.ops import fleet_bank_update_tree_pure
    leaf = LEAVES[width]
    _assert_kernel(
        lambda r, u, i, v: fleet_bank_update_tree_pure(r, u, i, v,
                                                       interpret=False),
        _tree(one_chip, (TRIALS, DENSE_ROWS), leaf),
        _tree(one_chip, (TRIALS, COHORT), leaf),
        _shape(one_chip, (TRIALS, COHORT), "int32"),
        _shape(one_chip, (TRIALS, COHORT), "bool"))


def _paged_rows():
    return (N_SLOTS + 1) * PAGE_SIZE


@pytest.mark.parametrize("width", list(LEAVES))
def test_paged_bank_scatter_compiles(one_chip, width):
    from repro.kernels.ops import paged_bank_update_tree_pure
    leaf = LEAVES[width]
    _assert_kernel(
        lambda p, u, pt, i, v: paged_bank_update_tree_pure(
            p, u, pt, i, v, page_size=PAGE_SIZE, interpret=False),
        _tree(one_chip, (_paged_rows(),), leaf),
        _tree(one_chip, (CROSS_COHORT,), leaf),
        _shape(one_chip, (PAGE_TABLE,), "int32"),
        _shape(one_chip, (CROSS_COHORT,), "int32"),
        _shape(one_chip, (CROSS_COHORT,), "bool"))


@pytest.mark.parametrize("width", list(LEAVES))
def test_paged_bank_gather_compiles(one_chip, width):
    from repro.kernels.ops import paged_bank_gather_tree_pure
    leaf = LEAVES[width]
    _assert_kernel(
        lambda p, pt, i: paged_bank_gather_tree_pure(
            p, pt, i, page_size=PAGE_SIZE, interpret=False),
        _tree(one_chip, (_paged_rows(),), leaf),
        _shape(one_chip, (PAGE_TABLE,), "int32"),
        _shape(one_chip, (CROSS_COHORT,), "int32"))


@pytest.mark.parametrize("width", list(LEAVES))
def test_paged_bank_scatter_batched_compiles(one_chip, width):
    from repro.kernels.ops import fleet_paged_bank_update_tree_pure
    leaf = LEAVES[width]
    # one trial's page table per trial: TRIALS x PAGE_TABLE entries of SMEM
    _assert_kernel(
        lambda p, u, pt, i, v: fleet_paged_bank_update_tree_pure(
            p, u, pt, i, v, page_size=PAGE_SIZE, interpret=False),
        _tree(one_chip, (TRIALS, 4 * PAGE_SIZE), leaf),
        _tree(one_chip, (TRIALS, CROSS_COHORT), leaf),
        _shape(one_chip, (TRIALS, PAGE_TABLE), "int32"),
        _shape(one_chip, (TRIALS, CROSS_COHORT), "int32"),
        _shape(one_chip, (TRIALS, CROSS_COHORT), "bool"))


def test_largest_accepted_page_table_compiles(one_chip):
    """The SMEM bound the paged bank enforces is one the compiler takes."""
    from repro.kernels.bank_scatter import SMEM_PAGE_TABLE_ENTRIES
    from repro.kernels.ops import paged_bank_update_tree_pure
    _assert_kernel(
        lambda p, u, pt, i, v: paged_bank_update_tree_pure(
            p, u, pt, i, v, page_size=PAGE_SIZE, interpret=False),
        _tree(one_chip, (64 * PAGE_SIZE,), (128,)),
        _tree(one_chip, (256,), (128,)),
        _shape(one_chip, (SMEM_PAGE_TABLE_ENTRIES,), "int32"),
        _shape(one_chip, (256,), "int32"),
        _shape(one_chip, (256,), "bool"))
