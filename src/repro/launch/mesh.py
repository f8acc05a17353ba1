"""Production mesh builders (docs/architecture.md §5).

Functions, not module-level constants: importing this module never touches JAX
device state. The dry-run sets XLA_FLAGS for 512 host devices *before* any JAX
import; smoke tests and benchmarks see the single real CPU device. Every mesh
axis is `AxisType.Auto`: the sharding rules place arrays with explicit
`NamedSharding`s and leave the rest to the partitioner.
"""
from __future__ import annotations

import jax


def _validate_axes(shape: tuple, axes: tuple) -> None:
    """Reject malformed mesh requests up front, naming the bad axis.

    JAX itself accepts duplicate axis names in AbstractMesh (the second
    silently shadows the first in `mesh.shape`) and lets non-positive
    sizes surface later as opaque reshape errors — both have bitten the
    sharding rules, which key on axis names and divide by axis sizes.
    """
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} and axes {axes} differ "
                         "in length")
    seen = set()
    for name, size in zip(axes, shape):
        if name in seen:
            raise ValueError(f"duplicate mesh axis name {name!r} in {axes}")
        seen.add(name)
        if not isinstance(size, int) or size < 1:
            raise ValueError(f"mesh axis {name!r} has non-positive size "
                             f"{size!r}; every axis needs an int >= 1")


def make_abstract_mesh(shape: tuple, axes: tuple):
    """Device-free `AbstractMesh` of `shape` over axis names `axes`."""
    _validate_axes(shape, axes)
    return jax.sharding.AbstractMesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def _make_mesh(shape: tuple, axes: tuple, devices=None):
    _validate_axes(shape, axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    The dry-run process exposes 512 host devices; the single-pod mesh takes
    the first 256 of them.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as _np
    n = int(_np.prod(shape))
    devices = jax.devices()[:n]
    return _make_mesh(shape, axes, devices=devices)


def data_parallel_size(mesh) -> int:
    """Total extent of the client/data axes ('pod' x 'data' on multi-pod) —
    the shard count for MemoryBank rows and the MIFA update array. Delegates
    to sharding.rules so mesh helpers and partition rules can't diverge."""
    from repro.sharding.rules import data_axis_size
    return data_axis_size(mesh)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small ("data", "model") mesh over whatever devices exist (tests /
    local runs / the forced-host-device worlds of tests/conftest.py)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"make_host_mesh({data}, {model}) needs {data * model} devices "
            f"but this process has {n}; force more host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=... before "
            "JAX initialises (see tests/conftest.py)")
    return _make_mesh((data, model), ("data", "model"))
