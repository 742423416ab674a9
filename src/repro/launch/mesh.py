"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — device count is locked on first jax init, and
only ``dryrun.py`` forces the 512-placeholder-device configuration.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "make_data_mesh"]


def _mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model code places arrays with
    sharding constraints and lets the partitioner propagate, which the
    Explicit axes ``make_mesh`` defaults to would reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2 pods of
    256 = 512 chips (pod, data, model); the pod axis is pure DP so the
    per-pod program is pod-count-invariant (1000+-node scaling story,
    DESIGN.md §6)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pod: int | None = None):
    """Small mesh over whatever devices exist (tests / local smoke)."""
    if pod is not None:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def make_data_mesh(data: int | None = None):
    """1-D ('data',) mesh for sharded SpMV (``repro.dist``). Defaults to
    every visible device; use XLA_FLAGS=--xla_force_host_platform_device_count=N
    (set before first jax import) to fake an N-device mesh on CPU."""
    return _mesh((data or len(jax.devices()),), ("data",))
