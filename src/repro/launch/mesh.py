"""Device meshes for the simulator's sharded paths.

Meshes are built by functions (never module-level constants) so
importing this module touches no JAX device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-partitioned)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires host-device override)."""
    return _mesh(shape, axes)


def make_rep_mesh(n_devices: int | None = None):
    """1-D device mesh over the simulator's replication axis.

    The streaming engine (:func:`repro.core.streaming.simulate_stream`)
    shards stacked replications / policy-sweep cells across devices by
    placing the batched carry and per-chunk inputs with a
    ``NamedSharding`` over this mesh's single ``"rep"`` axis (see
    :mod:`repro.distribution.sim_shard`).  Defaults to all local
    devices; pass ``n_devices`` to use a prefix of them.
    """
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    if n < 1:
        raise ValueError(f"n_devices must be >= 1, got {n}")
    return _mesh((n,), ("rep",))
