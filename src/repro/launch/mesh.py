"""Production mesh construction + sharding-context assembly.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no JAX device state.  The single-pod mesh
is 16×16 = 256 chips (one v5e pod); multi-pod adds a leading ``pod`` axis
(2 × 256 = 512 chips) used as an outer data-parallel / replica axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.distribution.sharding import ShardCtx, make_rules


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-partitioned)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


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


def make_ctx(mesh, cfg, shape_cfg=None, **rule_overrides) -> ShardCtx:
    """Build the sharding context for (arch cfg × input shape × mesh)."""
    multi_pod = "pod" in mesh.axis_names
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    seq_kv_data = bool(shape_cfg is not None
                       and shape_cfg.kind == "decode"
                       and shape_cfg.seq_len >= 262_144)
    rules = make_rules(multi_pod=multi_pod, fsdp=cfg.fsdp,
                       shard_heads=cfg.shard_heads,
                       seq_kv_data=seq_kv_data)
    if shape_cfg is not None and shape_cfg.global_batch % dp != 0:
        rules["batch"] = None            # e.g. long_500k's global_batch=1
    # sequence-parallel residual stream for many-token steps: the values
    # remat saves shrink by the TP degree (decode steps have S=1 — off).
    if (shape_cfg is not None and shape_cfg.kind in ("train", "prefill")
            and shape_cfg.seq_len % mesh.shape["model"] == 0):
        rules["act_seq"] = "model"
    # Serving weight layout: on the decode latency path a ZeRO-3/FSDP
    # layout forces a per-layer weight all-gather that moves far more
    # bytes than the few decode tokens need.  Serving replicas keep
    # params TP-sharded only (they fit without optimizer state); MoE
    # expert weights additionally shard their ff dim over 'data'
    # (reads stay local, the combine psum is [T,D]-sized).
    if shape_cfg is not None and shape_cfg.kind == "decode":
        rules["fsdp"] = None
        if cfg.moe is not None:
            rules["expert_ff"] = "data"
    rules.update(rule_overrides)
    return ShardCtx(mesh=mesh, rules=rules, dp_axes=dp_axes,
                    tp_axis="model",
                    pod_axis="pod" if multi_pod else None)
