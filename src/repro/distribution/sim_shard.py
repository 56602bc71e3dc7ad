"""Replication-axis device sharding for the streaming simulator.

The simulator's unit of parallelism is the replication: replications
(seeds / policy-sweep cells) are independent, so the batched carry of
:func:`repro.core.streaming.simulate_stream` simply splits its leading
``R`` axis across a 1-D ``"rep"`` mesh
(:func:`repro.launch.mesh.make_rep_mesh`).  Every leaf of the carry and
every per-chunk input is placed with ``NamedSharding(mesh, P("rep",
None, ...))``, and the chunk and drain programs run under
:func:`rep_local`: a ``shard_map`` over that axis, so each device scans
its own block of replications with no cross-device communication
(replications never interact).  Left to GSPMD instead, the vmapped
completion loop's predicate (any replication still busy) becomes an
all-reduce per iteration, and a Pallas kernel in the scan (Hermes)
cannot be partitioned at all.

Unbatched operands (the global-id / valid-mask vectors, whose cond
predicates must stay scalar) are left alone — jit replicates them.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

#: The mesh axis name the replication dimension maps onto.
REP_AXIS = "rep"


def rep_sharding(mesh, ndim: int) -> NamedSharding:
    """Sharding for one leaf: leading axis over ``"rep"``, rest replicated."""
    return NamedSharding(mesh, P(REP_AXIS, *([None] * (ndim - 1))))


def rep_local(fn, mesh, in_axes):
    """Run ``fn`` per device on its block of the replication axis.

    ``in_axes`` gives, per positional argument, ``0`` for an operand
    batched over reps (every leaf leads with the rep axis) or ``None``
    for one that every device sees whole; every output is rep-batched.
    """
    spec = lambda ax: P() if ax is None else P(REP_AXIS)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(spec(ax) for ax in in_axes),
                         out_specs=P(REP_AXIS), check_vma=False)


def shard_reps(tree, mesh):
    """``device_put`` every leaf with its leading rep axis sharded.

    Every leaf must carry the replication axis first (the streaming
    carry and batched workload planes do) and its extent must divide
    over the mesh — both violations raise named errors instead of XLA
    layout failures.
    """
    if REP_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh has axes {mesh.axis_names}, expected a 1-D "
            f"{REP_AXIS!r} mesh — build one with "
            f"repro.launch.mesh.make_rep_mesh()")
    n = mesh.shape[REP_AXIS]

    def put(x):
        if getattr(x, "ndim", 0) == 0:
            return jax.device_put(x, NamedSharding(mesh, P()))
        if x.shape[0] % n != 0:
            raise ValueError(
                f"replication axis of size {x.shape[0]} does not "
                f"divide across the {n}-device {REP_AXIS!r} mesh; "
                f"pad the rep count or shrink the mesh "
                f"(make_rep_mesh(n_devices=...))")
        return jax.device_put(x, rep_sharding(mesh, x.ndim))

    return jax.tree_util.tree_map(put, tree)
