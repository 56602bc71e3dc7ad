"""Logical-axis sharding context (MaxText-style rules, contextvar-scoped).

Model code never names physical mesh axes.  It annotates tensors with
*logical* dimension names (``shard(x, "batch", "seq", "embed")``) and the
launcher installs a :class:`ShardCtx` that maps logical names to physical
mesh axes.  Outside any context the annotations are no-ops, so the same
model code runs single-device (smoke tests) and SPMD (dry-run/production)
unchanged.

Logical axis vocabulary
=======================

==============  ==========================================================
``batch``       global batch — data parallel (``("pod","data")`` multi-pod)
``seq``         sequence — unsharded by default; ``seq_kv`` may map to
                ``data`` for long-context flash-decode merging
``embed``       d_model of activations — unsharded (activations replicate)
``heads``       attention query heads — tensor parallel
``kv_heads``    attention kv heads — tensor parallel when divisible
``ff``          MLP hidden — tensor parallel
``vocab``       embedding/logits vocabulary — tensor parallel
``expert``      MoE expert dim — expert parallel (maps to ``model``)
``fsdp``        parameter dim sharded over the data axis (ZeRO-3 style)
``tokens_tp``   token dim inside EP routing — maps to ``model``
``state``       recurrent state channels (RWKV/Mamba) — tensor parallel
==============  ==========================================================
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Any  # str | tuple[str, ...] | None


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    mesh: Mesh
    rules: Mapping[str, AxisVal]
    # physical axis names for collectives (shard_map paths)
    dp_axes: tuple[str, ...] = ("data",)
    tp_axis: str = "model"
    pod_axis: str | None = None

    def axis_size(self, logical: str) -> int:
        phys = self.rules.get(logical)
        if phys is None:
            return 1
        if isinstance(phys, str):
            phys = (phys,)
        n = 1
        for a in phys:
            n *= self.mesh.shape[a]
        return n


_ctx: contextvars.ContextVar[ShardCtx | None] = contextvars.ContextVar(
    "repro_shard_ctx", default=None)


def current_ctx() -> ShardCtx | None:
    return _ctx.get()


@contextlib.contextmanager
def sharding_ctx(ctx: ShardCtx):
    tok = _ctx.set(ctx)
    try:
        with ctx.mesh:
            yield ctx
    finally:
        _ctx.reset(tok)


def pspec(*logical: str | None) -> P:
    """Translate logical dim names into a PartitionSpec under the context."""
    ctx = _ctx.get()
    if ctx is None:
        return P()
    return P(*[ctx.rules.get(l) if l else None for l in logical])


def shard(x: jax.Array, *logical: str | None) -> jax.Array:
    """Constrain ``x``'s sharding by logical dim names (no-op w/o context)."""
    ctx = _ctx.get()
    if ctx is None:
        return x
    assert x.ndim == len(logical), (x.shape, logical)
    spec = pspec(*logical)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def named_sharding(*logical: str | None) -> NamedSharding | None:
    ctx = _ctx.get()
    if ctx is None:
        return None
    return NamedSharding(ctx.mesh, pspec(*logical))


def tp_size() -> int:
    ctx = _ctx.get()
    return 1 if ctx is None else ctx.mesh.shape[ctx.tp_axis]


def axis_size(logical: str) -> int:
    """Product of mesh-axis sizes a logical name maps to (1 w/o ctx)."""
    ctx = _ctx.get()
    return 1 if ctx is None else ctx.axis_size(logical)


def phys(*logical: str) -> tuple | None:
    """Concatenate the physical axes of several logical names (one dim).

    Used where a single tensor dim carries several logical shardings
    (e.g. a decode cache sequence dim sharded over data *and* model)."""
    ctx = _ctx.get()
    if ctx is None:
        return None
    axes: list = []
    for l in logical:
        a = ctx.rules.get(l)
        if a is None:
            continue
        axes.extend(a if isinstance(a, tuple) else (a,))
    return tuple(axes) if axes else None


def dp_size() -> int:
    ctx = _ctx.get()
    if ctx is None:
        return 1
    n = 1
    for a in ctx.dp_axes:
        n *= ctx.mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# Rules construction
# ---------------------------------------------------------------------------

def make_rules(*, multi_pod: bool = False, fsdp: bool = False,
               shard_heads: bool = True, shard_kv_heads: bool = True,
               seq_kv_data: bool = False) -> dict[str, AxisVal]:
    """Standard logical→physical rules for the production meshes.

    ``fsdp`` additionally shards a designated parameter dim over the data
    axis (ZeRO-3) for the ≥14 B archs.  ``shard_heads=False`` keeps
    attention replicated over the model axis (archs whose head count does
    not divide the TP degree and whose attention is a small param
    fraction, e.g. gemma-2b with 8 heads).  ``seq_kv_data=True`` maps the
    KV-cache sequence dim onto the data axis (long-context flash-decode).
    """
    dp: AxisVal = ("pod", "data") if multi_pod else ("data",)
    rules: dict[str, AxisVal] = {
        "batch": dp,
        "seq": None,
        "embed": None,
        "heads": "model" if shard_heads else None,
        "kv_heads": "model" if (shard_heads and shard_kv_heads) else None,
        "ff": "model",
        "vocab": "model",
        "expert": "model",
        "tokens_tp": "model",
        "state": "model",
        "fsdp": "data" if fsdp else None,
        # serving layout for MoE decode: expert weights sharded on the
        # per-expert ff dim over 'data' (no per-layer FSDP weight
        # all-gather on the latency path); launcher enables per-shape.
        "expert_ff": None,
        "seq_kv": "data" if seq_kv_data else None,
        "seq_kv_tp": "model",    # decode-cache seq dim when kv_heads ∤ TP
        # Megatron-style sequence parallelism: residual-stream activations
        # (the values remat saves at layer boundaries) are sharded over
        # the model axis; enabled per-shape by the launcher.
        "act_seq": None,
    }
    return rules


def param_sharding_tree(param_specs, mesh: Mesh):
    """Map a pytree of PartitionSpec to NamedSharding."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        param_specs, is_leaf=lambda s: isinstance(s, P))
