"""Logical-axis sharding context (MaxText-style rules, contextvar-scoped).

Model code never names physical mesh axes.  It annotates tensors with
*logical* dimension names (``shard(x, "batch", "seq", "embed")``) and the
caller installs a :class:`ShardCtx` that maps logical names to physical
mesh axes.  Outside any context the annotations are no-ops, so the same
model code runs single-device and SPMD unchanged.

Logical axis vocabulary
=======================

==============  ==========================================================
``batch``       global batch — data parallel
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
    tp_axis: str = "model"       # physical axis for shard_map collectives

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


# ---------------------------------------------------------------------------
# Rules construction
# ---------------------------------------------------------------------------

def make_rules() -> dict[str, AxisVal]:
    """Standard logical→physical rules for a ``("data", "model")`` mesh.

    ``fsdp``, ``expert_ff``, ``seq_kv`` and ``act_seq`` map to no axis;
    a caller that wants ZeRO-3 parameter sharding, MoE expert weights
    sharded on their ff dim, a data-sharded decode-cache sequence dim or
    sequence-parallel residual activations sets them in the returned
    dict.
    """
    return {
        "batch": ("data",),
        "seq": None,
        "embed": None,
        "heads": "model",
        "kv_heads": "model",
        "ff": "model",
        "vocab": "model",
        "expert": "model",
        "tokens_tp": "model",
        "state": "model",
        "fsdp": None,
        "expert_ff": None,
        "seq_kv": None,
        "seq_kv_tp": "model",    # decode-cache seq dim when kv_heads ∤ TP
        "act_seq": None,
    }
