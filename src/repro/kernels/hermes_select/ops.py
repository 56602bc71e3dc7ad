"""Jit'd wrapper for batched Hermes dispatch."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import hermes_select_batch, interpret_mode


@partial(jax.jit, static_argnames=("cores", "slots"))
def hermes_select(active, warm, funcs, *, cores: int, slots: int):
    """active: [W] i32; warm: [W, F] i32; funcs: [N] i32 arrival fn ids."""
    warm_cols = warm.T[funcs].astype(jnp.int32)       # [N, W]
    return hermes_select_batch(active.astype(jnp.int32), warm_cols,
                               cores=cores, slots=slots,
                               interpret=interpret_mode())
