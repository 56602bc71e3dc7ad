"""Pallas TPU kernel for the Hermes controller hot loop (paper §4.2, §6.6).

The OpenWhisk controller sustains ~3.8 k scheduling decisions per second;
each decision is a streaming reduction over the per-worker load vector
(score → argmax → load update).  A scalar implementation re-reads the
cluster state from HBM per invocation.  On TPU the natural formulation is
*batched sequential dispatch*: the whole ``[W]`` active-count vector stays
resident in VMEM while a batch of arrivals is dispatched in order — one
HBM read of cluster state per *batch* rather than per invocation, with
each decision a vectorized O(W) score + argmax on the VPU.

Semantics (must match ``repro.core.policies.hermes_score_np`` exactly —
the sequential dependency is preserved, this is not an approximation):

* low-load mode (∃ worker with a free core): among workers with a free
  core prefer class 3 = non-empty & warm, 2 = non-empty, 1 = warm,
  0 = empty; within a class prefer the most loaded (packing).
* high-load mode: least-loaded among workers with a free slot; warmth
  breaks ties.  All-full → sentinel ``-1`` (rejection).

Completions between arrivals are applied by the caller batch-by-batch
(the serving controller syncs worker state at batch boundaries, exactly
like the paper's synchronous Controller↔Worker protocol).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_BIG = 1 << 30
# strong int32 constants: under x64 a Python-int literal is a weak int64
# scalar, whose conversion the chip's kernel compiler cannot lower
_NEG = np.int32(-_BIG)
_NONE = np.int32(-1)


def _kernel(active_ref, warm_ref, out_ref, active_out_ref,
            *, n: int, cores: int, slots: int):
    W = active_ref.shape[1]
    lanes_w = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    lanes_n = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def body(i, carry):
        active, choices = carry                       # [1, W], [1, N] i32
        warm_i = (warm_ref[pl.ds(i, 1), :] > 0).astype(jnp.int32)
        has_slot = active < slots
        has_core = active < cores
        cls = jnp.where(active > 0, 2 + warm_i, warm_i)
        lo = jnp.where(has_core, cls * (slots + 1) + active, _NEG)
        hi = jnp.where(has_slot, -(active * 2 - warm_i), _NEG)
        # the chip reduces in f32 only; every score is an integer far
        # below 2**24 and -_BIG = -2**30 is exact, so the f32 max and
        # the min of the masked lane index are numpy's first argmax
        low_mode = jnp.max(has_core.astype(jnp.float32)) > 0
        score = jnp.where(low_mode, lo, hi).astype(jnp.float32)
        top = jnp.max(score)
        w = jnp.min(jnp.where(score == top, lanes_w.astype(jnp.float32),
                              jnp.float32(W))).astype(jnp.int32)
        ok = jnp.max(has_slot.astype(jnp.float32)) > 0
        # choices stay a vector (no scalar store to VMEM); one store at
        # the end
        choices = jnp.where(lanes_n == i, jnp.where(ok, w, _NONE), choices)
        active = jnp.where(ok & (lanes_w == w), active + 1, active)
        return active, choices

    # strong-typed bounds: Python-int literals would thread a weak int64
    # loop index through the loop (repro.analysis JXP001)
    active, choices = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(n), body,
        (active_ref[...], jnp.full((1, n), _NONE, dtype=jnp.int32)))
    out_ref[...] = choices
    active_out_ref[...] = active


def interpret_mode() -> bool:
    """Whether the kernel runs in the Pallas interpreter: off a TPU only.

    A CPU-only convenience for tests; on a TPU the kernel is compiled.
    """
    return jax.default_backend() != "tpu"


def hermes_select_batch(active, warm_cols, *, cores: int, slots: int,
                        interpret: bool = False):
    """Dispatch a batch of arrivals with Hermes hybrid balancing.

    active: [W] int32 current per-worker active counts;
    warm_cols: [N, W] int32 — warm-executor count of each arrival's
    function on each worker (gathered by the caller from ``warm[W, F]``).

    Returns (choices [N] int32 — worker ids or -1, active_out [W]).
    Every operand is at least 2-D with full-array blocks, so under
    ``vmap`` (the scan engine's replication axis) the batched blocks
    still meet the chip's (8, 128) tiling rule.
    """
    W = active.shape[0]
    N = warm_cols.shape[0]
    kernel = functools.partial(_kernel, n=N, cores=cores, slots=slots)
    # full-array blocks; int32 block indices (a Python 0 is int64 under
    # x64, which the kernel compiler refuses once vmap adds a grid axis)
    zero = lambda: (np.int32(0), np.int32(0))
    out, active_out = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec((1, W), zero), pl.BlockSpec((N, W), zero)],
        out_specs=[pl.BlockSpec((1, N), zero), pl.BlockSpec((1, W), zero)],
        out_shape=[jax.ShapeDtypeStruct((1, N), jnp.int32),
                   jax.ShapeDtypeStruct((1, W), jnp.int32)],
        interpret=interpret,
        name="hermes_select",
    )(active[None], warm_cols)
    return out[0], active_out[0]
