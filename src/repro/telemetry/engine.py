"""Telemetry plane 1 — streaming in-engine metrics (jax side).

The functional twins of :mod:`repro.telemetry.state`'s numpy updaters:
each takes the telemetry pytree (dict of jax arrays) plus traced event
operands and returns the updated pytree.  They are called *inside* the
simulator's ``lax.while_loop`` / ``lax.scan`` bodies, behind a python
gate (``if tel_on:``) identical in spirit to ``SimState.life`` — with
telemetry off the engine traces the bit-identical pre-telemetry
program.

Parity contract with the numpy side:

* bin assignment uses ``jnp.searchsorted(edges, x, side="right") - 1``
  over the *same float64 edge array* (``sketch.hist_edges()``) — a
  binary search over identical bits yields identical integer bins, so
  histogram counts are bitwise np ≡ jax;
* counters are int64 adds of exact small integers — bitwise equal;
* time integrals are float64 ``tau * occupancy`` sums accumulated in
  the same event order — equal to ~1e-9 relative (same tolerance class
  as the engines' ``server_time`` agreement).

Each updater runs under ``jax.named_scope("tel")``, so a device
profile can attribute the plane's operations (see
:data:`repro.core.simulator.STEP_SCOPES`).

This module is jax-only by design: it is imported from
``repro.core.simulator`` (a hot-path module), never from the numpy
oracle, so ``repro.telemetry`` stays importable without jax.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .sketch import N_BINS, hist_edges


def init_state(n_workers: int) -> dict:
    """Zeroed telemetry pytree — the jax twin of ``state.init_np``."""
    return {
        "slow_hist": jnp.zeros(N_BINS, dtype=jnp.int64),
        "lat_hist": jnp.zeros(N_BINS, dtype=jnp.int64),
        "n_cold": jnp.zeros((), dtype=jnp.int64),
        "n_warm": jnp.zeros((), dtype=jnp.int64),
        "n_evict": jnp.zeros((), dtype=jnp.int64),
        "n_reject": jnp.zeros((), dtype=jnp.int64),
        "busy_time": jnp.zeros(n_workers, dtype=jnp.float64),
        "depth_time": jnp.zeros(n_workers, dtype=jnp.float64),
        "qlen_time": jnp.zeros((), dtype=jnp.float64),
        "decisions": jnp.zeros(n_workers, dtype=jnp.int64),
    }


def edges_for_trace() -> jnp.ndarray:
    """The shared bin edges as a jax constant (closed over at build)."""
    return jnp.asarray(hist_edges(), dtype=jnp.float64)


def bin_index(x, edges) -> jnp.ndarray:
    """Clamped right-searchsorted bin — twin of ``sketch.bin_index_np``."""
    return jnp.clip(jnp.searchsorted(edges, x, side="right") - 1,
                    0, N_BINS - 1)


@jax.named_scope("tel")
def on_place(tel: dict, worker, is_cold, evicted) -> dict:
    """Record one placement (callers only place *accepted* arrivals)."""
    cold = is_cold.astype(jnp.int64)
    return {
        **tel,
        "n_cold": tel["n_cold"] + cold,
        "n_warm": tel["n_warm"] + (jnp.int64(1) - cold),
        "n_evict": tel["n_evict"] + evicted.astype(jnp.int64),
        "decisions": tel["decisions"].at[worker].add(jnp.int64(1)),
    }


@jax.named_scope("tel")
def on_advance(tel: dict, tau, active, depth, qlen) -> dict:
    """Accumulate pre-advance occupancy integrals over interval ``tau``."""
    return {
        **tel,
        "busy_time": tel["busy_time"]
        + tau * active.astype(jnp.float64),
        "depth_time": tel["depth_time"]
        + tau * depth.astype(jnp.float64),
        "qlen_time": tel["qlen_time"]
        + tau * qlen.astype(jnp.float64),
    }


@jax.named_scope("tel")
def on_complete(tel: dict, response, service, arr_idx, completed,
                cutoff, edges) -> dict:
    """Scatter one (masked) completion into both histograms.

    ``completed`` is the per-worker completion mask for this advance;
    completions of warmup tasks (``arr_idx < cutoff``) are masked out so
    the sketch population equals ``summarize``'s post-warmup set.
    Masked lanes scatter into a dropped out-of-range bin.
    """
    rec = completed & (arr_idx >= cutoff)
    slow = response / jnp.maximum(service, 1e-12)
    slow_bin = jnp.where(rec, bin_index(slow, edges), N_BINS)
    lat_bin = jnp.where(rec, bin_index(response, edges), N_BINS)
    return {
        **tel,
        "slow_hist": tel["slow_hist"].at[slow_bin].add(jnp.int64(1),
                                                       mode="drop"),
        "lat_hist": tel["lat_hist"].at[lat_bin].add(jnp.int64(1),
                                                    mode="drop"),
    }


@jax.named_scope("tel")
def on_evict(tel: dict, count) -> dict:
    """Add ``count`` lifecycle (idle-budget / keep-alive) evictions."""
    return {**tel, "n_evict": tel["n_evict"] + count.astype(jnp.int64)}


@jax.named_scope("tel")
def on_reject(tel: dict, rejected) -> dict:
    return {**tel,
            "n_reject": tel["n_reject"] + rejected.astype(jnp.int64)}
