"""Telemetry plane 4 — windowed time-series flight recorder (jax side).

The functional twins of :mod:`repro.telemetry.timeline`'s numpy
updaters: each takes the timeline pytree (dict of jax arrays) plus
traced event operands and returns the updated pytree.  They are called
inside the simulator's ``lax.scan`` / ``lax.while_loop`` bodies behind
a python gate (``if tl_on:``), so with the timeline off the engine
traces the bit-identical pre-timeline program — the same golden
contract as ``TelemetryState`` / ``life`` / ``fleet``.

Parity contract with the numpy side:

* the window index is ``clip(floor(now / window_s), 0, K-1)`` — one f64
  division, floor and clip over identical operands on both sides, so
  window assignment is bitwise np ≡ jax;
* sketch coarsening is *integer* division of the fine bin index
  (``bin // (N_BINS // B)``) — the fine bin comes from the shared
  ``searchsorted`` over :func:`repro.telemetry.sketch.hist_edges`, so
  coarse counts are bitwise equal;
* masked updates scatter into a dropped out-of-range row
  (``mode="drop"``), mirroring the oracle's plain ``if``;
* the bounded event log writes at ``where(count < E, count, E)`` with
  ``mode="drop"`` — the count keeps incrementing past the bound so
  truncation is observable, exactly like the numpy side.

Each updater runs under ``jax.named_scope("tl")``, so a device
profile can attribute the plane's operations (see
:data:`repro.core.simulator.STEP_SCOPES`).

jax-only by design (imported from ``repro.core.simulator``, never from
the numpy oracle), like :mod:`repro.telemetry.engine`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .engine import bin_index
from .sketch import N_BINS
from .timeline import TimelineCfg, coarse_group


def init_state(n_workers: int, cfg: TimelineCfg) -> dict:
    """Zeroed timeline pytree — the jax twin of ``timeline.init_tl_np``.

    ``window_s`` starts 0 and is overwritten with the runtime width
    (horizon / K, or the configured constant) before the scan runs.
    """
    K, B = int(cfg.n_windows), int(cfg.coarse_bins)
    E = int(cfg.max_events)
    return {
        "window_s": jnp.float64(0.0),
        "mode": jnp.int32(1),
        "arrivals": jnp.zeros(K, dtype=jnp.int64),
        "n_cold": jnp.zeros(K, dtype=jnp.int64),
        "n_warm": jnp.zeros(K, dtype=jnp.int64),
        "n_evict": jnp.zeros(K, dtype=jnp.int64),
        "n_reject": jnp.zeros(K, dtype=jnp.int64),
        "slow_hist": jnp.zeros((K, B), dtype=jnp.int64),
        "lat_hist": jnp.zeros((K, B), dtype=jnp.int64),
        "busy_time": jnp.zeros((K, n_workers), dtype=jnp.float64),
        "qlen_time": jnp.zeros(K, dtype=jnp.float64),
        "prov_core": jnp.zeros(K, dtype=jnp.float64),
        "n_on": jnp.zeros(K, dtype=jnp.int32),
        "ev_t": jnp.zeros(E, dtype=jnp.float64),
        "ev_kind": jnp.zeros(E, dtype=jnp.int32),
        "ev_val": jnp.zeros(E, dtype=jnp.int32),
        "ev_p99": jnp.full(E, jnp.nan, dtype=jnp.float64),
        "ev_count": jnp.zeros((), dtype=jnp.int64),
    }


def window_index(now, window_s, n_windows: int):
    """Twin of ``timeline.window_index_np`` (identical f64 ops)."""
    safe = jnp.where(window_s > 0.0, window_s, 1.0)
    k = jnp.clip(jnp.floor(now / safe).astype(jnp.int64),
                 0, n_windows - 1)
    return jnp.where(window_s > 0.0, k, jnp.int64(0))


def _k(tl: dict, t):
    return window_index(t, tl["window_s"], tl["arrivals"].shape[0])


@jax.named_scope("tl")
def on_arrival(tl: dict, t, n_on) -> dict:
    """Count an arrival; last-write-wins the active-worker level."""
    k = _k(tl, t)
    return {
        **tl,
        "arrivals": tl["arrivals"].at[k].add(jnp.int64(1)),
        "n_on": tl["n_on"].at[k].set(
            jnp.asarray(n_on, dtype=jnp.int32)),
    }


@jax.named_scope("tl")
def on_place(tl: dict, t, is_cold, evicted) -> dict:
    """Record one placement (callers only place *accepted* arrivals)."""
    k = _k(tl, t)
    cold = is_cold.astype(jnp.int64)
    return {
        **tl,
        "n_cold": tl["n_cold"].at[k].add(cold),
        "n_warm": tl["n_warm"].at[k].add(jnp.int64(1) - cold),
        "n_evict": tl["n_evict"].at[k].add(evicted.astype(jnp.int64)),
    }


@jax.named_scope("tl")
def on_advance(tl: dict, t, tau, active, qlen) -> dict:
    """Busy/queue-length integrals, credited to the interval start —
    the same left-Riemann convention as ``server_time``."""
    k = _k(tl, t)
    return {
        **tl,
        "busy_time": tl["busy_time"].at[k].add(
            tau * active.astype(jnp.float64)),
        "qlen_time": tl["qlen_time"].at[k].add(
            tau * qlen.astype(jnp.float64)),
    }


@jax.named_scope("tl")
def on_complete(tl: dict, t, response, service, completed,
                edges) -> dict:
    """Coarse sketch scatter at the (masked) completion time."""
    group = N_BINS // int(tl["slow_hist"].shape[1])
    K = tl["arrivals"].shape[0]
    k = _k(tl, t)
    kk = jnp.where(completed, k, jnp.int64(K))   # out of range -> drop
    slow = response / jnp.maximum(service, 1e-12)
    sb = bin_index(slow, edges) // group
    lb = bin_index(response, edges) // group
    return {
        **tl,
        "slow_hist": tl["slow_hist"].at[kk, sb].add(jnp.int64(1),
                                                    mode="drop"),
        "lat_hist": tl["lat_hist"].at[kk, lb].add(jnp.int64(1),
                                                  mode="drop"),
    }


@jax.named_scope("tl")
def on_evict(tl: dict, t, count) -> dict:
    k = _k(tl, t)
    return {**tl, "n_evict": tl["n_evict"].at[k].add(
        count.astype(jnp.int64))}


@jax.named_scope("tl")
def on_reject(tl: dict, t, rejected) -> dict:
    k = _k(tl, t)
    return {**tl, "n_reject": tl["n_reject"].at[k].add(
        rejected.astype(jnp.int64))}


@jax.named_scope("tl")
def on_prov(tl: dict, t, core_s) -> dict:
    k = _k(tl, t)
    return {**tl, "prov_core": tl["prov_core"].at[k].add(core_s)}


@jax.named_scope("tl")
def on_event(tl: dict, record, t, kind: int, val, p99) -> dict:
    """Masked append to the bounded decision log.

    ``record`` gates the write; the index parks out of range
    (``mode="drop"``) when not recording or when the log is full.  The
    count increments on every recorded event regardless, so truncation
    stays visible host-side.
    """
    E = tl["ev_t"].shape[0]
    c = tl["ev_count"]
    idx = jnp.where(record & (c < E), c, jnp.int64(E))
    return {
        **tl,
        "ev_t": tl["ev_t"].at[idx].set(t, mode="drop"),
        "ev_kind": tl["ev_kind"].at[idx].set(jnp.int32(kind),
                                             mode="drop"),
        "ev_val": tl["ev_val"].at[idx].set(
            jnp.asarray(val, dtype=jnp.int32), mode="drop"),
        "ev_p99": tl["ev_p99"].at[idx].set(p99, mode="drop"),
        "ev_count": c + record.astype(jnp.int64),
    }


def sensor_p99(window, edges):
    """Twin of ``timeline.sensor_p99_np`` — the exact op sequence of
    ``repro.fleet.policies._target_p99_jax``'s percentile read."""
    window = window.astype(jnp.int64)
    total = window.sum()
    tot_f = total.astype(jnp.float64)
    k = jnp.clip(jnp.ceil(0.99 * tot_f).astype(jnp.int64),
                 jnp.int64(1), jnp.maximum(total, 1))
    b = jnp.searchsorted(jnp.cumsum(window), k, side="left")
    return jnp.sqrt(edges[b] * edges[b + 1])
