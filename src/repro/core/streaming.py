"""Horizon-scale streaming front end over the chunked scan engine.

:func:`simulate_stream` runs the same discrete-event program as
:func:`repro.core.simulate_many`, but over fixed-size *chunks* of
arrivals instead of one monolithic ``lax.scan``:

* one compiled per-chunk scan (see ``_build_engine(..., stream=True)``)
  whose full carry — :class:`~repro.core.simulator.SimState` with the
  lifecycle pools, balancer state, telemetry sketches and fleet /
  autoscaler planes — is handed across segment boundaries with
  ``jax.jit(..., donate_argnums=(0,))`` buffer donation;
* no ``(N,)``-sized array anywhere on the long path: per-arrival
  outputs stream out through the scan ``ys`` (and are discarded unless
  ``collect_outputs=True``), metrics accumulate online in the
  :mod:`repro.telemetry` histogram sketches plus the exact counters in
  ``SimState.stream``;
* device memory and compile cost are both horizon-independent — the
  engine-cache key carries the chunk size, not ``N``, so growing the
  horizon reuses one compiled program per (policy, cluster, chunk).

Because every chunk step executes the *same ops* the monolithic scan
executes at that arrival (one shared ``early_arrival`` body), the final
carry and all pooled metrics are **bit-equal** to the monolithic engine
— gated per segment by ``benchmarks/fig14_stream.py`` against both the
monolithic scan and the numpy oracle's chunked replay
(:func:`repro.core.sim_ref.simulate_ref_chunks`).

The replication axis can additionally be sharded across devices: pass a
1-D mesh (see :func:`repro.launch.mesh.make_rep_mesh`) and the carry +
per-chunk inputs are placed with a ``NamedSharding`` over the leading
axis (:mod:`repro.distribution.sim_shard`), so policy sweeps scale with
device count.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.telemetry.spans import get_tracer
from repro.telemetry.state import (TelemetryCfg, TelemetryResult,
                                   warmup_cutoff)
from repro.telemetry.timeline import TimelineCfg, TimelineResult

from .cluster import ClusterCfg
from .simulator import (SimState, _count_drain, _get_stream_engine,
                        _prov_core_s, _record_scopes)
from .taxonomy import PolicySpec
from .workload import Workload, WorkloadBatch, stack_workloads

#: SimState planes that exist in only one of the two engines — excluded
#: from the bit-equality contract (everything else must match bitwise).
_MODE_ONLY_PLANES = frozenset({
    "q", "resp", "cold", "rejected", "worker_of",   # monolithic (N,)
    "task_fn", "task_svc", "stream",                # stream mirrors
})


@dataclasses.dataclass(frozen=True)
class StreamOutput:
    """Results of a chunked streaming run (leading axis ``R``).

    Unlike :class:`~repro.core.simulator.BatchSimOutput` there are no
    per-task arrays by default — percentiles come from the telemetry
    sketches, means from the exact online counters.  Pass
    ``collect_outputs=True`` (small-N parity checks only) to also get
    the per-arrival ``cold``/``rejected``/``worker`` planes.
    """

    #: pooled streaming metrics (histogram sketches, counters,
    #: occupancy integrals) — the percentile source at full horizon
    telemetry: TelemetryResult
    n_done: np.ndarray       # [R] i64 — completions over the horizon
    n_observed: np.ndarray   # [R] i64 — post-warmup completions
    resp_mean: np.ndarray    # [R] f64 — exact mean post-warmup response
    slow_mean: np.ndarray    # [R] f64 — exact mean post-warmup slowdown
    server_time: np.ndarray  # [R] f64
    core_time: np.ndarray    # [R] f64
    end_time: np.ndarray     # [R] f64
    prov_core_s: np.ndarray  # [R] f64
    n_arrivals: int
    chunk_size: int
    n_chunks: int
    #: per-arrival planes ([R, N]; None unless ``collect_outputs``)
    cold: np.ndarray | None = None
    rejected: np.ndarray | None = None
    worker: np.ndarray | None = None
    #: the post-drain device carry (None unless ``keep_final_state``;
    #: used by the bit-equality REPRO-CHECK gates)
    final_state: SimState | None = None
    #: windowed flight recorder ([R, ...] planes; None unless
    #: ``timeline=`` was passed) — fixed-shape virtual-time windows, so
    #: it rides the carry across chunk boundaries and is bit-equal to
    #: the monolithic engine's for any chunk size
    timeline: TimelineResult | None = None

    @property
    def n_reps(self) -> int:
        return int(self.n_done.shape[0])


def simulate_stream(policy: PolicySpec, cluster: ClusterCfg,
                    workloads, *, chunk_size: int,
                    backend: str = "auto",
                    telemetry: TelemetryCfg | None = None,
                    timeline: TimelineCfg | None = None,
                    collect_outputs: bool = False,
                    mesh=None,
                    keep_final_state: bool = False,
                    chunk_callback: Callable[[int, SimState], None]
                    | None = None) -> StreamOutput:
    """Run stacked replications through the chunked streaming engine.

    ``workloads`` is a single :class:`Workload`, a sequence of them, or
    a :class:`WorkloadBatch`.  ``chunk_size`` fixes the compiled scan
    length; results are bit-equal to :func:`simulate_many` for *any*
    chunk size (including sizes that do not divide ``N`` — the last
    chunk is padded with masked steps).  ``telemetry`` defaults to an
    enabled :class:`TelemetryCfg`: the streaming path reports
    percentiles from sketches, so it cannot run blind.

    ``mesh`` (a 1-D device mesh, see
    :func:`repro.launch.mesh.make_rep_mesh`) shards the replication
    axis across devices; the rep count must divide the mesh size.

    ``chunk_callback(chunk_idx, carry)`` observes the carry after each
    segment (the per-segment parity hook).  The *next* chunk dispatch
    donates that carry's buffers — callbacks must ``np.asarray`` any
    leaf they keep.

    With the process-wide tracer on, the call records the spans
    ``stream.init`` (the initial carry), one ``stream.feed`` per chunk
    (slicing, transfer and sharding of its inputs), ``stream.drain``
    (the final drain, waiting for the device) inside ``engine.run``,
    and ``engine.readout``; the chunk engine then counts drain
    iterations, recorded (with an ``engine.count`` span) as in
    :func:`repro.core.simulate_many`.  With a ``mesh`` each placement on
    it (``shard_reps``) is a ``stream.shard`` span: the function homes,
    the carry inside ``stream.init`` and each chunk's inputs inside its
    ``stream.feed``; and the counter's ``executed`` is per device, each
    device's loop running to the max of its own block of replications
    (``executed_max`` the largest device's), see
    :func:`repro.core.simulator._count_drain`.
    """
    if isinstance(workloads, Workload):
        workloads = [workloads]
    wb = workloads if isinstance(workloads, WorkloadBatch) \
        else stack_workloads(workloads)
    if telemetry is None:
        telemetry = TelemetryCfg()
    k = int(chunk_size)
    N, F, R = wb.n, wb.n_functions, wb.n_reps
    tr = get_tracer()
    (init, step_fn, drain_fn), fresh = _get_stream_engine(
        policy, cluster, k, F, backend, telemetry, timeline, mesh=mesh,
        count_drain=tr.enabled)
    cutoff = warmup_cutoff(N, telemetry)
    # the runtime window width is horizon-dependent (auto = horizon/K),
    # so it is computed host-side per replication and written into the
    # carry — one f64 division with the same operands as the monolithic
    # engine's in-trace arrivals[N-1]/K, hence bitwise identical
    window_s = None
    if timeline is not None:
        if float(timeline.window_s) > 0.0:
            window_s = np.full(R, float(timeline.window_s),
                               dtype=np.float64)
        else:
            window_s = np.asarray(wb.arrival[:, -1], dtype=np.float64) \
                / np.float64(int(timeline.n_windows))
    n_chunks = -(-N // k)
    pad = n_chunks * k - N

    def pad_tail(a, mode):
        a = np.asarray(a)
        if pad == 0:
            return a
        tail = np.repeat(a[:, -1:], pad, axis=1) if mode == "edge" \
            else np.zeros((R, pad), dtype=a.dtype)
        return np.concatenate([a, tail], axis=1)

    # padded tail steps are skipped via the valid mask; arrival times
    # pad with the last arrival so even the (dead) skip branch sees a
    # non-decreasing clock
    arr = pad_tail(wb.arrival, "edge")
    fns = pad_tail(wb.func, "zero")
    svcs = pad_tail(wb.service, "zero")
    us = pad_tail(wb.u_lb, "zero")
    gids = np.arange(n_chunks * k, dtype=np.int64)
    valid = gids < N
    homes = jnp.asarray(wb.func_home)

    shard = None
    blocks = 1
    if mesh is not None:
        from repro.distribution.sim_shard import REP_AXIS, shard_reps
        blocks = mesh.shape[REP_AXIS]

        def shard(tree):
            with tr.span("stream.shard"):
                return shard_reps(tree, mesh)
        homes = shard(homes)

    with tr.span("stream.init"):
        st = init(R, cutoff, window_s)
        if shard is not None:
            st = shard(st)
    outs: list[tuple] = []
    iters: list = []
    with tr.span("engine.first_run" if fresh else "engine.run",
                 policy=str(policy), backend=backend, n=N, reps=R,
                 chunk=k, chunks=n_chunks):
        for c in range(n_chunks):
            with tr.span("stream.feed", chunk=c):
                sl = slice(c * k, (c + 1) * k)
                ins = (jnp.asarray(arr[:, sl]), jnp.asarray(fns[:, sl]),
                       jnp.asarray(svcs[:, sl]), jnp.asarray(us[:, sl]))
                if shard is not None:
                    ins = shard(ins)
                args = (st, jnp.asarray(gids[sl]), jnp.asarray(valid[sl]),
                        *ins, homes)
            if tr.enabled and c == 0:
                # before the call, which donates the carry
                _record_scopes(tr, f"simulate_stream {policy} {backend} "
                               f"chunk={k} f={F} reps={R} mesh={mesh}",
                               step_fn, *args)
            st, ys = step_fn(*args)
            if collect_outputs:
                outs.append(tuple(np.asarray(y) for y in ys[:3]))
            if tr.enabled:
                iters.append(ys[3])
            if chunk_callback is not None:
                chunk_callback(c, st)
        with tr.span("stream.drain"):
            st = drain_fn(st)
            st = jax.block_until_ready(st)

    with tr.span("engine.readout"):
        sc = jax.tree_util.tree_map(np.asarray, st.stream)
        denom = np.maximum(sc["n_obs"], 1).astype(np.float64)
        cold = rej = wkr = None
        if collect_outputs:
            rej = np.concatenate([o[0] for o in outs], axis=1)[:, :N]
            cold = np.concatenate([o[1] for o in outs], axis=1)[:, :N]
            wkr = np.concatenate([o[2] for o in outs], axis=1)[:, :N]
        out = StreamOutput(
            telemetry=TelemetryResult.from_state(
                jax.tree_util.tree_map(np.asarray, st.tel), cfg=telemetry),
            n_done=sc["n_done"], n_observed=sc["n_obs"],
            resp_mean=sc["resp_sum"] / denom,
            slow_mean=sc["slow_sum"] / denom,
            server_time=np.asarray(st.server_time),
            core_time=np.asarray(st.core_time),
            end_time=np.asarray(st.now),
            prov_core_s=np.asarray(_prov_core_s(st, cluster),
                                   dtype=np.float64),
            n_arrivals=N, chunk_size=k, n_chunks=n_chunks,
            cold=cold, rejected=rej, worker=wkr,
            final_state=st if keep_final_state else None,
            timeline=None if timeline is None else TimelineResult.from_state(
                jax.tree_util.tree_map(np.asarray, st.tl), cfg=timeline))
    if tr.enabled:
        _count_drain(tr, iters, R, N, blocks)
    return out


def final_states_equal(a: SimState, b: SimState
                       ) -> tuple[bool, list[str]]:
    """Bitwise comparison of the carry planes both engines share.

    The monolithic-only ``(N,)`` planes and the stream-only slot
    mirrors/counters are skipped; everything else — slot matrices,
    warm pools, clocks, time integrals and the full lb/life/tel/fleet
    pytrees — must match bit for bit (``NaN`` compares equal to
    itself).  Returns ``(ok, mismatched plane names)``.
    """
    bad: list[str] = []
    for name in SimState._fields:
        if name in _MODE_ONLY_PLANES:
            continue
        la, ta = jax.tree_util.tree_flatten(getattr(a, name))
        lb, tb = jax.tree_util.tree_flatten(getattr(b, name))
        if ta != tb:
            bad.append(f"{name} (tree structure)")
            continue
        for i, (u, v) in enumerate(zip(la, lb)):
            u, v = np.asarray(u), np.asarray(v)
            eq = (u.shape == v.shape and u.dtype == v.dtype)
            if eq:
                eq = np.array_equal(u, v) or (
                    np.issubdtype(u.dtype, np.floating)
                    and np.array_equal(u, v, equal_nan=True))
            if not eq:
                bad.append(name if len(la) == 1 else f"{name}[{i}]")
    return (not bad, bad)
