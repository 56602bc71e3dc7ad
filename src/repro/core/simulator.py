"""Vectorized JAX discrete-event simulator for serverless scheduling.

The paper (§3.2) uses a discrete-event simulator to sweep the scheduling
policy space.  A classical event-heap simulator is pointer-chasing and
branchy — the opposite of what TPU/vector hardware wants.  This engine
re-expresses the identical semantics (see :mod:`repro.core.sim_ref` for the
contract) as:

* an outer :func:`jax.lax.scan` over arrivals (the only true sequential
  dependency in the system),
* an inner bounded :func:`jax.lax.while_loop` that fast-forwards the
  cluster through completion events between two arrivals — rates are
  piecewise constant between completions, so each iteration advances to
  the next completion in closed form over the whole ``[W, S]`` slot matrix,
* branch-free load-balancing selection and rate assignment resolved from
  the policy registry (:func:`repro.policy.resolve`) — the engine never
  branches on policy names, so registered balancers/schedulers are
  sweepable without touching it.

Selection dispatches through a *backend*: ``"jax"`` (pure jit/vmap
closures) or ``"pallas"`` (the batched controller kernel — for ``H``
this is :mod:`repro.kernels.hermes_select`, in interpret mode off-TPU).
The default ``"auto"`` picks ``pallas`` whenever the policy's balancer
ships a kernel, so ``simulate_many(HERMES, ...)`` shares one kernel
dispatch across all stacked replications per arrival (the replication
axis becomes the kernel batch under ``vmap``).

Three entry points share the engine: :func:`simulate` runs one workload;
:func:`simulate_many` runs ``R`` stacked replications (seeds / arrival-rate
scales with a shared ``(N, F)`` shape) through a single :func:`jax.vmap`-ed
program; :func:`repro.core.streaming.simulate_stream` feeds the same
arrival/placement bodies chunk by chunk for horizons too long to scan
monolithically (bit-equal by construction — see that module).  Compiled
engines are memoized process-wide on ``(policy, cluster, N, F, batched,
backend, telemetry, chunk)`` (see :func:`_cache_key`; streaming keys
carry the chunk size where monolithic ones carry the horizon), so
policy × load sweeps compile each engine exactly once.

All event times are float64 (the simulator enables x64; model code in this
repo always pins explicit dtypes so this is safe process-wide).

State layout (``W`` workers × ``S`` slots):

==============  ========  =====================================
``remaining``   f64       remaining work; ``inf`` in empty slots
``task_arr``    f64       arrival time of the occupying task
``task_idx``    i32       arrival index (doubles as FCFS seq); -1 empty
``warm``        i32       ``[W, F+1]`` idle warm executors (+1 pad col)
``queue``       i32       late-binding FIFO ring of arrival indices
``life``        pytree    container-lifecycle carry (``()`` disabled)
==============  ========  =====================================

With ``cluster.lifecycle`` set (:mod:`repro.lifecycle`), the carry
additionally threads per-pool idle-since clocks ``[W, F+1]``,
per-function last-completion times, the active keep-alive windows and
the policy's histogram state through the scan — the same carried-state
pattern the balancer registry uses.  Warm pools are then masked by the
windows wherever they are read (*alive* pools reserve slots and feed
the LRU eviction + ``max_idle`` budget; *materialized* pools serve warm
hits), cold starts charge the per-function preset cost, and every
transition mirrors :class:`repro.lifecycle.LifecycleRuntime` op for op
so the np ≡ jax parity contract extends to lifecycle state.  With the
default ``lifecycle=None`` the traced program is exactly the
pre-lifecycle one.

With ``cluster.fleet`` set (:mod:`repro.fleet`), workers become
heterogeneous: every rate the scheduler assigns is scaled by the
worker's ``speed`` (service *work* stays nominal; fast workers drain
it faster), cold-start penalties scale the same way, and stateful
balancers observe *effective* (wall-clock-equivalent) execution times
so throughput learners like ``SWARM`` can infer the speed vector
online.  A non-``STATIC`` autoscale policy additionally threads an
active-worker count ``n_on`` through the carry: arrivals only place on
workers ``< n_on`` (the rest are masked slot-full at selection — the
balancer contract is untouched), the registered ``decide`` hook
grows/shrinks ``n_on`` against the telemetry slowdown sketch under a
cooldown, and a provisioned-time integral accumulates the
core-seconds the fleet actually held.  ``fleet=None`` — the default —
python-gates all of it away (bit-for-bit golden contract, like
``lifecycle`` and ``telemetry``).
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial
from typing import Any, NamedTuple

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
from jax import lax

from repro.fleet import STATIC as _AUTO_STATIC, resolve_fleet
from repro.lifecycle import resolve_lifecycle
from repro.policy import default_backend, resolve
from repro.telemetry import engine as tel_engine
from repro.telemetry import timeline_engine as tln_engine
from repro.telemetry.spans import get_tracer, op_scopes
from repro.telemetry.state import (TelemetryCfg, TelemetryResult,
                                   warmup_cutoff)
from repro.telemetry.timeline import (EV_AUTOSCALE, EV_MODE_FLIP,
                                      TimelineCfg, TimelineResult,
                                      validate_timeline)

from .cluster import ClusterCfg
from .taxonomy import LoadBalance, PolicySpec
from .workload import Workload, WorkloadBatch, stack_workloads

EPS = 1e-9
_BIG_TIME = 1e18
#: the ``jax.named_scope`` names of a scan step's parts: the completion
#: drain, the balancer's select, placement, the container lifecycle,
#: and the telemetry and timeline planes' updates (set in their own
#: modules).  Scopes nest; an operation belongs to the innermost.
STEP_SCOPES = ("advance", "select", "place", "lifecycle", "tel", "tl")


class SimState(NamedTuple):
    remaining: jax.Array   # [W, S] f64
    task_arr: jax.Array    # [W, S] f64
    task_idx: jax.Array    # [W, S] i32, -1 = empty
    warm: jax.Array        # [W, F+1] i32
    q: jax.Array           # [Q] i32 ring buffer (late binding)
    q_head: jax.Array      # i32
    q_tail: jax.Array      # i32
    now: jax.Array         # f64
    resp: jax.Array        # [N+1] f64 (last = scratch)
    cold: jax.Array        # [N+1] bool
    rejected: jax.Array    # [N+1] bool
    worker_of: jax.Array   # [N+1] i32
    server_time: jax.Array  # f64
    core_time: jax.Array    # f64
    lb: Any                 # balancer carried state (pytree; () stateless)
    life: Any               # lifecycle carried state (pytree; () disabled)
    tel: Any                # telemetry carried state (pytree; () disabled)
    fleet: Any              # autoscaler carried state (pytree; () disabled)
    # Streaming-engine planes (repro.core.streaming).  () in the
    # monolithic engine — empty pytree nodes, so the monolithic carry
    # structure (and traced program) is unchanged.  In stream mode the
    # (N,)-sized planes above (resp/cold/rejected/worker_of/q) are ()
    # instead, and completions read the occupant's function/service
    # from these per-slot mirrors so a chunk never needs to gather from
    # arrivals that entered the system in an earlier chunk.
    task_fn: Any = ()       # [W, S] i32: occupant's function id
    task_svc: Any = ()      # [W, S] f64: occupant's nominal service
    stream: Any = ()        # exact online counters dict (see streaming)
    # Windowed time-series flight recorder (repro.telemetry.timeline).
    # () when disabled; otherwise a dict of fixed-[K]-window planes
    # whose shapes never depend on the horizon, so the same carry hands
    # across streaming chunk boundaries unchanged.
    tl: Any = ()


@dataclasses.dataclass(frozen=True)
class SimOutput:
    response: np.ndarray
    cold: np.ndarray
    rejected: np.ndarray
    worker: np.ndarray
    server_time: float
    core_time: float
    end_time: float
    #: streaming in-engine metrics (None unless ``telemetry=`` was passed)
    telemetry: TelemetryResult | None = None
    #: provisioned core-seconds: the autoscaler's ``n_on × cores`` time
    #: integral, or ``end_time × total_cores`` for a fixed fleet
    prov_core_s: float = 0.0
    #: windowed flight-recorder planes (None unless ``timeline=`` passed)
    timeline: TimelineResult | None = None


@dataclasses.dataclass(frozen=True)
class BatchSimOutput:
    """Results of ``R`` stacked workload replications (leading axis R)."""

    response: np.ndarray     # [R, N] f64
    cold: np.ndarray         # [R, N] bool
    rejected: np.ndarray     # [R, N] bool
    worker: np.ndarray       # [R, N] i32
    server_time: np.ndarray  # [R] f64
    core_time: np.ndarray    # [R] f64
    end_time: np.ndarray     # [R] f64
    #: batched streaming metrics, leading axis R (None unless enabled)
    telemetry: TelemetryResult | None = None
    #: provisioned core-seconds per replication ([R] f64)
    prov_core_s: np.ndarray | None = None
    #: batched flight-recorder planes, leading axis R (None unless enabled)
    timeline: TimelineResult | None = None

    @property
    def n_reps(self) -> int:
        return int(self.response.shape[0])

    def rep(self, r: int) -> SimOutput:
        """The ``r``-th replication as a plain :class:`SimOutput`."""
        return SimOutput(
            response=self.response[r], cold=self.cold[r],
            rejected=self.rejected[r], worker=self.worker[r],
            server_time=float(self.server_time[r]),
            core_time=float(self.core_time[r]),
            end_time=float(self.end_time[r]),
            telemetry=None if self.telemetry is None
            else self.telemetry.rep(r),
            prov_core_s=0.0 if self.prov_core_s is None
            else float(self.prov_core_s[r]),
            timeline=None if self.timeline is None
            else self.timeline.rep(r))

    def __getitem__(self, sl: slice) -> "BatchSimOutput":
        """A sub-batch over a slice of the replication axis."""
        return BatchSimOutput(
            response=self.response[sl], cold=self.cold[sl],
            rejected=self.rejected[sl], worker=self.worker[sl],
            server_time=self.server_time[sl], core_time=self.core_time[sl],
            end_time=self.end_time[sl],
            telemetry=None if self.telemetry is None
            else self.telemetry[sl],
            prov_core_s=None if self.prov_core_s is None
            else self.prov_core_s[sl],
            timeline=None if self.timeline is None
            else self.timeline[sl])


def _row(plane: jax.Array, w) -> jax.Array:
    """Row ``w`` of a ``[W, n]`` plane, read by selecting it over the
    worker axis and reducing that axis, bit for bit.

    Under ``vmap`` with a worker per replication, an indexed row compiles
    on the TPU to a serial loop of one trip per replication; this is one
    elementwise pass and a reduction.  Integers reduce by a sum of the
    selected row and zeros.  Floats reduce by a select per pair of rows,
    so no value passes through arithmetic, which could flush a subnormal
    or turn ``-0.0`` into ``0.0`` (the chip cannot bitcast float64).
    """
    hit = jnp.broadcast_to(
        (jnp.arange(plane.shape[0], dtype=jnp.int32) == w)[:, None],
        plane.shape)
    if jnp.issubdtype(plane.dtype, jnp.integer):
        return jnp.where(hit, plane, 0).sum(axis=0, dtype=plane.dtype)

    def first_hit(a, b):
        return jnp.where(a[1], a[0], b[0]), a[1] | b[1]

    row, _ = lax.reduce((plane, hit), (jnp.zeros((), plane.dtype), False),
                        first_hit, (0,))
    return row


def _hit(shape, w, s) -> jax.Array:
    """One-hot ``[W, n]`` mask of element ``(w, s)``: the write counterpart
    of :func:`_row`.

    ``jnp.where(_hit(...) & mask, value, plane)`` is
    ``plane.at[w, s].set(jnp.where(mask, value, plane[w, s]))`` bit for
    bit.  Under ``vmap`` with an element per replication, the indexed
    write compiles on the TPU to a scatter serial over the replications;
    the select fuses into the passes the plane already takes.
    """
    return ((jnp.arange(shape[0], dtype=jnp.int32) == w)[:, None]
            & (jnp.arange(shape[1], dtype=jnp.int32) == s)[None, :])


def _build_engine(policy: PolicySpec, cluster: ClusterCfg,
                  n_arrivals: int, n_functions: int,
                  backend: str = "jax",
                  telemetry: TelemetryCfg | None = None,
                  timeline: TimelineCfg | None = None,
                  stream: bool = False, count_drain: bool = False):
    """Build the raw (un-jitted) scan engine for (policy, cluster, N, F).

    ``backend`` selects how worker selection dispatches (``"jax"`` or
    ``"pallas"``); rate assignment always uses the registry's jax
    closures.  The returned ``run(arrivals, funcs, services, u_lb,
    homes) -> SimState`` is pure and rank-polymorphic under
    :func:`jax.vmap`: mapping every argument over a leading replication
    axis yields the batched engine used by :func:`simulate_many`.

    ``telemetry`` opts the carry into streaming in-engine metrics
    (:mod:`repro.telemetry`): histogram sketches, cold/evict/reject
    counters and occupancy integrals updated inside the scan.
    ``tel_on`` python-gates every update exactly like ``life_on``, so
    the default ``telemetry=None`` traces the bit-identical
    pre-telemetry program (golden contract).

    ``stream=True`` builds the *chunk engine* used by
    :func:`repro.core.streaming.simulate_stream`: ``n_arrivals`` is the
    fixed chunk length (not the horizon), and instead of ``run`` the
    builder returns ``(init, run_chunk, run_drain)``:

    * ``init(n_reps, cutoff)`` — the initial batched carry (every leaf
      gains a leading ``R`` axis; ``cutoff`` is the global warmup
      index, carried so the compiled program is horizon-independent);
    * ``run_chunk(st, gids, valid, arrivals, funcs, services, u_lb,
      homes) -> (st, ys)`` — one compiled scan over a chunk of
      arrivals; ``gids`` are global arrival indices, ``valid`` masks
      tail padding (invalid steps are identity on the carry), ``ys``
      are the per-arrival ``(rejected, cold, worker)`` outputs;
    * ``run_drain(st) -> st`` — the end-of-horizon completion drain
      (the monolithic engine's post-scan tail).

    The stream carry holds no ``(N,)``-sized plane: per-arrival outputs
    leave through ``ys``, responses reach metrics only through the
    telemetry sketches and the exact online counters in
    ``SimState.stream``, and completions read the occupant's
    function/service from the ``task_fn``/``task_svc`` slot mirrors.
    Every op a chunk step executes on the carry is the same op the
    monolithic scan executes at that arrival, so the handoff is
    bit-exact (the REPRO-CHECK contract gated by ``benchmarks``).

    ``count_drain`` adds one int32 per scan step: the iterations of the
    completion drain before that step's arrival.  ``run`` then returns
    ``(st, iterations)`` (``[N]``) and ``run_chunk``'s ``ys`` gain them
    as a fourth element (``0`` on padded steps).  Off, the traced
    program is the uncounted one.  The parts of a step run under the
    ``jax.named_scope`` names of :data:`STEP_SCOPES`.
    """
    W, C, S = cluster.n_workers, cluster.cores, cluster.slots
    if W * S > np.iinfo(np.int32).max:
        raise ValueError(
            f"{W} workers x {S} slots = {W * S} slots do not fit the "
            f"drain's int32 slot index")
    F = n_functions
    N = n_arrivals
    Q = N  # late-binding controller queue can hold every arrival
    res = resolve(policy, backend=backend, cluster=cluster)
    late = res.late
    if stream and late:
        raise ValueError(
            f"streaming engine requires early binding — policy "
            f"{policy!r} uses late binding, whose controller queue "
            f"scales with the horizon; run it through simulate_many")
    penalty = float(cluster.cold_start_penalty)
    select = res.select        # None for late binding
    # carried-state balancers (init_state registered): select threads a
    # state pytree through the scan carry and on_complete updates it per
    # task completion (see repro.policy.registry)
    stateful = res.stateful and not late
    # container lifecycle (repro.lifecycle).  life_on gates every
    # lifecycle op at trace time, so the disabled default traces the
    # exact pre-lifecycle program (bit-for-bit golden contract).
    lres = resolve_lifecycle(cluster, backend="jax", n_functions=F)
    life_on = lres is not None
    if life_on:
        life_windows, life_observe = lres.windows, lres.observe
        life_max_idle = lres.max_idle
        life_costs = None if lres.cold_costs is None \
            else jnp.asarray(lres.cold_costs)
    # streaming telemetry (repro.telemetry).  tel_on gates every update
    # at trace time — telemetry=None traces the pre-telemetry program.
    tel_on = telemetry is not None
    if tel_on:
        tel_edges = tel_engine.edges_for_trace()
        if not stream:
            tel_cutoff = warmup_cutoff(N, telemetry)
        # stream mode: N is the chunk length, not the horizon — the
        # global warmup index rides in the carry (SimState.stream)
    # windowed time-series flight recorder (repro.telemetry.timeline).
    # tl_on python-gates every update exactly like tel_on — the default
    # timeline=None traces the bit-identical pre-timeline program.  The
    # plane is independent of tel_on (a timeline without run-aggregate
    # telemetry is valid; the autoscaler separately mandates telemetry).
    tl_on = timeline is not None
    if tl_on:
        validate_timeline(timeline)
        tl_edges = tel_engine.edges_for_trace()
        TL_K = int(timeline.n_windows)
        # static trace-time constants (conversions hoisted out of the
        # traced bodies — HOT001-clean)
        TL_CORES = float(C)
        TL_WS_CFG = float(timeline.window_s)
        # hybrid-balancer pack<->spread flips only exist for Hermes
        # under early binding (late binding has no balancer select)
        flip_on = (not late) and policy.balance == LoadBalance.HYBRID
    # heterogeneous fleet + autoscaling (repro.fleet).  fleet_on gates
    # the speed scaling, auto_on the active-worker control loop; the
    # disabled default traces the exact pre-fleet program.
    fres = resolve_fleet(cluster, backend="jax")
    fleet_on = fres is not None
    auto_on = fleet_on and fres.auto_on
    if fleet_on:
        speed_arr = jnp.asarray(fres.speeds)          # [W] f64
    if auto_on:
        if late:
            raise ValueError(
                f"autoscaler {fres.policy.name!r} requires early binding"
                f" — late binding has no per-worker placement to mask")
        if fres.policy.needs_telemetry and not tel_on:
            raise ValueError(
                f"autoscaler {fres.policy.name!r} reads the telemetry "
                f"slowdown sketch as its sensor; pass telemetry="
                f"TelemetryCfg() to the simulator")
        auto_decide = fres.decide
        auto_cool = float(fres.cfg.cooldown_s)

    def rates_of(st: SimState) -> jax.Array:
        active = st.task_idx >= 0
        if late:
            r = active.astype(jnp.float64)
        else:
            r = res.rates(st.task_idx, st.remaining)
        if fleet_on:
            # worker speed multiplies every scheduler-assigned rate:
            # service *work* stays nominal, fast workers drain it faster
            r = r * speed_arr[:, None]
        return r

    @jax.named_scope("place")
    def place(st: SimState, tid, w, f, svc_nom, t_arr):
        """Place arrival ``tid`` (fn ``f``, nominal service ``svc_nom``,
        arrival time ``t_arr``) on worker ``w`` (must be valid).

        Returns the new state; in stream mode ``(state, is_cold)`` —
        the cold flag leaves through the scan ``ys`` instead of the
        dropped ``(N,)`` cold plane.
        """
        active_w = (st.task_idx[w] >= 0).sum()
        life = st.life
        if life_on:
            with jax.named_scope("lifecycle"):
                # lifecycle masks (mirroring LifecycleRuntime): only
                # *materialized* pools (inside their pre-warm + keep-alive
                # window) serve warm hits, occupy memory (slot pressure /
                # budget) and are eviction candidates.  The victim is the
                # LRU materialized pool — oldest idle-since, first index on
                # ties, the tie-breaking contract shared with the oracle
                lu = life["idle_since"]
                lu_w = _row(lu[:, :F], w)
                pre, keep = life["pre"], life["keep"]
                ages_w = st.now - lu_w
                mat_w = (ages_w >= pre) & (ages_w <= pre + keep)
                eff = jnp.where(mat_w, _row(st.warm[:, :F], w), 0)
                warm_cnt = eff[f]
                is_cold = warm_cnt == 0
                idle = eff.sum()
                need_evict = is_cold & (active_w + idle >= S)
                victim = jnp.argmin(jnp.where(eff > 0, lu_w, jnp.inf))
                pen_f = penalty if life_costs is None else life_costs[f]
                if life_observe is not None:
                    # observe the placed pool's idle age AFTER the
                    # warm/cold decision (LifecycleRuntime.observe_place);
                    # virgin pools (idle_since < 0) are masked out
                    seen = lu[w, f] >= 0.0
                    gap = jnp.maximum(st.now - lu[w, f], 0.0)
                    ka_new = life_observe(life["ka"], f, gap)
                    ka = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(seen, a, b), ka_new,
                        life["ka"])
                    pre2, keep2 = life_windows(ka)
                    life = dict(life, ka=ka, pre=pre2, keep=keep2)
        else:
            warm_w = _row(st.warm[:, :F], w)
            warm_cnt = warm_w[f]
            is_cold = warm_cnt == 0
            idle = warm_w.sum()
            need_evict = is_cold & (active_w + idle >= S)
            victim = jnp.argmax(warm_w)
            pen_f = penalty
        warm = st.warm.at[w, f].add(jnp.where(is_cold, 0, -1))
        warm = warm.at[w, victim].add(jnp.where(need_evict, -1, 0))
        slot = jnp.argmax(st.task_idx[w] < 0)
        svc = svc_nom + jnp.where(is_cold, pen_f, 0.0)
        tel = st.tel
        if tel_on:
            # one placement record per accepted arrival (rejections are
            # counted in step; place is never reached for them)
            tel = tel_engine.on_place(tel, w, is_cold, need_evict)
        tl = st.tl
        if tl_on:
            # credited to the window of the dispatch time (= the
            # arrival time under early binding)
            tl = tln_engine.on_place(tl, st.now, is_cold, need_evict)
        st = st._replace(
            remaining=st.remaining.at[w, slot].set(svc),
            task_arr=st.task_arr.at[w, slot].set(t_arr),
            task_idx=st.task_idx.at[w, slot].set(tid.astype(jnp.int32)),
            warm=warm,
            life=life,
            tel=tel,
            tl=tl,
        )
        if stream:
            # per-slot mirrors let the completion drain observe the
            # task's function/service without gathering from the (N,)
            # inputs of an earlier chunk
            st = st._replace(
                task_fn=st.task_fn.at[w, slot].set(f.astype(jnp.int32)),
                task_svc=st.task_svc.at[w, slot].set(svc_nom))
            return st, is_cold
        return st._replace(
            cold=st.cold.at[tid].set(is_cold),
            worker_of=st.worker_of.at[tid].set(w.astype(jnp.int32)))

    def pop_all(st: SimState, funcs, services, arrivals) -> SimState:
        """Dispatch queued invocations while any worker has a free core."""
        def cond(st):
            active = (st.task_idx >= 0).sum(axis=1)
            return (st.q_tail > st.q_head) & (active.min() < C)

        def body(st):
            active = (st.task_idx >= 0).sum(axis=1)
            w = jnp.argmin(active)
            arr_idx = st.q[st.q_head % Q]
            st = place(st, arr_idx, w, funcs[arr_idx],
                       services[arr_idx], arrivals[arr_idx])
            return st._replace(q_head=st.q_head + 1)

        return lax.while_loop(cond, body, st)

    @jax.named_scope("advance")
    def advance(st: SimState, dt, funcs, services, arrivals):
        """Fast-forward the cluster by ``dt`` seconds of wall time.

        One completion is processed per iteration — the earliest-finishing
        slot (the ``argmin`` of time-to-done).  Simultaneous completions
        drain in successive zero-``tau`` iterations; their bookkeeping
        (response writes to distinct indices, warm-pool increments)
        commutes, so results are identical to batch-completing them.  The
        slot is retired by one-hot selects (:func:`_hit`), not scatters:
        under ``vmap`` the loop already selects every carried plane after
        each iteration, and the writes fuse into that pass.

        Returns ``(st, iterations)``: the loop's iteration count (int32)
        with ``count_drain``, else ``None``.
        """

        def cond(carry):
            st, dt_left = carry[0], carry[1]
            active = st.task_idx >= 0
            # a tie can be left pending when a completion lands exactly on
            # the window edge — drain it before yielding to the caller
            pending = (active & (st.remaining <= EPS)).any()
            go = active.any() & ((dt_left > 0) | pending)
            if late:
                n_active = active.sum(axis=1)
                can_pop = (st.q_tail > st.q_head) & (n_active.min() < C)
                go = go | can_pop
            return go

        def body(carry):
            st, dt_left = carry
            if late:
                st = pop_all(st, funcs, services, arrivals)
            active = st.task_idx >= 0
            rates = rates_of(st)
            t_done = jnp.where(rates > 0, st.remaining / rates, jnp.inf)
            tmin = t_done.min()
            tau = jnp.minimum(dt_left, tmin)
            tau = jnp.where(jnp.isfinite(tau) & (tau > 0), tau, 0.0)
            # integrate occupancy (constant over tau)
            n_w = active.sum(axis=1)
            server_time = st.server_time + tau * (n_w > 0).sum()
            core_time = st.core_time + tau * jnp.minimum(n_w, C).sum()
            tel = st.tel
            if tel_on:
                # busy/depth/queue-length time integrals, pre-advance
                # occupancy — the same left-Riemann convention as
                # server_time/core_time just above
                tel = tel_engine.on_advance(tel, tau, n_w > 0, n_w,
                                            st.q_tail - st.q_head)
            tl = st.tl
            if tl_on:
                # same integrals, windowed; the whole tau slice credits
                # the window of its start (left-start convention —
                # identical in the oracle and serving platform)
                tl = tln_engine.on_advance(tl, st.now, tau, n_w > 0,
                                           st.q_tail - st.q_head)
            now = st.now + tau
            remaining = st.remaining - rates * tau
            # complete the argmin slot only (resp idx N is scratch);
            # the remaining<=EPS clause matches cond's pending drain — a
            # task left within EPS of done at the window edge completes
            # here (as both the old batch-done engine and the oracle do)
            # rather than stalling the loop
            # int32 index (first minimum, as jnp.argmin): with x64 on,
            # jnp.argmin yields int64, whose // and % the TPU emulates as
            # a long dependent chain in every drain iteration
            j = lax.argmin(t_done.reshape(-1), 0, jnp.int32)
            wj, sj = j // S, j % S
            tid = st.task_idx[wj, sj]
            completed = (tmin <= dt_left) | \
                ((tid >= 0) & (st.remaining[wj, sj] <= EPS))
            resp_val = now - st.task_arr[wj, sj]
            if stream:
                # the (N,)-input gathers of the monolithic path are
                # replaced by the per-slot mirrors written at placement
                # — same bits, so every downstream FP op is identical
                svc_nom = st.task_svc[wj, sj]
                f_j = st.task_fn[wj, sj]
                cutoff_op = st.stream["cutoff"]
                resp = st.resp
            else:
                svc_nom = services[jnp.maximum(tid, 0)]
                f_j = funcs[jnp.maximum(tid, 0)]
                cutoff_op = tel_cutoff if tel_on else None
                resp = st.resp.at[jnp.where(completed, tid, N)].set(
                    jnp.where(completed, resp_val, 0.0))
            if tel_on:
                # histogram scatter for the (masked) completion; warmup
                # tasks are dropped inside on_complete to match
                # summarize's post-warmup population
                tel = tel_engine.on_complete(
                    tel, resp_val, svc_nom, tid, completed,
                    cutoff_op, tel_edges)
            if tl_on:
                # windowed coarse sketches take ALL completions (no
                # warmup cutoff — the recorder shows the ramp), in the
                # window of the completion time
                tl = tln_engine.on_complete(tl, now, resp_val, svc_nom,
                                            completed, tl_edges)
            life = st.life
            if life_on:
                w_pad = jnp.where(completed, wj, 0)
                f_pad = jnp.where(completed, f_j, F)
                with jax.named_scope("lifecycle"):
                    # mirror LifecycleRuntime.on_complete: zero a stale
                    # pool before the increment (expired executors must not
                    # resurrect), refresh the idle clock, then enforce the
                    # max_idle budget by LRU eviction over the worker's
                    # materialized pools
                    lu = life["idle_since"]
                    pre, keep = life["pre"], life["keep"]
                    age_j = now - lu[wj, f_j]
                    stale = age_j > pre[f_j] + keep[f_j]
                    base = jnp.where(stale, 0, st.warm[wj, f_j])
                    warm = st.warm.at[w_pad, f_pad].set(
                        jnp.where(completed, base + 1,
                                  st.warm[w_pad, f_pad]).astype(jnp.int32))
                    lu = lu.at[w_pad, f_pad].set(
                        jnp.where(completed, now, lu[w_pad, f_pad]))
                    life = dict(life, idle_since=lu)
                    if life_max_idle > 0:
                        ages_row = now - lu[wj, :F]
                        mat_row = (ages_row >= pre) & (ages_row <= pre + keep)
                        eff = jnp.where(mat_row, warm[wj, :F], 0)
                        over = completed & (eff.sum() > life_max_idle)
                        evict = jnp.argmin(jnp.where(eff > 0, lu[wj, :F],
                                                     jnp.inf))
                        warm = warm.at[jnp.where(over, wj, 0),
                                       jnp.where(over, evict, F)].add(
                            -over.astype(jnp.int32))
                        if tel_on:
                            tel = tel_engine.on_evict(tel, over)
                        if tl_on:
                            tl = tln_engine.on_evict(tl, now, over)
                warm = warm.at[:, F].set(0)
            else:
                warm = st.warm + (_hit(st.warm.shape, wj, f_j)
                                  & completed).astype(jnp.int32)
            done = _hit(remaining.shape, wj, sj) & completed
            remaining = jnp.where(done, jnp.inf, remaining)
            task_idx = jnp.where(done, jnp.int32(-1), st.task_idx)
            lb = st.lb
            if stateful:
                # one hook call per completion, masked branch-free: the
                # updated pytree is selected only where the argmin slot
                # really completed (simultaneous completions drain one
                # zero-tau iteration each, lowest worker index first —
                # the same order the numpy oracle applies its hooks)
                n_after = (task_idx[wj] >= 0).sum()
                svc_obs = svc_nom
                if fleet_on:
                    # the hook observes the *effective* execution time
                    # on the completing worker (f64 division in both
                    # backends — bitwise np ≡ jax), so throughput
                    # learners see the heterogeneity
                    svc_obs = svc_obs / speed_arr[wj]
                upd = res.on_complete(lb, wj, f_j, svc_obs, n_after)
                lb = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(completed, a, b), upd, lb)
            st = st._replace(
                remaining=remaining, task_idx=task_idx,
                warm=warm, now=now, resp=resp,
                server_time=server_time, core_time=core_time, lb=lb,
                life=life, tel=tel, tl=tl)
            if stream:
                # exact online counters: the long path never holds a
                # per-task slowdown array, but the mean response /
                # slowdown over the post-warmup population stays exact
                sc = st.stream
                rec = completed & (tid >= sc["cutoff"])
                slow_v = resp_val / jnp.maximum(svc_nom, 1e-12)
                st = st._replace(stream=dict(
                    sc,
                    n_done=sc["n_done"] + completed.astype(jnp.int64),
                    n_obs=sc["n_obs"] + rec.astype(jnp.int64),
                    resp_sum=sc["resp_sum"]
                    + jnp.where(rec, resp_val, 0.0),
                    slow_sum=sc["slow_sum"]
                    + jnp.where(rec, slow_v, 0.0)))
            return st, dt_left - tau

        if count_drain:
            st, _, n_iter = lax.while_loop(
                cond, lambda c: body(c[:2]) + (c[2] + 1,),
                (st, dt, jnp.int32(0)))
        else:
            (st, _), n_iter = lax.while_loop(cond, body, (st, dt)), None
        if late:
            st = pop_all(st, funcs, services, arrivals)
        return st, n_iter

    def early_arrival(st: SimState, tid, t_i, f_i, u_i, svc_i,
                      funcs, services, arrivals, homes):
        """Advance to ``t_i`` and run the early-binding select/place for
        arrival ``tid`` — the one shared body of the monolithic and
        stream steps, so chunked ≡ monolithic holds by construction.
        Returns ``(st, w, is_cold, iterations)``, the last from
        :func:`advance`.
        """
        if auto_on:
            # provisioned-time integral over [now, t_i] at the current
            # n_on (decisions only take effect at arrival boundaries,
            # so n_on is constant across the whole advance)
            fl = st.fleet
            st = st._replace(fleet=dict(fl, prov_time=(
                fl["prov_time"]
                + (t_i - st.now) * fl["n_on"].astype(jnp.float64))))
        if tl_on:
            # windowed provisioned core-seconds over the same interval,
            # credited to the interval-start window; without an
            # autoscaler the whole fleet is provisioned throughout
            n_prov = st.fleet["n_on"].astype(jnp.float64) if auto_on \
                else jnp.float64(W)
            st = st._replace(tl=tln_engine.on_prov(
                st.tl, st.now, (t_i - st.now) * n_prov * TL_CORES))
        st, n_iter = advance(st, t_i - st.now, funcs, services, arrivals)
        st = st._replace(now=t_i)
        active = (st.task_idx >= 0).sum(axis=1).astype(jnp.int32)
        if life_on:
            with jax.named_scope("lifecycle"):
                # selection sees the materialized warm column (pools in
                # their pre-warm phase or past their window are
                # invisible) — mirrors LifecycleRuntime.materialized_col
                lu = st.life["idle_since"]
                pre, keep = st.life["pre"], st.life["keep"]
                ages = st.now - lu[:, f_i]
                m = (ages >= pre[f_i]) & (ages <= pre[f_i] + keep[f_i])
                wcol = jnp.where(m, st.warm[:, f_i], 0)
        else:
            wcol = st.warm[:, f_i]
        sel_active = active
        if auto_on:
            # autoscale decision: read the slowdown-sketch window
            # (counts since the last snapshot), decide only when the
            # cooldown elapsed and the window is non-empty, then
            # snapshot + re-arm — identical gating in the oracle
            fl = st.fleet
            window = st.tel["slow_hist"] - fl["snap"]
            do = (t_i >= fl["cool_until"]) & (window.sum() >= 1)
            n_new = auto_decide(fl["n_on"], window)
            n_on = jnp.where(do, n_new, fl["n_on"]).astype(jnp.int32)
            st = st._replace(fleet=dict(
                fl, n_on=n_on,
                cool_until=jnp.where(do, t_i + auto_cool,
                                     fl["cool_until"]),
                snap=jnp.where(do, st.tel["slow_hist"], fl["snap"])))
            # deprovisioned workers are masked slot-full at
            # selection (the serving platform's health-mask idiom):
            # the balancer contract is untouched, and running tasks
            # on scaled-down workers drain normally
            sel_active = jnp.where(
                jnp.arange(W, dtype=jnp.int32) < n_on, active,
                jnp.int32(S))
            if tl_on:
                # log the decision just taken (only when it changed the
                # level), with the sensor p99 the controller read off
                # the same window — fl still holds the pre-decision
                # n_on here
                changed = do & (n_new != fl["n_on"])
                st = st._replace(tl=tln_engine.on_event(
                    st.tl, changed, t_i, EV_AUTOSCALE, n_new,
                    tln_engine.sensor_p99(window, tl_edges)))
        if tl_on:
            # arrival count + last-write-wins active-worker level, in
            # the arrival's window (post-decision, so the plane shows
            # the trajectory the decision log replays)
            st = st._replace(tl=tln_engine.on_arrival(
                st.tl, t_i, n_on if auto_on else jnp.int32(W)))
            if flip_on:
                # the hybrid balancer packs while any active worker
                # still has a free core (hermes_score's low_load read
                # on the same masked active vector select sees)
                new_mode = (sel_active < C).any().astype(jnp.int32)
                st = st._replace(tl=tln_engine.on_event(
                    st.tl, new_mode != st.tl["mode"], t_i,
                    EV_MODE_FLIP, new_mode, jnp.float64(np.nan)))
                st = st._replace(tl=dict(st.tl, mode=new_mode))
        with jax.named_scope("select"):
            if stateful:
                w, lb = select(st.lb, sel_active, wcol, f_i, homes,
                               u_i, tid)
                st = st._replace(lb=lb)
            else:
                w = select(sel_active, wcol, f_i, homes, u_i, tid)
        if not stream:
            st = st._replace(rejected=st.rejected.at[tid].set(w < 0))
        if tel_on:
            st = st._replace(tel=tel_engine.on_reject(st.tel, w < 0))
        if tl_on:
            st = st._replace(tl=tln_engine.on_reject(st.tl, t_i, w < 0))
        if stream:
            st, is_cold = lax.cond(
                w >= 0,
                lambda s: place(s, tid, jnp.maximum(w, 0), f_i, svc_i,
                                t_i),
                lambda s: (s, jnp.bool_(False)), st)
        else:
            st = lax.cond(
                w >= 0,
                lambda s: place(s, tid, jnp.maximum(w, 0), f_i, svc_i,
                                t_i),
                lambda s: s, st)
            is_cold = jnp.bool_(False)
        return st, w, is_cold, n_iter

    if stream:
        def step(st: SimState, xs, funcs, services, arrivals, homes):
            gid, valid, t_i, f_i, u_i, svc_i = xs

            def live(s):
                s, w, is_cold, n_iter = early_arrival(
                    s, gid, t_i, f_i, u_i, svc_i, funcs, services,
                    arrivals, homes)
                ys = (w < 0, is_cold,
                      jnp.where(w >= 0, w, -1).astype(jnp.int32))
                return s, ys + ((n_iter,) if count_drain else ())

            def skip(s):
                ys = (jnp.bool_(False), jnp.bool_(False), jnp.int32(-1))
                return s, ys + ((jnp.int32(0),) if count_drain else ())

            # ``valid`` masks the padded tail of the last chunk.  It is
            # passed unbatched under vmap, so the predicate stays
            # scalar and the cond stays a real branch — padded steps
            # execute nothing and are identity on the carry
            return lax.cond(valid, live, skip, st)
    else:
        def step(st: SimState, xs, funcs, services, arrivals, homes):
            i, t_i, f_i, u_i = xs
            if late:
                if tl_on:
                    # fixed fleet: provisioned core-seconds accrue over
                    # the inter-arrival gap at the full W
                    st = st._replace(tl=tln_engine.on_prov(
                        st.tl, st.now,
                        (t_i - st.now) * jnp.float64(W) * TL_CORES))
                st, n_iter = advance(st, t_i - st.now, funcs, services,
                                     arrivals)
                st = st._replace(now=t_i)
                if tl_on:
                    st = st._replace(tl=tln_engine.on_arrival(
                        st.tl, t_i, jnp.int32(W)))
                active = (st.task_idx >= 0).sum(axis=1).astype(jnp.int32)

                def do_place(st):
                    return place(st, i, jnp.argmin(active), f_i,
                                 services[i], t_i)

                def do_queue(st):
                    return st._replace(q=st.q.at[st.q_tail % Q].set(
                        i.astype(jnp.int32)), q_tail=st.q_tail + 1)
                st = lax.cond(active.min() < C, do_place, do_queue, st)
            else:
                st, _, _, n_iter = early_arrival(
                    st, i, t_i, f_i, u_i, services[i], funcs, services,
                    arrivals, homes)
            return st, (n_iter if count_drain else ())

    def init_planes():
        """Initial lb/life/tel/fleet/tl carry pytrees (shared between
        the monolithic ``run`` and the stream ``init`` — identical
        bits)."""
        lb0 = ()
        if stateful:
            lb0 = jax.tree_util.tree_map(jnp.asarray,
                                         res.init_state(W, F))
        life0 = ()
        if life_on:
            ka0 = ()
            if lres.stateful:
                ka0 = jax.tree_util.tree_map(
                    jnp.asarray, lres.init_policy_state(W, F))
            pre0, keep0 = life_windows(ka0)
            life0 = {
                # +1 pad col: completion scatters park on the pad when
                # nothing completed, exactly like ``warm``.  -1 marks a
                # pool with no completion history (masks observations)
                "idle_since": jnp.full((W, F + 1), -1.0,
                                       dtype=jnp.float64),
                # explicit dtype also strips any weak type a keep-alive
                # policy's windows() may have produced
                "pre": jnp.asarray(pre0, dtype=jnp.float64),
                "keep": jnp.asarray(keep0, dtype=jnp.float64),
                "ka": ka0,
            }
        tel0 = tel_engine.init_state(W) if tel_on else ()
        fleet0 = ()
        if auto_on:
            from repro.telemetry.sketch import N_BINS
            fleet0 = {
                # start fully provisioned; the controller scales down
                # through troughs (min_workers floor) and back up
                "n_on": jnp.int32(W),
                "cool_until": jnp.float64(0.0),
                "prov_time": jnp.float64(0.0),
                # slowdown-sketch snapshot at the last decision; the
                # decision window is slow_hist - snap
                "snap": jnp.zeros((N_BINS,), dtype=jnp.int64),
            }
        tl0 = tln_engine.init_state(W, timeline) if tl_on else ()
        return lb0, life0, tel0, fleet0, tl0

    def run(arrivals, funcs, services, u_lb, homes):
        lb0, life0, tel0, fleet0, tl0 = init_planes()
        st = SimState(
            remaining=jnp.full((W, S), jnp.inf, dtype=jnp.float64),
            task_arr=jnp.zeros((W, S), dtype=jnp.float64),
            task_idx=jnp.full((W, S), -1, dtype=jnp.int32),
            warm=jnp.zeros((W, F + 1), dtype=jnp.int32),
            q=jnp.zeros((Q,), dtype=jnp.int32),
            q_head=jnp.int32(0), q_tail=jnp.int32(0),
            now=jnp.float64(0.0),
            resp=jnp.full((N + 1,), jnp.nan, dtype=jnp.float64),
            cold=jnp.zeros((N + 1,), dtype=bool),
            rejected=jnp.zeros((N + 1,), dtype=bool),
            worker_of=jnp.full((N + 1,), -1, dtype=jnp.int32),
            server_time=jnp.float64(0.0), core_time=jnp.float64(0.0),
            lb=lb0, life=life0, tel=tel0, fleet=fleet0, tl=tl0,
        )
        if tl_on:
            # runtime window width: the configured constant, or the
            # horizon (last arrival) over K — one f64 division of the
            # same operands the numpy oracle divides, so window
            # assignment is bitwise identical across engines
            ws = jnp.float64(TL_WS_CFG) if TL_WS_CFG > 0.0 \
                else arrivals[N - 1] / jnp.float64(TL_K)
            st = st._replace(tl=dict(st.tl, window_s=ws))
        xs = (jnp.arange(N, dtype=jnp.int64), arrivals, funcs, u_lb)
        st, iters = lax.scan(
            partial(step, funcs=funcs, services=services, arrivals=arrivals,
                    homes=homes), st, xs)
        t_last = st.now
        st, _ = advance(st, jnp.float64(_BIG_TIME), funcs, services,
                        arrivals)
        if auto_on:
            # drain tail: the fleet stays provisioned until the last
            # completion (advance stops accumulating when idle)
            fl = st.fleet
            st = st._replace(fleet=dict(fl, prov_time=(
                fl["prov_time"]
                + (st.now - t_last) * fl["n_on"].astype(jnp.float64))))
        if tl_on:
            n_prov = st.fleet["n_on"].astype(jnp.float64) if auto_on \
                else jnp.float64(W)
            st = st._replace(tl=tln_engine.on_prov(
                st.tl, t_last, (st.now - t_last) * n_prov * TL_CORES))
        return (st, iters) if count_drain else st

    if not stream:
        return run

    # ---- stream mode: horizon-independent chunk engine ----------------

    def init(n_reps: int, cutoff: int, window_s=None) -> SimState:
        """Initial batched carry (leading ``n_reps`` axis, eager).

        ``cutoff`` is the *global* post-warmup index — it rides in the
        carry so one compiled chunk program serves any horizon.
        ``window_s`` (timeline engines only) is the per-replication
        ``[R]`` runtime window width — computed host-side by
        ``simulate_stream`` from each replication's horizon, exactly as
        the monolithic engine computes it in-trace.
        """
        lb0, life0, tel0, fleet0, tl0 = init_planes()
        st = SimState(
            remaining=jnp.full((W, S), jnp.inf, dtype=jnp.float64),
            task_arr=jnp.zeros((W, S), dtype=jnp.float64),
            task_idx=jnp.full((W, S), -1, dtype=jnp.int32),
            warm=jnp.zeros((W, F + 1), dtype=jnp.int32),
            q=(), q_head=jnp.int32(0), q_tail=jnp.int32(0),
            now=jnp.float64(0.0),
            resp=(), cold=(), rejected=(), worker_of=(),
            server_time=jnp.float64(0.0), core_time=jnp.float64(0.0),
            lb=lb0, life=life0, tel=tel0, fleet=fleet0, tl=tl0,
            task_fn=jnp.zeros((W, S), dtype=jnp.int32),
            task_svc=jnp.zeros((W, S), dtype=jnp.float64),
            stream={
                "cutoff": jnp.int64(cutoff),
                "n_done": jnp.int64(0), "n_obs": jnp.int64(0),
                "resp_sum": jnp.float64(0.0),
                "slow_sum": jnp.float64(0.0),
            })
        st = jax.tree_util.tree_map(
            lambda x: jnp.tile(x[None], (n_reps,) + (1,) * x.ndim), st)
        if tl_on and window_s is not None:
            st = st._replace(tl=dict(st.tl, window_s=jnp.asarray(
                window_s, dtype=jnp.float64)))
        return st

    def run_chunk(st, gids, valid, arrivals, funcs, services, u_lb,
                  homes):
        """One compiled scan over a chunk of arrivals.

        Returns ``(st, ys)`` where ``ys`` are the per-arrival
        ``(rejected, cold, worker)`` outputs of the chunk (and the drain
        iterations with ``count_drain``).
        """
        xs = (gids, valid, arrivals, funcs, u_lb, services)
        return lax.scan(
            partial(step, funcs=funcs, services=services,
                    arrivals=arrivals, homes=homes), st, xs)

    def run_drain(st):
        """End-of-horizon drain — the monolithic engine's scan tail."""
        t_last = st.now
        st, _ = advance(st, jnp.float64(_BIG_TIME), None, None, None)
        if auto_on:
            fl = st.fleet
            st = st._replace(fleet=dict(fl, prov_time=(
                fl["prov_time"]
                + (st.now - t_last) * fl["n_on"].astype(jnp.float64))))
        if tl_on:
            n_prov = st.fleet["n_on"].astype(jnp.float64) if auto_on \
                else jnp.float64(W)
            st = st._replace(tl=tln_engine.on_prov(
                st.tl, t_last, (st.now - t_last) * n_prov * TL_CORES))
        return st

    return init, run_chunk, run_drain


# --------------------------------------------------------------------------
# Process-wide compile cache (bounded LRU).
#
# ``simulate()`` used to rebuild (and therefore re-trace + re-compile) the
# whole scan program on every call — a policy × load sweep paid XLA
# compilation per *cell*.  The engine is fully determined by
# ``(policy, cluster, N, F)`` (``cluster`` folds in W/C/S and the cold-start
# penalty), so compiled programs are memoized on that key; jit's own shape
# cache then handles the batch axis, and a sweep over arrival-rate scale
# reuses one compiled program per policy.
#
# The cache is LRU-bounded at ``ENGINE_CACHE_MAX`` entries: every distinct
# (N, F) shape pins its jitted callable plus XLA executable, so an
# unbounded dict grows without limit under long multi-shape sweeps
# (trace replays with per-trace F, scale studies varying N).  64 covers
# every in-repo sweep (the full benchmark harness compiles < 40 engines)
# while evicting cold programs in recompile-on-miss fashion.
# --------------------------------------------------------------------------

#: Default max resident compiled engines; see note above.  This is only
#: the *initial* bound — rebinding this name later has no effect; use
#: :func:`set_engine_cache_capacity` to change the live limit.
ENGINE_CACHE_MAX = 64

_ENGINE_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_ENGINE_CACHE_CAPACITY = ENGINE_CACHE_MAX
#: Lifetime lookup counters (reset together with the cache); exported by
#: :func:`engine_cache_stats` and surfaced in BENCH_report.json.
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def _resolve_backend(policy: PolicySpec, backend: str) -> str:
    """Normalize the user-facing backend choice (``"auto"`` dispatch)."""
    if backend == "auto":
        return default_backend(policy)
    return backend


def _cache_key(policy: PolicySpec, cluster: ClusterCfg,
               n_arrivals: int, n_functions: int, batched: bool,
               backend: str,
               telemetry: TelemetryCfg | None = None,
               chunk: int | None = None,
               timeline: TimelineCfg | None = None,
               mesh=None, count_drain: bool = False) -> tuple:
    # telemetry-on engines trace a different program, so the cfg is part
    # of the key (None = the golden pre-telemetry program).  ``chunk``
    # marks a streaming chunk engine (the chunk size IS the key's shape
    # axis — n_arrivals then holds the chunk length, and one compiled
    # program serves any horizon); None = monolithic.  ``timeline``
    # likewise gates a different traced program (the flight-recorder
    # plane), so its cfg joins the key as a trailing element, as do
    # the device mesh of a rep-sharded streaming engine and the drain
    # counter of a traced run.
    return (tuple(policy), tuple(cluster), int(n_arrivals),
            int(n_functions), batched, backend,
            None if telemetry is None else tuple(telemetry),
            None if chunk is None else int(chunk),
            None if timeline is None else tuple(timeline),
            mesh, bool(count_drain))


def _cache_get_or_build(key: tuple, build):
    """Return ``(engine, fresh)``; ``fresh`` marks a cache-miss build.

    The build is wrapped in an ``engine.build`` tracer span, so with
    tracing on every compile-cache miss is visible on the timeline
    (hits cost one dict lookup and no span).
    """
    fn = _ENGINE_CACHE.get(key)
    if fn is not None:
        _CACHE_STATS["hits"] += 1
        _ENGINE_CACHE.move_to_end(key)
        return fn, False
    _CACHE_STATS["misses"] += 1
    with get_tracer().span("engine.build", backend=key[5],
                           batched=key[4], n=key[2]):
        fn = build()
    _ENGINE_CACHE[key] = fn
    while len(_ENGINE_CACHE) > _ENGINE_CACHE_CAPACITY:
        _ENGINE_CACHE.popitem(last=False)
        _CACHE_STATS["evictions"] += 1
    return fn, True


def engine_cache_stats() -> dict:
    """Cache occupancy + lifetime hit/miss/eviction counters."""
    keys = list(_ENGINE_CACHE)
    return {"entries": len(keys),
            "batched": sum(1 for k in keys if k[4]),
            "single": sum(1 for k in keys if not k[4]),
            "capacity": _ENGINE_CACHE_CAPACITY,
            "hits": _CACHE_STATS["hits"],
            "misses": _CACHE_STATS["misses"],
            "evictions": _CACHE_STATS["evictions"]}


def engine_cache_capacity() -> int:
    return _ENGINE_CACHE_CAPACITY


def set_engine_cache_capacity(capacity: int) -> None:
    """Re-bound the LRU (evicting oldest entries if shrinking)."""
    global _ENGINE_CACHE_CAPACITY
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    _ENGINE_CACHE_CAPACITY = int(capacity)
    while len(_ENGINE_CACHE) > _ENGINE_CACHE_CAPACITY:
        _ENGINE_CACHE.popitem(last=False)
        _CACHE_STATS["evictions"] += 1


def clear_engine_cache() -> None:
    """Drop all compiled engines and reset the lookup counters."""
    _ENGINE_CACHE.clear()
    for k in _CACHE_STATS:
        _CACHE_STATS[k] = 0


def _get_engine(policy: PolicySpec, cluster: ClusterCfg,
                n_arrivals: int, n_functions: int, batched: bool,
                backend: str, telemetry: TelemetryCfg | None,
                timeline: TimelineCfg | None = None,
                count_drain: bool = False):
    """Cached engine lookup; returns ``(engine, fresh)``.

    ``fresh`` marks a cache-miss build — the next dispatch through the
    callable pays XLA compilation, which :func:`simulate` /
    :func:`simulate_many` surface as an ``engine.first_run`` span
    (vs ``engine.run`` for steady-state cached dispatches).
    ``count_drain`` builds the engine that also returns the drain
    iterations (see :func:`_build_engine`).
    """
    cluster.validate()   # named errors instead of deep broadcast failures
    backend = _resolve_backend(policy, backend)
    key = _cache_key(policy, cluster, n_arrivals, n_functions, batched,
                     backend, telemetry, timeline=timeline,
                     count_drain=count_drain)
    raw = lambda: _build_engine(policy, cluster, n_arrivals, n_functions,
                                backend, telemetry=telemetry,
                                timeline=timeline, count_drain=count_drain)
    if batched:
        return _cache_get_or_build(key, lambda: jax.jit(jax.vmap(raw())))
    return _cache_get_or_build(key, lambda: jax.jit(raw()))


def _get_stream_engine(policy: PolicySpec, cluster: ClusterCfg,
                       chunk: int, n_functions: int, backend: str,
                       telemetry: TelemetryCfg | None,
                       timeline: TimelineCfg | None = None,
                       mesh=None, count_drain: bool = False):
    """Cached streaming chunk-engine lookup.

    Returns ``((init, step_fn, drain_fn), fresh)``.  ``step_fn`` is the
    jitted+vmapped chunk scan with the carry donated
    (``donate_argnums=(0,)``), so handing the carry across segment
    boundaries reuses its device buffers instead of copying them;
    ``drain_fn`` donates the same way.  The key carries the chunk size
    instead of the horizon — growing ``N`` reuses one compiled program.
    With a 1-D ``"rep"`` ``mesh`` both programs run per device on its
    block of replications (:func:`repro.distribution.sim_shard.rep_local`).
    ``count_drain`` adds the drain iterations to the chunk's outputs.
    """
    if chunk < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk}")
    cluster.validate()
    backend = _resolve_backend(policy, backend)
    key = _cache_key(policy, cluster, int(chunk), n_functions, True,
                     backend, telemetry, chunk=int(chunk),
                     timeline=timeline, mesh=mesh, count_drain=count_drain)

    def build():
        init, run_chunk, run_drain = _build_engine(
            policy, cluster, int(chunk), n_functions, backend,
            telemetry=telemetry, timeline=timeline, stream=True,
            count_drain=count_drain)
        # carry batched over reps; gids/valid unbatched so the padding
        # cond keeps a scalar predicate (a real branch, not a select)
        batched = (0, None, None, 0, 0, 0, 0, 0)
        step = jax.vmap(run_chunk, in_axes=batched)
        drain = jax.vmap(run_drain)
        if mesh is not None:
            from repro.distribution.sim_shard import rep_local
            step = rep_local(step, mesh, batched)
            drain = rep_local(drain, mesh, (0,))
        step_fn = jax.jit(step, donate_argnums=(0,))
        drain_fn = jax.jit(drain, donate_argnums=(0,))
        return (init, step_fn, drain_fn)

    return _cache_get_or_build(key, build)


def build_simulator(policy: PolicySpec, cluster: ClusterCfg, *,
                    n_arrivals: int, n_functions: int,
                    backend: str = "auto",
                    telemetry: TelemetryCfg | None = None,
                    timeline: TimelineCfg | None = None):
    """Jitted single-workload simulator, memoized process-wide.

    Repeated calls with an equal key return the *same* compiled callable, so
    sweeps over loads/seeds (which only change array values, not shapes)
    compile exactly once per policy.  ``backend`` is ``"jax"``,
    ``"pallas"`` or ``"auto"`` (kernel dispatch whenever the balancer
    ships one — see :func:`repro.policy.default_backend`).  The memo is a
    bounded LRU (``ENGINE_CACHE_MAX`` entries by default; resize with
    :func:`set_engine_cache_capacity`); a key evicted by newer shapes is
    transparently rebuilt on the next call.  ``telemetry`` selects the
    streaming-metrics variant (a distinct cache entry — the carry shape
    differs); ``timeline`` likewise selects the windowed flight-recorder
    variant.
    """
    fn, _ = _get_engine(policy, cluster, n_arrivals, n_functions, False,
                        backend, telemetry, timeline)
    return fn


def build_batch_simulator(policy: PolicySpec, cluster: ClusterCfg, *,
                          n_arrivals: int, n_functions: int,
                          backend: str = "auto",
                          telemetry: TelemetryCfg | None = None,
                          timeline: TimelineCfg | None = None):
    """Jitted ``vmap``-ed simulator over a leading replication axis.

    All five inputs carry a leading ``R`` axis (``arrivals/funcs/services/
    u_lb`` are ``[R, N]``, ``homes`` is ``[R, F]``); one compiled program
    advances all R replications in lockstep.  With the ``pallas``
    backend (the ``auto`` choice for Hermes), the replication axis maps
    onto the controller kernel's batch dimension: one
    :mod:`repro.kernels.hermes_select` dispatch serves every stacked
    replication per arrival.
    """
    fn, _ = _get_engine(policy, cluster, n_arrivals, n_functions, True,
                        backend, telemetry, timeline)
    return fn


def _cluster_auto_on(cluster: ClusterCfg) -> bool:
    """Whether this cluster runs an active autoscale control loop."""
    fl = cluster.fleet
    return fl is not None and \
        str(fl.autoscale).strip().upper() != _AUTO_STATIC


def _prov_core_s(st, cluster: ClusterCfg):
    """Provisioned core-seconds: ∫ n_on(t)·cores dt (fig. 13 x-axis).

    Without an autoscaler the active set is the whole fleet for the
    whole run, so the integral degenerates to ``end_time × W × C``.
    """
    if _cluster_auto_on(cluster):
        return np.asarray(st.fleet["prov_time"]) * cluster.cores
    return np.asarray(st.now) * cluster.n_workers * cluster.cores


@partial(jax.jit, static_argnums=1)
def _drain_totals(iters, blocks: int):
    """``[R, steps]`` drain iterations reduced to their sum and, per
    block of ``R / blocks`` consecutive replications, the sum over steps
    of the max over the block, which is what a vmapped ``while_loop``
    over that block runs."""
    per_block = iters.reshape(blocks, -1, iters.shape[1]).max(axis=1)
    return (iters.sum(dtype=jnp.int64),
            per_block.sum(axis=1, dtype=jnp.int64))


def _count_drain(tracer, iters: list, n_reps: int, steps: int,
                 blocks: int = 1) -> None:
    """Record a run's drain iterations (``[R, steps]`` device arrays) as
    one ``drain.iterations`` counter sample: ``needed``, the sum over
    steps of the mean over replications; ``executed``, what each device's
    loop ran, the mean over the ``blocks`` devices of a ``"rep"`` mesh of
    the sum over steps of the max over the device's block;
    ``executed_max``, the largest device's such sum; and the ``steps``.
    With one block (no mesh) both are the sum over steps of the max over
    all replications.  Reducing them and bringing the totals to the host
    is an ``engine.count`` span."""
    with tracer.span("engine.count"):
        totals = jax.device_get([_drain_totals(it, blocks) for it in iters])
    ran = sum(np.asarray(b, np.int64) for _, b in totals)
    tracer.count("drain.iterations",
                 needed=sum(int(t) for t, _ in totals) / n_reps,
                 executed=int(ran.sum()) / blocks,
                 executed_max=int(ran.max()), steps=steps)


def _record_scopes(tracer, label: str, fn, *args) -> None:
    """Keep on the tracer, once per ``label``, the map from each HLO
    instruction of the program the jitted ``fn`` runs on ``args`` to its
    innermost :data:`STEP_SCOPES` scope.  Where ``fn`` already ran on
    such arguments, lowering and compiling hit jit's caches; where it
    has not, its first call reuses this compilation."""
    if label not in tracer.scopes:
        tracer.scopes[label] = op_scopes(
            fn.lower(*args).compile().as_text(), STEP_SCOPES)


def simulate(policy: PolicySpec, cluster: ClusterCfg, wl: Workload,
             *, backend: str = "auto",
             telemetry: TelemetryCfg | None = None,
             timeline: TimelineCfg | None = None) -> SimOutput:
    """Run the JAX simulator on a workload; returns host-side results.

    With ``telemetry`` set, the returned output carries a
    :class:`~repro.telemetry.TelemetryResult` accumulated inside the
    scan (histogram percentile sketches, counters, occupancy
    integrals).  With ``timeline`` set, it additionally carries a
    :class:`~repro.telemetry.TimelineResult` — the windowed
    flight-recorder plane (per-window counters/sketches/integrals and
    the bounded decision-event log).
    """
    run, fresh = _get_engine(policy, cluster, wl.n, wl.n_functions,
                             False, backend, telemetry, timeline)
    tr = get_tracer()
    with tr.span("engine.first_run" if fresh else "engine.run",
                 policy=str(policy), backend=backend, n=wl.n):
        st = run(jnp.asarray(wl.arrival), jnp.asarray(wl.func),
                 jnp.asarray(wl.service), jnp.asarray(wl.u_lb),
                 jnp.asarray(wl.func_home))
        if tr.enabled:
            st = jax.block_until_ready(st)
    return SimOutput(
        response=np.asarray(st.resp[:wl.n]),
        cold=np.asarray(st.cold[:wl.n]),
        rejected=np.asarray(st.rejected[:wl.n]),
        worker=np.asarray(st.worker_of[:wl.n]),
        server_time=float(st.server_time),
        core_time=float(st.core_time),
        end_time=float(st.now),
        telemetry=None if telemetry is None else TelemetryResult.from_state(
            jax.tree_util.tree_map(np.asarray, st.tel), cfg=telemetry),
        prov_core_s=float(_prov_core_s(st, cluster)),
        timeline=None if timeline is None else TimelineResult.from_state(
            jax.tree_util.tree_map(np.asarray, st.tl), cfg=timeline),
    )


def simulate_many(policy: PolicySpec, cluster: ClusterCfg,
                  workloads, *, backend: str = "auto",
                  telemetry: TelemetryCfg | None = None,
                  timeline: TimelineCfg | None = None
                  ) -> BatchSimOutput:
    """Run ``R`` stacked workload replications through one compiled program.

    ``workloads`` is a :class:`~repro.core.workload.WorkloadBatch` or a
    sequence of :class:`Workload` sharing one ``(N, F)`` shape (stacked
    here).  Semantically identical to ``R`` independent :func:`simulate`
    calls — the batched engine is the same scan program under ``vmap`` —
    but compiles once and advances every replication per XLA dispatch.
    With ``telemetry`` set, the output's
    :class:`~repro.telemetry.TelemetryResult` keeps the leading ``R``
    axis; its percentile readers pool across it (matching
    ``summarize_batch``'s pooled statistics).

    With the process-wide tracer on, the call records the spans
    ``engine.put`` (inputs to the device), ``engine.run`` (or
    ``engine.first_run`` after a build; waits for the device) and
    ``engine.readout`` (outputs to the host); a ``drain.iterations``
    counter and its ``engine.count`` span (see :func:`_count_drain`); and
    the compiled program's
    op-to-scope map in the tracer's ``scopes``.  The engine it runs then
    counts drain iterations (a separate cache entry).
    """
    wb = workloads if isinstance(workloads, WorkloadBatch) \
        else stack_workloads(workloads)
    tr = get_tracer()
    run, fresh = _get_engine(policy, cluster, wb.n, wb.n_functions,
                             True, backend, telemetry, timeline,
                             count_drain=tr.enabled)
    with tr.span("engine.put"):
        args = (jnp.asarray(wb.arrival), jnp.asarray(wb.func),
                jnp.asarray(wb.service), jnp.asarray(wb.u_lb),
                jnp.asarray(wb.func_home))
    with tr.span("engine.first_run" if fresh else "engine.run",
                 policy=str(policy), backend=backend, n=wb.n,
                 reps=wb.n_reps):
        st = run(*args)
        if tr.enabled:
            st, iters = jax.block_until_ready(st)
    if tr.enabled:
        _record_scopes(tr, f"simulate_many {policy} {backend} n={wb.n} "
                       f"f={wb.n_functions} reps={wb.n_reps}", run, *args)
    with tr.span("engine.readout"):
        out = BatchSimOutput(
            response=np.asarray(st.resp[:, :wb.n]),
            cold=np.asarray(st.cold[:, :wb.n]),
            rejected=np.asarray(st.rejected[:, :wb.n]),
            worker=np.asarray(st.worker_of[:, :wb.n]),
            server_time=np.asarray(st.server_time),
            core_time=np.asarray(st.core_time),
            end_time=np.asarray(st.now),
            telemetry=None if telemetry is None
            else TelemetryResult.from_state(
                jax.tree_util.tree_map(np.asarray, st.tel), cfg=telemetry),
            prov_core_s=np.asarray(_prov_core_s(st, cluster),
                                   dtype=np.float64),
            timeline=None if timeline is None
            else TimelineResult.from_state(
                jax.tree_util.tree_map(np.asarray, st.tl), cfg=timeline),
        )
    if tr.enabled:
        _count_drain(tr, [iters], wb.n_reps, wb.n)
    return out
