"""Built-in load balancers — numpy / jax (/ Pallas) triplets.

Every backend of a balancer implements the identical deterministic
contract (see :mod:`repro.policy.registry`) so the numpy oracle, the
jitted scan engine, and the Pallas controller kernel can be compared
task-by-task (``tests/test_policies.py`` asserts this for every
registered balancer).

Paper balancers (§3.1, §4.2): ``LOC`` (OpenWhisk sticky hashing), ``R``
(uniform over free workers), ``LL`` (join-shortest-queue), ``H`` (Hermes
hybrid — packing at low load, least-loaded at high load, warm-executor
tie-breaks; its ``pallas`` backend is the batched controller kernel in
:mod:`repro.kernels.hermes_select`).

Registry extensions beyond the paper (the policy zoo):

* ``JSQ2`` — power-of-two-choices: sample two workers from the single
  pre-drawn uniform ``u``, join the shorter queue; falls back to the
  global least-loaded worker when both candidates are slot-full (so it
  only rejects when the whole cluster is full, like every balancer
  here).
* ``RR`` — round-robin: start at worker ``idx mod W`` (``idx`` is the
  arrival sequence number) and linear-probe to the first worker with a
  free slot — LOC's ring walk with a rotating home.
* ``HIKU`` — pull-based assignment (Hiku, Akbari & Hauswirth 2025):
  workers *advertise* themselves into a FIFO ready-ring when their last
  active task completes; an arrival pops the oldest advertised (idle)
  worker and falls back to least-loaded when the ring is empty.  The
  ring (worker ids + membership flags + head/tail counters) is carried
  state threaded through the engines; duplicates are impossible (the
  membership flag gates pushes), so a popped worker is idle by
  invariant and a pop never rejects.  All workers start advertised.
* ``DD`` — data-driven dispatch (per-function execution-time estimates
  à la Przybylski et al. 2021): carried state holds a per-function EMA
  of observed execution times (``α = 0.25``, prior 1 s) plus each
  worker's expected outstanding work; an arrival joins the worker with
  the least expected work (shortest-expected-load), charging the
  function's current estimate, and completions both discharge the
  worker and refine the function's estimate.
* ``SWARM`` — smoothed-priority throughput learning (per the
  Helix/SWARM exemplar): least-loaded *weighted by a learned
  per-worker slowness factor*.  Completions report the *observed*
  (wall-clock) execution time on the completing worker; the state
  tracks a per-function duration scale ``est[f]`` and a per-worker
  slowness ``inv[w]`` (prior 1.0 ≈ learned ``1/speed``), both as
  multiplicative sign-EMAs — exponentially-weighted *median* trackers,
  robust to the heavy-tailed Azure duration mix where a mean EMA is
  noise-dominated.  The slowness sample is ``observed / est[f]``
  (function-scale-normalized, so every worker's samples are
  comparable), stepped fast for a worker's first completions and an
  order of magnitude slower once burned in.  Selection is
  congestion-gated: below core saturation an arrival simply joins the
  fastest (min ``inv``) worker with a free slot; at saturation it
  minimizes ``(active + 1) × inv[w]`` — queue depth scaled by
  slowness, i.e. expected wait.  On a heterogeneous fleet
  (``ClusterCfg.fleet``) this learns the speed vector online without
  ever reading it; on a homogeneous cluster ``inv`` stays flat and
  SWARM degrades to pack-then-least-loaded.

The Hermes lexicographic score (shared by np / jax / Pallas):

* low-load mode (some worker has a free core) — among workers with a
  free core, prefer class ``3`` = non-empty with a warm executor for the
  function, ``2`` = non-empty, ``1`` = empty with warm executor, ``0`` =
  empty; within a class prefer the *most* loaded (packing / fill-up).
* high-load mode (no free core anywhere) — least-loaded among workers
  with a free slot, warm executor breaks ties.
"""
from __future__ import annotations

import numpy as np

from .registry import register_balancer

_INT_INF = np.int64(1 << 40)


def hermes_score_np(active: np.ndarray, warm_f: np.ndarray, cores: int,
                    slots: int) -> tuple[np.ndarray, bool]:
    """Return (score vector to maximize, low_load_mode)."""
    has_core = active < cores
    low_load = bool(has_core.any())
    warm = warm_f > 0
    if low_load:
        nonempty = active > 0
        cls = np.where(nonempty, 2 + warm.astype(np.int64),
                       warm.astype(np.int64))
        score = cls * (slots + 1) + active
        score = np.where(has_core, score, -_INT_INF)
    else:
        has_slot = active < slots
        key = active.astype(np.int64) * 2 - warm.astype(np.int64)
        score = np.where(has_slot, -key, -_INT_INF)  # maximize = least loaded
    return score, low_load


def _two_choices(u: float, n_workers: int) -> tuple[int, int]:
    """Two candidate indices derived from one uniform draw.

    Splits ``u`` into integer part (first candidate) and the fractional
    remainder rescaled (second candidate) — float64 on every backend, so
    numpy and jax truncate identically.
    """
    x = u * n_workers
    a = min(int(x), n_workers - 1)
    frac = x - np.floor(x)
    b = min(int(frac * n_workers), n_workers - 1)
    return a, b


# --------------------------------------------------------------------------
# numpy backends
# --------------------------------------------------------------------------

def _loc_np(cores: int, slots: int):
    def select(active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        if not has_slot.any():
            return -1
        W = active.shape[0]
        home = int(func_home[func])
        ring = (home + np.arange(W)) % W
        return int(ring[int(np.argmax(has_slot[ring]))])
    return select


def _random_np(cores: int, slots: int):
    def select(active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        if not has_slot.any():
            return -1
        free_idx = np.nonzero(has_slot)[0]
        return int(free_idx[min(int(u * len(free_idx)), len(free_idx) - 1)])
    return select


def _ll_np(cores: int, slots: int):
    def select(active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        if not has_slot.any():
            return -1
        key = np.where(has_slot, active, _INT_INF)
        return int(np.argmin(key))
    return select


def _hybrid_np(cores: int, slots: int):
    def select(active, warm_col, func, func_home, u, idx):
        if not (active < slots).any():
            return -1
        score, _ = hermes_score_np(active, warm_col, cores, slots)
        return int(np.argmax(score))
    return select


def _jsq2_np(cores: int, slots: int):
    def select(active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        if not has_slot.any():
            return -1
        W = active.shape[0]
        a, b = _two_choices(float(u), W)
        key = np.where(has_slot, active, _INT_INF)
        w = b if key[b] < key[a] else a
        if not has_slot[w]:            # both sampled workers full
            w = int(np.argmin(key))
        return int(w)
    return select


def _rr_np(cores: int, slots: int):
    def select(active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        if not has_slot.any():
            return -1
        W = active.shape[0]
        ring = (int(idx) % W + np.arange(W)) % W
        return int(ring[int(np.argmax(has_slot[ring]))])
    return select


# --------------------------------------------------------------------------
# jax backends — jax imported lazily so numpy-only users avoid jax init
# --------------------------------------------------------------------------

def _guarded(jnp):
    def guard(w, has_slot):
        return jnp.where(has_slot.any(), w, -1).astype(jnp.int32)
    return guard


def _loc_jax(cores: int, slots: int):
    import jax.numpy as jnp
    guard = _guarded(jnp)

    def select(active, warm_col, func, func_home, u, idx):
        W = active.shape[0]
        has_slot = active < slots
        home = func_home[func]
        ring = (home + jnp.arange(W, dtype=jnp.int32)) % W
        return guard(ring[jnp.argmax(has_slot[ring])], has_slot)
    return select


def _random_jax(cores: int, slots: int):
    import jax.numpy as jnp
    guard = _guarded(jnp)

    def select(active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        k = has_slot.sum()
        target = jnp.minimum((u * k).astype(jnp.int32), k - 1)
        # index of the (target+1)-th free worker
        csum = jnp.cumsum(has_slot.astype(jnp.int32)) - 1
        hit = has_slot & (csum == target)
        return guard(jnp.argmax(hit), has_slot)
    return select


def _ll_jax(cores: int, slots: int):
    import jax.numpy as jnp
    guard = _guarded(jnp)
    BIG = jnp.int32(1 << 30)

    def select(active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        key = jnp.where(has_slot, active, BIG)
        return guard(jnp.argmin(key), has_slot)
    return select


def _hybrid_jax(cores: int, slots: int):
    import jax.numpy as jnp
    guard = _guarded(jnp)
    BIG = jnp.int32(1 << 30)

    def select(active, warm_col, func, func_home, u, idx):
        active = active.astype(jnp.int32)
        has_slot = active < slots
        has_core = active < cores
        warm = (warm_col > 0).astype(jnp.int32)
        nonempty = (active > 0).astype(jnp.int32)
        cls = jnp.where(nonempty > 0, 2 + warm, warm)
        lo_score = jnp.where(has_core, cls * (slots + 1) + active, -BIG)
        hi_key = active * 2 - warm
        hi_score = jnp.where(has_slot, -hi_key, -BIG)
        score = jnp.where(has_core.any(), lo_score, hi_score)
        return guard(jnp.argmax(score), has_slot)
    return select


def _jsq2_jax(cores: int, slots: int):
    import jax
    import jax.numpy as jnp
    # the two-choices derivation truncates u*W, so it matches the f64
    # numpy oracle only under x64.  The engines enable x64 process-wide
    # on import (repro.core.simulator); enforce the same here so a
    # standalone jax_select("JSQ2", ...) keeps the cross-backend
    # contract (model code in this repo pins explicit dtypes — safe).
    jax.config.update("jax_enable_x64", True)
    guard = _guarded(jnp)
    BIG = jnp.int32(1 << 30)

    def select(active, warm_col, func, func_home, u, idx):
        W = active.shape[0]
        has_slot = active < slots
        x = jnp.asarray(u, jnp.float64) * W
        a = jnp.minimum(x.astype(jnp.int32), W - 1)
        frac = x - jnp.floor(x)
        b = jnp.minimum((frac * W).astype(jnp.int32), W - 1)
        key = jnp.where(has_slot, active.astype(jnp.int32), BIG)
        w = jnp.where(key[b] < key[a], b, a)
        w = jnp.where(has_slot[w], w, jnp.argmin(key).astype(jnp.int32))
        return guard(w, has_slot)
    return select


def _rr_jax(cores: int, slots: int):
    import jax.numpy as jnp
    guard = _guarded(jnp)

    def select(active, warm_col, func, func_home, u, idx):
        W = active.shape[0]
        has_slot = active < slots
        home = jnp.asarray(idx, jnp.int32) % W
        ring = (home + jnp.arange(W, dtype=jnp.int32)) % W
        return guard(ring[jnp.argmax(has_slot[ring])], has_slot)
    return select


# --------------------------------------------------------------------------
# Carried-state balancers: HIKU (pull-based ready-ring) and DD
# (data-driven per-function EMA).  Their make_* factories return
# (select, on_complete) pairs — see the carried-state contract in
# repro.policy.registry.  Both backends of each balancer perform the
# identical float/int operations in the identical order, so np ≡ jax
# holds bitwise (the parity tests thread state across both).
# --------------------------------------------------------------------------

# EMA smoothing factor for DD's per-function estimates.  A power of two,
# and the update is written in incremental form est + α·(obs − est):
# α·d is then *exact* (pure exponent shift), so XLA fusing the
# multiply-add into an FMA rounds identically to numpy's separate
# mul-then-add and the np ≡ jax bitwise parity contract holds.
DD_ALPHA = 0.25
DD_PRIOR_S = 1.0      # estimate before a function's first completion


def _hiku_init(n_workers: int, n_functions: int):
    """All workers start advertised (everyone is idle at t=0)."""
    return {"ring": np.arange(n_workers, dtype=np.int32),
            "in_ring": np.ones(n_workers, dtype=np.int32),
            "head": np.int32(0),
            "tail": np.int32(n_workers)}


def _hiku_np(cores: int, slots: int):
    def select(state, active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        if not has_slot.any():
            return -1, state
        if int(state["tail"]) > int(state["head"]):
            ring = state["ring"]
            cand = int(ring[int(state["head"]) % ring.shape[0]])
            in_ring = state["in_ring"].copy()
            in_ring[cand] = 0
            new = dict(state, head=np.int32(int(state["head"]) + 1),
                       in_ring=in_ring)
            # inside the engines a ring member is idle by invariant, but
            # external placements (serving-platform re-dispatch) can
            # busy an advertised worker — validate before committing,
            # falling back to least-loaded (identical check in the jax
            # backend keeps bitwise parity)
            if has_slot[cand]:
                return cand, new
            key = np.where(has_slot, active, _INT_INF)
            return int(np.argmin(key)), new
        key = np.where(has_slot, active, _INT_INF)
        return int(np.argmin(key)), state

    def on_complete(state, w, func, service, n_active_after):
        if n_active_after != 0 or int(state["in_ring"][w]) != 0:
            return state
        ring = state["ring"].copy()
        ring[int(state["tail"]) % ring.shape[0]] = w
        in_ring = state["in_ring"].copy()
        in_ring[w] = 1
        return dict(state, ring=ring, in_ring=in_ring,
                    tail=np.int32(int(state["tail"]) + 1))

    return select, on_complete


def _hiku_jax(cores: int, slots: int):
    import jax.numpy as jnp
    guard = _guarded(jnp)
    BIG = jnp.int32(1 << 30)

    def select(state, active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        ring, in_ring = state["ring"], state["in_ring"]
        W = ring.shape[0]
        pop = (state["tail"] > state["head"]) & has_slot.any()
        cand = ring[state["head"] % W]
        key = jnp.where(has_slot, active.astype(jnp.int32), BIG)
        ll_w = jnp.argmin(key).astype(jnp.int32)
        # a popped worker is idle by engine invariant; under external
        # perturbation (serving re-dispatch) validate its slot and fall
        # back to least-loaded — mirrors the np backend bit-for-bit
        w = jnp.where(pop & has_slot[cand], cand, ll_w)
        in_ring = in_ring.at[cand].set(
            jnp.where(pop, 0, in_ring[cand]).astype(in_ring.dtype))
        new = dict(state, head=state["head"] + pop.astype(state["head"].dtype),
                   in_ring=in_ring)
        return guard(w, has_slot), new

    def on_complete(state, w, func, service, n_active_after):
        ring, in_ring = state["ring"], state["in_ring"]
        W = ring.shape[0]
        push = (n_active_after == 0) & (in_ring[w] == 0)
        pos = state["tail"] % W
        ring = ring.at[pos].set(
            jnp.where(push, w, ring[pos]).astype(ring.dtype))
        in_ring = in_ring.at[w].set(
            jnp.where(push, 1, in_ring[w]).astype(in_ring.dtype))
        return dict(state, ring=ring, in_ring=in_ring,
                    tail=state["tail"] + push.astype(state["tail"].dtype))

    return select, on_complete


def _dd_init(n_workers: int, n_functions: int):
    return {"est": np.full(n_functions, DD_PRIOR_S, dtype=np.float64),
            "ew": np.zeros(n_workers, dtype=np.float64)}


def _dd_np(cores: int, slots: int):
    def select(state, active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        if not has_slot.any():
            return -1, state
        key = np.where(has_slot, state["ew"], np.inf)
        w = int(np.argmin(key))
        ew = state["ew"].copy()
        ew[w] = ew[w] + state["est"][func]
        return w, dict(state, ew=ew)

    def on_complete(state, w, func, service, n_active_after):
        est = state["est"].copy()
        ew = state["ew"].copy()
        ew[w] = np.maximum(ew[w] - est[func], 0.0)
        est[func] = est[func] + DD_ALPHA * (service - est[func])
        return dict(state, est=est, ew=ew)

    return select, on_complete


def _dd_jax(cores: int, slots: int):
    import jax.numpy as jnp
    guard = _guarded(jnp)

    def select(state, active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        key = jnp.where(has_slot, state["ew"], jnp.inf)
        w = jnp.argmin(key).astype(jnp.int32)
        placed = has_slot.any()
        ew = state["ew"].at[w].add(jnp.where(placed, state["est"][func], 0.0))
        return guard(w, has_slot), dict(state, ew=ew)

    def on_complete(state, w, func, service, n_active_after):
        est_f = state["est"][func]
        ew = state["ew"].at[w].set(
            jnp.maximum(state["ew"][w] - est_f, 0.0))
        est = state["est"].at[func].set(
            est_f + DD_ALPHA * (service - est_f))
        return dict(state, est=est, ew=ew)

    return select, on_complete


# SWARM smoothing factors.  Every update is a *multiplicative sign-EMA*
# (a geometric median tracker): the tracked value is multiplied by a
# compile-time constant chosen by a comparison — a single IEEE multiply
# per update, with no add to fuse into, so XLA FMA fusion cannot change
# rounding and np ≡ jax stays bitwise.  The only other float combining
# op is an IEEE division (never fused).  Median tracking (not mean EMA)
# is what makes the learner robust to the heavy-tailed Azure duration
# mix: a mean EMA of lognormal samples is dominated by outliers and the
# learned slowness barely separates a 2× speed gap (measured on
# azure-diurnal), while the median tracker recovers it cleanly.
SWARM_ALPHA = 0.25          # est step: est ×= (1±α) toward the median
SWARM_GAMMA = 0.125         # inv step while a worker is burning in
SWARM_GAMMA_COLD = 0.0078125   # 1/128 — inv step after burn-in
SWARM_WARM_N = 128          # completions per worker before the step drop
SWARM_PRIOR_S = 1.0


def _swarm_init(n_workers: int, n_functions: int):
    return {"est": np.full(n_functions, SWARM_PRIOR_S, dtype=np.float64),
            "inv": np.ones(n_workers, dtype=np.float64),
            "cnt": np.zeros(n_workers, dtype=np.int64)}


# Precomputed multiplicative steps (python floats; identical constants
# embedded in both backends' traces).
_SW_EST_UP = 1.0 + SWARM_ALPHA
_SW_EST_DN = 1.0 / (1.0 + SWARM_ALPHA)
_SW_HOT_UP = 1.0 + SWARM_GAMMA
_SW_HOT_DN = 1.0 / (1.0 + SWARM_GAMMA)
_SW_COLD_UP = 1.0 + SWARM_GAMMA_COLD
_SW_COLD_DN = 1.0 / (1.0 + SWARM_GAMMA_COLD)


def _swarm_np(cores: int, slots: int):
    def select(state, active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        if not has_slot.any():
            return -1, state
        inv = state["inv"]
        # congestion-gated key: below core saturation join the fastest
        # free worker; at saturation minimize queue-depth × slowness
        # (= expected wait).  argmin ties resolve to the first index on
        # both backends.
        key = np.where(has_slot,
                       np.where(active + 1 <= cores, inv,
                                (active + 1.0) * inv),
                       np.inf)
        return int(np.argmin(key)), state

    def on_complete(state, w, func, service, n_active_after):
        # ``service`` is the observed wall-clock execution time on
        # worker ``w`` (the engines report effective durations when a
        # fleet is configured; see repro.policy.registry)
        est = state["est"].copy()
        inv = state["inv"].copy()
        cnt = state["cnt"].copy()
        sample = service / est[func]          # function-normalized slowness
        est[func] = est[func] * (_SW_EST_UP if service > est[func]
                                 else _SW_EST_DN)
        hot = cnt[w] < SWARM_WARM_N
        inv[w] = inv[w] * ((_SW_HOT_UP if hot else _SW_COLD_UP)
                           if sample > inv[w]
                           else (_SW_HOT_DN if hot else _SW_COLD_DN))
        cnt[w] = cnt[w] + 1
        return dict(state, est=est, inv=inv, cnt=cnt)

    return select, on_complete


def _swarm_jax(cores: int, slots: int):
    import jax.numpy as jnp
    guard = _guarded(jnp)

    def select(state, active, warm_col, func, func_home, u, idx):
        has_slot = active < slots
        inv = state["inv"]
        key = jnp.where(has_slot,
                        jnp.where(active + 1 <= cores, inv,
                                  (active + 1.0) * inv),
                        jnp.inf)
        w = jnp.argmin(key).astype(jnp.int32)
        return guard(w, has_slot), state

    def on_complete(state, w, func, service, n_active_after):
        est_f = state["est"][func]
        sample = service / est_f
        est = state["est"].at[func].set(
            est_f * jnp.where(service > est_f, _SW_EST_UP, _SW_EST_DN))
        hot = state["cnt"][w] < SWARM_WARM_N
        inv_w = state["inv"][w]
        step = jnp.where(sample > inv_w,
                         jnp.where(hot, _SW_HOT_UP, _SW_COLD_UP),
                         jnp.where(hot, _SW_HOT_DN, _SW_COLD_DN))
        inv = state["inv"].at[w].set(inv_w * step)
        cnt = state["cnt"].at[w].add(1)
        return dict(state, est=est, inv=inv, cnt=cnt)

    return select, on_complete


# --------------------------------------------------------------------------
# Pallas backend (H) — the batched controller kernel as a per-arrival
# select inside the scan engine, and as the batched dispatch for the
# serving controller
# --------------------------------------------------------------------------

def _hybrid_pallas(cores: int, slots: int):
    import jax.numpy as jnp
    from repro.kernels.hermes_select.kernel import (hermes_select_batch,
                                                    interpret_mode)
    interpret = interpret_mode()

    def select(active, warm_col, func, func_home, u, idx):
        # N=1 batch: the sequential contract is preserved exactly — the
        # engine applies completions between arrivals, so each decision
        # sees fresh cluster state.  Under ``vmap`` (simulate_many) the
        # replication axis becomes the kernel's batch dimension: one
        # kernel dispatch serves every stacked replication per arrival.
        out, _ = hermes_select_batch(
            active.astype(jnp.int32), warm_col.astype(jnp.int32)[None, :],
            cores=cores, slots=slots, interpret=interpret)
        return out[0]
    return select


def _hybrid_batch(cores: int, slots: int):
    from repro.kernels.hermes_select.ops import hermes_select

    def batch(active, warm, funcs):
        return hermes_select(active, warm, funcs, cores=cores, slots=slots)
    return batch


register_balancer(
    "LOC", doc="locality/sticky hashing (OpenWhisk default)",
    make_np=_loc_np, make_jax=_loc_jax)
register_balancer(
    "R", doc="uniform over workers with a free slot",
    make_np=_random_np, make_jax=_random_jax)
register_balancer(
    "LL", doc="least-loaded / join-shortest-queue",
    make_np=_ll_np, make_jax=_ll_jax)
register_balancer(
    "H", doc="Hermes hybrid: pack at low load, LL at high load",
    make_np=_hybrid_np, make_jax=_hybrid_jax,
    make_pallas=_hybrid_pallas, make_batch=_hybrid_batch)
register_balancer(
    "JSQ2", doc="power-of-two-choices: join the shorter of two sampled "
                "queues",
    make_np=_jsq2_np, make_jax=_jsq2_jax)
register_balancer(
    "RR", doc="round-robin ring probe from worker (idx mod W)",
    make_np=_rr_np, make_jax=_rr_jax)
register_balancer(
    "HIKU", doc="pull-based: idle workers advertise into a ready-ring; "
                "arrivals pop it, LL fallback when empty",
    make_np=_hiku_np, make_jax=_hiku_jax, init_state=_hiku_init)
register_balancer(
    "DD", doc="data-driven: shortest expected load via per-function "
              "execution-time EMAs",
    make_np=_dd_np, make_jax=_dd_jax, init_state=_dd_init)
register_balancer(
    "SWARM", doc="slowness-weighted least-loaded: learns per-worker "
                 "1/speed online via median-tracking priorities",
    make_np=_swarm_np, make_jax=_swarm_jax, init_state=_swarm_init)
