"""Plain numpy reference of the simulated semantics.

Written for the benchmark's check and independent of the program: it
imports nothing of it.  Semantics (arXiv:2111.07226 Sec. 3.2; cold-start
penalty 0 as in the paper's simulator):

* arrivals are taken in order; before each one the cluster advances to
  its time through every completion, with rates piecewise constant
  between events (the worker scheduler gives each active invocation its
  rate);
* early binding: the balancer picks a worker from the per-worker active
  counts and the warm column of the arrival's function; -1 rejects;
* a placement uses an idle warm executor of its function if the worker
  has one (warm start), else it is a cold start, and when busy plus
  idle executors fill the worker's slots the function with the most
  idle executors (first on ties) loses one;
* a completion leaves one idle warm executor of its function;
* after the last arrival the cluster drains to empty.

With ``telemetry`` the run also keeps the streaming engine's outputs:
log-spaced slowdown and latency histograms of the completions past the
warm-up cutoff, placement, eviction and rejection counters, per-worker
busy and depth time integrals, and the exact completion counters.

``dtype`` is the precision of every time and work value: float64 is
what the configurations state; float32 is the control.
"""
from __future__ import annotations

import importlib
import math

import numpy as np

EPS = 1e-9
N_BINS = 1536
_EDGES = np.logspace(math.log10(1e-4), math.log10(1e6), N_BINS + 1)


def bin_of(x: float) -> int:
    """Histogram bin: the last edge at or below ``x``, clamped."""
    return int(min(max(np.searchsorted(_EDGES, x, side="right") - 1, 0),
                   N_BINS - 1))


def policy_parts(policy: str):
    """``(balancer select, scheduler rates)`` of an ``E/<LB>/<S>`` stack."""
    binding, lb, sched = policy.split("/")
    if binding != "E":
        raise ValueError(f"the reference runs early binding only: {policy}")
    return (importlib.import_module(f"chipbench.policies.lb_{lb}").select,
            importlib.import_module(f"chipbench.policies.sched_{sched}")
            .rates)


def simulate(cluster: dict, policy: str, rep: dict, *, dtype=np.float64,
             telemetry: bool = False, warmup_frac: float = 0.1) -> dict:
    """Run one replication; ``rep`` holds ``arrival``, ``func``,
    ``service`` ([N]) and ``n_functions``."""
    select, rates_of = policy_parts(policy)
    W, C = int(cluster["n_workers"]), int(cluster["cores"])
    S = C * int(cluster["capacity_factor"])
    F = int(rep["n_functions"])
    arrival = np.asarray(rep["arrival"], dtype=dtype)
    service = np.asarray(rep["service"], dtype=dtype)
    funcs = np.asarray(rep["func"], dtype=np.int64)
    N = arrival.shape[0]
    cutoff = int(N * warmup_frac)
    zero = dtype(0.0)

    rem = np.full((W, S), np.inf, dtype=dtype)   # remaining work
    t_arr = np.zeros((W, S), dtype=dtype)        # occupant's arrival
    tid = np.full((W, S), -1, dtype=np.int64)    # occupant's index
    warm = np.zeros((W, F), dtype=np.int64)      # idle warm executors
    n_w = np.zeros(W, dtype=np.int64)            # active per worker
    out = {"response": np.full(N, np.nan, dtype=dtype),
           "cold": np.zeros(N, dtype=bool),
           "rejected": np.zeros(N, dtype=bool),
           "worker": np.full(N, -1, dtype=np.int32)}
    acc = {"now": zero, "server_time": zero, "core_time": zero}
    tel = None
    if telemetry:
        tel = {"slow_hist": np.zeros(N_BINS, np.int64),
               "lat_hist": np.zeros(N_BINS, np.int64),
               "n_cold": 0, "n_warm": 0, "n_evict": 0, "n_reject": 0,
               "decisions": np.zeros(W, np.int64),
               "busy_time": np.zeros(W, dtype), "depth_time": np.zeros(W, dtype),
               "n_done": 0, "n_obs": 0, "resp_sum": zero, "slow_sum": zero}

    def complete(w, s):
        i = int(tid[w, s])
        resp = acc["now"] - t_arr[w, s]
        out["response"][i] = resp
        if tel is not None:
            tel["n_done"] += 1
            if i >= cutoff:
                slow = resp / max(service[i], dtype(1e-12))
                tel["slow_hist"][bin_of(slow)] += 1
                tel["lat_hist"][bin_of(resp)] += 1
                tel["n_obs"] += 1
                tel["resp_sum"] = dtype(tel["resp_sum"] + resp)
                tel["slow_sum"] = dtype(tel["slow_sum"] + slow)
        warm[w, funcs[i]] += 1
        tid[w, s] = -1
        rem[w, s] = np.inf
        n_w[w] -= 1

    def advance(dt_left):
        row_min = rem.min(axis=1)
        while n_w.any():
            rate = rates_of(n_w, C, dtype)
            with np.errstate(divide="ignore", invalid="ignore"):
                t_done = row_min / rate              # idle rows: inf
            tau = min(dt_left, dtype(t_done.min()))
            if tau <= 0 and dt_left <= 0:
                return
            tau = max(tau, zero)
            busy = n_w > 0
            acc["server_time"] = dtype(acc["server_time"]
                                       + tau * dtype(busy.sum()))
            acc["core_time"] = dtype(acc["core_time"]
                                     + tau * dtype(np.minimum(n_w, C).sum()))
            if tel is not None:
                tel["busy_time"] += tau * busy.astype(dtype)
                tel["depth_time"] += tau * n_w.astype(dtype)
            acc["now"] = dtype(acc["now"] + tau)
            dt_left = dtype(dt_left - tau)
            rem[:] -= (rate * tau)[:, None]          # empty slots stay inf
            row_min = rem.min(axis=1)
            for w in np.flatnonzero(row_min <= EPS):
                for s in np.flatnonzero(rem[w] <= EPS):
                    complete(w, s)
                row_min[w] = rem[w].min()
            if dt_left <= 0:
                return

    for i in range(N):
        t_i = arrival[i]
        advance(dtype(t_i - acc["now"]))
        acc["now"] = t_i
        f = int(funcs[i])
        active = n_w.copy()
        w = select(active, warm[:, f], C, S)
        if w < 0:
            out["rejected"][i] = True
            if tel is not None:
                tel["n_reject"] += 1
            continue
        is_cold = warm[w, f] == 0
        evicted = False
        if not is_cold:
            warm[w, f] -= 1
        elif active[w] + warm[w].sum() >= S:
            warm[w, int(np.argmax(warm[w]))] -= 1
            evicted = True
        s = int(np.argmax(tid[w] < 0))
        rem[w, s] = service[i] + (dtype(cluster.get("cold_start_penalty", 0.0))
                                  if is_cold else zero)
        t_arr[w, s] = t_i
        tid[w, s] = i
        n_w[w] += 1
        out["cold"][i] = is_cold
        out["worker"][i] = w
        if tel is not None:
            tel["n_cold" if is_cold else "n_warm"] += 1
            tel["n_evict"] += int(evicted)
            tel["decisions"][w] += 1
    advance(dtype(np.inf))
    out.update(acc)
    if tel is not None:
        out["telemetry"] = tel
    return out
