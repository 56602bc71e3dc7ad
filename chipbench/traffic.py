"""The benchmark's one traffic generator: Azure-Functions-shaped arrivals.

A copy, kept with the benchmark so that the yardstick stays fixed while
the program's own generators change, of the repository's trace path
(``repro/trace/synth_trace.py``, the percentile columns of
``repro/trace/schema.py`` and ``repro/trace/replay.py``).  Its shape is
that of the Azure Functions 2019 trace (Shahrad et al., ATC'20):
per-function per-minute invocation counts drawn around an intensity
profile, per-function Log-normal durations materialised as the trace's
percentile columns and fitted back, minute-exact arrival replay, and
the platform's 600 s timeout cap.  Offered load is reached by time
compression, so one trace seed gives the same trace at every load.

A traffic mix is a JSON file of parameters under ``chipbench/traffic/``;
:func:`make_unit` reads it.  Keys (defaults in brackets):

* ``profile`` -- ``diurnal``, ``bursty``, ``cold-heavy`` or ``flash-crowd``;
* ``n_functions`` [40], ``minutes`` [180], ``oversample`` [1.25]: the
  trace holds ``max(int(n_arrivals * oversample), 64)`` invocations;
* profile knobs: ``zipf_s``, ``amplitude`` (diurnal), ``burst_factor``,
  ``max_bursts`` (bursty), ``hot_share``, ``hot_load`` (cold-heavy),
  ``spike_factor``, ``spike_at``, ``spike_rank`` (flash-crowd);
* ``loads`` and ``seeds_per_load``: the replications of one unit, load
  major; ``n_arrivals`` per replication; ``max_service_s`` [600];
* ``pool``: distinct units made in set-up and cycled in the window.

Everything is a pure function of the run seed.
"""
from __future__ import annotations

import math

import numpy as np

MINUTE_S = 60.0
_REPLAY_SEED_OFFSET = 7919
_FIT_PERCENTILES = (1, 25, 50, 75, 99)


def norm_ppf(p: float) -> float:
    """Inverse standard-normal CDF (Acklam; |rel. error| < 1.15e-9)."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)

    def tail(q):
        return (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4])
                * q + c[5]) / \
               ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    if p < 0.02425:
        return tail(math.sqrt(-2 * math.log(p)))
    if p > 1 - 0.02425:
        return -tail(math.sqrt(-2 * math.log(1 - p)))
    q = p - 0.5
    r = q * q
    return (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4])
            * r + a[5]) * q / \
           (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4])
            * r + 1)


_PCTL_Z = {1: norm_ppf(0.01), 25: norm_ppf(0.25), 50: 0.0,
           75: norm_ppf(0.75), 99: norm_ppf(0.99)}
_FIT_Z = np.array([norm_ppf(p / 100.0) for p in _FIT_PERCENTILES])


def _zipf(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def _intensity(mix: dict, F: int, T: int, rng) -> np.ndarray:
    """Unnormalised ``(F, T)`` mean invocation rate of a profile."""
    prof = mix["profile"]
    m = np.arange(T)
    if prof == "diurnal":
        w = _zipf(F, mix.get("zipf_s", 1.1))
        phase = rng.uniform(0, 2 * math.pi, size=F)
        cycle = 1.0 + mix.get("amplitude", 0.8) * np.sin(
            2 * math.pi * m[None, :] / min(T, 1440) + phase[:, None])
        return w[:, None] * cycle
    if prof == "bursty":
        base = _zipf(F, mix.get("zipf_s", 0.7))[:, None] * np.ones(T)[None, :]
        burst = np.zeros((F, T))
        for f in range(F):
            for _ in range(rng.integers(1, mix.get("max_bursts", 3) + 1)):
                start = int(rng.integers(0, T))
                width = int(rng.integers(max(2, T // 60), max(3, T // 12)))
                burst[f, start:start + width] = 1.0
        return base * (1.0 + mix.get("burst_factor", 11.0) * burst)
    if prof == "cold-heavy":
        n_hot = max(1, int(F * mix.get("hot_share", 0.2)))
        hot = mix.get("hot_load", 0.8)
        w = np.full(F, (1.0 - hot) / max(F - n_hot, 1))
        w[:n_hot] = hot / n_hot
        return w[:, None] * rng.uniform(0.5, 1.5, size=(F, T))
    if prof == "flash-crowd":
        prof_ft = _zipf(F, mix.get("zipf_s", 0.5))[:, None] \
            * np.ones(T)[None, :]
        start = int(mix.get("spike_at", 0.45) * T)
        f = min(mix.get("spike_rank", 2), F - 1)
        prof_ft[f, start:start + max(2, T // 20)] *= \
            mix.get("spike_factor", 40.0)
        return prof_ft
    raise ValueError(f"unknown traffic profile {prof!r}")


def _trace(mix: dict, total: int, seed: int):
    """Per-minute counts ``(F, T)`` and fitted Log-normal ``(mu, sigma)``."""
    F, T = mix.get("n_functions", 40), mix.get("minutes", 180)
    rng = np.random.default_rng(seed)
    lam = _intensity(mix, F, T, rng)
    counts = rng.poisson(lam * (total / max(lam.sum(), 1e-12)))
    mu = rng.normal(-0.4, 0.8, size=F)
    sigma = rng.uniform(0.4, 1.5, size=F)
    # the trace carries durations as percentile columns (ms); the replay
    # fits them back by least squares in log space, as a real slice is
    zc = _FIT_Z - _FIT_Z.mean()
    fit_mu, fit_sigma = np.empty(F), np.empty(F)
    for f in range(F):
        pct_ms = [1000.0 * math.exp(float(mu[f]) + float(sigma[f])
                                    * _PCTL_Z[p]) for p in _FIT_PERCENTILES]
        y = np.log(np.array(pct_ms) / 1000.0)
        s = max(float((zc * (y - y.mean())).sum() / (zc * zc).sum()), 0.0)
        fit_mu[f], fit_sigma[f] = float(y.mean() - s * _FIT_Z.mean()), s
    return counts.astype(np.int64), fit_mu, fit_sigma


def replication(mix: dict, n_workers: int, cores: int, load: float,
                seed: int) -> dict:
    """One replication: ``n_arrivals`` invocations at offered ``load``."""
    n = int(mix["n_arrivals"])
    counts, mus, sigmas = _trace(
        mix, max(int(n * mix.get("oversample", 1.25)), 64), seed)
    F, T = counts.shape
    rng = np.random.default_rng(seed + _REPLAY_SEED_OFFSET)
    t_parts, f_parts, produced, rep = [], [], 0, 0
    f_ids, m_ids = np.nonzero(counts)
    c = counts[f_ids, m_ids]
    while produced < n:            # tile the trace whole when too short
        t = (np.repeat(m_ids, c) + rep * T) * MINUTE_S \
            + rng.uniform(0.0, MINUTE_S, size=int(c.sum()))
        order = np.argsort(t, kind="stable")
        t_parts.append(t[order])
        f_parts.append(np.repeat(f_ids, c).astype(np.int32)[order])
        produced += len(t)
        rep += 1
    arrival = np.concatenate(t_parts)[:n]
    func = np.concatenate(f_parts)[:n]
    service = np.minimum(
        np.exp(mus[func] + sigmas[func] * rng.standard_normal(n)),
        float(mix.get("max_service_s", 600.0)))
    realized = float(service.sum()) / (float(arrival[-1])
                                       * (n_workers * cores))
    return {"arrival": (arrival * (realized / load)).astype(np.float64),
            "func": func, "service": service.astype(np.float64),
            "u_lb": rng.uniform(size=n),
            "func_home": rng.integers(0, n_workers,
                                      size=F).astype(np.int32),
            "n_functions": F, "load": float(load)}


def trace_seeds(run_seed: int, unit: int, n: int) -> list[int]:
    """``n`` trace seeds of one unit, a pure function of the run seed."""
    ss = np.random.SeedSequence([int(run_seed), int(unit)])
    return [int(s) for s in ss.generate_state(n, dtype=np.uint32)]


def make_unit(mix: dict, n_workers: int, cores: int, run_seed: int,
              unit: int) -> dict:
    """The stacked ``[R, N]`` inputs of one unit of work.

    Replications are load major over ``mix["loads"]`` x
    ``seeds_per_load`` seeds; the seeds are shared across loads (common
    random numbers), so a load sweep differs only in time compression.
    """
    seeds = trace_seeds(run_seed, unit, int(mix["seeds_per_load"]))
    reps = [replication(mix, n_workers, cores, ld, s)
            for ld in mix["loads"] for s in seeds]
    out = {k: np.stack([r[k] for r in reps])
           for k in ("arrival", "func", "service", "u_lb", "func_home")}
    out["n_functions"] = reps[0]["n_functions"]
    out["loads"] = tuple(r["load"] for r in reps)
    return out
