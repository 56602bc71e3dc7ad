"""Sweep cells: one unit is a whole ``simulate_many`` policy sweep.

A unit takes the stacked ``[R, N]`` inputs of the cell's traffic from
the host through the program's public entry to the per-replication and
pooled percentiles of ``summarize_batch`` on the host.  The check
replays sampled replications through the plain reference and compares
every arrival's placement and response, and the summary the program
read off them.
"""
from __future__ import annotations

import numpy as np

from chipbench.engines import common


class Engine(common.Engine):
    def __init__(self, cfg, mix, seed, chips, spans):
        if chips != 1:
            raise ValueError("sweep cells run on one chip")
        super().__init__(cfg, mix, seed, chips, spans)

    def setup(self) -> None:
        """Make the traffic pool; warm the engine and its read-out at the
        unit's shapes with inputs that cost the device little: every
        arrival at time 0 with 600 s of work, so the scan fills the
        cluster once and rejects the rest."""
        from repro.core.simulator import simulate_many
        self.make_pool()
        idle = dict(self.pool[0])
        idle["arrival"] = np.zeros_like(idle["arrival"])
        idle["service"] = np.full_like(idle["service"], 600.0)
        with self.spans("setup.compile"):
            simulate_many(self.policy, self.cluster, self.batch(idle),
                          backend=self.backend)

    def unit(self, k: int) -> int:
        """Run unit ``k``; returns the arrivals it simulated."""
        from repro.core import summarize_batch
        from repro.core.simulator import simulate_many
        wb = self.batch(self.inputs(k))
        with self.call("sweep.simulate_many"):
            out = simulate_many(
                self.policy, self.cluster, wb, backend=self.backend)
        with self.spans("sweep.summarize"):
            summ = summarize_batch(
                out.response, wb.service, out.cold, out.rejected,
                out.server_time, out.core_time, out.end_time,
                warmup_frac=self.warmup_frac)
        self.kept[k] = {"out": out, "summary": summ}
        return wb.n * wb.n_reps

    # -- the check ---------------------------------------------------------
    def view(self, raw: dict, k: int, r: int) -> dict:
        """A reference run in the form :meth:`program` gives."""
        resp = np.asarray(raw["response"], np.float64)
        return {"response": resp, "cold": raw["cold"],
                "rejected": raw["rejected"], "worker": raw["worker"],
                "summary": summary_of(resp, self.inputs(k)["service"][r],
                                      raw["cold"], raw["rejected"],
                                      self.warmup_frac)}

    def program(self, k: int, r: int) -> dict:
        kept = self.kept[k]
        out, s = kept["out"], kept["summary"].per_rep[r]
        return {"response": out.response[r], "cold": out.cold[r],
                "rejected": out.rejected[r], "worker": out.worker[r],
                "summary": {"slow_p99": s.slow_p99, "lat_p99": s.lat_p99,
                            "slow_mean": s.slow_mean,
                            "cold_frac": s.cold_frac}}

    @staticmethod
    def compare(got: dict, ref: dict) -> dict:
        """The numbers the check holds against their limits."""
        mism = ((got["worker"] != ref["worker"])
                | (got["cold"] != ref["cold"])
                | (got["rejected"] != ref["rejected"]))
        g = np.asarray(got["response"], np.float64)
        w = np.asarray(ref["response"], np.float64)
        both = np.isfinite(g) & np.isfinite(w)
        resp_err = float(np.max(np.abs(g[both] - w[both]) / np.abs(w[both]),
                                initial=0.0))
        if (np.isfinite(g) != np.isfinite(w)).any():
            resp_err = float("inf")
        summ_err = max(_rel(got["summary"][key], ref["summary"][key])
                       for key in ref["summary"])
        return {"placement_mismatch": float(mism.sum()),
                "response_rel_err": resp_err,
                "summary_rel_err": summ_err}


def summary_of(response, service, cold, rejected, warmup_frac) -> dict:
    """Per-replication percentiles as the paper reports them: slowdown is
    response over execution time, over completions past the warm-up."""
    keep = np.ones(len(response), bool)
    keep[:int(len(response) * warmup_frac)] = False
    ok = keep & ~rejected & np.isfinite(response)
    resp = response[ok]
    slow = resp / np.maximum(service[ok], 1e-12)
    return {"slow_p99": float(np.percentile(slow, 99)),
            "lat_p99": float(np.percentile(resp, 99)),
            "slow_mean": float(slow.mean()),
            "cold_frac": float(cold[ok].mean())}


def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(b), 1e-300)
