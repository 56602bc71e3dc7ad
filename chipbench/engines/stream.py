"""Stream cells: one unit is a whole ``simulate_stream`` horizon.

A unit takes the stacked ``[R, N]`` inputs of one horizon from the host
through the program's chunked streaming entry (the carry handed across
chunk boundaries, the telemetry plane on; with more than one chip the
replications sharded over a ``rep`` mesh) to the sketch's slowdown p99
and the exact online counters on the host.  The horizons of the pool
are run back to back.  The check replays sampled replications through
the plain reference with its telemetry on and compares every integer
counter and histogram bin exactly and every time integral and mean.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench.engines import common

#: integer outputs: compared entry by entry, exactly
INT_KEYS = ("n_done", "n_obs", "n_cold", "n_warm", "n_evict", "n_reject",
            "decisions", "slow_hist", "lat_hist")
#: float outputs: compared by the largest relative error
FLOAT_KEYS = ("server_time", "core_time", "end_time", "busy_time",
              "depth_time", "resp_mean", "slow_mean")


class Engine(common.Engine):
    def __init__(self, cfg, mix, seed, chips, spans):
        from repro.telemetry import TelemetryCfg
        super().__init__(cfg, mix, seed, chips, spans)
        self.chunk = int(cfg["engine"]["chunk"])
        self.tel = TelemetryCfg(warmup_frac=self.warmup_frac)
        self.mesh = None
        #: host seconds between successive chunk dispatches, every unit
        self.feed_s: list[float] = []

    def _run(self, wb):
        from repro.core.streaming import simulate_stream
        stamps: list[float] = []
        out = simulate_stream(
            self.policy, self.cluster, wb, chunk_size=self.chunk,
            backend=self.backend, telemetry=self.tel, mesh=self.mesh,
            chunk_callback=lambda c, st: stamps.append(time.perf_counter()))
        return out, stamps

    def setup(self) -> None:
        """Make the traffic pool and warm the chunk and drain programs
        with a short horizon of the same chunk and replication shapes."""
        if self.chips > 1:
            from repro.launch.mesh import make_rep_mesh
            self.mesh = make_rep_mesh(self.chips)
        self.make_pool()
        with self.spans("setup.compile"):
            self._run(self.batch(self.pool[0], n=min(64, self.chunk)))

    def unit(self, k: int) -> int:
        """Run horizon ``k``; returns the arrivals it simulated."""
        wb = self.batch(self.inputs(k))
        with self.call("stream.simulate_stream"):
            out, stamps = self._run(wb)
        with self.spans("stream.readout"):
            read = {"slow_p99": out.telemetry.slow_percentile(99.0),
                    "n_done": int(out.n_done.sum()),
                    "resp_mean": float(out.resp_mean.mean())}
        self.feed_s.extend(np.diff(stamps).tolist())
        self.kept[k] = {"out": out, "read": read}
        return wb.n * wb.n_reps

    # -- the check ---------------------------------------------------------
    def reference_args(self, k: int, r: int, dtype=np.float64) -> dict:
        return super().reference_args(k, r, dtype, telemetry=True,
                                      warmup_frac=self.warmup_frac)

    def view(self, raw: dict, k: int, r: int) -> dict:
        """A reference run in the form :meth:`program` gives."""
        tel = raw["telemetry"]
        n_obs = max(tel["n_obs"], 1)
        view = {key: tel[key]
                for key in INT_KEYS + ("busy_time", "depth_time")}
        view.update(resp_mean=tel["resp_sum"] / n_obs,
                    slow_mean=tel["slow_sum"] / n_obs,
                    server_time=raw["server_time"],
                    core_time=raw["core_time"], end_time=raw["now"])
        return view

    def program(self, k: int, r: int) -> dict:
        out = self.kept[k]["out"]
        t = out.telemetry
        view = {"n_done": out.n_done[r], "n_obs": out.n_observed[r],
                "resp_mean": out.resp_mean[r], "slow_mean": out.slow_mean[r],
                "server_time": out.server_time[r],
                "core_time": out.core_time[r], "end_time": out.end_time[r]}
        for key in INT_KEYS[2:] + ("busy_time", "depth_time"):
            view[key] = getattr(t, key)[r]
        return view

    @staticmethod
    def compare(got: dict, ref: dict) -> dict:
        """The numbers the check holds against their limits."""
        mism = sum(int(np.sum(np.asarray(got[key]) != np.asarray(ref[key])))
                   for key in INT_KEYS)
        err = max(float(np.max(_rel(np.asarray(got[key], np.float64),
                                    np.asarray(ref[key], np.float64))))
                  for key in FLOAT_KEYS)
        return {"counter_mismatch": float(mism), "float_rel_err": err}


def _rel(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    diff = np.abs(a - b)
    return np.where(diff == 0, 0.0, diff / np.maximum(np.abs(b), 1e-300))
