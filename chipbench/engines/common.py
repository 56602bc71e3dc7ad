"""What every engine mode shares: the deployment as the program's
``ClusterCfg`` and policy, the traffic pool, and the reference run of
one replication."""
from __future__ import annotations

import contextlib
import time

import numpy as np

from chipbench import traffic


class Engine:
    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int, spans):
        from repro.core import ClusterCfg, parse_policy
        self.cfg, self.mix, self.seed, self.chips = cfg, mix, seed, chips
        self.spans = spans
        d = cfg["deployment"]
        self.cluster = ClusterCfg(
            n_workers=d["n_workers"], cores=d["cores"],
            capacity_factor=d["capacity_factor"],
            cold_start_penalty=d["cold_start_penalty"])
        self.policy = parse_policy(cfg["policy"])
        self.backend = cfg["engine"]["backend"]
        self.warmup_frac = cfg["engine"]["warmup_frac"]
        self.pool: list[dict] = []
        self.kept: dict[int, dict] = {}
        #: host seconds of each unit's call into the program
        self.call_s: list[float] = []

    @contextlib.contextmanager
    def call(self, name: str):
        """The span ``name`` around one unit's call into the program,
        its host seconds kept in :attr:`call_s`."""
        t0 = time.perf_counter()
        with self.spans(name):
            yield
        self.call_s.append(time.perf_counter() - t0)

    def n_reps(self) -> int:
        return len(self.mix["loads"]) * int(self.mix["seeds_per_load"])

    def make_pool(self) -> None:
        """The ``pool`` distinct units the window cycles through."""
        d = self.cfg["deployment"]
        with self.spans("setup.traffic"):
            self.pool = [traffic.make_unit(self.mix, d["n_workers"],
                                           d["cores"], self.seed, u)
                         for u in range(int(self.mix["pool"]))]

    def inputs(self, k: int) -> dict:
        return self.pool[k % len(self.pool)]

    def batch(self, inp: dict, n: int | None = None):
        """The program's ``WorkloadBatch`` of a unit (its first ``n``
        arrivals where given)."""
        from repro.core import WorkloadBatch
        return WorkloadBatch(
            arrival=inp["arrival"][:, :n], func=inp["func"][:, :n],
            service=inp["service"][:, :n], u_lb=inp["u_lb"][:, :n],
            func_home=inp["func_home"], n_functions=inp["n_functions"],
            loads=inp["loads"], names=("chipbench",) * len(inp["loads"]))

    def reference_args(self, k: int, r: int, dtype=np.float64,
                       **kw) -> dict:
        """Keyword arguments of :func:`chipbench.reference.simulate` for
        replication ``r`` of unit ``k``."""
        inp = self.inputs(k)
        rep = {key: inp[key][r] for key in ("arrival", "func", "service")}
        rep["n_functions"] = inp["n_functions"]
        return {"cluster": self.cfg["deployment"],
                "policy": self.cfg["policy"], "rep": rep, "dtype": dtype,
                **kw}
