"""What the benchmark finds by name, and the check that decides ``correct``.

Everything that belongs to one configuration, traffic mix, engine mode
or per-layer metric is a file of its own, found from ``BENCHMARK.json``:

* ``chipbench/configs/<config>.json`` -- the deployment as it is run;
* ``chipbench/traffic/<traffic>.json`` -- the mix, read by
  :mod:`chipbench.traffic`;
* ``chipbench/engines/<mode>.py`` -- one unit of work of an engine mode
  and its check against the reference (``engine.mode`` of the config);
* ``chipbench/limits/<config>.json`` -- the limit of each compared number;
* ``chipbench/metrics/<metric>.py`` -- ``read(run)``, one per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os

import numpy as np

BENCH_DIR = "chipbench"


def load_module(root: str, *parts: str):
    """Import ``<root>/chipbench/<parts...>.py`` by its path."""
    path = os.path.join(root, BENCH_DIR, *parts[:-1], parts[-1] + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{'_'.join(parts)}".replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(root: str, *parts: str) -> dict:
    path = os.path.join(root, BENCH_DIR, *parts[:-1], parts[-1] + ".json")
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with everything it names, loaded."""
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list


def load_cell(root: str, workload: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise FileNotFoundError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as fh:
        config = json.load(fh)

    def mine(m):
        return workload in m["workloads"] if "workloads" in m else None

    e2e = [m for m in bench["end_to_end"] if mine(m) in (True, None)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if mine(m) or (mine(m) is None and m["moves"] in names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                mix=_json(root, "traffic", w["traffic"]),
                limits=_json(root, "limits", w["config"]),
                end_to_end=e2e, per_layer=per_layer)


def make_engine(root: str, cell: Cell, seed: int, spans):
    mod = load_module(root, "engines", cell.config["engine"]["mode"])
    return mod.Engine(cell.config, cell.mix, seed, cell.chips, spans)


def check_reps(cell: Cell) -> int:
    """Replications the check replays: one from each chip's block of the
    batch, and at least two."""
    return max(2, cell.chips)


def sample(seed: int, units: int, n_reps: int, count: int) -> list:
    """``count`` ``(unit, replication)`` pairs the check replays, drawn
    from the seed.  The ``j``-th replication comes from the ``j``-th of
    ``count`` equal blocks of the batch, each of another parity than the
    one before, so a fault that leaves out half of the batch (or one
    chip's block of it, where ``count`` is the number of chips) or every
    other replication cannot hide from the check."""
    rng = np.random.default_rng([int(seed), 0xC4EC])
    size = max(n_reps // count, 1)
    out: list[tuple[int, int]] = []
    for j in range(count):
        lo = min(j * size, n_reps - 1)
        hi = n_reps if j == count - 1 else min(lo + size, n_reps)
        cand = list(range(lo, hi))
        if out and any((x - out[-1][1]) % 2 for x in cand):
            cand = [x for x in cand if (x - out[-1][1]) % 2]
        out.append((int(rng.integers(0, units)),
                    cand[int(rng.integers(0, len(cand)))]))
    return out


def references(engine, pairs, dtype=np.float64) -> list:
    """The reference's view of each ``(unit, replication)`` pair, one
    process per pair (the reference is numpy on the host and imports
    no JAX, so the processes leave the chip alone)."""
    from concurrent.futures import ProcessPoolExecutor
    from multiprocessing import get_context

    args = [engine.reference_args(k, r, dtype) for k, r in pairs]
    with ProcessPoolExecutor(max_workers=len(pairs),
                             mp_context=get_context("spawn")) as pool:
        raws = list(pool.map(_simulate, args))
    return [engine.view(raw, k, r) for raw, (k, r) in zip(raws, pairs)]


def _simulate(kw: dict) -> dict:
    from chipbench import reference
    return reference.simulate(**kw)


def readings(engine, pairs, refs, got=None) -> dict:
    """Each compared number, the worst over the pairs (NaN reads as
    infinitely wrong).  ``got`` defaults to the program's answers."""
    worst: dict[str, float] = {}
    for j, (k, r) in enumerate(pairs):
        g = engine.program(k, r) if got is None else got[j]
        for name, v in engine.compare(g, refs[j]).items():
            v = math.inf if math.isnan(v) else v
            worst[name] = max(worst.get(name, -math.inf), v)
    return worst


def check(engine, pairs, limits: dict) -> tuple[bool, dict]:
    """Replay ``pairs`` through the reference; each compared number must
    not exceed its limit."""
    worst = readings(engine, pairs, references(engine, pairs))
    checks = {name: {"value": v, "limit": float(limits[name])}
              for name, v in sorted(worst.items())}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def finite(x: float) -> float:
    """JSON has no infinity or NaN: the largest double stands for both."""
    return x if math.isfinite(x) else 1.7976931348623157e308
