#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python3 chipbench/control.py --workload <cell> --seeds <n> ... \\
        --control-seeds <n> ... [--out <file.json>]

For each of ``--seeds`` it runs one unit of the cell through the program
on the chip, exactly as a benchmark run's window does, and reads every
compared number against the float64 reference on the replications a run
would sample: the lower readings.  For each of ``--control-seeds`` it
puts the reference computed in float32, the precision below the one the
configurations state, in the program's place and reads the same
numbers: the upper readings.  The benchmark's own runs do not run this.
"""
import json
import os
import sys
import time
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT,
                                                             "chipbench"):
    sys.path[0] = ROOT


def collect(root: str, workload: str, seeds, control_seeds,
            require=None) -> dict:
    """``{"program": {seed: readings}, "control": {seed: readings}}`` with
    the largest program and smallest control reading of each number."""
    import numpy as np

    from chipbench import devtrace, harness, run

    cell = harness.load_cell(root, workload)
    sys.path.insert(0, os.path.join(root, "src"))
    run._compile_cache(root)
    (require or run.require_chips)(cell.chips)
    out = {"workload": workload, "program": {}, "control": {}}
    with ProcessPoolExecutor(max_workers=4,
                             mp_context=get_context("spawn")) as pool:
        pending = []
        for seed, dtype, key in ([(s, np.float64, "program") for s in seeds]
                                 + [(s, np.float32, "control")
                                    for s in control_seeds]):
            engine = harness.make_engine(root, cell, seed, devtrace.Spans())
            engine.setup()
            if key == "program":
                engine.unit(0)
            pairs = harness.sample(seed, 1, engine.n_reps(),
                                   harness.check_reps(cell))
            dtypes = [np.float64] if key == "program" else [np.float64, dtype]
            jobs = [pool.submit(harness._simulate,
                                engine.reference_args(k, r, d))
                    for d in dtypes for k, r in pairs]
            pending.append((seed, key, engine, pairs, jobs))
        for seed, key, engine, pairs, jobs in pending:
            raws = [j.result() for j in jobs]
            views = [engine.view(raw, k, r) for raw, (k, r)
                     in zip(raws, pairs * (len(jobs) // len(pairs)))]
            refs = views[:len(pairs)]
            got = None if key == "program" else views[len(pairs):]
            out[key][str(seed)] = harness.readings(engine, pairs, refs, got)
    names = sorted({n for v in out["program"].values() for n in v})
    out["lower"] = {n: max(v[n] for v in out["program"].values())
                    for n in names}
    out["upper"] = {n: min(v[n] for v in out["control"].values())
                    for n in names} if out["control"] else {}
    out["limits"] = cell.limits
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    res = collect(ROOT, args.workload, args.seeds, args.control_seeds)
    res["seconds"] = time.perf_counter() - t0
    for key in ("program", "control"):
        for seed, rd in res[key].items():
            print(f"{key} seed {seed}: {rd}", flush=True)
    print(f"lower {res['lower']}\nupper {res['upper']}\n"
          f"limits {res['limits']}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(res, fh, indent=1, default=float)
    return 0


if __name__ == "__main__":
    sys.exit(main())
