#!/usr/bin/env python3
"""Chip benchmark of the serverless-scheduling simulator.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout, on a machine with the TPU chips the
cell asks for (``BENCHMARK.json``); off a TPU it exits non-zero and
prints no result.  Set-up makes the cell's traffic from ``--seed`` and
warms its one engine, served from JAX's persistent compile cache
(``$JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache`` in the
checkout).  The window then runs whole units of work (a sweep, or a
streamed horizon) back to back for ``--seconds``; every started unit
finishes and counts.  After the window the plain reference replays a
sample of replications drawn from the seed; the numbers it compares
are printed beside their limits as the last lines of standard error
and under ``checks`` in the result, the last line of standard output.

``--trace 0`` reports the end-to-end metrics: ``arrivals_per_s`` (all
simulated arrivals of the window's units over their wall time) and
``setup_s`` (process start to the window).  ``--trace 1`` profiles two
slices of the window, one inside a unit's scan and one across the
boundary between two units, and reports the per-layer metrics instead,
with a ``breakdown`` of the busiest device operations and the longest
device idle gaps named by the host span they fell in.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT,
                                                             "chipbench"):
    sys.path[0] = ROOT          # import the harness as the package it is
#: profiler slices: the boundary one opens SLICE_LEAD_S before the unit
#: that repeats the first unit's traffic is due to return from its
#: program call, and closes SLICE_TAIL_S after the next unit starts; the
#: steady one starts SLICE_DELAY_S into a later unit and lasts SLICE_S
SLICE_LEAD_S = 0.12
SLICE_TAIL_S = 0.05
SLICE_DELAY_S = 1.0
SLICE_S = 0.05
MONITORED = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
}


class Failed(Exception):
    """The run cannot produce a result; the message says why."""


def require_chips(n: int):
    """The devices the cell runs on: ``n`` TPU chips, or :class:`Failed`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Failed(f"JAX runs on {devs[0].platform!r}, not a TPU; "
                     f"nothing was measured")
    if len(devs) < n:
        raise Failed(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def _compile_cache(root: str) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class Monitor:
    """``jax.monitoring`` durations and persistent-cache events, split
    into set-up and window by a flag the harness flips."""

    def __init__(self):
        self.phase = "setup"
        self.seconds: dict[tuple, float] = {}
        self.counts: dict[tuple, int] = {}

    def on_duration(self, event: str, secs: float, **_) -> None:
        if event in MONITORED:
            key = (self.phase, MONITORED[event])
            self.seconds[key] = self.seconds.get(key, 0.0) + secs
            self.counts[key] = self.counts.get(key, 0) + 1

    def on_event(self, event: str, **_) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            key = (self.phase, event.rsplit("/", 1)[1])
            self.counts[key] = self.counts.get(key, 0) + 1


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def per_layer(root: str, slicer, host: list, run, result: dict) -> None:
    """Reduce the profiler slices, read the cell's per-layer metrics off
    them and the run, and add them, the device's busy time and the
    breakdown to ``result``."""
    from chipbench import devtrace, harness
    run.slices = {}
    for name, rec in slicer.slices.items():
        t_read = time.perf_counter()
        record = devtrace.read_slice(rec)
        red = run.slices[name] = devtrace.reduce(record, host)
        t0, t1, b = record["t0"], record["t1"], record["boundary_ns"]
        _log(f"chipbench: {name} slice of {(t1 - t0) / 1e9} s, unit "
             f"boundary {None if b is None else (b - t0) / 1e9} s into it; "
             f"stopping the profiler took {rec['stop_s']} s, reading and "
             f"reducing the slice {time.perf_counter() - t_read} s")
        for dev, d in red["devices"].items():
            ops = record["devices"][dev]
            first = min((o[1] for o in ops), default=t1) - t0
            last = t1 - max((o[1] + o[2] for o in ops), default=t0)
            progs = [(n.split("(")[0], (s - t0) / 1e9, ns / 1e9)
                     for n, s, ns in record["programs"].get(dev, [])]
            _log(f"chipbench: {name} slice {dev}: {d['ops']} ops, first "
                 f"{first} ns after its start, last {last} ns before its "
                 f"end; busy {d['busy_ns'] / 1e9} s of "
                 f"{d['program_ns'] / 1e9} s in programs, idle {d['idle']}; "
                 f"{d['steps']} steps marked by {d['marker']!r}, busy "
                 f"{d['step_busy_ns'] / 1e9} s; no program for "
                 f"{d['unit_gap_ns']} ns at the unit boundary; programs "
                 f"(name, start, seconds) {progs[:12]} "
                 f"and {max(len(progs) - 12, 0)} more")
    shutil.rmtree(slicer.log_dir, ignore_errors=True)
    for m in run.cell.per_layer:
        v = harness.load_module(root, "metrics", m["name"]).read(run)
        if v is not None:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    busy = window = 0.0
    self_ns: dict[str, float] = {}
    gaps = []
    for red in run.slices.values():
        n_dev = len(red["devices"]) or 1
        busy += sum(d["busy_ns"] for d in red["devices"].values()) / n_dev
        window += red["window_ns"]
        for k, v in red["self_ns"].items():
            self_ns[k] = self_ns.get(k, 0.0) + v
        gaps.extend(red["gaps"])
    result["device"]["busy_s"] = busy / 1e9
    result["device"]["window_s"] = window / 1e9
    top = sorted(self_ns.items(), key=lambda kv: -kv[1])
    result["breakdown"] = {
        "device_ops": [[n, t / 1e9] for n, t in top[:10]],
        "idle_gaps": [[n, g / 1e9] for n, g in
                      sorted(gaps, key=lambda x: -x[1])[:10]]}


def measure(args, root: str) -> dict:
    from chipbench import devtrace, harness

    cell = harness.load_cell(root, args.workload)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise Failed(f"no program under {root}/src/repro: run from a "
                     f"checkout of the repository")
    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    cache = _compile_cache(root)
    devices = require_chips(cell.chips)
    kind = devices[0].device_kind
    with open(os.path.join(root, harness.BENCH_DIR, "peaks.json")) as fh:
        peaks_doc = json.load(fh)
    if kind not in peaks_doc["devices"]:
        raise Failed(f"device kind {kind!r} is not in chipbench/peaks.json")
    mon = Monitor()
    jax.monitoring.register_event_duration_secs_listener(mon.on_duration)
    jax.monitoring.register_event_listener(mon.on_event)
    _log(f"chipbench: {cell.name} seed={args.seed} device={kind!r} "
         f"x{len(devices)} jax={jax.__version__} cache={cache}")

    tracer = None
    prog_base_ns = 0
    if args.trace:
        from repro.telemetry.spans import Tracer, set_tracer
        prog_base_ns = time.perf_counter_ns()
        tracer = Tracer(enabled=True, jax_bridge=True)
        set_tracer(tracer)
    spans = devtrace.Spans(tracer)
    engine = harness.make_engine(root, cell, args.seed, spans)
    engine.setup()

    mon.phase = "window"
    t_win = time.perf_counter()
    setup_s = t_win - T_START
    slicer = None
    if args.trace:
        log_dir = os.path.join(root, "experiments", "chipbench",
                               cell.name, "profile")
        shutil.rmtree(log_dir, ignore_errors=True)
        slicer = devtrace.Slicer(log_dir, SLICE_DELAY_S, SLICE_S,
                                 SLICE_TAIL_S)
        slicer.start()
    units = []
    while (time.perf_counter() - t_win < args.seconds
           or (slicer is not None and not slicer.sliced.is_set())):
        t0 = time.perf_counter()
        if slicer is not None:
            now = time.perf_counter_ns()
            slicer.unit_started(now)
            if len(units) == len(engine.pool):
                # this unit repeats the first one's traffic, so its
                # program call lasts as long: slice across its end, for
                # at most twice the host's work after the first unit's
                # call beyond the lead and the tail
                after = units[0][1] - engine.call_s[0]
                slicer.boundary_at(
                    now + int((engine.call_s[0] - SLICE_LEAD_S) * 1e9),
                    SLICE_LEAD_S + 2 * after + SLICE_TAIL_S + 0.1)
        arrivals = engine.unit(len(units))
        units.append((arrivals, time.perf_counter() - t0))
    mon.phase = "after"
    if slicer is not None:
        slicer.join()
        if slicer.error is not None:
            raise Failed(f"profiler slice failed: {slicer.error!r}")
    arrivals = sum(a for a, _ in units)
    busy_s = sum(s for _, s in units)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    _log(f"chipbench: window {len(units)} units, {arrivals} arrivals in "
         f"{busy_s} s; unit seconds {[s for _, s in units]}")
    in_window = {k[1]: v for k, v in mon.counts.items() if k[0] == "window"}
    parts: dict[str, float] = {}
    for n, b, e in spans.events:
        parts[n] = parts.get(n, 0.0) + (e - b) / 1e9
    _log(f"chipbench: set-up {setup_s} s; host span seconds {parts}; "
         f"set-up events "
         f"{ {k[1]: v for k, v in mon.counts.items() if k[0] == 'setup'} }; "
         f"in the window {in_window or 'none'}")

    result = {"correct": False, "attempted": arrivals, "failed": 0,
              "metrics": {},
              "device": {"platform": devices[0].platform, "kind": kind,
                         "count": len(devices), "memory_peak_bytes": peak}}
    if args.trace:
        host = list(spans.events) + [
            (ev["name"], prog_base_ns + int(ev["ts"] * 1e3),
             prog_base_ns + int((ev["ts"] + ev["dur"]) * 1e3))
            for ev in tracer.events
            if ev.get("ph") == "X" and ev.get("pid") == 1]
        run = SimpleNamespace(
            cell=cell, units=units, monitor=mon,
            feed_s=list(getattr(engine, "feed_s", [])),
            peaks=peaks_doc["devices"][kind], chips=cell.chips,
            n_reps=engine.n_reps())
        per_layer(root, slicer, host, run, result)
    else:
        values = {"arrivals_per_s": arrivals / busy_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}

    pairs = harness.sample(args.seed, len(units), engine.n_reps(),
                           harness.check_reps(cell))
    t_chk = time.perf_counter()
    ok, checks = harness.check(engine, pairs, cell.limits)
    _log(f"chipbench: check of (unit, replication) {pairs} took "
         f"{time.perf_counter() - t_chk} s")
    _log(f"chipbench: run took {time.perf_counter() - T_START} s")
    for name, c in checks.items():
        _log(f"check {name}: {c['value']} limit {c['limit']} "
             f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    result["correct"] = ok
    result["checks"] = {n: {"value": harness.finite(c["value"]),
                            "limit": c["limit"]} for n, c in checks.items()}
    return result


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if root not in sys.path:
        sys.path.insert(0, root)
    try:
        result = measure(args, root)
    except (Failed, FileNotFoundError) as e:
        _log(f"chipbench: {e}")
        return 1
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
