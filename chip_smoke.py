#!/usr/bin/env python3
"""Smoke run of the simulator's main path on one TPU chip.

    python chip_smoke.py                # one chip: testbed + streamed day
    python chip_smoke.py --four-chips   # only the rep-sharded stream, 4 chips

A smoke run, not a benchmark: it proves that the public entry points run
on the chip and give the oracle's answers.  Each check is made against
the repo's own references:

* ``kernel`` — the batched Hermes kernel (``hermes_select``) against its
  numpy oracle at both clusters' widths.
* ``testbed`` — ``simulate_many`` on the paper testbed (W=8 x 12 cores,
  96 slots), ``azure-diurnal``, N=20,000 arrivals x 32 replications
  (8 loads in 0.3-0.9 x 4 seeds), for ``E/H/PS`` (backend ``auto``: the
  compiled Pallas kernel, asserted in the program's HLO) and
  ``E/LL/PS``.  Two replications are replayed by the numpy oracle
  ``simulate_ref``: per arrival, worker, cold and rejected are identical
  and response time agrees to 1e-9 relative.
* ``day`` — ``simulate_stream`` over the fig14 horizon configuration
  (W=1000, 2 cores, capacity factor 2, ``azure-diurnal`` at load 0.7),
  86,400 arrivals in chunks of 4096 with telemetry on; stream equals
  the monolithic engine bit for bit on a 12,000-arrival prefix.
* ``--four-chips`` runs only ``simulate_stream`` with
  ``mesh=make_rep_mesh(4)`` (W=1000, R=8, 12,000 arrivals) and checks
  it bit-equal to the same call on one device, with the carry spread
  over 4 devices.

The last line of standard output is ``{"ok": true, "device": {...}}``
only when JAX runs on a TPU, the kernel is compiled (not interpreted)
and every check passed; otherwise the script exits non-zero and prints
no such line.  ``tests/test_chip_smoke.py`` rehearses the phases on the
CPU at ``SIZES["small"]`` (shorter horizons, fewer replications, the
same widths and checks).  The compile cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` in the
checkout (:mod:`repro.launch.compile_cache`).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

#: Horizons and replication counts; widths come from the configurations.
SIZES = {
    "full": dict(tb_n=20_000, tb_loads=8, tb_seeds=4, tb_oracle_reps=2,
                 day_n=86_400, day_prefix=12_000, chunk=4096,
                 four_reps=8, four_n=12_000, kernel_n=256),
    "small": dict(tb_n=600, tb_loads=2, tb_seeds=2, tb_oracle_reps=2,
                  day_n=2_000, day_prefix=900, chunk=512,
                  four_reps=8, four_n=700, kernel_n=64),
}
TB_LOADS = (0.3, 0.9)      # testbed load range
RESP_RTOL = 1e-9
SMOKE = "[smoke run, not a benchmark]"


class Smoke:
    """Check and timing log; every failed check fails the run."""

    def __init__(self, device_kind: str):
        self.device_kind = device_kind
        self.failed: list[str] = []
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"  check {name}: {'PASS' if ok else 'FAIL'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def phase(self, name: str, fn, *args) -> None:
        """Run one phase; an exception fails it, the run goes on."""
        try:
            fn(self, *args)
        except Exception:
            traceback.print_exc()
            self.check(f"{name} raised", False)

    def report(self, phase: str, policy: str, *, compile_s: float,
               run_s: float, arrivals: int, parity: bool, **extra) -> None:
        rest = "".join(f" {k}={v}" for k, v in extra.items())
        print(f"{SMOKE} phase={phase} policy={policy} "
              f"device={self.device_kind!r} compile_s={compile_s} "
              f"run_s={run_s} arrivals_per_s={arrivals / run_s} "
              f"parity={'PASS' if parity else 'FAIL'}{rest}", flush=True)

    # jax.monitoring listeners: compile seconds and persistent-cache use
    def on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def _prefix(wl, n: int):
    return dataclasses.replace(wl, arrival=wl.arrival[:n], func=wl.func[:n],
                               service=wl.service[:n], u_lb=wl.u_lb[:n])


def _first_divergence(got, want, rtol: float = 0.0) -> str:
    """'' when equal (within ``rtol``), else the first differing arrival."""
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return f"shape {got.shape} != {want.shape}"
    if rtol:
        bad = ~np.isclose(got, want, rtol=rtol, atol=0.0, equal_nan=True)
    else:
        bad = got != want
    if not bad.any():
        return ""
    i = np.unravel_index(int(np.argmax(bad)), bad.shape)
    return (f"{int(bad.sum())} differ; first at {tuple(int(j) for j in i)}:"
            f" got {got[i]!r}, want {want[i]!r}")


def _timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def phase_kernel(sm: Smoke, size: dict) -> None:
    """Batched Hermes kernel vs its numpy oracle at both widths."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import PAPER_TESTBED
    from repro.kernels.hermes_select.ops import hermes_select
    from repro.kernels.hermes_select.ref import hermes_select_ref
    from benchmarks.fig14_stream import HORIZON_CLUSTER

    rng = np.random.default_rng(0)
    for cl in (PAPER_TESTBED, HORIZON_CLUSTER):
        W, F, N = cl.n_workers, 40, size["kernel_n"]
        active = rng.integers(0, cl.slots + 1, W).astype(np.int32)
        warm = rng.integers(0, 3, (W, F)).astype(np.int32)
        funcs = rng.integers(0, F, N).astype(np.int32)
        out, act = hermes_select(jnp.asarray(active), jnp.asarray(warm),
                                 jnp.asarray(funcs), cores=cl.cores,
                                 slots=cl.slots)
        ro, ra = hermes_select_ref(active, warm.T[funcs], cores=cl.cores,
                                   slots=cl.slots)
        d = _first_divergence(out, ro) or _first_divergence(act, ra)
        sm.check(f"kernel W={W} N={N} == oracle", not d, d)


def phase_testbed(sm: Smoke, size: dict) -> None:
    """simulate_many on the paper testbed, replayed by the numpy oracle."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import (E_LL_PS, HERMES, PAPER_TESTBED, WORKLOADS,
                            replicate_workload)
    from repro.core.sim_ref import simulate_ref
    from repro.core.simulator import build_batch_simulator, simulate_many

    cl = PAPER_TESTBED
    wb = replicate_workload(
        WORKLOADS["azure-diurnal"], cl,
        np.linspace(*TB_LOADS, size["tb_loads"]), size["tb_n"],
        seeds=tuple(range(size["tb_seeds"])))
    args = [jnp.asarray(a) for a in (wb.arrival, wb.func, wb.service,
                                     wb.u_lb, wb.func_home)]
    oracle_reps = np.unique(np.linspace(
        0, wb.n_reps - 1, size["tb_oracle_reps"]).astype(int))
    for pol in (HERMES, E_LL_PS):
        # compile ahead of the call: simulate_many then reuses this
        # executable, and its HLO shows whether the kernel is compiled
        t0 = time.perf_counter()
        engine = build_batch_simulator(pol, cl, n_arrivals=wb.n,
                                       n_functions=wb.n_functions,
                                       backend="auto")
        hlo = engine.lower(*args).compile().as_text()
        compile_s = time.perf_counter() - t0
        if pol == HERMES:
            sm.check("E/H/PS engine HLO holds tpu_custom_call",
                     "tpu_custom_call" in hlo)
        out, run_s = _timed(simulate_many, pol, cl, wb, backend="auto")
        ok = True
        for r in oracle_reps:
            ref = simulate_ref(pol, cl, wb.rep(int(r)))
            for name, got, want, rtol in (
                    ("worker", out.worker[r], ref.worker, 0.0),
                    ("cold", out.cold[r], ref.cold, 0.0),
                    ("rejected", out.rejected[r], ref.rejected, 0.0),
                    ("response", out.response[r], ref.response,
                     RESP_RTOL)):
                d = _first_divergence(got, want, rtol)
                ok &= sm.check(f"testbed {pol.name} rep{r} {name} == "
                               f"oracle", not d, d)
        sm.report("testbed", pol.name, compile_s=compile_s, run_s=run_s,
                  arrivals=wb.n * wb.n_reps, parity=ok, reps=wb.n_reps,
                  n=wb.n)


def _stream_matches_monolithic(sm: Smoke, pol, cl, wl, size, tel) -> bool:
    """Stream == monolithic engine, bitwise, on one workload."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import stack_workloads
    from repro.core.simulator import build_batch_simulator
    from repro.core.streaming import final_states_equal, simulate_stream

    wb = stack_workloads([wl])
    out = simulate_stream(pol, cl, wb, chunk_size=size["chunk"],
                          telemetry=tel, collect_outputs=True,
                          keep_final_state=True)
    mono = build_batch_simulator(pol, cl, n_arrivals=wb.n,
                                 n_functions=wb.n_functions, telemetry=tel)(
        jnp.asarray(wb.arrival), jnp.asarray(wb.func),
        jnp.asarray(wb.service), jnp.asarray(wb.u_lb),
        jnp.asarray(wb.func_home))
    ok, bad = final_states_equal(out.final_state, mono)
    ok = sm.check(f"day {pol.name} stream carry == monolithic (prefix "
                  f"{wb.n})", ok, ";".join(bad))
    for name, got, want in (("rejected", out.rejected, mono.rejected),
                            ("cold", out.cold, mono.cold),
                            ("worker", out.worker, mono.worker_of)):
        d = _first_divergence(got, np.asarray(want)[:, :wb.n])
        ok &= sm.check(f"day {pol.name} stream {name} == monolithic", not d,
                       d)
    return ok


def phase_day(sm: Smoke, size: dict) -> None:
    """The fig14 horizon day through simulate_stream."""
    from repro.core import E_LL_PS, HERMES, WORKLOADS
    from repro.core.streaming import simulate_stream
    from repro.telemetry import TelemetryCfg
    from benchmarks.fig14_stream import (HORIZON_CLUSTER, HORIZON_LOAD,
                                         HORIZON_WORKLOAD)

    cl, tel = HORIZON_CLUSTER, TelemetryCfg()
    wl = WORKLOADS[HORIZON_WORKLOAD](cl, HORIZON_LOAD, size["day_n"], seed=1)
    for pol in (E_LL_PS, HERMES):
        c0 = sm.compile_s
        ok = _stream_matches_monolithic(sm, pol, cl,
                                        _prefix(wl, size["day_prefix"]),
                                        size, tel)
        compile_s = sm.compile_s - c0
        out, run_s = _timed(simulate_stream, pol, cl, wl,
                            chunk_size=size["chunk"], telemetry=tel)
        n_done = int(out.n_done.sum())
        ok &= sm.check(f"day {pol.name} completes arrivals", n_done > 0,
                       f"n_done={n_done}")
        sm.report("day", pol.name, compile_s=compile_s, run_s=run_s,
                  arrivals=wl.n, parity=ok, n=wl.n, chunk=size["chunk"],
                  n_done=n_done,
                  slow_p99=float(out.telemetry.slow_percentile(99.0)))


def phase_four_chips(sm: Smoke, size: dict) -> None:
    """simulate_stream sharded over 4 devices vs the same on one."""
    import jax
    from repro.core import E_LL_PS, HERMES, WORKLOADS, stack_workloads
    from repro.core.streaming import final_states_equal, simulate_stream
    from repro.launch.mesh import make_rep_mesh
    from repro.telemetry import TelemetryCfg
    from benchmarks.fig14_stream import (HORIZON_CLUSTER, HORIZON_LOAD,
                                         HORIZON_WORKLOAD)

    if not sm.check("4 devices visible", len(jax.devices()) >= 4,
                    f"{len(jax.devices())} devices"):
        return
    cl, tel = HORIZON_CLUSTER, TelemetryCfg()
    wb = stack_workloads(
        WORKLOADS[HORIZON_WORKLOAD](cl, HORIZON_LOAD, size["four_n"],
                                    seed=s)
        for s in range(size["four_reps"]))
    mesh = make_rep_mesh(4)
    for pol in (E_LL_PS, HERMES):
        runs = {}
        for label, m in (("one", None), ("four", mesh)):
            c0 = sm.compile_s
            t0 = time.perf_counter()
            runs[label] = simulate_stream(
                pol, cl, wb, chunk_size=size["chunk"], telemetry=tel,
                collect_outputs=True, keep_final_state=True, mesh=m)
            runs[label + "_s"] = (sm.compile_s - c0,
                                  time.perf_counter() - t0)
        one, four = runs["one"], runs["four"]
        eq, bad = final_states_equal(four.final_state, one.final_state)
        ok = sm.check(f"four {pol.name} carry == one device", eq,
                      ";".join(bad))
        for name in ("rejected", "cold", "worker", "n_done"):
            d = _first_divergence(getattr(four, name), getattr(one, name))
            ok &= sm.check(f"four {pol.name} {name} == one device", not d,
                           d)
        spread = sorted({len(x.sharding.device_set)
                         for x in jax.tree_util.tree_leaves(
                             four.final_state)})
        ok &= sm.check(f"four {pol.name} carry spread over 4 devices",
                       spread == [4], f"device counts {spread}")
        for label in ("one", "four"):
            compile_s, wall_s = runs[label + "_s"]
            sm.report(f"four_chips/{label}", pol.name,
                      compile_s=compile_s, run_s=wall_s - compile_s,
                      arrivals=wb.n * wb.n_reps, parity=ok,
                      reps=wb.n_reps, n=wb.n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the rep-sharded stream on 4 devices, "
                         "against the same call on one")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        print(f"chip_smoke: no repro package under {REPO}/src; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(REPO, "src"), REPO]

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    from repro.kernels.hermes_select.kernel import interpret_mode

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX runs on {dev.platform!r}, not a TPU; "
              f"nothing was run", file=sys.stderr)
        return 1
    sm = Smoke(dev.device_kind)
    jax.monitoring.register_event_duration_secs_listener(sm.on_duration)
    jax.monitoring.register_event_listener(sm.on_event)
    print(f"{SMOKE} device={dev.device_kind!r} count={len(devices)} "
          f"jax={jax.__version__} cache={cache_dir}",
          flush=True)
    sm.check("Hermes kernel compiled, not interpreted",
             not interpret_mode())

    size = SIZES["full"]
    t0 = time.perf_counter()
    if args.four_chips:
        sm.phase("four_chips", phase_four_chips, size)
    else:
        for name, fn in (("kernel", phase_kernel),
                         ("testbed", phase_testbed), ("day", phase_day)):
            sm.phase(name, fn, size)
    print(f"{SMOKE} total_s={time.perf_counter() - t0} "
          f"compile_s={sm.compile_s} cache_hits={sm.cache_hits} "
          f"cache_misses={sm.cache_misses} failed={len(sm.failed)}",
          flush=True)
    if sm.failed:
        print("chip_smoke: FAILED: " + "; ".join(sm.failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
