"""Serving launcher: Hermes-scheduled cluster over a request trace.

Two modes:

* ``--backend platform`` (default) — the event-driven serving platform
  (cold-start model, straggler mitigation), any ``T/LB/S`` policy,
  Azure-shaped or custom workload.  This is the §6 evaluation vehicle.
* ``--backend models`` — real reduced-config JAX models behind the
  Hermes frontend with measured compile-time cold starts.

Workloads are any ``repro.core.WORKLOADS`` entry — the synthetic §6.1
generators *and* the ``azure-*`` trace-replay scenarios — or a real
Azure-schema trace slice given as the two dataset CSVs.

Container lifecycle: ``--keepalive <name>`` threads a keep-alive policy
from the :mod:`repro.lifecycle` registry (``NONE`` / ``FIXED_TTL`` /
``HYBRID_HIST`` or anything registered) through the platform, and
``--cold-start-preset <name>`` swaps the scalar spin-up cost for a
per-function provider preset; both flags are validated against the
lifecycle registry with named errors, like ``--policy`` is against the
policy registry.

Fleet & autoscaling: ``--fleet-preset`` / ``--speed`` make the worker
pool heterogeneous (per-worker speeds from a :mod:`repro.fleet` preset
or given explicitly), and ``--autoscale`` turns on an active-worker
control loop (``TARGET_P99`` with ``--target-p99`` / ``--min-workers``
/ ``--cooldown`` / ``--hysteresis``).  All names are validated against
the fleet registries with named errors; autoscalers that read the
telemetry sketch enable telemetry automatically.  With every fleet
flag at its default the launcher keeps the exact homogeneous fixed-W
model.

Examples::

    python -m repro.launch.serve --policy E/H/PS --load 0.6 -n 5000
    python -m repro.launch.serve --workload azure-diurnal --load 0.7
    python -m repro.launch.serve --keepalive HYBRID_HIST --ttl 30 \
        --cold-start-preset aws-lambda
    python -m repro.launch.serve --fleet-preset two-gen --policy E/SWARM/PS
    python -m repro.launch.serve --workload azure-diurnal \
        --autoscale TARGET_P99 --target-p99 3 --min-workers 2 --cooldown 2
    python -m repro.launch.serve \
        --trace-invocations inv.csv --trace-durations dur.csv
    python -m repro.launch.serve --backend models --requests 12
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=["platform", "models"],
                    default="platform")
    ap.add_argument("--policy", default="E/H/PS",
                    help="T/LB/S triple over the repro.policy registry "
                         "(e.g. E/H/PS, E/JSQ2/PS, L/*/*)")
    ap.add_argument("--workload", default="ms-trace",
                    help="any repro.core.WORKLOADS name, incl. azure-* "
                         "trace-replay scenarios")
    ap.add_argument("--trace-invocations", metavar="CSV",
                    help="Azure-schema invocations-per-minute file; "
                         "replayed instead of --workload")
    ap.add_argument("--trace-durations", metavar="CSV",
                    help="Azure-schema duration-percentiles file "
                         "(required with --trace-invocations)")
    ap.add_argument("--load", type=float, default=0.6)
    ap.add_argument("-n", type=int, default=4000)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--cores", type=int, default=12)
    ap.add_argument("--cold-start", type=float, default=0.5)
    ap.add_argument("--keepalive", metavar="NAME",
                    help="container keep-alive policy from the "
                         "repro.lifecycle registry (NONE, FIXED_TTL, "
                         "HYBRID_HIST, ...); omit for the legacy "
                         "keep-forever warm pool")
    ap.add_argument("--ttl", type=float, default=60.0,
                    help="keep-alive window seconds (FIXED_TTL window / "
                         "HYBRID_HIST fallback+range unit)")
    ap.add_argument("--max-idle", type=int, default=0,
                    help="per-worker warm-pool budget (idle executors; "
                         "0 = bounded only by slot pressure)")
    ap.add_argument("--cold-start-preset", metavar="NAME",
                    default="scalar",
                    help="per-function cold-start latency preset from "
                         "the lifecycle registry ('scalar' keeps "
                         "--cold-start)")
    ap.add_argument("--fleet-preset", metavar="NAME",
                    help="per-worker speed preset from the repro.fleet "
                         "registry (uniform, two-gen, long-tail, ...); "
                         "omit (with no other fleet flag) for the "
                         "homogeneous pool")
    ap.add_argument("--speed", nargs="+", type=float, metavar="S",
                    help="explicit per-worker speed vector (overrides "
                         "--fleet-preset; length must equal --workers)")
    ap.add_argument("--autoscale", metavar="NAME",
                    help="active-worker autoscale policy from the "
                         "repro.fleet registry (STATIC, TARGET_P99, ...)")
    ap.add_argument("--target-p99", type=float, default=5.0,
                    help="autoscaler p99 slowdown ceiling")
    ap.add_argument("--min-workers", type=int, default=1,
                    help="autoscaler floor on active workers")
    ap.add_argument("--cooldown", type=float, default=60.0,
                    help="seconds between autoscale decisions")
    ap.add_argument("--hysteresis", type=float, default=0.1,
                    help="autoscaler dead-band half-width (fraction of "
                         "the setpoint)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-kernel", action="store_true",
                    help="dispatch through the balancer's batched Pallas "
                         "controller kernel (policies whose balancer "
                         "ships one, e.g. E/H/*)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--telemetry", action="store_true",
                    help="collect streaming platform telemetry "
                         "(repro.telemetry) and print its summary; "
                         "with --trace-out also records per-task "
                         "virtual-time lifecycle events")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="export a Perfetto-loadable Chrome trace JSON "
                         "of the run (implies --telemetry)")
    ap.add_argument("--timeline-out", metavar="PATH", default=None,
                    help="record the windowed flight-recorder timeline "
                         "(repro.telemetry.timeline) and export it: "
                         "per-window CSV at PATH plus an OpenMetrics "
                         "text sibling at PATH.om; with --trace-out "
                         "the windows also land in the trace JSON as "
                         "Perfetto counter tracks")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.backend == "models":
        from repro import configs
        from repro.lifecycle import parse_keepalive
        from repro.serving.backends import (HermesFrontend, Invocation,
                                            ModelRegistry)
        import numpy as np
        reg = ModelRegistry()
        reg.register("olmo-tiny", configs.get_smoke("olmo-1b"))
        reg.register("rwkv-tiny", configs.get_smoke("rwkv6-3b"))
        # on real models, keep-alive maps to executor idle expiry with
        # the --ttl window (cold starts here are measured XLA compiles,
        # so --cold-start-preset does not apply); the name is still
        # validated against the lifecycle registry
        keepalive_s = None
        if args.keepalive is not None:
            parse_keepalive(args.keepalive)   # named ValueError
            keepalive_s = args.ttl
        fe = HermesFrontend(reg, n_workers=2, cores=2, max_len=64,
                            keepalive_s=keepalive_s)
        rng = np.random.default_rng(0)
        for i in range(args.requests):
            fn = ("olmo-tiny", "rwkv-tiny")[i % 2]
            out = fe.dispatch(Invocation(
                func=fn, prompt=rng.integers(0, 100, 8), n_new=4))
            print(f"req {i:2d} {fn:10s} worker={out.worker} "
                  f"{'COLD' if out.cold else 'warm'} "
                  f"{out.response_s*1e3:8.1f}ms")
        return

    from repro.core import (ClusterCfg, WORKLOADS, parse_policy, summarize)
    from repro.fleet import STATIC, fleet_from_flags, get_autoscaler
    from repro.lifecycle import lifecycle_from_flags
    from repro.serving.engine import ServeCfg, ServingCluster
    # named ValueError on unknown names; a preset/budget without an
    # explicit --keepalive gets an infinite window (no surprise expiry)
    lifecycle = lifecycle_from_flags(args.keepalive, args.ttl,
                                     args.max_idle, args.cold_start_preset)
    # same contract for the fleet axes: all defaults -> fleet=None
    fleet = fleet_from_flags(args.fleet_preset, args.speed, args.autoscale,
                             args.target_p99, args.min_workers,
                             args.cooldown, args.hysteresis)
    cl = ClusterCfg(n_workers=args.workers, cores=args.cores,
                    lifecycle=lifecycle, fleet=fleet).validate()
    if args.trace_invocations or args.trace_durations:
        if not (args.trace_invocations and args.trace_durations):
            ap.error("--trace-invocations and --trace-durations "
                     "must be given together")
        from repro.trace.cache import load_trace_cached
        from repro.trace.replay import replay_trace
        trace = load_trace_cached(args.trace_invocations,
                                  args.trace_durations,
                                  allow_missing_durations=True)
        wl = replay_trace(trace, cl, load=args.load, n_arrivals=args.n,
                          seed=args.seed, name="trace-file")
        wname = args.trace_invocations
    else:
        wl = WORKLOADS[args.workload](cl, args.load, args.n,
                                      seed=args.seed)
        wname = args.workload
    # a sketch-reading autoscaler needs the telemetry carry regardless
    # of whether the user asked for a printed summary
    auto_needs_tel = (fleet is not None and
                      get_autoscaler(fleet.autoscale).needs_telemetry)
    telemetry_on = bool(args.telemetry or args.trace_out or auto_needs_tel)
    tel_cfg = None
    tracer = None
    if telemetry_on:
        from repro.telemetry import TelemetryCfg, configure_tracing
        tel_cfg = TelemetryCfg()
        if args.telemetry or args.trace_out:   # span tracing stays opt-in
            tracer = configure_tracing(True)
    tl_cfg = None
    if args.timeline_out:
        from repro.telemetry import TimelineCfg
        tl_cfg = TimelineCfg()
    cfg = ServeCfg(cluster=cl, cold_start_s=args.cold_start)
    sc = ServingCluster(cfg, parse_policy(args.policy),
                        use_kernel=args.use_kernel, telemetry=tel_cfg,
                        timeline=tl_cfg)
    if tracer is not None:
        with tracer.span("serve.run", policy=args.policy,
                         workload=wname, load=args.load, n=args.n):
            out = sc.run(wl)
    else:
        out = sc.run(wl)
    s = summarize(out.response, wl.service, out.cold, out.rejected,
                  out.server_time, out.core_time, out.end_time)
    ka = lifecycle.keepalive if lifecycle else "legacy-inf"
    preset = lifecycle.coldstart if lifecycle else "scalar"
    fdesc = "homogeneous" if fleet is None else \
        f"{'explicit' if fleet.speed else fleet.preset}/{fleet.autoscale}"
    print(f"policy={args.policy} workload={wname} "
          f"load={args.load} keepalive={ka} coldstart={preset} "
          f"fleet={fdesc}")
    print(f"  slow p50/p99 = {s.slow_p50:.2f} / {s.slow_p99:.1f}")
    print(f"  lat  p50/p99 = {s.lat_p50:.2f}s / {s.lat_p99:.2f}s")
    print(f"  cold starts  = {100*s.cold_frac:.1f}%   "
          f"servers = {s.mean_servers:.2f}   rejected = {s.n_rejected}")
    if fleet is not None and fleet.autoscale != STATIC:
        print(f"  autoscale    : target p99 ≤ {fleet.target_p99:g}, "
              f"provisioned = {out.prov_core_s:.0f} core-s "
              f"(static fleet would be "
              f"{out.end_time * cl.n_workers * cl.cores:.0f})")
    if out.telemetry is not None:
        t = out.telemetry.summary()
        print(f"  telemetry    : sketch slow p50/p99 = "
              f"{t['slow_p50']:.2f} / {t['slow_p99']:.1f}  "
              f"cold={t['n_cold']} warm={t['n_warm']} "
              f"evict={t['n_evict']} reject={t['n_reject']}  "
              f"busy={t['busy_time_s']:.1f}s")
    if out.timeline is not None:
        ts = out.timeline.summary()
        csv_p = out.timeline.write_csv(args.timeline_out)
        om_p = out.timeline.write_openmetrics(args.timeline_out + ".om")
        if tracer is not None:
            out.timeline.emit_counters(tracer)
        print(f"  timeline     : {ts['n_windows']} windows of "
              f"{ts['window_s']:.2f}s, peak arrivals="
              f"{ts['arrivals_peak']}, {ts['n_events']} decision "
              f"events -> {csv_p} + {om_p}")
    if args.trace_out:
        tracer.export(args.trace_out)
        print(f"  trace        : {args.trace_out} "
              f"(load at https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
