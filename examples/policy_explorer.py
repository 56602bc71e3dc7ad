"""Interactive policy-space exploration (paper §3 methodology).

Sweep any ``T/LB/S`` policy over load and workload knobs; prints a
slowdown/latency/efficiency table.  ``LB`` and ``S`` accept every
balancer/scheduler registered in :mod:`repro.policy` — the paper's
``LOC``/``R``/``LL``/``H`` plus zoo extensions like ``JSQ2``
(power-of-two-choices) and ``RR`` (round-robin), and anything you add
via :func:`repro.policy.register_balancer` (``--list-policies`` shows
what is registered).  Examples::

    PYTHONPATH=src python examples/policy_explorer.py \
        --policies E/H/PS E/JSQ2/PS E/LL/PS L/*/* --loads 0.3 0.6 0.9 \
        --workload ms-trace --workers 8 --cores 12

Batched sweeps
--------------
With ``--engine sim`` (the default) the whole ``loads × reps`` grid is
stacked into one :class:`~repro.core.workload.WorkloadBatch` per policy
and run through a single ``jax.vmap``-ed compiled program
(:func:`repro.core.simulator.simulate_many`) — one XLA compile per
policy regardless of how many load points or seed replications you
sweep.  ``--reps R`` replicates every load point over ``R`` consecutive
seeds inside the same batch and reports the across-replication mean
± 95 % confidence half-width of each metric::

    PYTHONPATH=src python examples/policy_explorer.py \
        --policies E/H/PS E/LL/PS --loads 0.3 0.5 0.7 0.9 --reps 5

The ``--engine serve`` path (cold-start platform with straggler
mitigation hooks) remains per-cell and ignores ``--reps``.

``--workload`` accepts every ``repro.core.WORKLOADS`` entry, including
the non-stationary ``azure-*`` trace-replay scenarios
(:mod:`repro.trace`)::

    PYTHONPATH=src python examples/policy_explorer.py \
        --workload azure-bursty --loads 0.5 0.7 --reps 3

Container lifecycle
-------------------
``--keepalive`` threads a keep-alive policy from the
:mod:`repro.lifecycle` registry through whichever engine you pick —
``NONE`` (tear down at completion), ``FIXED_TTL`` (``--ttl`` seconds),
``HYBRID_HIST`` (learned per-function pre-warm + keep-alive windows),
or anything you add via :func:`repro.lifecycle.register_keepalive`.
``--max-idle`` caps the per-worker warm pool and ``--cold-start-preset``
swaps the scalar penalty for per-function provider costs.  Without
``--keepalive``, executors never expire: a preset or budget alone runs
with an *infinite* keep-alive window, and with every lifecycle flag at
its default the explorer keeps the exact legacy warm-pool model.
``--list-policies`` also prints the registered keep-alive policies and
cold-start presets::

    PYTHONPATH=src python examples/policy_explorer.py \
        --policies E/H/PS E/LL/PS --keepalive HYBRID_HIST --ttl 30 \
        --max-idle 8 --cold-start-preset openwhisk --loads 0.3 0.7

Heterogeneous fleets & autoscaling
----------------------------------
``--fleet-preset`` / ``--speed`` give workers unequal speeds
(:mod:`repro.fleet`; try the ``SWARM`` balancer, which learns the
speeds online), and ``--autoscale TARGET_P99`` turns on the
latency-target control loop (telemetry is enabled automatically when
the autoscaler reads the sketch)::

    PYTHONPATH=src python examples/policy_explorer.py \
        --policies E/LL/PS E/SWARM/PS --fleet-preset two-gen \
        --workload azure-diurnal --loads 0.5 0.8

With every fleet flag at its default the explorer keeps the exact
homogeneous fixed-W model; ``--list-policies`` prints the registered
fleet presets and autoscale policies alongside the other axes.
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policies", nargs="+",
                    default=["E/H/PS", "E/LL/PS", "E/LOC/PS", "L/*/*"])
    ap.add_argument("--loads", nargs="+", type=float,
                    default=[0.3, 0.6, 0.9])
    ap.add_argument("--workload", default="ms-trace",
                    help="any repro.core.WORKLOADS name, incl. azure-* "
                         "trace-replay scenarios")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--cores", type=int, default=12)
    ap.add_argument("-n", type=int, default=4000)
    ap.add_argument("--engine", choices=["sim", "serve"], default="sim",
                    help="pure simulator vs serving platform (cold starts)")
    ap.add_argument("--keepalive", metavar="NAME",
                    help="container keep-alive policy (repro.lifecycle "
                         "registry); omit for the legacy keep-forever "
                         "warm pool")
    ap.add_argument("--ttl", type=float, default=60.0,
                    help="keep-alive window seconds")
    ap.add_argument("--max-idle", type=int, default=0,
                    help="per-worker warm-pool budget (0 = unbounded)")
    ap.add_argument("--cold-start-preset", metavar="NAME",
                    default="scalar",
                    help="per-function cold-start preset ('scalar' = "
                         "legacy single penalty)")
    ap.add_argument("--fleet-preset", metavar="NAME",
                    help="per-worker speed preset (repro.fleet registry); "
                         "omit (with no other fleet flag) for the "
                         "homogeneous pool")
    ap.add_argument("--speed", nargs="+", type=float, metavar="S",
                    help="explicit per-worker speeds (overrides "
                         "--fleet-preset; length must equal --workers)")
    ap.add_argument("--autoscale", metavar="NAME",
                    help="active-worker autoscale policy (repro.fleet "
                         "registry: STATIC, TARGET_P99, ...)")
    ap.add_argument("--target-p99", type=float, default=5.0,
                    help="autoscaler p99 slowdown ceiling")
    ap.add_argument("--min-workers", type=int, default=1,
                    help="autoscaler floor on active workers")
    ap.add_argument("--cooldown", type=float, default=60.0,
                    help="seconds between autoscale decisions")
    ap.add_argument("--hysteresis", type=float, default=0.1,
                    help="autoscaler dead-band half-width")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=1,
                    help="seed replications per load point (sim engine); "
                         ">1 adds ±95%% CI columns")
    ap.add_argument("--list-policies", action="store_true",
                    help="print registered balancers/schedulers and exit")
    ap.add_argument("--telemetry", action="store_true",
                    help="carry streaming telemetry (repro.telemetry) "
                         "through the sweep and print per-policy sketch "
                         "summaries")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="export a Perfetto-loadable Chrome trace JSON "
                         "of the sweep (implies --telemetry)")
    ap.add_argument("--timeline-out", metavar="PATH", default=None,
                    help="carry the windowed flight-recorder timeline "
                         "(repro.telemetry.timeline) and export it per "
                         "policy: per-window CSV at PATH with the "
                         "policy name suffixed, plus an OpenMetrics "
                         "sibling (.om); with --trace-out the windows "
                         "also land in the trace as Perfetto counter "
                         "tracks")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.list_policies:
        from repro.fleet import (autoscaler_names, fleet_preset_names,
                                 get_autoscaler)
        from repro.lifecycle import (cold_preset_names, get_keepalive,
                                     keepalive_names)
        from repro.policy import (balancer_names, get_balancer, get_sched,
                                  sched_names)
        print("balancers (LB):")
        for name in balancer_names():
            bal = get_balancer(name)
            print(f"  {name:6s} [{','.join(bal.backends())}]  {bal.doc}")
        print("worker schedulers (S):")
        for name in sched_names():
            print(f"  {name:6s} {get_sched(name).doc}")
        print("bindings (T): E (early), L (late; 'L/*/*' alias works)")
        print("keep-alive policies (--keepalive):")
        for name in keepalive_names():
            ka = get_keepalive(name)
            print(f"  {name:12s} [{','.join(ka.backends())}]  {ka.doc}")
        print(f"cold-start presets (--cold-start-preset): "
              f"{', '.join(cold_preset_names())}")
        print(f"fleet presets (--fleet-preset): "
              f"{', '.join(fleet_preset_names())}")
        print("autoscale policies (--autoscale):")
        for name in autoscaler_names():
            pol = get_autoscaler(name)
            tel = "telemetry" if pol.needs_telemetry else "no-telemetry"
            print(f"  {name:12s} [{tel}]  {pol.doc}")
        return

    from repro.core import (ClusterCfg, WORKLOADS, parse_policy,
                            replicate_workload, summarize,
                            summarize_batch_sim)
    from repro.core.simulator import simulate_many
    from repro.fleet import fleet_from_flags, get_autoscaler
    from repro.lifecycle import lifecycle_from_flags
    from repro.serving.engine import ServeCfg, ServingCluster

    if args.workload not in WORKLOADS:
        ap.error(f"unknown --workload {args.workload!r}; choose from "
                 f"{', '.join(sorted(WORKLOADS))}")
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
    # sketch-reading autoscalers need the telemetry carry even when no
    # summary was requested
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

    def export_timeline(tag, tl):
        import os
        base, ext = os.path.splitext(args.timeline_out)
        ext = ext or ".csv"
        p_csv = tl.write_csv(f"{base}.{tag}{ext}")
        p_om = tl.write_openmetrics(f"{base}.{tag}{ext}.om")
        if tracer is not None:
            tl.emit_counters(tracer, prefix=f"timeline/{tag}")
        print(f"timeline[{tag}]: {p_csv} + {p_om}")

    wfn = WORKLOADS[args.workload]
    ci = " ±ci95" if args.reps > 1 and args.engine == "sim" else ""
    print(f"{'policy':10s} {'load':>5s} {'slow50':>8s} "
          f"{'slow99':>10s}{ci} {'lat99':>9s} {'cold%':>6s} "
          f"{'servers':>8s}")

    if args.engine == "sim":
        seeds = tuple(range(args.seed, args.seed + args.reps))
        wb = replicate_workload(wfn, cl, args.loads, args.n, seeds=seeds)
        results = {}
        for ptext in args.policies:
            pol = parse_policy(ptext)
            results[pol.name] = (pol, simulate_many(pol, cl, wb,
                                                    telemetry=tel_cfg,
                                                    timeline=tl_cfg))
        for li, load in enumerate(args.loads):
            sl = slice(li * args.reps, (li + 1) * args.reps)
            for pname, (pol, out) in results.items():
                bs = summarize_batch_sim(out[sl], wb[sl])
                s = bs.pooled
                ci_txt = (f" ±{bs.stats['slow_p99'].ci95:6.1f}"
                          if args.reps > 1 else "")
                print(f"{pname:10s} {load:5.2f} {s.slow_p50:8.2f} "
                      f"{s.slow_p99:10.1f}{ci_txt} {s.lat_p99:9.2f} "
                      f"{100*s.cold_frac:6.1f} {s.mean_servers:8.2f}")
        if telemetry_on:
            print("telemetry (pooled sketch over the whole batch):")
            for pname, (pol, out) in results.items():
                t = out.telemetry.summary()
                print(f"  {pname:10s} sketch slow p50/p99 = "
                      f"{t['slow_p50']:.2f} / {t['slow_p99']:.1f}  "
                      f"cold={t['n_cold']} warm={t['n_warm']} "
                      f"evict={t['n_evict']} reject={t['n_reject']}")
        if args.timeline_out:
            # the batched timeline pools over loads × reps (same
            # horizon, shared virtual-time windows)
            for pname, (pol, out) in results.items():
                export_timeline(pname.replace("/", "-"), out.timeline)
        if args.trace_out:
            tracer.export(args.trace_out)
            print(f"trace: {args.trace_out} "
                  f"(load at https://ui.perfetto.dev)")
        return

    for load in args.loads:
        wl = wfn(cl, load, args.n, seed=args.seed)
        for ptext in args.policies:
            pol = parse_policy(ptext)
            sc = ServingCluster(ServeCfg(cluster=cl), pol,
                                telemetry=tel_cfg, timeline=tl_cfg)
            if tracer is not None:
                with tracer.span("explore.serve", policy=pol.name,
                                 load=load, n=args.n):
                    out = sc.run(wl)
            else:
                out = sc.run(wl)
            s = summarize(out.response, wl.service, out.cold,
                          out.rejected, out.server_time, out.core_time,
                          out.end_time)
            print(f"{pol.name:10s} {load:5.2f} {s.slow_p50:8.2f} "
                  f"{s.slow_p99:10.1f} {s.lat_p99:9.2f} "
                  f"{100*s.cold_frac:6.1f} {s.mean_servers:8.2f}")
            if out.timeline is not None:
                export_timeline(f"{pol.name.replace('/', '-')}-{load}",
                                out.timeline)
    if args.trace_out:
        tracer.export(args.trace_out)
        print(f"trace: {args.trace_out} (load at https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
