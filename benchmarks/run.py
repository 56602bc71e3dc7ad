"""Benchmark harness entry point: one module per paper table/figure.

``python -m benchmarks.run [--quick|--full]`` executes every benchmark
(``--quick``, the default, is sized for a single-core CPU container and
is the tier the ``bench-smoke`` CI job gates on), writes one CSV per
figure under ``experiments/``, prints a compact summary, checks the
paper's headline claims (printed as REPRO-CHECK lines) and emits a
machine-readable ``experiments/BENCH_report.json`` (uploaded as a CI
artifact) with every check verdict and all figure rows.

Every figure sweep runs on the batched engine: per policy, all load
points are stacked into one ``simulate_many`` call, and the process-wide
compile cache (keyed on ``(policy, cluster, N, F)``) means each distinct
engine is traced + compiled exactly once across the whole harness.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_CHECKS: list = []


def _claim(name: str, ok: bool, detail: str) -> bool:
    print(f"REPRO-CHECK {'PASS' if ok else 'FAIL'}  {name}: {detail}")
    _CHECKS.append({"name": name, "ok": bool(ok), "detail": detail})
    return ok


def _by(rows, **kv):
    out = [r for r in rows
           if all(r[k] == v for k, v in kv.items())]
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--quick", action="store_true",
                      help="tiny-N/R smoke sweeps (the default; this is "
                           "what CI's bench-smoke job runs)")
    mode.add_argument("--full", action="store_true",
                      help="paper-scale sweeps (hours)")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable span tracing (repro.telemetry): exports "
                         "a Perfetto-loadable Chrome trace JSON with "
                         "engine build/compile/run and figure-phase "
                         "spans")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="trace JSON path (default "
                         "experiments/trace_bench.json; implies "
                         "--telemetry)")
    ap.add_argument("--timeline-out", default=None, metavar="PATH",
                    help="export the fig15 decision-lane flight "
                         "recorder: per-window CSV at PATH plus an "
                         "OpenMetrics text sibling at PATH.om; with "
                         "tracing on, the windows also land in the "
                         "trace JSON as Perfetto counter tracks")
    args = ap.parse_args()
    quick = not args.full
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # monotonic clock for elapsed time (immune to wall-clock steps);
    # wall-clock start is recorded separately in the manifest
    t_start = time.perf_counter()
    ok = True

    from repro.telemetry import (collect_manifest, configure_tracing,
                                 get_tracer, wall_split_from_aggregate)
    trace_on = bool(args.telemetry or args.trace_out)
    if trace_on:
        configure_tracing(True)
    tracer = get_tracer()
    manifest = collect_manifest(
        seeds={"workload_base": 0},
        args={"mode": "quick" if quick else "full",
              "telemetry": trace_on})

    from . import (bench_telemetry, fig2_policy_space, fig3_srpt,
                   fig4_scale, fig6_slowdown, fig7_coldstarts,
                   fig8_resources, fig9_robustness, fig10_trace_replay,
                   fig11_policy_zoo, fig12_keepalive, fig13_autoscale,
                   fig14_stream, fig15_timeline, tab_overhead)

    print("== fig2: policy space (4x12 cores, Azure workload) ==",
          flush=True)
    with tracer.span("fig2"):
        f2 = fig2_policy_space.run(quick)
    hi = [r for r in f2 if r["load"] == 0.8]
    ps = next(r for r in hi if r["policy"] == "E/LL/PS")
    late = next(r for r in hi if r["policy"] == "L/LL/FCFS")
    loc = next(r for r in hi if r["policy"] == "E/LOC/PS")
    ok &= _claim("L1: PS beats Late Binding on p99 slowdown @0.8",
                 ps["slow_p99"] < late["slow_p99"],
                 f"E/LL/PS={ps['slow_p99']:.1f} vs Late={late['slow_p99']:.1f}")
    ok &= _claim("L2: LL beats LOC on p99 slowdown @0.8",
                 ps["slow_p99"] < loc["slow_p99"],
                 f"E/LL/PS={ps['slow_p99']:.1f} vs E/LOC/PS={loc['slow_p99']:.1f}")
    lat_ratio = ps["lat_p99"] / max(late["lat_p99"], 1e-9)
    ok &= _claim("Fig2a: p99 *latency* hides the gap (ratio ~1)",
                 0.2 < lat_ratio < 5.0, f"lat99 ratio={lat_ratio:.2f}")
    lo2 = [r for r in f2 if r["load"] == 0.3]
    hiku2 = next(r for r in lo2 if r["policy"] == "E/HIKU/PS")
    ll2 = next(r for r in lo2 if r["policy"] == "E/LL/PS")
    ok &= _claim("Zoo: pull-based HIKU ≤ LL mean slowdown at low load "
                 "(popping an advertised idle worker ≈ joining an empty "
                 "queue)",
                 hiku2["slow_mean"] <= ll2["slow_mean"] * 1.05,
                 f"HIKU={hiku2['slow_mean']:.3f} vs "
                 f"LL={ll2['slow_mean']:.3f} @0.3")

    print("== fig3: SRPT vs PS ==", flush=True)
    with tracer.span("fig3"):
        f3 = fig3_srpt.run(quick)
    hi3 = [r for r in f3 if r["load"] == max(r["load"] for r in f3)]
    srpt = next(r for r in hi3 if r["policy"] == "E/LL/SRPT")
    psr = next(r for r in hi3 if r["policy"] == "E/LL/PS")
    ok &= _claim("L3 (median half): oracle SRPT's median ≤ PS's at high "
                 "load", srpt["slow_p50"] <= psr["slow_p50"] * 1.05,
                 f"p50 {srpt['slow_p50']:.2f} <= {psr['slow_p50']:.2f}")
    # Tail half of L3 (SRPT p99 ≫ PS p99) does NOT reproduce at stable
    # loads under drain-complete measurement — documented deviation, see
    # EXPERIMENTS.md §Fig3.  We report the observation instead of gating.
    print(f"  [L3 tail observation] SRPT p99={srpt['slow_p99']:.1f} vs "
          f"PS p99={psr['slow_p99']:.1f} at load={srpt['load']} "
          f"(paper expects SRPT ≫ PS; see EXPERIMENTS.md)")

    print("== fig4: 100-server scale ==", flush=True)
    with tracer.span("fig4"):
        f4 = fig4_scale.run(quick)
    hi4 = [r for r in f4 if r["load"] == 0.9]
    ll = next(r for r in hi4 if r["policy"] == "E/LL/PS")
    lb100 = next(r for r in hi4 if r["policy"] == "L/LL/FCFS")
    loc100 = next(r for r in hi4 if r["policy"] == "E/LOC/PS")
    r100 = next(r for r in hi4 if r["policy"] == "E/R/PS")
    lb4 = next(r for r in f2 if r["policy"] == "L/LL/FCFS"
               and r["load"] == 0.9)
    ok &= _claim("§3.5: Late Binding improves dramatically with scale "
                 "(100 vs 4 servers @0.9)",
                 lb100["slow_p99"] < 0.1 * lb4["slow_p99"],
                 f"W=100: {lb100['slow_p99']:.1f} vs W=4: "
                 f"{lb4['slow_p99']:.1f}")
    ok &= _claim("§3.5: LOC/R still degrade at scale, LL does not @0.9",
                 ll["slow_p99"] < loc100["slow_p99"]
                 and ll["slow_p99"] < r100["slow_p99"],
                 f"LL={ll['slow_p99']:.1f} LOC={loc100['slow_p99']:.1f} "
                 f"R={r100['slow_p99']:.1f}")
    # The >0.96 LL-vs-Late crossover needs multi-hour traces at W=100 to
    # materialize under calibrated load (2% overload accumulates too
    # slowly in a 5-minute window) — observation reported, not gated.
    hi97 = [r for r in f4 if r["load"] == max(r["load"] for r in f4)]
    ll97 = next(r for r in hi97 if r["policy"] == "E/LL/PS")
    lb97 = next(r for r in hi97 if r["policy"] == "L/LL/FCFS")
    print(f"  [§3.5 observation @load={ll97['load']}] "
          f"E/LL/PS p99={ll97['slow_p99']:.1f} vs "
          f"Late p99={lb97['slow_p99']:.1f} (paper: LL wins >0.96)")

    print("== fig6/7/8: serving platform (cold starts) ==", flush=True)
    with tracer.span("fig6"):
        f6 = fig6_slowdown.run(quick)
    lo = _by(f6, workload="ms-trace", load=0.3)
    hermes = next(r for r in lo if r["scheduler"] == "hermes")
    vanilla = next(r for r in lo if r["scheduler"] == "vanilla-ow")
    ok &= _claim("§6.2: Hermes ≥50% lower p99 slowdown than vanilla "
                 "OpenWhisk at low load",
                 hermes["slow_p99"] < 0.5 * vanilla["slow_p99"],
                 f"hermes={hermes['slow_p99']:.1f} vs "
                 f"vanilla={vanilla['slow_p99']:.1f}")
    lor = _by(f6, workload="ms-representative", load=0.3)
    hermes = next(r for r in lor if r["scheduler"] == "hermes")
    ll6 = next(r for r in lor if r["scheduler"] == "least-loaded")
    ok &= _claim("§6.2: Hermes ≤ least-loaded slowdown (locality win)",
                 hermes["slow_p99"] <= ll6["slow_p99"] * 1.1,
                 f"hermes={hermes['slow_p99']:.1f} vs "
                 f"LL={ll6['slow_p99']:.1f}")
    ok &= _claim("§6.3: Hermes fewer cold starts than least-loaded",
                 hermes["cold_frac"] < ll6["cold_frac"],
                 f"{100*hermes['cold_frac']:.1f}% < "
                 f"{100*ll6['cold_frac']:.1f}%")
    with tracer.span("fig8"):
        f8 = fig8_resources.run(quick)
    lo8 = [r for r in f8 if r["load"] == 0.3]
    h8 = next(r for r in lo8 if r["scheduler"] == "hermes")
    l8 = next(r for r in lo8 if r["scheduler"] == "least-loaded")
    ok &= _claim("§6.4: Hermes uses fewer servers than least-loaded "
                 "at low load", h8["mean_servers"] < l8["mean_servers"],
                 f"{h8['mean_servers']:.2f} < {l8['mean_servers']:.2f}")
    with tracer.span("fig7"):
        fig7_coldstarts.run(quick)

    print("== fig9: homogeneous exec times ==", flush=True)
    with tracer.span("fig9"):
        f9 = fig9_robustness.run(quick)
    hi9 = _by(f9, load=0.7)
    h9 = next(r for r in hi9 if r["scheduler"] == "hermes")
    l9 = next(r for r in hi9 if r["scheduler"] == "least-loaded")
    ok &= _claim("§6.5: Hermes ≈ least-loaded on light-tailed workload",
                 h9["slow_p99"] <= l9["slow_p99"] * 1.5 + 5,
                 f"hermes={h9['slow_p99']:.1f} vs LL={l9['slow_p99']:.1f}")

    print("== fig10: non-stationary Azure-schema trace replay ==",
          flush=True)
    with tracer.span("fig10"):
        f10 = fig10_trace_replay.run(quick)
    d10 = _by(f10, workload="azure-diurnal", load=0.5)
    h10 = next(r for r in d10 if r["scheduler"] == "hermes")
    v10 = next(r for r in d10 if r["scheduler"] == "vanilla-ow")
    l10 = next(r for r in d10 if r["scheduler"] == "least-loaded")
    ok &= _claim("Trace replay: Hermes ≥50% below vanilla OW p99 slowdown "
                 "under diurnal load",
                 h10["slow_p99_mean"] < 0.5 * v10["slow_p99_mean"],
                 f"hermes={h10['slow_p99_mean']:.1f}"
                 f"±{h10['slow_p99_ci95']:.1f} vs "
                 f"vanilla={v10['slow_p99_mean']:.1f}"
                 f"±{v10['slow_p99_ci95']:.1f}")
    ok &= _claim("Trace replay: Hermes fewer cold starts than "
                 "least-loaded under diurnal load",
                 h10["cold_frac_mean"] < l10["cold_frac_mean"],
                 f"{100 * h10['cold_frac_mean']:.1f}% < "
                 f"{100 * l10['cold_frac_mean']:.1f}%")
    b10 = _by(f10, workload="azure-bursty", load=0.7)
    hb = next(r for r in b10 if r["scheduler"] == "hermes")
    lb = next(r for r in b10 if r["scheduler"] == "least-loaded")
    print(f"  [bursty @0.7 observation] hermes "
          f"p99={hb['slow_p99_mean']:.1f}±{hb['slow_p99_ci95']:.1f} vs "
          f"least-loaded p99={lb['slow_p99_mean']:.1f}"
          f"±{lb['slow_p99_ci95']:.1f}")

    print("== fig11: policy zoo (full registry: JSQ2, RR, HIKU, DD) ==",
          flush=True)
    with tracer.span("fig11"):
        f11 = fig11_policy_zoo.run(quick)
    hi11 = _by(f11, workload="ms-trace", load=0.9)
    jsq2 = next(r for r in hi11 if r["policy"] == "E/JSQ2/PS")
    r11 = next(r for r in hi11 if r["policy"] == "E/R/PS")
    ll11 = next(r for r in hi11 if r["policy"] == "E/LL/PS")
    rr11 = next(r for r in hi11 if r["policy"] == "E/RR/PS")
    hiku11 = next(r for r in hi11 if r["policy"] == "E/HIKU/PS")
    ok &= _claim("Zoo: two choices beat one — E/JSQ2/PS p99 < E/R/PS @0.9",
                 jsq2["slow_p99"] < r11["slow_p99"],
                 f"JSQ2={jsq2['slow_p99']:.1f} vs R={r11['slow_p99']:.1f}")
    ok &= _claim("Zoo: JSQ2 tracks full-information LL (≤1.5x p99) @0.9",
                 jsq2["slow_p99"] <= 1.5 * ll11["slow_p99"],
                 f"JSQ2={jsq2['slow_p99']:.1f} vs LL={ll11['slow_p99']:.1f}")
    print(f"  [zoo observation @0.9] RR p99={rr11['slow_p99']:.1f} "
          f"(blind rotation, between R and JSQ2); "
          f"HIKU p99={hiku11['slow_p99']:.1f} vs "
          f"LL p99={ll11['slow_p99']:.1f}")
    bi11 = _by(f11, workload="bimodal-exec", load=0.8)
    dd11 = next(r for r in bi11 if r["policy"] == "E/DD/PS")
    rb11 = next(r for r in bi11 if r["policy"] == "E/R/PS")
    ok &= _claim("Zoo: data-driven DD beats size-blind R on bimodal "
                 "durations @0.8 (learned per-function estimates)",
                 dd11["slow_p99"] < rb11["slow_p99"],
                 f"DD={dd11['slow_p99']:.1f} vs R={rb11['slow_p99']:.1f}")
    mx = _by(f11, workload="azure-bursty",
             load=fig11_policy_zoo.MIXED_LOAD)
    if mx:
        mh = next(r for r in mx if r["policy"] == "E/HIKU/PS")
        md = next(r for r in mx if r["policy"] == "E/DD/PS")
        ml = next(r for r in mx if r["policy"] == "E/LL/PS")
        print(f"  [mixed-batch observation: bursty replay @"
              f"{fig11_policy_zoo.MIXED_LOAD}] "
              f"HIKU p99={mh['slow_p99']:.1f} DD p99={md['slow_p99']:.1f} "
              f"LL p99={ml['slow_p99']:.1f}")

    print("== fig12: container lifecycle / keep-alive axis ==", flush=True)
    with tracer.span("fig12"):
        f12 = fig12_keepalive.run(quick)
    bud = _by(f12, workload=fig12_keepalive.BUDGET_WORKLOAD,
              scheduler="hermes")
    cold_of = {ka: sum(r["cold_frac"] for r in bud if r["keepalive"] == ka)
               for ka in fig12_keepalive.BUDGET_KEEPALIVES}
    ok &= _claim("Lifecycle: HYBRID_HIST fewer cold starts than "
                 "FIXED_TTL at equal warm-pool budget (learned "
                 "per-function windows)",
                 cold_of["HYBRID_HIST"] < cold_of["FIXED_TTL"],
                 f"HYBRID={cold_of['HYBRID_HIST']:.3f} vs "
                 f"FIXED={cold_of['FIXED_TTL']:.3f} "
                 f"(summed cold_frac across loads)")
    ok &= _claim("Lifecycle: NONE is the cold-start upper bound",
                 cold_of["NONE"] >= cold_of["FIXED_TTL"]
                 and cold_of["NONE"] >= cold_of["HYBRID_HIST"],
                 f"NONE={cold_of['NONE']:.3f}")
    bal12 = _by(f12, workload=fig12_keepalive.BALANCER_WORKLOAD)
    h12 = sum(r["cold_frac"] for r in bal12 if r["scheduler"] == "hermes")
    l12 = sum(r["cold_frac"] for r in bal12
              if r["scheduler"] == "least-loaded")
    ok &= _claim("Lifecycle: Hermes keeps its cold-start edge over LL "
                 "under FIXED_TTL on azure-diurnal",
                 h12 < l12,
                 f"hermes={h12:.3f} vs LL={l12:.3f} "
                 f"(summed cold_frac across loads)")

    print("== fig13: heterogeneous fleet / latency-target autoscaling ==",
          flush=True)
    with tracer.span("fig13"):
        f13 = fig13_autoscale.run(quick)
    bal13 = _by(f13, lane="balancer", load=fig13_autoscale.BALANCER_LOAD)
    sw13 = next(r for r in bal13 if r["scheduler"] == "swarm")
    ll13 = next(r for r in bal13 if r["scheduler"] == "least-loaded")
    ok &= _claim("Fleet: SWARM ≤ speed-blind LL p99 slowdown on a "
                 "two-gen fleet @0.8 (learned per-worker slowness)",
                 sw13["slow_p99"] <= ll13["slow_p99"],
                 f"SWARM={sw13['slow_p99']:.2f} vs LL={ll13['slow_p99']:.2f}")
    fr13 = _by(f13, lane="frontier")
    tgt13 = fig13_autoscale.TARGET_P99
    auto_ok, auto_bits = True, []
    for seed in sorted({r["seed"] for r in fr13}):
        sr = _by(fr13, seed=seed)
        auto = next(r for r in sr if r["provision"] == "auto")
        meet = [r for r in sr if r["provision"] != "auto"
                and r["slow_p99"] <= tgt13]
        # smallest static fleet that meets the target (upper bound inf
        # if none does: the autoscaler then only has to meet the target)
        best = min(meet, key=lambda r: r["prov_core_s"]) if meet else None
        cap = best["prov_core_s"] if best else float("inf")
        auto_ok &= (auto["slow_p99"] <= tgt13
                    and auto["prov_core_s"] < cap)
        auto_bits.append(
            f"seed{seed}: p99={auto['slow_p99']:.2f} "
            f"prov={auto['prov_core_s']:.0f} vs "
            f"{best['provision'] if best else 'none'}="
            f"{cap:.0f}")
    ok &= _claim("Fleet: TARGET_P99 autoscaler meets the p99 target with "
                 f"fewer provisioned core-seconds than the smallest "
                 f"static fleet meeting it (target={tgt13})",
                 auto_ok, "; ".join(auto_bits))

    print("== fig14: horizon-scale streaming engine ==", flush=True)
    with tracer.span("fig14"):
        f14 = fig14_stream.run(quick)
    eq14 = _by(f14, lane="equivalence")
    bad14 = [f"{r['stack']}@k{r['chunk']}: {r['mismatches']}"
             for r in eq14 if not r["ok"]]
    ok &= _claim("Streaming: chunked scan ≡ monolithic bit-for-bit "
                 "(final carry, per-arrival outputs, telemetry "
                 "sketches; per-segment vs the numpy oracle) across "
                 f"{len(eq14)} registry stacks incl. non-dividing "
                 "chunk sizes",
                 not bad14,
                 f"{len(eq14)} cells bitwise" if not bad14
                 else "; ".join(bad14))
    hz14 = _by(f14, lane="horizon")[0]
    ok &= _claim("Streaming: "
                 f"{'full' if hz14['full_day'] else 'shortened'} "
                 f"synthetic {hz14['workload']} day "
                 f"(N={hz14['n_arrivals']}) at W={hz14['n_workers']} "
                 "completes in ONE run under the peak-memory budget",
                 hz14["ok"],
                 f"peak={hz14['peak_rss_mb']:.0f}MiB ≤ "
                 f"{hz14['peak_mb_budget']:.0f}MiB, "
                 f"{hz14['n_chunks']} chunks of {hz14['chunk']}, "
                 f"{hz14['n_done']} completions, "
                 f"wall={hz14['wall_s']:.1f}s")

    print("== fig15: windowed flight-recorder timeline ==", flush=True)
    with tracer.span("fig15"):
        f15 = fig15_timeline.run(quick)
    par15 = _by(f15, lane="parity")
    bad15 = [f"{r['stack']}: {r['mismatches']}"
             for r in par15 if not r["ok"]]
    ok &= _claim("Timeline: per-window planes are exact — np oracle ≡ "
                 "jax scan (ints bitwise, integrals 1e-9) and "
                 "streamed ≡ monolithic bitwise across a non-dividing "
                 "chunking",
                 not bad15,
                 f"{len(par15)} stacks exact" if not bad15
                 else "; ".join(bad15))
    di15 = _by(f15, lane="diurnal")
    ok &= _claim("Timeline: diurnal load shape reproduced and window "
                 "counters reconcile with the exact per-arrival planes "
                 "(scan + serving platform)",
                 all(r["ok"] for r in di15),
                 "; ".join(
                     f"{r['stack']}: peak={r['arrivals_peak']}"
                     f"/med={r['arrivals_median']:.0f}"
                     + (f" {r['mismatches']}" if r["mismatches"] else "")
                     for r in di15))
    dec15 = _by(f15, lane="decision")[0]
    ok &= _claim("Timeline: decision log replays the exact n_on "
                 "trajectory (two-gen fleet + TARGET_P99 on "
                 "azure-diurnal)",
                 dec15["ok"],
                 f"{dec15['n_events']} events "
                 f"({dec15['n_autoscale']} autoscale), "
                 f"n_on∈[{dec15['n_on_min']},{dec15['n_on_max']}]"
                 + (f"; {dec15['mismatches']}"
                    if dec15["mismatches"] else ""))

    print("== §6.6: scheduler overhead ==", flush=True)
    with tracer.span("tab_overhead"):
        tov = tab_overhead.run(quick)
    py = {r["scheduler"]: r for r in tov if r["impl"] == "python"}
    ok &= _claim("§6.6: Hermes decision cost ≈ least-loaded (<2x)",
                 py["hermes(H)"]["us_per_decision"]
                 < 2.0 * py["least-loaded"]["us_per_decision"] + 20,
                 f"hermes={py['hermes(H)']['us_per_decision']:.1f}us vs "
                 f"LL={py['least-loaded']['us_per_decision']:.1f}us")
    for r in tov:
        print(f"  {r['scheduler']:16s} {r['impl']:14s} "
              f"{r['decisions_per_s']:12.0f} dec/s")

    print("== telemetry: streaming sketch vs exact percentiles ==",
          flush=True)
    with tracer.span("bench_telemetry"):
        ftel = bench_telemetry.run(quick)
    fsk = _by(ftel, lane="sketch")
    worst50 = max(r["rel_err_p50"] for r in fsk)
    worst99 = max(r["rel_err_p99"] for r in fsk)
    ok &= _claim("Telemetry: sketch p50/p99 slowdown within "
                 f"{bench_telemetry.TOL_REL:.0%} of exact "
                 "summarize_batch for every registered balancer at "
                 f"loads {bench_telemetry.LOADS}",
                 all(r["ok"] for r in fsk),
                 f"{len(fsk)} cells; worst rel err "
                 f"p50={worst50:.4f} p99={worst99:.4f}")
    fov = _by(ftel, lane="overhead")[0]
    ok &= _claim("Timeline: flight-recorder plane adds ≤"
                 f"{bench_telemetry.TOL_TL_OVERHEAD:.0%} steady-state "
                 "wall over telemetry-only",
                 fov["ok"],
                 f"tel={fov['tel_wall_s']:.3f}s vs "
                 f"+timeline={fov['tl_wall_s']:.3f}s "
                 f"({100 * fov['overhead_frac']:+.1f}%)")

    print("== analysis: jaxpr eqn budgets ==", flush=True)
    from repro.analysis import bench_rows
    analysis_rows, analysis_ok, analysis_detail = bench_rows()
    ok &= _claim("Analysis: every engine within its jaxpr eqn budget",
                 analysis_ok, analysis_detail)

    from repro.core.simulator import engine_cache_stats
    from .common import OUT_DIR
    elapsed = time.perf_counter() - t_start
    cache = engine_cache_stats()
    manifest.duration_s = round(elapsed, 3)
    manifest.engine_cache = cache
    manifest.wall_split = wall_split_from_aggregate(tracer.aggregate())
    os.makedirs(OUT_DIR, exist_ok=True)
    tl15 = fig15_timeline.LAST_TIMELINE
    timeline_paths = None
    if tl15 is not None:
        manifest.timeline = tl15.summary()
        if trace_on:
            # merge the windows into the span trace as Perfetto
            # counter tracks (one track per tracked series)
            tl15.emit_counters(tracer)
        if args.timeline_out:
            timeline_paths = (tl15.write_csv(args.timeline_out),
                              tl15.write_openmetrics(
                                  args.timeline_out + ".om"))
    trace_path = None
    if trace_on:
        trace_path = args.trace_out or \
            os.path.join(OUT_DIR, "trace_bench.json")
        tracer.export(trace_path)
    report = {
        "mode": "quick" if quick else "full",
        "started_at": manifest.started_at,
        "elapsed_s": round(elapsed, 1),
        "ok": bool(ok),
        "checks": _CHECKS,
        "engine_cache": cache,
        "manifest": manifest.as_dict(),
        "trace": trace_path,
        "analysis": analysis_rows,
        "figures": {"fig2": f2, "fig3": f3, "fig4": f4, "fig6": f6,
                    "fig8": f8, "fig9": f9, "fig10": f10, "fig11": f11,
                    "fig12": f12, "fig13": f13, "fig14": f14,
                    "fig15": f15, "tab_overhead": tov,
                    "bench_telemetry": ftel},
    }
    report_path = os.path.join(OUT_DIR, "BENCH_report.json")
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1, default=float)
    hit_total = max(cache["hits"] + cache["misses"], 1)
    print(f"engine cache: {cache['entries']}/{cache['capacity']} "
          f"resident, {cache['hits']} hits / {cache['misses']} misses "
          f"({100 * cache['hits'] / hit_total:.0f}% hit rate), "
          f"{cache['evictions']} evictions")
    if timeline_paths:
        print(f"timeline: {timeline_paths[0]} (per-window CSV) + "
              f"{timeline_paths[1]} (OpenMetrics)")
    if trace_path:
        print(f"trace: {trace_path} (load at https://ui.perfetto.dev)")
    print(f"\nbenchmarks done in {elapsed:.0f}s; CSVs in "
          f"experiments/; report: {report_path}; "
          f"overall: {'PASS' if ok else 'FAIL'}")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
