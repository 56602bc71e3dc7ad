"""repro.telemetry contract tests.

Three planes, three obligations:

* the histogram sketch approximates exact percentiles within its
  documented tolerance over heavy-tailed / multi-modal / trace-replay
  service distributions;
* the in-scan jax telemetry carry is *bitwise* equal to the numpy
  oracle's (integer planes) for every registered engine, and enabling
  it never perturbs the simulation results;
* spans export valid Chrome trace JSON, the run manifest collects, and
  the engine cache keys/stats see telemetry correctly.
"""
import json

import numpy as np
import pytest

from repro.core import ClusterCfg, parse_policy, synth_workload
from repro.core.sim_ref import simulate_ref
from repro.core.simulator import (engine_cache_stats, simulate,
                                  simulate_many)
from repro.core.workload import ms_trace, stack_workloads
from repro.policy import balancer_names
from repro.telemetry import (N_BINS, TelemetryCfg, Tracer, bin_index_np,
                             hist_edges, sketch_count, sketch_percentile,
                             wall_split_from_aggregate)
from repro.telemetry.manifest import collect as collect_manifest

CLUSTER = ClusterCfg(n_workers=4, cores=3, capacity_factor=2)
TEL = TelemetryCfg(warmup_frac=0.1)

ALL_POLICIES = [parse_policy(f"E/{b}/PS") for b in balancer_names()] \
    + [parse_policy("L/*/*")]


def _wl(load, n=250, seed=0):
    return synth_workload(CLUSTER, load, n, n_functions=5,
                          hot_fraction=0.8, seed=seed)


# --------------------------------------------------------------------------
# plane 1a: sketch accuracy (documented ≤2% tolerance; half-bin ≈0.76%)
# --------------------------------------------------------------------------

def _draws(kind, n=20000, seed=0):
    rng = np.random.default_rng(seed)
    if kind == "lognormal":
        return rng.lognormal(mean=0.5, sigma=1.5, size=n)
    if kind == "bimodal":
        # unbalanced modes so p50/p90/p99 each fall *inside* a mode —
        # a quantile exactly in the inter-mode density gap is
        # ill-conditioned for any estimator (interpolation across the
        # gap), not a sketch property
        n_short = int(n * 0.6)
        short = rng.lognormal(mean=-2.0, sigma=0.4, size=n_short)
        long = rng.lognormal(mean=2.5, sigma=0.6, size=n - n_short)
        return np.concatenate([short, long])
    if kind == "azure-replay":
        # trace-replay-shaped service draws: the azure-* generators'
        # per-function duration percentiles span ms..minutes
        from repro.core import WORKLOADS
        wl = WORKLOADS["azure-bursty"](CLUSTER, 0.6, n, seed=seed)
        return np.asarray(wl.service, dtype=np.float64)
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", ["lognormal", "bimodal", "azure-replay"])
@pytest.mark.parametrize("q", [50, 90, 99])
def test_sketch_percentile_accuracy(kind, q):
    x = _draws(kind)
    counts = np.bincount(bin_index_np(x), minlength=N_BINS)
    got = sketch_percentile(counts, q)
    want = float(np.percentile(x, q))
    # half-bin geometric error is ≈0.76%; rank interpolation adds a
    # little slack on top for discrete ranks
    assert abs(got - want) / want < 0.02
    assert sketch_count(counts) == x.size


def test_sketch_edges_and_bins():
    e = hist_edges()
    assert e.shape == (N_BINS + 1,) and e.dtype == np.float64
    assert np.all(np.diff(e) > 0)
    # clipping at both ends, exact-edge goes to the right-closed bin
    assert bin_index_np(np.array([0.0, 1e-9])).tolist() == [0, 0]
    assert bin_index_np(np.array([1e9])).tolist() == [N_BINS - 1]
    b = bin_index_np(np.array([1.0]))[0]
    assert e[b] <= 1.0 < e[b + 1]


def test_sketch_percentile_empty_is_nan():
    assert np.isnan(sketch_percentile(np.zeros(N_BINS, dtype=np.int64),
                                      50))


# --------------------------------------------------------------------------
# plane 1b: np ≡ jax parity + telemetry-off goldenness, every engine
# --------------------------------------------------------------------------

def _assert_tel_equal(a, b):
    np.testing.assert_array_equal(a.slow_hist, b.slow_hist)
    np.testing.assert_array_equal(a.lat_hist, b.lat_hist)
    for f in ("n_cold", "n_warm", "n_evict", "n_reject"):
        assert int(np.sum(getattr(a, f))) == int(np.sum(getattr(b, f))), f
    np.testing.assert_array_equal(a.decisions, b.decisions)
    np.testing.assert_allclose(a.busy_time, b.busy_time, rtol=1e-9)
    np.testing.assert_allclose(a.depth_time, b.depth_time, rtol=1e-9)
    np.testing.assert_allclose(a.qlen_time, b.qlen_time, rtol=1e-9)


@pytest.mark.parametrize("policy", ALL_POLICIES, ids=lambda p: p.name)
def test_jax_telemetry_matches_oracle(policy):
    wl = _wl(0.9)
    ref = simulate_ref(policy, CLUSTER, wl, telemetry=TEL)
    out = simulate(policy, CLUSTER, wl, telemetry=TEL)
    _assert_tel_equal(out.telemetry, ref.telemetry)


@pytest.mark.parametrize("policy", ALL_POLICIES[:3],
                         ids=lambda p: p.name)
def test_telemetry_does_not_perturb_results(policy):
    wl = _wl(0.9, seed=2)
    base = simulate(policy, CLUSTER, wl)
    tel = simulate(policy, CLUSTER, wl, telemetry=TEL)
    np.testing.assert_array_equal(
        np.nan_to_num(base.response, nan=-1.0),
        np.nan_to_num(tel.response, nan=-1.0))
    np.testing.assert_array_equal(base.cold, tel.cold)
    np.testing.assert_array_equal(base.rejected, tel.rejected)
    assert base.telemetry is None and tel.telemetry is not None


def test_telemetry_counts_match_population():
    # the sketch observes exactly the post-warmup accepted completions
    wl = _wl(0.8, n=400, seed=3)
    pol = parse_policy("E/H/PS")
    out = simulate(pol, CLUSTER, wl, telemetry=TEL)
    cut = int(wl.n * TEL.warmup_frac)
    accepted = (~out.rejected)[cut:].sum()
    assert sketch_count(out.telemetry.slow_hist) == accepted
    assert sketch_count(out.telemetry.lat_hist) == accepted
    t = out.telemetry
    # every arrival lands in exactly one of placed-cold/warm/rejected
    assert int(t.n_cold + t.n_warm + t.n_reject) == wl.n


def test_lifecycle_eviction_telemetry_parity():
    from repro.lifecycle import LifecycleCfg
    cl = CLUSTER._replace(lifecycle=LifecycleCfg(
        keepalive="FIXED_TTL", ttl_s=5.0, max_idle=2))
    wl = synth_workload(cl, 0.9, 250, n_functions=5, hot_fraction=0.8,
                        seed=1)
    pol = parse_policy("E/LL/PS")
    ref = simulate_ref(pol, cl, wl, telemetry=TEL)
    out = simulate(pol, cl, wl, telemetry=TEL)
    _assert_tel_equal(out.telemetry, ref.telemetry)


def test_batch_telemetry_pools_and_slices():
    wls = [ms_trace(CLUSTER, 0.6, 300, seed=s) for s in (0, 1, 2)]
    wb = stack_workloads(wls)
    pol = parse_policy("E/LL/PS")
    out = simulate_many(pol, CLUSTER, wb, telemetry=TEL)
    refs = [simulate_ref(pol, CLUSTER, w, telemetry=TEL) for w in wls]
    # pooled hist == sum of per-rep oracle hists; rep(r) == oracle r
    np.testing.assert_array_equal(
        out.telemetry.slow_hist.sum(axis=0),
        np.sum([r.telemetry.slow_hist for r in refs], axis=0))
    for r, ref in enumerate(refs):
        _assert_tel_equal(out.telemetry.rep(r), ref.telemetry)
    sl = out[1:3]
    np.testing.assert_array_equal(sl.telemetry.slow_hist,
                                  out.telemetry.slow_hist[1:3])
    assert np.isfinite(out.telemetry.slow_percentile(99))


def test_serving_matches_oracle_telemetry():
    from repro.serving.engine import ServeCfg, ServingCluster
    wl = _wl(0.8, n=300, seed=5)
    pol = parse_policy("E/H/PS")
    sc = ServingCluster(
        ServeCfg(cluster=CLUSTER,
                 cold_start_s=CLUSTER.cold_start_penalty,
                 ctrl_latency_s=0.0),
        pol, telemetry=TEL)
    out = sc.run(wl)
    ref = simulate_ref(pol, CLUSTER, wl, telemetry=TEL)
    _assert_tel_equal(out.telemetry, ref.telemetry)


def test_summary_fields():
    wl = _wl(0.8)
    out = simulate(parse_policy("E/LL/PS"), CLUSTER, wl, telemetry=TEL)
    s = out.telemetry.summary()
    for k in ("n_observed", "slow_p50", "slow_p99", "lat_p50_s",
              "lat_p99_s", "n_cold", "n_warm", "cold_frac", "n_evict",
              "n_reject", "busy_time_s", "qlen_time_s",
              "decision_max_frac"):
        assert k in s, k
    assert s["slow_p50"] >= 1.0 - 0.02  # sketch slack around exact ≥1


# --------------------------------------------------------------------------
# plane 2: span tracing
# --------------------------------------------------------------------------

def test_tracer_spans_export_chrome_trace(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("outer", mode="test"):
        with tr.span("inner"):
            pass
    tr.instant("mark")
    tr.event_at("task", 1.5, 0.25, tid=2, cold=True)
    path = tmp_path / "trace.json"
    tr.export(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    names = [e["name"] for e in evs]
    assert "outer" in names and "inner" in names and "task" in names
    complete = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in complete)
    task = next(e for e in evs if e["name"] == "task")
    assert task["ts"] == pytest.approx(1.5e6) \
        and task["dur"] == pytest.approx(0.25e6)
    agg = tr.aggregate()
    assert agg["outer"]["count"] == 1
    assert agg["outer"]["total_s"] >= agg["inner"]["total_s"]


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("x"):
        pass
    assert tr.events == []


def test_wall_split_from_aggregate():
    agg = {"engine.build": {"count": 2, "total_s": 1.0},
           "engine.first_run": {"count": 2, "total_s": 3.0},
           "engine.run": {"count": 10, "total_s": 5.0}}
    ws = wall_split_from_aggregate(agg)
    assert ws["builds"] == 2 and ws["runs"] == 10
    assert ws["compile_heavy_s"] == pytest.approx(4.0)
    assert ws["steady_state_s"] == pytest.approx(5.0)


# --------------------------------------------------------------------------
# plane 3: provenance + engine-cache integration
# --------------------------------------------------------------------------

def test_manifest_collects():
    m = collect_manifest(seeds={"base": 0}, args={"mode": "test"})
    d = m.as_dict()
    for k in ("git_sha", "python", "jax_version", "numpy_version",
              "devices", "started_at", "seeds", "args"):
        assert k in d, k
    assert d["seeds"] == {"base": 0}
    import jax
    assert d["device_platform"] == jax.devices()[0].platform
    assert d["device_kind"] == jax.devices()[0].device_kind


def test_manifest_does_not_hide_a_failed_device_query(monkeypatch):
    import jax

    def broken():
        raise RuntimeError("TPU backend failed to start")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="failed to start"):
        collect_manifest()


def test_engine_cache_sees_telemetry():
    from repro.core.simulator import build_simulator
    pol = parse_policy("E/LL/PS")
    kw = dict(n_arrivals=16, n_functions=3)
    e_off = build_simulator(pol, CLUSTER, **kw)
    e_on = build_simulator(pol, CLUSTER, telemetry=TEL, **kw)
    e_on2 = build_simulator(pol, CLUSTER, telemetry=TEL, **kw)
    e_on3 = build_simulator(pol, CLUSTER,
                            telemetry=TEL._replace(warmup_frac=0.2),
                            **kw)
    assert e_off is not e_on
    assert e_on is e_on2
    assert e_on is not e_on3


def test_engine_cache_stats_counters():
    from repro.core.simulator import build_simulator
    stats0 = engine_cache_stats()
    pol = parse_policy("E/R/PS")
    kw = dict(n_arrivals=24, n_functions=3)
    build_simulator(pol, CLUSTER, **kw)   # miss (fresh key)
    build_simulator(pol, CLUSTER, **kw)   # hit
    stats1 = engine_cache_stats()
    assert stats1["misses"] >= stats0["misses"] + 1
    assert stats1["hits"] >= stats0["hits"] + 1
    for k in ("entries", "capacity", "hits", "misses", "evictions"):
        assert k in stats1, k
