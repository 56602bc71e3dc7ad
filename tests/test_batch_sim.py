"""Batched engine contract: ``simulate_many`` ≡ R independent ``simulate``
calls bit-for-bit, plus compile-cache identity and batch metrics."""
import numpy as np
import pytest

from repro.core import (ClusterCfg, E_LL_SRPT, E_LOC_FCFS, E_R_PS, HERMES,
                        LATE_BINDING, replicate_workload, stack_workloads,
                        summarize_batch_sim, summarize_sim, synth_workload)
from repro.core.simulator import (build_batch_simulator, build_simulator,
                                  simulate, simulate_many)

# One policy per binding/balance/sched family:
#   L/LL/FCFS (late binding), E/LOC/FCFS (locality + FCFS),
#   E/R/PS (random + PS), E/LL/SRPT (least-loaded + SRPT),
#   E/H/PS (Hermes hybrid + PS).
FAMILY_POLICIES = (LATE_BINDING, E_LOC_FCFS, E_R_PS, E_LL_SRPT, HERMES)
CLUSTER = ClusterCfg(n_workers=4, cores=3, capacity_factor=2)


def _wls(n=200):
    """Replications differing in both load and seed (shared (N, F))."""
    return [synth_workload(CLUSTER, load, n, n_functions=5,
                           hot_fraction=0.8, seed=seed)
            for load, seed in ((0.4, 0), (0.9, 1), (1.3, 2))]


@pytest.mark.parametrize("policy", FAMILY_POLICIES, ids=lambda p: p.name)
def test_simulate_many_matches_independent_runs(policy):
    wls = _wls()
    batch = simulate_many(policy, CLUSTER, wls)
    assert batch.n_reps == len(wls)
    for r, wl in enumerate(wls):
        single = simulate(policy, CLUSTER, wl)
        # bit-for-bit: the batched engine is the same program under vmap
        np.testing.assert_array_equal(
            np.nan_to_num(batch.response[r], nan=-1.0),
            np.nan_to_num(single.response, nan=-1.0))
        np.testing.assert_array_equal(batch.cold[r], single.cold)
        np.testing.assert_array_equal(batch.rejected[r], single.rejected)
        np.testing.assert_array_equal(batch.worker[r], single.worker)
        assert float(batch.server_time[r]) == single.server_time
        assert float(batch.core_time[r]) == single.core_time
        assert float(batch.end_time[r]) == single.end_time
        # rep() view round-trips
        rep = batch.rep(r)
        np.testing.assert_array_equal(
            np.nan_to_num(rep.response, nan=-1.0),
            np.nan_to_num(single.response, nan=-1.0))


def test_completion_within_eps_of_arrival_edge_terminates():
    """Regression: a task finishing EPS-close past an arrival boundary
    must complete in the pending-drain iteration, not livelock the
    while_loop (remaining in (0, EPS] with the window exhausted)."""
    import numpy as np
    from repro.core import Workload, E_LL_FCFS
    from repro.core.sim_ref import simulate_ref
    cl = ClusterCfg(n_workers=1, cores=2, capacity_factor=2)
    wl = Workload(
        arrival=np.array([0.0, 1.0]),
        func=np.zeros(2, dtype=np.int32),
        service=np.array([1.0 + 5e-10, 1.0]),   # done 5e-10 past arrival 2
        u_lb=np.zeros(2),
        func_home=np.zeros(1, dtype=np.int32),
        n_functions=1, load=0.5, name="eps-edge")
    out = simulate(E_LL_FCFS, cl, wl)
    ref = simulate_ref(E_LL_FCFS, cl, wl)
    np.testing.assert_allclose(out.response, ref.response, atol=1e-6)
    batch = simulate_many(E_LL_FCFS, cl, [wl, wl])
    np.testing.assert_array_equal(batch.response[0], out.response)


def test_compile_cache_returns_same_fn():
    kw = dict(n_arrivals=200, n_functions=5)
    a = build_simulator(HERMES, CLUSTER, **kw)
    b = build_simulator(HERMES, CLUSTER, **kw)
    assert a is b
    ab = build_batch_simulator(HERMES, CLUSTER, **kw)
    bb = build_batch_simulator(HERMES, CLUSTER, **kw)
    assert ab is bb
    assert ab is not a
    # any key component change misses the cache
    assert build_simulator(E_R_PS, CLUSTER, **kw) is not a
    assert build_simulator(
        HERMES, CLUSTER._replace(cold_start_penalty=0.5), **kw) is not a
    assert build_simulator(HERMES, CLUSTER, n_arrivals=201,
                           n_functions=5) is not a


def test_engine_cache_lru_bounded_and_evicts():
    """The compile cache is a bounded LRU: recently-used engines survive,
    the oldest are evicted once capacity is exceeded."""
    from repro.core import simulator as sim
    sim.clear_engine_cache()
    old_cap = sim.engine_cache_capacity()
    try:
        sim.set_engine_cache_capacity(2)
        kw = dict(n_functions=2)
        a = sim.build_simulator(HERMES, CLUSTER, n_arrivals=10, **kw)
        b = sim.build_simulator(HERMES, CLUSTER, n_arrivals=11, **kw)
        # touching a makes b the LRU entry
        assert sim.build_simulator(HERMES, CLUSTER, n_arrivals=10,
                                   **kw) is a
        sim.build_simulator(HERMES, CLUSTER, n_arrivals=12, **kw)
        stats = sim.engine_cache_stats()
        assert stats["entries"] == 2 and stats["capacity"] == 2
        # a survived (was MRU), b was evicted and rebuilds fresh
        assert sim.build_simulator(HERMES, CLUSTER, n_arrivals=10,
                                   **kw) is a
        assert sim.build_simulator(HERMES, CLUSTER, n_arrivals=11,
                                   **kw) is not b
        # shrinking the bound evicts immediately
        sim.set_engine_cache_capacity(1)
        assert sim.engine_cache_stats()["entries"] == 1
        with pytest.raises(ValueError):
            sim.set_engine_cache_capacity(0)
    finally:
        sim.set_engine_cache_capacity(old_cap)
        sim.clear_engine_cache()


def test_stack_workloads_validates_shape():
    a = synth_workload(CLUSTER, 0.5, 100, n_functions=5, seed=0)
    b = synth_workload(CLUSTER, 0.5, 101, n_functions=5, seed=0)
    c = synth_workload(CLUSTER, 0.5, 100, n_functions=6, seed=0)
    with pytest.raises(ValueError):
        stack_workloads([a, b])
    with pytest.raises(ValueError):
        stack_workloads([a, c])
    with pytest.raises(ValueError):
        stack_workloads([])
    wb = stack_workloads([a])
    assert wb.n_reps == 1 and wb.n == 100


def test_replicate_workload_grid_order():
    def wfn(cluster, load, n, seed):
        return synth_workload(cluster, load, n, n_functions=3, seed=seed,
                              name=f"l{load}-s{seed}")
    wb = replicate_workload(wfn, CLUSTER, [0.3, 0.8], 50, seeds=(0, 1, 2))
    assert wb.n_reps == 6
    # load-major: loads change slowest, seeds fastest
    assert wb.names == ("l0.3-s0", "l0.3-s1", "l0.3-s2",
                        "l0.8-s0", "l0.8-s1", "l0.8-s2")
    assert wb.loads == (0.3, 0.3, 0.3, 0.8, 0.8, 0.8)
    # distinct seeds produce distinct traces
    assert not np.allclose(wb.service[0], wb.service[1])


def test_summarize_batch_single_rep_matches_summarize():
    wl = synth_workload(CLUSTER, 0.8, 300, n_functions=5, seed=3)
    out = simulate(HERMES, CLUSTER, wl)
    bout = simulate_many(HERMES, CLUSTER, [wl])
    wb = stack_workloads([wl])
    bs = summarize_batch_sim(bout, wb)
    s = summarize_sim(out, wl)
    assert bs.n_reps == 1
    assert bs.per_rep[0] == s
    assert bs.pooled == s
    # no spread estimate from a single replication
    assert all(st.ci95 == 0.0 for st in bs.stats.values()
               if np.isfinite(st.ci95))


def test_mixed_synthetic_and_replay_batch():
    """Synthetic generators + azure-* trace replays stack into ONE
    ``simulate_many`` batch (the ROADMAP mixed-batches item): the shape
    is harmonized by resampling, per-rep results are bit-identical to
    independent ``simulate`` runs, and metrics summarize per workload.
    """
    from benchmarks.common import mixed_workload_batch, sweep_policies_mixed
    names = ("ms-trace", "azure-diurnal", "azure-bursty")
    wb = mixed_workload_batch(CLUSTER, names, 0.6, 180, seed=0)
    assert wb.n_reps == len(names)
    # harmonized shape: truncated to shortest N, widened to the largest
    # component F (ms-trace's 50; replay traces carry fewer functions)
    assert wb.n == 180
    assert wb.n_functions == 50
    assert int(wb.func.max()) < wb.n_functions
    assert wb.names[1].startswith("azure-diurnal")
    # mixed batch ≡ R independent runs, including a carried-state policy
    from repro.core import E_DD_PS
    for policy in (HERMES, E_DD_PS):
        batch = simulate_many(policy, CLUSTER, wb)
        for r in range(wb.n_reps):
            single = simulate(policy, CLUSTER, wb.rep(r))
            np.testing.assert_array_equal(
                np.nan_to_num(batch.response[r], nan=-1.0),
                np.nan_to_num(single.response, nan=-1.0))
    rows = sweep_policies_mixed([HERMES, E_DD_PS], CLUSTER, names, 0.6,
                                180, seed=0)
    assert len(rows) == 2 * len(names)
    assert {r["workload"] for r in rows} == set(names)
    assert all(np.isfinite(r["slow_p50"]) for r in rows)


def test_summarize_batch_confidence_intervals():
    wls = [synth_workload(CLUSTER, 0.8, 300, n_functions=5, seed=s)
           for s in range(4)]
    bout = simulate_many(HERMES, CLUSTER, wls)
    bs = summarize_batch_sim(bout, stack_workloads(wls))
    assert bs.n_reps == 4
    st = bs.stats["slow_p50"]
    per = [s.slow_p50 for s in bs.per_rep]
    assert min(per) <= st.mean <= max(per)
    assert st.ci95 >= 0.0 and st.lo <= st.mean <= st.hi
    row = bs.row()
    assert row["slow_p50_mean"] == st.mean
    assert row["slow_p50_ci95"] == st.ci95


# ------------------------------------------------- streaming engine

# The heaviest carry the engines support: carried-state balancer (DD) +
# hybrid-histogram keep-alive + telemetry sketches + two-generation
# fleet + TARGET_P99 autoscaler, over several chunk sizes.  Every
# balancer × scheduler and keep-alive preset is checked at one chunk size
# in test_stream_parity.py.
def _stream_cluster():
    from repro.core import LifecycleCfg
    from repro.fleet import FleetCfg
    return CLUSTER._replace(
        lifecycle=LifecycleCfg(keepalive="HYBRID_HIST", ttl_s=2.0,
                               max_idle=3, coldstart="paper-sim"),
        fleet=FleetCfg(preset="two-gen", autoscale="TARGET_P99",
                       min_workers=2, target_p99=4.0, cooldown_s=2.0))


@pytest.mark.parametrize("chunk", [16, 50, 97, 300],
                         ids=lambda k: f"k{k}")
def test_stream_matches_monolithic_bitwise(chunk):
    """Chunked(N, k) ≡ monolithic(N) bit-for-bit — final carry,
    per-arrival outputs, telemetry sketches and pooled metrics — for
    dividing, non-dividing (97) and larger-than-horizon (300) chunks."""
    from repro.core import E_DD_PS
    from repro.telemetry import TelemetryCfg
    from repro.core.simulator import build_batch_simulator
    from repro.core.streaming import final_states_equal, simulate_stream
    import jax.numpy as jnp

    cl = _stream_cluster()
    tel = TelemetryCfg()
    wls = [synth_workload(cl, load, 200, n_functions=5, seed=seed)
           for load, seed in ((0.5, 0), (1.1, 1))]
    wb = stack_workloads(wls)
    run = build_batch_simulator(E_DD_PS, cl, n_arrivals=wb.n,
                                n_functions=wb.n_functions,
                                telemetry=tel)
    mono = run(jnp.asarray(wb.arrival), jnp.asarray(wb.func),
               jnp.asarray(wb.service), jnp.asarray(wb.u_lb),
               jnp.asarray(wb.func_home))
    out = simulate_stream(E_DD_PS, cl, wb, chunk_size=chunk,
                          telemetry=tel, collect_outputs=True,
                          keep_final_state=True)
    ok, bad = final_states_equal(out.final_state, mono)
    assert ok, f"carry mismatch in planes: {bad}"
    # per-arrival outputs stream out through the scan ys
    np.testing.assert_array_equal(out.rejected,
                                  np.asarray(mono.rejected[:, :wb.n]))
    np.testing.assert_array_equal(out.cold,
                                  np.asarray(mono.cold[:, :wb.n]))
    np.testing.assert_array_equal(out.worker,
                                  np.asarray(mono.worker_of[:, :wb.n]))
    # exact online counters reproduce the monolithic per-task planes
    from repro.telemetry.state import warmup_cutoff
    cut = warmup_cutoff(wb.n, tel)
    resp = np.asarray(mono.resp[:, :wb.n])
    done = ~np.isnan(resp)
    obs = done & (np.arange(wb.n) >= cut)
    np.testing.assert_array_equal(out.n_done, done.sum(axis=1))
    np.testing.assert_array_equal(out.n_observed, obs.sum(axis=1))
    np.testing.assert_allclose(
        out.resp_mean,
        np.where(obs, resp, 0.0).sum(axis=1) / np.maximum(
            obs.sum(axis=1), 1), rtol=1e-12)
    assert out.n_chunks == -(-wb.n // chunk)
    assert out.chunk_size == chunk


def test_stream_matches_numpy_oracle_per_segment():
    """The chunked jax engine and the numpy oracle's chunked replay
    agree at every segment boundary, not just at the end."""
    from repro.core import E_LL_PS
    from repro.telemetry import TelemetryCfg
    from repro.core.sim_ref import simulate_ref_chunks
    from repro.core.streaming import simulate_stream

    cl = CLUSTER
    tel = TelemetryCfg()
    wl = synth_workload(cl, 0.9, 140, n_functions=5, seed=4)
    ref, snaps = simulate_ref_chunks(E_LL_PS, cl, wl, chunk_size=40,
                                     telemetry=tel)
    seen = []
    simulate_stream(
        E_LL_PS, cl, wl, chunk_size=40, telemetry=tel,
        chunk_callback=lambda c, st: seen.append(
            {k: np.copy(np.asarray(v)[0]) for k, v in st.tel.items()}))
    assert len(seen) == len(snaps) == 4
    for got, want in zip(seen, snaps):
        for key in ("slow_hist", "lat_hist", "n_cold", "n_warm",
                    "n_evict", "n_reject", "decisions"):
            np.testing.assert_array_equal(got[key], want[key],
                                          err_msg=key)
        for key in ("busy_time", "depth_time", "qlen_time"):
            np.testing.assert_allclose(got[key], want[key], atol=1e-9,
                                       err_msg=key)


def test_stream_engine_cache_horizon_independent():
    """One compiled chunk program serves any horizon; the cache key is
    (policy, cluster, chunk), never N."""
    from repro.core import E_LL_PS
    from repro.telemetry import TelemetryCfg
    from repro.core.simulator import _get_stream_engine

    tel = TelemetryCfg()
    a, fresh_a = _get_stream_engine(E_LL_PS, CLUSTER, 32, 5, "auto", tel)
    b, fresh_b = _get_stream_engine(E_LL_PS, CLUSTER, 32, 5, "auto", tel)
    assert a is b and not fresh_b
    c, _ = _get_stream_engine(E_LL_PS, CLUSTER, 64, 5, "auto", tel)
    assert c is not a
    # different horizons reuse the same engine end to end
    from repro.core.streaming import simulate_stream
    wl_s = synth_workload(CLUSTER, 0.7, 64, n_functions=5, seed=0)
    wl_l = synth_workload(CLUSTER, 0.7, 200, n_functions=5, seed=0)
    o1 = simulate_stream(E_LL_PS, CLUSTER, wl_s, chunk_size=32,
                         telemetry=tel)
    o2 = simulate_stream(E_LL_PS, CLUSTER, wl_l, chunk_size=32,
                         telemetry=tel)
    assert o1.n_chunks == 2 and o2.n_chunks == 7
    d, fresh_d = _get_stream_engine(E_LL_PS, CLUSTER, 32, 5, "auto", tel)
    assert d is a and not fresh_d


def test_stream_requires_early_binding():
    from repro.telemetry import TelemetryCfg
    from repro.core.streaming import simulate_stream

    wl = synth_workload(CLUSTER, 0.5, 50, n_functions=5, seed=0)
    with pytest.raises(ValueError, match="early binding"):
        simulate_stream(LATE_BINDING, CLUSTER, wl, chunk_size=16,
                        telemetry=TelemetryCfg())


@pytest.mark.slow
def test_stream_sharded_reps_match_unsharded(devices_script):
    """Rep-axis device sharding changes placement, not results: the
    sharded run is bit-equal to the single-device run (the Hermes
    kernel included), its carry lives on every device of the mesh, and
    a rep count that does not divide the mesh raises the named error."""
    devices_script("""
import jax
import numpy as np
from repro.core import ClusterCfg, E_DD_PS, HERMES, synth_workload
from repro.telemetry import TelemetryCfg
from repro.core.streaming import final_states_equal, simulate_stream
from repro.launch.mesh import make_rep_mesh

cl = ClusterCfg(n_workers=4, cores=3, capacity_factor=2)
tel = TelemetryCfg()
wls = [synth_workload(cl, 0.8, 120, n_functions=5, seed=s)
       for s in range(4)]
mesh = make_rep_mesh(4)
for pol in (E_DD_PS, HERMES):
    a = simulate_stream(pol, cl, wls, chunk_size=40, telemetry=tel,
                        keep_final_state=True, collect_outputs=True)
    b = simulate_stream(pol, cl, wls, chunk_size=40, telemetry=tel,
                        keep_final_state=True, collect_outputs=True,
                        mesh=mesh)
    ok, bad = final_states_equal(a.final_state, b.final_state)
    assert ok, bad
    np.testing.assert_array_equal(a.worker, b.worker)
    np.testing.assert_array_equal(a.n_done, b.n_done)
    np.testing.assert_array_equal(a.resp_mean, b.resp_mean)
    spread = {len(x.sharding.device_set)
              for x in jax.tree_util.tree_leaves(b.final_state)}
    assert spread == {4}, spread
try:
    simulate_stream(E_DD_PS, cl, wls[:3], chunk_size=40, telemetry=tel,
                    mesh=mesh)
except ValueError as e:
    assert "does not divide" in str(e), e
else:
    raise AssertionError("expected named divisibility error")
print("sharded-ok")
""", n_devices=4)


@pytest.mark.slow
def test_stream_full_day_large_fleet_under_memory_budget():
    """The horizon gate end-to-end: one full synthetic azure-diurnal
    day at W=1000 in a single streaming run, peak RSS under budget."""
    from benchmarks.fig14_stream import (PEAK_MB_BUDGET, _horizon_lane)

    row = _horizon_lane(quick=False)[0]
    assert row["ok"], row
    assert row["n_workers"] >= 1000
    assert row["full_day"] and row["n_done"] > 0
    assert row["peak_rss_mb"] <= PEAK_MB_BUDGET
