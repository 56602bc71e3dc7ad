"""The benchmark's plain reference gives the program's numpy oracle's
answers, bit for bit, on both stacks the cells run, into the same
histogram bins as the program's sketch."""
import numpy as np
import pytest

from chipbench import reference, traffic

STACKS = [("E/H/PS", 8, 1500, 0.85), ("E/LL/PS", 100, 1200, 0.7)]


def _rep(workers: int, n: int, load: float, seed: int) -> dict:
    mix = {"profile": "diurnal", "n_arrivals": n}
    return traffic.replication(mix, workers, 12, load, seed)


@pytest.mark.parametrize("policy,workers,n,load", STACKS)
def test_matches_the_program_oracle(policy, workers, n, load):
    from repro.core import ClusterCfg, parse_policy
    from repro.core.sim_ref import simulate_ref
    from repro.core.workload import Workload
    from repro.telemetry import TelemetryCfg

    rep = _rep(workers, n, load, 2**31 + 99)
    got = reference.simulate(
        {"n_workers": workers, "cores": 12, "capacity_factor": 8}, policy,
        rep, telemetry=True)
    wl = Workload(arrival=rep["arrival"], func=rep["func"],
                  service=rep["service"], u_lb=rep["u_lb"],
                  func_home=rep["func_home"],
                  n_functions=rep["n_functions"], load=load)
    want = simulate_ref(parse_policy(policy),
                        ClusterCfg(n_workers=workers, cores=12), wl,
                        telemetry=TelemetryCfg())
    np.testing.assert_array_equal(got["worker"], want.worker)
    np.testing.assert_array_equal(got["cold"], want.cold)
    np.testing.assert_array_equal(got["rejected"], want.rejected)
    np.testing.assert_array_equal(got["response"], want.response)
    assert got["server_time"] == want.server_time
    assert got["core_time"] == want.core_time
    assert got["now"] == want.end_time
    tel, wt = got["telemetry"], want.telemetry
    for key in ("slow_hist", "lat_hist", "n_cold", "n_warm", "n_evict",
                "n_reject", "decisions", "busy_time", "depth_time"):
        np.testing.assert_array_equal(tel[key], getattr(wt, key), key)
    assert tel["n_obs"] == int(wt.slow_hist.sum())


def test_float32_run_departs_from_float64():
    rep = _rep(8, 1500, 0.85, 7)
    cl = {"n_workers": 8, "cores": 12, "capacity_factor": 8}
    a = reference.simulate(cl, "E/H/PS", rep)
    b = reference.simulate(cl, "E/H/PS", rep, dtype=np.float32)
    assert b["response"].dtype == np.float32
    ok = np.isfinite(a["response"])
    err = np.abs(b["response"][ok] - a["response"][ok]) / a["response"][ok]
    assert err.max() > 1e-7


def test_histogram_bins_are_the_sketch_bins():
    from repro.telemetry.sketch import bin_index_np, hist_edges
    np.testing.assert_array_equal(reference._EDGES, hist_edges())
    x = np.exp(np.random.default_rng(3).uniform(-12, 16, 5000))
    assert [reference.bin_of(v) for v in x] == list(bin_index_np(x))


def test_early_binding_only():
    with pytest.raises(ValueError, match="early binding"):
        reference.policy_parts("L/LL/FCFS")
