"""End-to-end behaviour tests: the paper's claims at test scale, plus
examples and benchmark plumbing."""
import numpy as np
import pytest

from repro.core import (E_LL_PS, E_LL_FCFS, E_LOC_PS, HERMES, LATE_BINDING,
                        ClusterCfg, ms_trace, multi_balanced, summarize_sim)
from repro.core.simulator import simulate

CL = ClusterCfg(n_workers=4, cores=12)


def _slow99(policy, wl):
    return summarize_sim(simulate(policy, CL, wl), wl).slow_p99


def test_lesson1_head_of_line_blocking():
    """PS-based early binding beats FCFS/late binding on tail slowdown
    under the Azure-shaped heavy-tailed workload (paper Lesson 1)."""
    wl = ms_trace(CL, 0.9, 6000, seed=0)
    ps = _slow99(E_LL_PS, wl)
    fcfs = _slow99(E_LL_FCFS, wl)
    late = _slow99(LATE_BINDING, wl)
    assert ps * 5 < fcfs, (ps, fcfs)
    assert ps * 5 < late, (ps, late)


def test_lesson2_locality_balancing_saturates():
    """Sticky locality hashing overloads the hot worker (Lesson 2)."""
    wl = ms_trace(CL, 0.6, 6000, seed=0)
    assert _slow99(E_LOC_PS, wl) > 3 * _slow99(E_LL_PS, wl)


def test_vanilla_wins_only_on_balanced_mix():
    """§6.2: with zero skew, locality hashing is fine — the OpenWhisk
    scheduler is 'optimized for the wrong workload'."""
    wl = multi_balanced(CL, 0.5, 6000, seed=0)
    loc = _slow99(E_LOC_PS, wl)
    ll = _slow99(E_LL_PS, wl)
    assert loc < ll * 2 + 2          # comparable on balanced mix


def test_hermes_equals_ll_performance_with_fewer_servers():
    wl = ms_trace(CL, 0.3, 6000, seed=1)
    h = summarize_sim(simulate(HERMES, CL, wl), wl)
    ll = summarize_sim(simulate(E_LL_PS, CL, wl), wl)
    assert h.slow_p99 <= ll.slow_p99 * 1.2 + 1.0
    assert h.mean_servers < ll.mean_servers


def test_benchmark_modules_run_tiny():
    """Benchmark plumbing: every figure module produces rows."""
    import benchmarks.fig2_policy_space as f2
    rows = f2.sweep_policies if False else None
    from benchmarks.common import sweep_policies
    from repro.core import FIG2_POLICIES
    rows = sweep_policies(FIG2_POLICIES[:2], CL, [0.5], 300, ms_trace)
    assert len(rows) == 2 and all(r["slow_p99"] >= 1 for r in rows)


def test_quickstart_example_runs():
    import subprocess
    import sys
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["JAX_PLATFORMS"] = "cpu"   # a parent holding a chip cannot share it
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", "quickstart.py"),
         "--quick"], capture_output=True, text=True, timeout=560, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
