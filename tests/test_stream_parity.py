"""Streamed engine ≡ monolithic engine, bit for bit, for every stack.

``simulate_stream`` retires completions from the carry's own slot
mirrors (``task_fn``/``task_svc``) where the monolithic scan indexes its
per-arrival planes, so each balancer's ``on_complete`` and each
scheduler's rates go through a completion path of their own.  Every
early-binding balancer × scheduler registered in :mod:`repro.policy` is
checked here, so a newly registered one is covered without an edit,
plus the keep-alive presets that drive the lifecycle drain.
"""
import itertools
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ClusterCfg, LifecycleCfg, parse_policy,
                        stack_workloads, synth_workload)
from repro.core.simulator import build_batch_simulator
from repro.core.streaming import final_states_equal, simulate_stream
from repro.policy import balancer_names, sched_names
from repro.telemetry import TelemetryCfg

CLUSTER = ClusterCfg(n_workers=4, cores=3, capacity_factor=2)
CHUNK = 97          # does not divide N=200: the last chunk is short

CASES = [pytest.param(f"E/{lb}/{s}", CLUSTER, id=f"E/{lb}/{s}")
         for lb, s in itertools.product(balancer_names(), sched_names())]
CASES += [pytest.param("E/LL/PS", CLUSTER._replace(lifecycle=LifecycleCfg(
              keepalive=ka, ttl_s=2.0, max_idle=3)), id=f"E/LL/PS+{ka}")
          for ka in ("NONE", "FIXED_TTL", "HYBRID_HIST")]


@pytest.mark.parametrize("policy,cluster", CASES)
def test_stream_matches_monolithic(policy, cluster):
    policy = parse_policy(policy)
    tel = TelemetryCfg()
    wb = stack_workloads([synth_workload(cluster, load, 200, n_functions=5,
                                         seed=seed)
                          for load, seed in ((0.5, 0), (1.1, 1))])
    run = build_batch_simulator(policy, cluster, n_arrivals=wb.n,
                                n_functions=wb.n_functions, telemetry=tel)
    mono = run(jnp.asarray(wb.arrival), jnp.asarray(wb.func),
               jnp.asarray(wb.service), jnp.asarray(wb.u_lb),
               jnp.asarray(wb.func_home))
    out = simulate_stream(policy, cluster, wb, chunk_size=CHUNK,
                          telemetry=tel, collect_outputs=True,
                          keep_final_state=True)
    ok, bad = final_states_equal(out.final_state, mono)
    assert ok, f"carry mismatch in planes: {bad}"
    np.testing.assert_array_equal(out.worker,
                                  np.asarray(mono.worker_of[:, :wb.n]))
    np.testing.assert_array_equal(out.cold, np.asarray(mono.cold[:, :wb.n]))
    np.testing.assert_array_equal(out.rejected,
                                  np.asarray(mono.rejected[:, :wb.n]))
    done = ~np.isnan(np.asarray(mono.resp[:, :wb.n]))
    np.testing.assert_array_equal(out.n_done, done.sum(axis=1))


def test_simulator_path_imports_no_lm_code(devices_script):
    """The chip path (rep mesh, streamed and swept engines) loads none
    of the LM models or their logical-axis sharding rules."""
    out = devices_script(textwrap.dedent("""
        import sys
        from repro.core import ClusterCfg, HERMES, E_LL_PS, synth_workload
        from repro.core.simulator import simulate_many
        from repro.core.streaming import simulate_stream
        from repro.launch.mesh import make_rep_mesh

        cl = ClusterCfg(n_workers=4, cores=3, capacity_factor=2)
        wls = [synth_workload(cl, 0.7, 60, n_functions=5, seed=s)
               for s in (0, 1)]
        simulate_many(HERMES, cl, wls, backend="pallas")
        simulate_stream(E_LL_PS, cl, wls, chunk_size=32,
                        mesh=make_rep_mesh(2))
        mods = sorted(m for m in sys.modules if m.startswith("repro."))
        bad = [m for m in mods
               if m.startswith("repro.models")
               or m == "repro.distribution.sharding"
               or (m.startswith("repro.kernels.")
                   and not m.startswith("repro.kernels.hermes_select"))]
        assert "repro.kernels.hermes_select" in mods, mods
        assert not bad, bad
        print("OK")
        """), n_devices=2, timeout=300)
    assert "OK" in out
