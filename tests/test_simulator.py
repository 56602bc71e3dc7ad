"""Simulator contract tests: JAX engine ≡ numpy oracle + invariants."""
import numpy as np
import pytest

from repro.core import (ClusterCfg, FIG2_POLICIES, HERMES, E_LL_SRPT,
                        synth_workload, summarize_sim)
from repro.core.sim_ref import simulate_ref
from repro.core.simulator import simulate

POLICIES = list(FIG2_POLICIES) + [HERMES, E_LL_SRPT]
CLUSTER = ClusterCfg(n_workers=4, cores=3, capacity_factor=2)


def _wl(load, n=250, seed=0, **kw):
    return synth_workload(CLUSTER, load, n, n_functions=5,
                          hot_fraction=0.8, seed=seed, **kw)


@pytest.mark.parametrize("policy", POLICIES, ids=lambda p: p.name)
@pytest.mark.parametrize("load", [0.4, 0.9, 1.3])
def test_jax_matches_oracle(policy, load):
    wl = _wl(load)
    ref = simulate_ref(policy, CLUSTER, wl)
    out = simulate(policy, CLUSTER, wl)
    np.testing.assert_allclose(
        np.nan_to_num(out.response, nan=-1.0),
        np.nan_to_num(ref.response, nan=-1.0), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(out.cold, ref.cold)
    np.testing.assert_array_equal(out.rejected, ref.rejected)
    assert abs(out.server_time - ref.server_time) < 1e-3 * max(
        1.0, ref.server_time)
    assert abs(out.core_time - ref.core_time) < 1e-3 * max(
        1.0, ref.core_time)


@pytest.mark.parametrize("policy", POLICIES, ids=lambda p: p.name)
def test_invariants(policy):
    wl = _wl(0.8, n=400, seed=3)
    out = simulate(policy, CLUSTER, wl)
    done = ~out.rejected
    # every accepted invocation completes after the drain
    assert np.isfinite(out.response[done]).all()
    # response ≥ service (can't finish faster than its work)
    assert (out.response[done] >= wl.service[done] - 1e-6).all()
    # work conservation: total core-time == total service of accepted
    assert abs(out.core_time - wl.service[done].sum()) < 1e-3 * \
        wl.service[done].sum()
    # rejected only when genuinely full is possible
    if out.rejected.any():
        assert CLUSTER.slots * CLUSTER.n_workers <= 400
    s = summarize_sim(out, wl)
    assert s.slow_p50 >= 1.0 - 1e-9
    assert s.slow_p99 >= s.slow_p50


def test_seeds_differ():
    a = _wl(0.5, seed=0)
    b = _wl(0.5, seed=1)
    assert not np.allclose(a.service, b.service)


def test_service_cap():
    wl = _wl(0.5, n=4000, max_service=100.0)
    assert wl.service.max() <= 100.0


def test_cold_start_penalty_increases_response():
    wl = _wl(0.5, n=300)
    cold_cluster = CLUSTER._replace(cold_start_penalty=0.7)
    base = simulate_ref(HERMES, CLUSTER, wl)
    pen = simulate_ref(HERMES, cold_cluster, wl)
    assert np.nansum(pen.response) > np.nansum(base.response)


def test_warm_reuse_reduces_cold_starts():
    """A single-function workload should cold-start ~once per worker."""
    wl = synth_workload(CLUSTER, 0.5, 300, n_functions=1,
                        hot_fraction=1.0, seed=2)
    out = simulate_ref(HERMES, CLUSTER, wl)
    # far fewer cold starts than invocations
    assert out.cold.sum() < 0.2 * wl.n


@pytest.mark.parametrize("policy", [HERMES, POLICIES[0], POLICIES[2],
                                    POLICIES[4], POLICIES[6]],
                         ids=lambda p: p.name)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_eviction_agreement_under_full_warm_pools(policy, seed):
    """Randomized lock on the slot-pressure eviction tie-breaking
    contract: with tiny slot counts (capacity_factor=1), overload and
    many functions, warm pools sit at capacity and the ``need_evict``
    drain fires constantly — the JAX engine's victim choice (legacy:
    argmax warm count; lifecycle: LRU idle-since, ties to the lowest
    function id) must match the numpy oracle invocation-by-invocation,
    with and without a lifecycle configured."""
    from repro.core import LifecycleCfg
    base = ClusterCfg(n_workers=3, cores=2, capacity_factor=1,
                      cold_start_penalty=0.3)
    wl = synth_workload(base, 1.1, 250, n_functions=8,
                        hot_fraction=0.4, seed=seed)
    for lc in (None, LifecycleCfg(ttl_s=4.0, max_idle=1)):
        cl = base._replace(lifecycle=lc)
        out = simulate(policy, cl, wl)
        ref = simulate_ref(policy, cl, wl)
        np.testing.assert_array_equal(out.cold, ref.cold)
        np.testing.assert_array_equal(out.worker, ref.worker)
        np.testing.assert_array_equal(out.rejected, ref.rejected)
        np.testing.assert_allclose(
            np.nan_to_num(out.response, nan=-1.0),
            np.nan_to_num(ref.response, nan=-1.0), atol=1e-6)


def test_slot_index_beyond_int32_is_refused():
    """The drain indexes the cluster's slots in int32."""
    from repro.core.simulator import _build_engine
    cl = ClusterCfg(n_workers=2 ** 20, cores=2 ** 9, capacity_factor=8)
    with pytest.raises(ValueError, match="int32 slot index"):
        _build_engine(HERMES, cl, n_arrivals=4, n_functions=2)


def _tied_warm():
    warm = np.zeros((3, 4, 6), np.int32)
    warm[0, 2] = [1, 3, 0, 3, 2, 3]     # tied maxima (the victim: first)
    warm[1, 0] = [0, 0, 5, 5, 0, 1]
    warm[2, 3] = [7, 7, 7, 7, 7, 7]
    warm[:, 1] = 9                      # a larger row nobody reads
    return warm, np.array([2, 0, 3])


def _zero_row():
    warm = np.arange(3 * 4 * 6, dtype=np.int32).reshape(3, 4, 6)
    warm[np.arange(3), [1, 3, 0]] = 0
    return warm, np.array([1, 3, 0])


def _idle_since():
    lu = np.random.default_rng(0).normal(size=(3, 4, 6)) * 1e3
    lu[0, 3] = [-1.0, -0.0, 0.0, -1e-300, 5.0, -0.0]   # virgin pools < 0
    lu[1, 1] = -0.0
    lu[2, 0] = [np.inf, -np.inf, -2.5, -0.0, 1e300, -5e-324]
    return lu, np.array([3, 1, 0])


@pytest.mark.parametrize("case", [_tied_warm, _zero_row, _idle_since],
                         ids=["tied-maxima", "zero-row", "idle-since-f64"])
def test_row_read_matches_indexing_per_replication(case):
    """``place`` reads the chosen worker's row by a select and reduction
    over the worker axis; under ``vmap``, with another worker per
    replication, it gives the indexed row back bit for bit."""
    import jax
    from repro.core.simulator import _row
    plane, ws = case()
    got = np.asarray(jax.vmap(_row)(plane, ws))
    want = plane[np.arange(len(ws)), ws]
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))


def _slot_plane_f64():
    plane = np.random.default_rng(1).normal(size=(4, 3, 5)) * 1e3
    plane[0, 1] = [np.inf, -0.0, 0.0, 5e-324, -1e-310]
    plane[1, 2] = -0.0
    plane[2, 0] = [-np.inf, 4e-320, -0.0, np.inf, 1e300]
    plane[3] = 5e-324
    return plane, np.array([1, 2, 0, 2]), np.array([1, 0, 1, 4])


def _slot_plane_i32():
    plane = np.arange(4 * 3 * 5, dtype=np.int32).reshape(4, 3, 5) - 30
    plane[:, :, 4] = -1
    return plane, np.array([0, 2, 1, 2]), np.array([4, 0, 3, 4])


@pytest.mark.parametrize("mask", [[True] * 4, [True, False, True, False],
                                  [False] * 4],
                         ids=["all", "some", "none"])
@pytest.mark.parametrize("case,value", [
    (_slot_plane_f64, np.inf), (_slot_plane_f64, -0.0),
    (_slot_plane_i32, -1), (_slot_plane_i32, None)],
    ids=["f64-set-inf", "f64-set-neg-zero", "i32-set-free", "i32-add"])
def test_one_hot_write_matches_indexed_write_per_replication(case, value,
                                                             mask):
    """The drain retires the completing slot by a select on a one-hot
    mask (an add of it, for the warm pool); under ``vmap``, with another
    slot and mask per replication, it writes what the indexed write
    wrote and leaves every other element (inf, -0.0, subnormals) as it
    was, bit for bit."""
    import jax
    import jax.numpy as jnp
    from repro.core.simulator import _hit
    plane, ws, ss = case()
    mask = np.array(mask)

    def one_hot(p, w, s, m):
        hit = _hit(p.shape, w, s) & m
        if value is None:
            return p + hit.astype(p.dtype)
        return jnp.where(hit, jnp.asarray(value, p.dtype), p)

    def indexed(p, w, s, m):
        if value is None:
            return p.at[w, s].add(m.astype(p.dtype))
        return p.at[w, s].set(jnp.where(m, jnp.asarray(value, p.dtype),
                                        p[w, s]))

    got = np.asarray(jax.vmap(one_hot)(plane, ws, ss, mask))
    want = np.asarray(jax.vmap(indexed)(plane, ws, ss, mask))
    assert got.dtype == want.dtype == plane.dtype
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))
    kept = ~mask
    np.testing.assert_array_equal(got[kept].view(np.uint8),
                                  plane[kept].view(np.uint8))
