"""Compile rehearsals for a TPU v5e that is described, not attached.

The chip's compiler is installed with JAX, so the main path's Pallas
kernel and engines are compiled here for a described v5e chip: what the
compiler would refuse on the chip (unsupported dtypes, scalar stores to
VMEM, block shapes off the (8, 128) tiling) fails here at no chip time.
Nothing runs; these say nothing about results or speed.

The topology is described inside a module fixture (only one process may
load the TPU library, so never at import), and the persistent compile
cache is off meanwhile: a described-chip entry cannot be read back.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import HERMES, PAPER_TESTBED
from repro.kernels.hermes_select.kernel import hermes_select_batch


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Engines built while JAX reports a TPU, so the kernel compiles
    instead of running in the interpreter; caches cleared around."""
    from repro.core import simulator as sim
    from repro.policy.registry import _factory_cache_clear
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _factory_cache_clear()
    sim.clear_engine_cache()
    yield sim
    _factory_cache_clear()
    sim.clear_engine_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("W,N", [(8, 1), (8, 256), (1000, 1)])
def test_hermes_select_batch_compiles_for_v5e(one_chip, W, N):
    cl = PAPER_TESTBED
    hlo = _compile_text(
        lambda a, w: hermes_select_batch(a, w, cores=cl.cores,
                                         slots=cl.slots),
        _sds((W,), jnp.int32, one_chip), _sds((N, W), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


def test_hermes_select_vmapped_scan_call_compiles_for_v5e(one_chip):
    """The engine's call: N=1 per arrival, vmapped over R=32 reps."""
    cl, R = PAPER_TESTBED, 32

    def select(active, warm_col):
        out, _ = hermes_select_batch(active, warm_col[None, :],
                                     cores=cl.cores, slots=cl.slots)
        return out[0]

    hlo = _compile_text(jax.vmap(select),
                        _sds((R, cl.n_workers), jnp.int32, one_chip),
                        _sds((R, cl.n_workers), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


def test_hermes_testbed_engine_compiles_for_v5e(one_chip, compiled_kernels):
    R, N, F = 32, 20_000, 40
    engine = compiled_kernels.build_batch_simulator(
        HERMES, PAPER_TESTBED, n_arrivals=N, n_functions=F,
        backend="pallas")
    f64, i32 = jnp.float64, jnp.int32
    hlo = engine.lower(
        _sds((R, N), f64, one_chip), _sds((R, N), i32, one_chip),
        _sds((R, N), f64, one_chip), _sds((R, N), f64, one_chip),
        _sds((R, F), i32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_hermes_stream_chunk_engine_compiles_for_v5e(one_chip,
                                                     compiled_kernels):
    from benchmarks.fig14_stream import HORIZON_CHUNK, HORIZON_CLUSTER
    from repro.telemetry import TelemetryCfg
    k, F, R = HORIZON_CHUNK, 40, 1
    (init, step_fn, _), _ = compiled_kernels._get_stream_engine(
        HERMES, HORIZON_CLUSTER, k, F, "pallas", TelemetryCfg())
    carry = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: init(R, 0)))
    f64, i32 = jnp.float64, jnp.int32
    hlo = step_fn.lower(
        carry, _sds((k,), jnp.int64, one_chip),
        _sds((k,), jnp.bool_, one_chip), _sds((R, k), f64, one_chip),
        _sds((R, k), i32, one_chip), _sds((R, k), f64, one_chip),
        _sds((R, k), f64, one_chip), _sds((R, F), i32, one_chip)
    ).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_counted_testbed_engine_compiles_for_v5e(one_chip, compiled_kernels):
    """The sweep cell's engine as a traced run builds it: the drain's
    iterations leave as a per-step output, and the Hermes launch sits in
    the ``select`` scope of the compiled program's op-to-scope map."""
    from repro.telemetry.spans import op_scopes
    R, N, F = 32, 20_000, 40
    engine, _ = compiled_kernels._get_engine(
        HERMES, PAPER_TESTBED, N, F, True, "pallas", None, count_drain=True)
    f64, i32 = jnp.float64, jnp.int32
    shapes = (_sds((R, N), f64, one_chip), _sds((R, N), i32, one_chip),
              _sds((R, N), f64, one_chip), _sds((R, N), f64, one_chip),
              _sds((R, F), i32, one_chip))
    _, iters = jax.eval_shape(engine, *shapes)
    assert (iters.shape, iters.dtype) == ((R, N), jnp.int32)
    hlo = engine.lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in hlo
    scopes = op_scopes(hlo, compiled_kernels.STEP_SCOPES)
    kernel = {s for op, s in scopes.items() if op.startswith("hermes_select")}
    assert kernel == {"select"}
    assert {"advance", "place"} <= set(scopes.values())


def test_counted_stream_chunk_engine_compiles_for_v5e(one_chip,
                                                      compiled_kernels):
    """The stream cell's chunk engine as a traced run builds it (W=100 x
    12 cores, E/LL/PS, telemetry on, chunk 4,096, R=8): the drain's
    iterations are a fourth per-step output."""
    from repro.core import E_LL_PS, ClusterCfg
    from repro.telemetry import TelemetryCfg
    from repro.telemetry.spans import op_scopes
    cl = ClusterCfg(n_workers=100, cores=12, capacity_factor=8)
    k, F, R = 4096, 40, 8
    (init, step_fn, _), _ = compiled_kernels._get_stream_engine(
        E_LL_PS, cl, k, F, "jax", TelemetryCfg(), count_drain=True)
    carry = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: init(R, 0)))
    f64, i32 = jnp.float64, jnp.int32
    shapes = (carry, _sds((k,), jnp.int64, one_chip),
              _sds((k,), jnp.bool_, one_chip), _sds((R, k), f64, one_chip),
              _sds((R, k), i32, one_chip), _sds((R, k), f64, one_chip),
              _sds((R, k), f64, one_chip), _sds((R, F), i32, one_chip))
    _, ys = jax.eval_shape(step_fn, *shapes)
    assert len(ys) == 4 and (ys[3].shape, ys[3].dtype) == ((R, k), jnp.int32)
    hlo = step_fn.lower(*shapes).compile().as_text()
    scopes = op_scopes(hlo, compiled_kernels.STEP_SCOPES)
    assert {"advance", "select", "place", "tel"} <= set(scopes.values())
