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
import re

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


def _computations(hlo: str) -> dict[str, list[str]]:
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None and line.strip():
            comps[name].append(line.strip())
    return comps


#: Bookkeeping that moves no data on the chip, left out of a body's count.
_BOOKKEEPING = {"parameter", "get-tuple-element", "tuple", "constant"}


def _drain_body(hlo: str) -> list[str]:
    """Top-level instructions of the drain loop's body inside the scan step
    (the ``while`` whose op_name ends in ``advance/while``), bookkeeping
    left out."""
    comps = _computations(hlo)
    bodies = [re.search(r"body=%?([\w.\-]+)", line).group(1)
              for lines in comps.values() for line in lines
              if " while(" in line
              and re.search(r'op_name="[^"]*advance/while"', line)]
    assert len(bodies) == 1, bodies
    return [line for line in comps[bodies[0]]
            if re.search(r" = .*? ([a-z][\w\-]*)\(", line).group(1)
            not in _BOOKKEEPING]


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


def _counted_sweep(sim, sharding):
    """The sweep cell's engine as a traced run builds it (the paper
    testbed, E/H/PS on the kernel, R=32 x 20,000 arrivals, F=40), and its
    argument shapes."""
    R, N, F = 32, 20_000, 40
    engine, _ = sim._get_engine(
        HERMES, PAPER_TESTBED, N, F, True, "pallas", None, count_drain=True)
    f64, i32 = jnp.float64, jnp.int32
    return engine, (_sds((R, N), f64, sharding), _sds((R, N), i32, sharding),
                    _sds((R, N), f64, sharding), _sds((R, N), f64, sharding),
                    _sds((R, F), i32, sharding))


def _counted_stream(sim, sharding):
    """The stream cell's chunk engine as a traced run builds it (W=100 x
    12 cores, E/LL/PS, telemetry on, chunk 4,096, R=8, F=40), and its
    argument shapes."""
    from repro.core import E_LL_PS, ClusterCfg
    from repro.telemetry import TelemetryCfg
    cl = ClusterCfg(n_workers=100, cores=12, capacity_factor=8)
    k, F, R = 4096, 40, 8
    (init, step_fn, _), _ = sim._get_stream_engine(
        E_LL_PS, cl, k, F, "jax", TelemetryCfg(), count_drain=True)
    carry = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sharding),
        jax.eval_shape(lambda: init(R, 0)))
    f64, i32 = jnp.float64, jnp.int32
    return step_fn, (carry, _sds((k,), jnp.int64, sharding),
                     _sds((k,), jnp.bool_, sharding),
                     _sds((R, k), f64, sharding), _sds((R, k), i32, sharding),
                     _sds((R, k), f64, sharding), _sds((R, k), f64, sharding),
                     _sds((R, F), i32, sharding))


def test_counted_testbed_engine_compiles_for_v5e(one_chip, compiled_kernels):
    """The sweep cell's engine as a traced run builds it: the drain's
    iterations leave as a per-step output, and the Hermes launch sits in
    the ``select`` scope of the compiled program's op-to-scope map."""
    from repro.telemetry.spans import op_scopes
    R, N = 32, 20_000
    engine, shapes = _counted_sweep(compiled_kernels, one_chip)
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
    from repro.telemetry.spans import op_scopes
    R, k = 8, 4096
    step_fn, shapes = _counted_stream(compiled_kernels, one_chip)
    _, ys = jax.eval_shape(step_fn, *shapes)
    assert len(ys) == 4 and (ys[3].shape, ys[3].dtype) == ((R, k), jnp.int32)
    hlo = step_fn.lower(*shapes).compile().as_text()
    scopes = op_scopes(hlo, compiled_kernels.STEP_SCOPES)
    assert {"advance", "select", "place", "tel"} <= set(scopes.values())


def test_drain_loop_has_no_emulated_64bit_division(one_chip,
                                                   compiled_kernels):
    """The drain's argmin slot index is split into worker and slot in
    int32: the chip has no 64-bit integer unit, so an int64 ``// S`` and
    ``% S`` compile to a long dependent chain in every drain iteration."""
    from repro.core import E_LL_PS
    R, N, F = 4, 64, 8
    engine = compiled_kernels.build_batch_simulator(
        E_LL_PS, PAPER_TESTBED, n_arrivals=N, n_functions=F, backend="jax")
    f64, i32 = jnp.float64, jnp.int32
    hlo = engine.lower(
        _sds((R, N), f64, one_chip), _sds((R, N), i32, one_chip),
        _sds((R, N), f64, one_chip), _sds((R, N), f64, one_chip),
        _sds((R, F), i32, one_chip)).compile().as_text()
    body = _drain_body(hlo)
    assert not [line for line in body if "floor_divide" in line]
    # 39 with the slot retired by one-hot selects, 49 with its scatters;
    # the int64 index compiled to 77, 27 of them the division
    assert len(body) <= 39 + 4, len(body)


@pytest.mark.parametrize("build,planes", [
    (_counted_sweep, {(32, 8, 96), (32, 8, 41)}),
    (_counted_stream, {(8, 100, 96), (8, 100, 41)})],
    ids=["sweep", "stream"])
def test_drain_retires_without_slot_scatters(one_chip, compiled_kernels,
                                             build, planes):
    """The drain retires the completing slot of ``remaining``,
    ``task_idx`` and ``warm`` (``[R, W, S]`` and ``[R, W, F+1]``) by
    one-hot selects: under ``vmap`` an indexed write compiles on the chip
    to a scatter serial over the replications, among the busiest
    operations of a drain iteration.  ``resp``'s ``[R, N+1]`` scatter and
    ``tel``'s histogram scatter-adds stay."""
    fn, shapes = build(compiled_kernels, one_chip)
    body = _drain_body(fn.lower(*shapes).compile().as_text())
    writes = [line for line in body if re.search(
        r'op_name="[^"]*advance/while/body/[^"]*scatter(-add)?"', line)]
    assert writes

    def written(line):
        out = re.search(r" = (.*?) [a-z][\w\-]*\(", line).group(1)
        return {tuple(map(int, dims.split(",")))
                for dims in re.findall(r"\[([\d,]+)\]", out)}
    plane_writes = [line for line in writes if written(line) & planes]
    assert not plane_writes, plane_writes
    assert not [line for line in body if "dynamic-update-slice" in line]


@pytest.mark.parametrize("build", [_counted_sweep, _counted_stream],
                         ids=["sweep", "stream"])
def test_placement_compiles_to_no_loop(one_chip, compiled_kernels, build):
    """``place`` reads the chosen worker's warm-pool row without a loop:
    a row indexed by a per-replication worker compiles on the chip to a
    serial ``while`` of one trip per replication (op_name
    ``place/gather``), the busiest operation of the sweep's step."""
    fn, shapes = build(compiled_kernels, one_chip)
    hlo = fn.lower(*shapes).compile().as_text()
    loops = [line for lines in _computations(hlo).values() for line in lines
             if " while(" in line
             and re.search(r'op_name="[^"]*\bplace/', line)]
    assert not loops, loops
