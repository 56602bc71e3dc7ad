"""Spans, counters and named scopes inside the simulator.

With the process-wide tracer on, ``simulate_many`` and
``simulate_stream`` record their host work as nested spans, count the
completion drain's loop iterations per scan step, and keep each compiled
scan program's map from HLO instruction to named scope.  With it off
they record nothing and run the uncounted program.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (E_LL_PS, HERMES, ClusterCfg, stack_workloads,
                        summarize_batch, synth_workload)
from repro.core import simulator as sim
from repro.core.sim_ref import simulate_ref
from repro.core.simulator import SimState, simulate_many
from repro.core.streaming import simulate_stream
from repro.telemetry import TelemetryCfg
from repro.telemetry.spans import Tracer, get_tracer, op_scopes, set_tracer

CLUSTER = ClusterCfg(n_workers=4, cores=3, capacity_factor=2)
N = 200


def _batch(loads=(0.4, 0.9, 1.3)):
    return stack_workloads([synth_workload(CLUSTER, load, N, n_functions=5,
                                           hot_fraction=0.8, seed=seed)
                            for seed, load in enumerate(loads)])


@pytest.fixture
def tracer():
    """A fresh enabled process-wide tracer; the disabled one restored."""
    tr = Tracer(enabled=True)
    old = set_tracer(tr)
    yield tr
    set_tracer(old)


def _spans(tr):
    by_id = {e["id"]: e for e in tr.events if e["ph"] == "X"}
    return [(e["name"], by_id[e["parent"]]["name"]
             if e["parent"] is not None else None)
            for e in sorted(by_id.values(), key=lambda e: e["ts"])]


def _drain_counts(tr):
    return [e["args"] for e in tr.events
            if e["ph"] == "C" and e["name"] == "drain.iterations"]


def _run(entry, wb):
    if entry == "simulate_many":
        out = simulate_many(E_LL_PS, CLUSTER, wb, backend="jax")
        summarize_batch(out.response, wb.service, out.cold, out.rejected,
                        out.server_time, out.core_time, out.end_time)
        return out
    return simulate_stream(E_LL_PS, CLUSTER, wb, chunk_size=64,
                           backend="jax", telemetry=TelemetryCfg())


@pytest.mark.parametrize("entry,expected", [
    ("simulate_many", [("unit", None), ("engine.put", "unit"),
                       ("engine.run", "unit"), ("engine.readout", "unit"),
                       ("engine.count", "unit"),
                       ("metrics.summarize_batch", "unit")]),
    ("simulate_stream", [("unit", None), ("stream.init", "unit"),
                         ("engine.run", "unit")]
     + [("stream.feed", "engine.run")] * 4
     + [("stream.drain", "engine.run"), ("engine.readout", "unit"),
        ("engine.count", "unit")]),
])
def test_traced_entry_points_nest_their_spans(entry, expected, tracer):
    wb = _batch()
    _run(entry, wb)                       # builds and compiles the engine
    tracer.clear()
    with tracer.span("unit"):
        _run(entry, wb)
    assert _spans(tracer) == expected
    (count,) = _drain_counts(tracer)
    assert count["steps"] == N
    assert 0 < count["needed"] <= count["executed"]
    assert len(tracer.scopes) == 1
    (scopes,) = tracer.scopes.values()
    assert {"advance", "select", "place"} <= set(scopes.values())
    assert ("tel" in scopes.values()) == (entry == "simulate_stream")


@pytest.mark.parametrize("entry", ["simulate_many", "simulate_stream"])
def test_untraced_entry_points_record_nothing(entry):
    tr = get_tracer()
    assert not tr.enabled
    _run(entry, _batch())
    assert tr.events == [] and tr.scopes == {}


def test_untraced_engine_is_the_uncounted_program(tracer):
    wb = _batch()
    S = jax.ShapeDtypeStruct
    shapes = [S(wb.arrival.shape, jnp.float64), S(wb.func.shape, jnp.int64),
              S(wb.service.shape, jnp.float64),
              S(wb.u_lb.shape, jnp.float64),
              S(wb.func_home.shape, jnp.int64)]

    def untraced():
        sim.clear_engine_cache()
        run, _ = sim._get_engine(E_LL_PS, CLUSTER, N, wb.n_functions, True,
                                 "jax", None)
        return run, run.lower(*shapes).as_text()   # no debug metadata

    set_tracer(Tracer(enabled=False))
    run, before = untraced()
    out = run(*(jnp.asarray(a) for a in (wb.arrival, wb.func, wb.service,
                                         wb.u_lb, wb.func_home)))
    assert isinstance(out, SimState)
    set_tracer(tracer)
    traced = simulate_many(E_LL_PS, CLUSTER, wb, backend="jax")
    set_tracer(Tracer(enabled=False))
    _, after = untraced()
    assert before == after
    plain = simulate_many(E_LL_PS, CLUSTER, wb, backend="jax")
    for name in ("response", "cold", "rejected", "worker", "end_time"):
        np.testing.assert_array_equal(getattr(traced, name),
                                      getattr(plain, name))


def test_engine_cache_keeps_counted_engines_apart():
    sim.clear_engine_cache()
    args = (E_LL_PS, CLUSTER, N, 5, True, "jax", None)
    plain, fresh = sim._get_engine(*args)
    counted, fresh_counted = sim._get_engine(*args, count_drain=True)
    assert fresh and fresh_counted and counted is not plain
    assert sim._get_engine(*args)[0] is plain
    assert sim._get_engine(*args, count_drain=True)[0] is counted
    tel = TelemetryCfg()
    (_, step, _), _ = sim._get_stream_engine(E_LL_PS, CLUSTER, 64, 5, "jax",
                                             tel)
    (_, step_c, _), _ = sim._get_stream_engine(E_LL_PS, CLUSTER, 64, 5,
                                               "jax", tel, count_drain=True)
    assert step_c is not step
    assert sim.engine_cache_stats()["entries"] == 4


def _iterations(wb, batched):
    run, _ = sim._get_engine(HERMES, CLUSTER, N, wb.n_functions, batched,
                             "jax", None, count_drain=True)
    args = [wb.arrival, wb.func, wb.service, wb.u_lb, wb.func_home]
    if batched:
        return np.asarray(run(*map(jnp.asarray, args))[1])
    return np.stack([np.asarray(run(*(jnp.asarray(a[r]) for a in args))[1])
                     for r in range(wb.n_reps)])


def test_drain_counter_needed_and_executed(tracer):
    wb = _batch()
    batched = _iterations(wb, True)
    alone = _iterations(wb, False)
    np.testing.assert_array_equal(batched, alone)
    simulate_many(HERMES, CLUSTER, wb, backend="jax")
    (count,) = _drain_counts(tracer)
    assert count["needed"] == pytest.approx(alone.mean(axis=0).sum())
    assert count["executed"] == alone.max(axis=0).sum()
    # mixed loads: the busiest replication sets the executed count
    assert count["executed"] > count["needed"]


@pytest.mark.parametrize("load,seed", [(0.5, 3), (1.2, 4)])
def test_drain_iterations_are_the_oracles_completions(load, seed, tracer):
    """Each iteration retires one completion, except the last of a step
    that advances to an arrival finding a task still running."""
    wl = synth_workload(CLUSTER, load, N, n_functions=5, hot_fraction=0.8,
                        seed=seed)
    ref = simulate_ref(HERMES, CLUSTER, wl)
    placed = ~ref.rejected & np.isfinite(ref.response)
    done = np.where(placed, wl.arrival + ref.response, np.inf)
    t = wl.arrival
    # no completion falls on an arrival time: the rule above is exact
    gap = np.abs(done[placed][:, None] - t[None, :])
    assert gap.min() > 1e-6
    retired = int((done <= t[-1]).sum())
    running = sum(int(t[i] > t[i - 1] and (done[:i] > t[i]).any())
                  for i in range(1, N))
    simulate_many(HERMES, CLUSTER, [wl], backend="jax")
    (count,) = _drain_counts(tracer)
    assert count["needed"] == count["executed"] == retired + running


def test_op_scopes_of_a_compiled_function():
    def f(x):
        with jax.named_scope("advance"):
            y = jax.lax.while_loop(lambda c: c.sum() < 100.0,
                                   lambda c: c * 2.0 + 1.0, x)
            with jax.named_scope("tel"):
                z = jnp.sin(y) * 3.0
        with jax.named_scope("select"):
            i = jnp.argmax(z)
        return i, jnp.cos(x).sum()

    text = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
    scopes = op_scopes(text, sim.STEP_SCOPES)
    assert {"advance", "tel", "select", ""} <= set(scopes.values())
    loops = {line.split(" = ")[0].split()[-1].lstrip("%")
             for line in text.splitlines() if " while(" in line}
    assert loops and not loops & set(scopes)
    assert op_scopes(text, ()) == dict.fromkeys(scopes, "")


#: the stream traced without a mesh and on a 4-device ``"rep"`` mesh (W=8,
#: R=8 at mixed loads, so the devices' blocks drain unevenly, 512
#: arrivals in 4 chunks): each run's counter sample and span tree, and
#: the per-replication iterations of the run without a mesh
MESH_RUNS = """
import json
import numpy as np
from repro.core import E_LL_PS, ClusterCfg, stack_workloads, synth_workload
from repro.core import streaming
from repro.launch.mesh import make_rep_mesh
from repro.telemetry import TelemetryCfg
from repro.telemetry.spans import Tracer, set_tracer

cl = ClusterCfg(n_workers=8, cores=3, capacity_factor=2)
wb = stack_workloads([synth_workload(cl, load, 512, n_functions=5,
                                     hot_fraction=0.8, seed=seed)
                      for seed, load in enumerate((0.4, 0.9, 1.3, 0.7) * 2)])
kept = []
count_drain = streaming._count_drain
def keep(tr, iters, *args):
    kept.append(np.concatenate([np.asarray(i) for i in iters], axis=1))
    count_drain(tr, iters, *args)
streaming._count_drain = keep
out = {}
for name, mesh in (("one", None), ("mesh", make_rep_mesh(4))):
    def run():
        streaming.simulate_stream(E_LL_PS, cl, wb, chunk_size=128,
                                  backend="jax", telemetry=TelemetryCfg(),
                                  mesh=mesh)
    set_tracer(Tracer(enabled=True))
    run()                                   # builds and compiles
    tr = Tracer(enabled=True)
    set_tracer(tr)
    run()
    by_id = {e["id"]: e["name"] for e in tr.events if e["ph"] == "X"}
    out[name] = {
        "counts": [e["args"] for e in tr.events
                   if e["ph"] == "C" and e["name"] == "drain.iterations"],
        "spans": [[e["name"], by_id.get(e["parent"])] for e in tr.events
                  if e["ph"] == "X"]}
out["iters"] = kept[1].tolist()          # the timed run without a mesh
out["same"] = bool((kept[1] == kept[3]).all())
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_runs(devices_script):
    out = devices_script(MESH_RUNS, n_devices=4)
    line = [x for x in out.splitlines() if x.startswith("RESULT ")]
    res = json.loads(line[-1][len("RESULT "):])
    res["iters"] = np.asarray(res["iters"])
    return res


def test_mesh_drain_counter_is_per_device(mesh_runs):
    """On 4 devices each one's loop runs to the max of its own block of
    R/4 replications: ``executed`` is the mean over the blocks of each
    block's per-step max summed, ``executed_max`` the largest block's."""
    iters = mesh_runs["iters"]
    R, steps = iters.shape
    assert (R, steps) == (8, 512) and mesh_runs["same"]
    per_block = iters.reshape(4, R // 4, steps).max(axis=1).sum(axis=1)
    (count,) = mesh_runs["mesh"]["counts"]
    assert count["steps"] == steps
    assert count["needed"] == pytest.approx(iters.mean(axis=0).sum())
    assert count["executed"] == per_block.mean()
    assert count["executed_max"] == per_block.max()
    # the blocks drain unevenly, and less than one loop over all of R
    assert count["executed"] < count["executed_max"]
    assert count["executed_max"] < iters.max(axis=0).sum()


def test_one_device_drain_counter_is_unchanged(mesh_runs):
    iters = mesh_runs["iters"]
    (count,) = mesh_runs["one"]["counts"]
    assert count["needed"] == pytest.approx(iters.mean(axis=0).sum())
    assert count["executed"] == iters.max(axis=0).sum()
    assert count["executed_max"] == count["executed"]


def test_shard_spans_only_under_a_mesh(mesh_runs):
    """Each placement on the mesh is a ``stream.shard`` span: the homes,
    the carry inside ``stream.init`` and each of the 4 chunks' inputs
    inside its ``stream.feed``; without a mesh there is none."""
    shard = [p for n, p in mesh_runs["mesh"]["spans"] if n == "stream.shard"]
    assert sorted(shard, key=str) == sorted(
        [None, "stream.init"] + ["stream.feed"] * 4, key=str)
    names = [n for n, _ in mesh_runs["one"]["spans"]]
    assert "stream.shard" not in names and "stream.feed" in names
