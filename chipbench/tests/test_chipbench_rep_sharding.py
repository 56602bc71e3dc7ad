"""The four-chip cell: its two readers of the ``rep`` sharding layer, fed
a synthetic tracer, and its timed path on four virtual CPU devices,
where the sharded horizon equals the plain reference on one replication
of each chip's block."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from chipbench import harness, program_trace
from chipbench.tests.chipbench_fixtures import REPO

CELL = "scale-ll.diurnal-stream-4chip"
DEVICE = "/device:TPU:0"


def _span(i, name, parent, dur_ms):
    return {"name": name, "ph": "X", "id": i, "parent": parent, "ts": 0.0,
            "dur": dur_ms * 1e3, "pid": 1, "tid": 0, "args": {}}


def _count(parent, executed, executed_max):
    return {"name": "drain.iterations", "ph": "C", "parent": parent,
            "ts": 0.0, "pid": 1, "tid": 0,
            "args": {"needed": 10, "executed": executed,
                     "executed_max": executed_max, "steps": 20}}


#: the warm-up horizon (under ``setup.compile``, left out), then two
#: window horizons, each with its placements: the homes, the carry and
#: one chunk's inputs
EVENTS = [
    _span(0, "setup.compile", None, 900.0),
    _span(1, "stream.shard", 0, 99.0),
    _count(0, 10, 99),
    _span(10, "stream.simulate_stream", None, 5000.0),
    _span(11, "stream.shard", 10, 1.0),
    _span(12, "stream.init", 10, 12.0),
    _span(13, "stream.shard", 12, 5.0),
    _span(14, "stream.feed", 10, 3.0),
    _span(15, "stream.shard", 14, 2.0),
    _count(10, 40.0, 44),
    _span(20, "stream.simulate_stream", None, 5000.0),
    _span(21, "stream.shard", 20, 2.0),
    _count(20, 60.0, 62),
]
EXPECTED = {"drain_chip_spread": (44 + 62) / (40 + 60),
            "shard_put_ms": (1.0 + 5.0 + 2.0 + 2.0) / 4}


def _read(name, run):
    return harness.load_module(REPO, "metrics", name).read(run)


def _run(devices=True):
    dev = {DEVICE: {"steps": 10, "step_busy_ns": 3_000_000,
                    "busy_ns": 1000.0, "idle": 0.01}} if devices else {}
    return SimpleNamespace(slices={"steady": {"devices": dev}})


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_the_expected_value(name, monkeypatch):
    tr = SimpleNamespace(events=EVENTS)
    monkeypatch.setattr(program_trace, "tracer", lambda: tr)
    assert _read(name, _run()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("case", ["no device", "no mesh in the program"])
def test_reader_reads_nothing_where_nothing_was_recorded(name, case,
                                                          monkeypatch):
    events = EVENTS
    if case != "no device":
        # a program with neither the span nor the per-chip count
        events = [dict(e, args={k: v for k, v in e["args"].items()
                                if k != "executed_max"})
                  for e in EVENTS if e["name"] != "stream.shard"]
    tr = SimpleNamespace(events=events)
    monkeypatch.setattr(program_trace, "tracer", lambda: tr)
    assert _read(name, _run(devices=case != "no device")) is None


def test_the_four_chip_cell_lists_both_readers_and_no_other_cell_does():
    mine = {m["name"] for m in harness.load_cell(REPO, CELL).per_layer}
    assert set(EXPECTED) <= mine
    for other in ("testbed-hermes.diurnal-sweep", "scale-ll.diurnal-stream"):
        cell = harness.load_cell(REPO, other)
        assert not set(EXPECTED) & {m["name"] for m in cell.per_layer}
    assert harness.load_cell(REPO, CELL).chips == 4


def test_the_four_chip_configuration_is_the_one_chip_deployment():
    """Each chip runs the one-chip stream cell's deployment, policy and
    engine; the layout adds the mesh and nothing the program reads."""
    four = harness.load_cell(REPO, CELL)
    one = harness.load_cell(REPO, "scale-ll.diurnal-stream")
    for key in ("deployment", "policy", "engine", "precision", "reduced"):
        assert four.config[key] == one.config[key], key
    assert set(one.config["guarantees"]) < set(four.config["guarantees"])
    assert four.limits == one.limits
    lay = four.config["layout"]
    assert lay["chips"] == four.chips
    reps = len(four.mix["loads"]) * four.mix["seeds_per_load"]
    assert reps == lay["replications_per_chip"] * four.chips
    assert reps // four.chips == \
        len(one.mix["loads"]) * one.mix["seeds_per_load"]
    assert {k: v for k, v in four.mix.items() if k != "seeds_per_load"} \
        == {k: v for k, v in one.mix.items() if k != "seeds_per_load"}


FOUR_DEVICES = """
import json, sys
sys.path[:0] = [{repo!r}]
from chipbench import devtrace, harness
cell = harness.load_cell({repo!r}, {cell!r})
cfg = dict(cell.config,
           deployment=dict(cell.config["deployment"], n_workers=8),
           engine=dict(cell.config["engine"], chunk=128))
mix = dict(cell.mix, n_arrivals=512, seeds_per_load=8, pool=1)
seed = 2147483923
engine = harness.load_module({repo!r}, "engines", "stream").Engine(
    cfg, mix, seed, cell.chips, devtrace.Spans())
engine.setup()
engine.unit(0)
pairs = harness.sample(seed, 1, engine.n_reps(), harness.check_reps(cell))
worst = harness.readings(engine, pairs, harness.references(engine, pairs))
print("RESULT " + json.dumps({{"devices": engine.mesh.devices.size,
                               "pairs": pairs, "worst": worst,
                               "limits": cell.limits}}))
"""


def test_sharded_horizon_equals_the_reference_on_every_chips_block():
    """W=8, R=8 (two replications per device), 512 arrivals in 4 chunks
    of the cell's program on a 4-device ``rep`` mesh: one sampled
    replication from each device's block matches the reference in every
    counter and bin, and in every time integral and mean within the
    cell's limit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES.format(repo=REPO, cell=CELL)],
        env=env, capture_output=True, text=True, timeout=600)
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, p.stderr[-3000:]
    out = json.loads(line[0][len("RESULT "):])
    assert out["devices"] == 4
    assert [r // 2 for _, r in out["pairs"]] == [0, 1, 2, 3]
    assert out["worst"]["counter_mismatch"] == 0
    assert out["worst"]["float_rel_err"] <= out["limits"]["float_rel_err"]
