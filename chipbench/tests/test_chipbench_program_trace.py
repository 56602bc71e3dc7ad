"""The per-layer metrics that read the program's own spans, counters and
named scopes, fed synthetic runs and a synthetic tracer."""
from types import SimpleNamespace

import pytest

from chipbench import harness, program_trace
from chipbench.tests.chipbench_fixtures import REPO

DEVICE = "/device:TPU:0"


def _span(i, name, parent, dur_ms, ts=0.0):
    return {"name": name, "ph": "X", "id": i, "parent": parent, "ts": ts,
            "dur": dur_ms * 1e3, "pid": 1, "tid": 0, "args": {}}


def _count(parent, needed, executed, steps):
    return {"name": "drain.iterations", "ph": "C", "parent": parent,
            "ts": 0.0, "pid": 1, "tid": 0,
            "args": {"needed": needed, "executed": executed,
                     "steps": steps}}


#: set-up (under ``setup.compile``, left out), then two window units of
#: the stream and a sweep's summary
EVENTS = [
    _span(0, "setup.compile", None, 900.0),
    _span(1, "engine.run", 0, 800.0),
    _span(2, "stream.feed", 1, 99.0),
    _span(3, "engine.readout", 0, 99.0),
    _span(4, "stream.init", 0, 99.0),
    _span(5, "metrics.summarize_batch", 0, 99.0),
    _count(0, 1000, 1000, 10),
    _span(10, "stream.simulate_stream", None, 5000.0),
    _span(11, "stream.init", 10, 12.0),
    _span(12, "engine.run", 10, 4900.0),
    _span(13, "stream.feed", 12, 2.0),
    _span(14, "stream.feed", 12, 4.0),
    _span(15, "engine.readout", 10, 3.0),
    _count(10, 30, 60, 20),
    _span(20, "stream.simulate_stream", None, 5000.0),
    _span(21, "stream.init", 20, 14.0),
    _span(22, "engine.run", 20, 4900.0),
    _span(23, "stream.feed", 22, 3.0),
    _span(25, "engine.readout", 20, 5.0),
    _count(20, 50, 60, 20),
    _span(27, "sweep.summarize", None, 97.0),
    _span(26, "metrics.summarize_batch", 27, 96.0),
]
#: the scan program's map and a smaller program's; the steady slice's
#: self times (ns): leaves of the scan program 1,000 in all, a loop and
#: an operation of no mapped program
SCOPES = {
    "chunk": {"fusion.1": "advance", "fusion.2": "tel",
              "hermes_select.3": "select", "fusion.4": "place",
              "copy.5": ""},
    "drain": {"fusion.2": "advance", "fusion.8": "tel"},
}
SELF_NS = {"fusion.1": 500.0, "fusion.2": 200.0, "hermes_select.3": 100.0,
           "fusion.4": 100.0, "copy.5": 100.0, "while.9": 300.0,
           "other.7": 50.0}
#: steps of 300 us each
EXPECTED = {"drain_us": 150.0, "select_us": 30.0, "place_us": 30.0,
            "tel_us": 60.0, "drain_iters_per_step": 120 / 40,
            "drain_useful_share": 80 / 120, "readout_ms": 4.0,
            "summarize_ms": 96.0, "carry_init_ms": 13.0,
            "chunk_feed_ms": 3.0}


def _run(devices=True):
    dev = {DEVICE: {"steps": 10, "step_busy_ns": 3_000_000,
                    "busy_ns": 1000.0, "idle": 0.01}} if devices else {}
    steady = {"devices": dev, "self_ns": SELF_NS if devices else {},
              "count": {}}
    return SimpleNamespace(slices={"boundary": {"devices": dev},
                                   "steady": steady}, units=[(1, 1.0)] * 2)


def _read(name, run):
    return harness.load_module(REPO, "metrics", name).read(run)


@pytest.fixture
def program(monkeypatch):
    """The synthetic tracer as the program's process-wide one."""
    tr = SimpleNamespace(events=EVENTS, scopes=SCOPES)
    monkeypatch.setattr(program_trace, "tracer", lambda: tr)
    return tr


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_the_expected_value(name, program):
    assert _read(name, _run()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("case", ["no device", "older program"])
def test_reader_reads_nothing_where_nothing_was_recorded(name, case,
                                                          monkeypatch):
    run = _run(devices=case != "no device")
    tr = {"no device": SimpleNamespace(events=EVENTS, scopes=SCOPES),
          # a tracer with neither the new spans, the counter nor scopes
          "older program": SimpleNamespace(events=[
              {"name": "engine.run", "ph": "X", "ts": 0.0, "dur": 5.0}])
          }[case]
    monkeypatch.setattr(program_trace, "tracer", lambda: tr)
    assert _read(name, run) is None


def test_scope_reader_reads_nothing_for_a_scope_with_no_operation(
        monkeypatch):
    tr = SimpleNamespace(events=[], scopes={"sweep": {
        k: v for k, v in SCOPES["chunk"].items() if v != "tel"}})
    monkeypatch.setattr(program_trace, "tracer", lambda: tr)
    run = _run()
    assert _read("tel_us", run) is None
    assert _read("drain_us", run) == pytest.approx(300.0 * 500 / 800)


def test_scoped_parts_and_the_unscoped_rest_make_up_the_step(program,
                                                             capsys):
    run = _run()
    parts = program_trace.step_scopes(run)
    step = harness.load_module(REPO, "metrics", "step_device_us").read(run)
    assert sum(parts.values()) == pytest.approx(step)
    assert parts[""] == pytest.approx(30.0)
    err = capsys.readouterr().err
    assert "'chunk'" in err and "unscoped share 0.1" in err
    program_trace.step_scopes(run)              # printed once per run
    assert capsys.readouterr().err == ""


def test_every_new_reader_is_listed_for_the_cells_it_reads():
    cells = {w: {m["name"] for m in harness.load_cell(REPO, w).per_layer}
             for w in ("testbed-hermes.diurnal-sweep",
                       "scale-ll.diurnal-stream")}
    both = set(EXPECTED) - {"tel_us", "summarize_ms", "carry_init_ms",
                            "chunk_feed_ms"}
    assert both | {"summarize_ms"} <= cells["testbed-hermes.diurnal-sweep"]
    assert both | {"tel_us", "carry_init_ms", "chunk_feed_ms"} \
        <= cells["scale-ll.diurnal-stream"]
    assert not {"tel_us", "carry_init_ms", "chunk_feed_ms"} \
        & cells["testbed-hermes.diurnal-sweep"]
