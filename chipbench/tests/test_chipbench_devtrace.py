"""The reduction from a profiler slice to device numbers, and the metric
readers over it, on a small slice recorded on a TPU v5e (cut from a
slice across a unit boundary of the testbed sweep: the end of one
unit's program, the host's work between units, the first steps of the
next) and on hand-made ones."""
import gzip
import json
import os
from types import SimpleNamespace as Run

import numpy as np
import pytest

from chipbench import devtrace
from chipbench.tests.chipbench_fixtures import REPO

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "slice_testbed_hermes.json.gz")


def _reader(name):
    from chipbench import harness
    return harness.load_module(REPO, "metrics", name).read


def _brute_busy(record) -> dict:
    """Busy nanoseconds per device: the leaves found by brute force over
    all pairs of operations (half-open intervals: an event of no length
    at another's end is not inside it), their cover counted segment by
    segment."""
    out = {}
    for dev, ops in record["devices"].items():
        leaves = [(s, s + d) for i, (_, s, d) in enumerate(ops)
                  if not any(j != i and s <= s2 < s + d and s2 + d2 <= s + d
                             and (s2, -d2) > (s, -d)
                             for j, (_, s2, d2) in enumerate(ops))]
        cuts = np.unique([x for iv in leaves for x in iv])
        cover = np.zeros(max(len(cuts) - 1, 0), bool)
        for s, e in leaves:
            cover[np.searchsorted(cuts, s):np.searchsorted(cuts, e)] = True
        out[dev] = int(np.diff(cuts)[cover].sum())
    return out


def test_union_merges_overlaps_and_nesting():
    assert devtrace.union([(5, 9), (0, 3), (2, 4), (6, 7), (9, 10)]) == \
        [(0, 4), (5, 10)]


#: a slice from inside one unit's program (opened before the slice,
#: two whole steps marked by ``cond``) across the unit boundary at 220 to
#: inside the next unit's (one whole step, one cut by the slice's end)
HAND = {"t0": 100, "t1": 300, "boundary_ns": 220,
        "programs": {"/device:TPU:0": [["jit_run", 100, 80],
                                       ["jit_run", 240, 60]]},
        "devices": {"/device:TPU:0": [
            ["while", 100, 80],
            ["cond", 105, 30], ["fusion.1", 110, 10],
            ["hermes_select.3", 125, 4],
            ["cond", 140, 30], ["fusion.1", 145, 10],
            ["hermes_select.3", 160, 4],
            ["while", 240, 60],
            ["cond", 250, 30], ["fusion.1", 255, 10],
            ["hermes_select.3", 270, 4],
            ["cond", 285, 15], ["fusion.1", 290, 5]]}}


def test_hand_made_slice():
    spans = [("unit", 50, 220), ("sweep.simulate_many", 60, 190),
             ("sweep.summarize", 190, 215), ("unit", 220, 400),
             ("sweep.simulate_many", 225, 400)]
    red = devtrace.reduce(HAND, spans)
    d = red["devices"]["/device:TPU:0"]
    # the loops' and conditionals' own time between operations is idle
    assert d["busy_ns"] == 10 + 4 + 10 + 4 + 10 + 4 + 5 and d["ops"] == 13
    assert d["program_ns"] == 80 + 60
    assert d["idle"] == pytest.approx(1 - 47 / 140)
    # the loops span the slice's edges: ``cond`` marks the steps, and of
    # them only the first program holds two starts, so one whole step
    assert d["marker"] == "cond"
    assert (d["steps"], d["step_busy_ns"]) == (1, 14)
    assert d["unit_gap_ns"] == 200 - 140
    assert red["self_ns"]["while"] == (80 - 30 - 30) + (60 - 30 - 15)
    # the longest idle gap falls in the host's work between the units
    assert red["gaps"][:3] == [("sweep.summarize", 91),
                               ("sweep.simulate_many", 16),
                               ("sweep.simulate_many", 16)]
    assert red["count"]["hermes_select.3"] == 3
    run = Run(slices={"steady": red, "boundary": red}, n_reps=32, chips=1,
              peaks={"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11},
              cell=type("C", (), {"config": {"deployment":
                                             {"n_workers": 8}}})())
    assert _reader("device_idle")(run) == pytest.approx(1 - 47 / 140)
    assert _reader("step_device_us")(run) == pytest.approx(14 / 1e3)
    assert _reader("unit_gap_ms")(run) == pytest.approx(60 / 1e6)
    assert _reader("hermes_select_us")(run) == pytest.approx(4e-3)
    least = 32 * (3 * 8 + 1) * 4 / 8.19e11
    assert _reader("hermes_select_roofline")(run) == pytest.approx(
        least / 4e-9 * 100)


@pytest.mark.parametrize("edit", ["no_boundary", "opens_between_units",
                                  "no_programs"])
def test_slice_that_misses_the_boundary_reads_no_gap(edit):
    rec = json.loads(json.dumps(HAND))
    dev = "/device:TPU:0"
    if edit == "no_boundary":
        rec["boundary_ns"] = None
    elif edit == "opens_between_units":
        # the first unit's program had ended when the slice opened
        rec["t0"] = 190
        rec["programs"][dev] = rec["programs"][dev][1:]
        rec["devices"][dev] = rec["devices"][dev][7:]
    else:
        del rec["programs"]
    red = devtrace.reduce(rec, [])
    d = red["devices"][dev]
    assert d["unit_gap_ns"] is None
    run = Run(slices={"steady": red, "boundary": red})
    assert _reader("unit_gap_ms")(run) is None
    if edit == "no_programs":
        assert d["idle"] is None and d["steps"] == 0
        assert _reader("device_idle")(run) is None
        assert _reader("step_device_us")(run) is None


def test_readers_find_nothing_where_there_is_nothing():
    run = Run(slices=None, feed_s=[],
              monitor=type("M", (), {"counts": {}, "seconds": {}})())
    for name in ("device_idle", "step_device_us", "unit_gap_ms",
                 "hermes_select_us",
                 "hermes_select_roofline", "host_feed_ms",
                 "backend_compile_s", "trace_lower_s"):
        assert _reader(name)(run) is None


def test_recorded_slice_reduces_like_a_brute_force_count():
    with gzip.open(RECORDED, "rt") as fh:
        rec = json.load(fh)
    red = devtrace.reduce(rec, [])
    brute = _brute_busy(rec)
    for dev, d in red["devices"].items():
        ops, progs = rec["devices"][dev], rec["programs"][dev]
        assert d["busy_ns"] == brute[dev]
        assert 0.0 < d["idle"] < 0.1
        # the host's work between the units: over a tenth of a second
        # in which the device ran no program
        in_prog = sum(e - s for s, e in devtrace.union(
            (s, s + n) for _, s, n in progs))
        assert d["unit_gap_ns"] == rec["t1"] - rec["t0"] - in_prog > 1e8
        # the marked steps hold one launch of the kernel each
        starts = sorted(s for n, s, _ in ops if n == d["marker"]
                        and rec["t0"] < s and s + _ < rec["t1"])
        first, last = starts[0], starts[-1]
        kernel = [s for n, s, _ in ops
                  if "hermes_select" in n and first <= s < last]
        assert d["steps"] >= 5 and len(kernel) == d["steps"]
    gaps = [g for _, g in red["gaps"]]
    assert gaps == sorted(gaps, reverse=True) and gaps[0] > 1e8
    kernel = [n for n in red["count"] if "hermes_select" in n]
    assert kernel
    # one launch per scan step, about 14 us each on the v5e
    us = sum(red["self_ns"][n] for n in kernel) / \
        sum(red["count"][n] for n in kernel) / 1e3
    assert 5 < us < 50
