"""BENCHMARK.json keeps to the benchmark's format, every cell finds its
files by name, and a new cell, configuration, traffic mix or per-layer
metric is only new files and new entries."""
import json
import os
import re
import shutil

import pytest

from chipbench import harness
from chipbench.tests.chipbench_fixtures import (REPO, cells, make_tiny_root,
                                                on_cpu, result_of)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def _bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_command_paths_and_run_length():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert 1 <= len(b["paths"]) <= 16
    for p in b["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    assert 1 <= len(b["command"]) <= 32
    for word in b["command"][1:]:
        assert _line(word) and not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in b["paths"])
    rs = b["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells has to fit into its time
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entry_keys():
    b = _bench()
    names = [c["name"] for c in b["configs"]]
    names += [w["name"] for w in b["workloads"]]
    names += [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    for n in names:
        assert NAME.match(n), n
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in b[kind]]
        assert len(got) == len(set(got)), kind
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"_(dim|rank)$|hidden|width|size", key)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_reports_set_up_another_metric_and_a_layer():
    b = _bench()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        cell = harness.load_cell(REPO, w["name"])
        mine = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in mine
    for m in b["per_layer"]:
        for name in m.get("workloads", []):
            assert name in {w["name"] for w in b["workloads"]}


@pytest.mark.parametrize("cell", cells())
def test_cell_finds_its_files_by_name(cell):
    c = harness.load_cell(REPO, cell)
    mode = c.config["engine"]["mode"]
    assert os.path.isfile(os.path.join(REPO, "chipbench", "engines",
                                       mode + ".py"))
    for m in c.per_layer:
        assert callable(harness.load_module(REPO, "metrics", m["name"]).read)
    assert harness.check_reps(c) >= 2 and c.mix["pool"] >= 1
    assert set(c.limits) >= {"placement_mismatch"} or \
        set(c.limits) >= {"counter_mismatch"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        conf = {x["name"]: x for x in json.load(fh)["configs"]}
    assert conf[c.config["name"]]["file"].startswith("chipbench/")


def test_new_cell_config_traffic_and_metric_are_files_and_entries(
        tmp_path, on_cpu, capsys):
    root = make_tiny_root(str(tmp_path))
    before = {p: open(p, "rb").read() for p in _files(root)}
    cb = os.path.join(root, "chipbench")
    # a configuration, a traffic mix, its limits and a metric, as new files
    with open(os.path.join(cb, "configs", "testbed-hermes.json")) as fh:
        cfg = json.load(fh)
    cfg["name"], cfg["policy"] = "testbed-ll", "E/LL/PS"
    with open(os.path.join(cb, "configs", "testbed-ll.json"), "w") as fh:
        json.dump(cfg, fh)
    shutil.copy(os.path.join(cb, "limits", "testbed-hermes.json"),
                os.path.join(cb, "limits", "testbed-ll.json"))
    with open(os.path.join(cb, "traffic", "diurnal-sweep.json")) as fh:
        mix = json.load(fh)
    mix.update(profile="bursty", loads=[0.7], seeds_per_load=2)
    with open(os.path.join(cb, "traffic", "bursty-sweep.json"), "w") as fh:
        json.dump(mix, fh)
    with open(os.path.join(cb, "metrics", "units_run.py"), "w") as fh:
        fh.write("def read(run):\n    return float(len(run.units))\n")
    # ... and new entries
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    b["configs"].append({"name": "testbed-ll", "source": "test",
                         "file": "chipbench/configs/testbed-ll.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "testbed-ll.bursty-sweep",
                           "config": "testbed-ll", "traffic": "bursty-sweep",
                           "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "units_run", "unit": "units",
                           "better": "higher", "source": "host_clock",
                           "layer": "host front end",
                           "moves": "arrivals_per_s",
                           "workloads": ["testbed-ll.bursty-sweep"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(b, fh)
    changed = [p for p, data in before.items()
               if p.endswith(".py") and open(p, "rb").read() != data]
    assert changed == []
    rc = on_cpu.main(["--workload", "testbed-ll.bursty-sweep", "--seed",
                      "2147483659", "--seconds", "0.5", "--trace", "1"],
                     root=root)
    res = result_of(capsys)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["units_run"]["value"] >= 1


def _files(root):
    for dirpath, _, names in os.walk(os.path.join(root, "chipbench")):
        for n in names:
            yield os.path.join(dirpath, n)
