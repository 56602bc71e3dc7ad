"""Shared helpers of the benchmark's CPU tests.

``tiny_root`` is a scratch checkout: ``BENCHMARK.json`` and a copy of
``chipbench/`` with every traffic mix cut to a few hundred arrivals and
a few replications (widths unchanged), the stream chunk cut to 256, and
a peaks entry for the CPU; ``src`` links to the program.  ``on_cpu``
lets a run take the CPU for its chips: the tests skip the harness's look
for a TPU, never through an option of the program.
"""
import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY_N = {"diurnal-sweep": 1000, "diurnal-stream": 1200}
#: arrivals per horizon at which the stream's float32 control goes astray
#: on the CPU as it does at the cell's size on the chip
CONTROL_N = 8192


def make_tiny_root(path: str, n: dict | None = None) -> str:
    """A tiny checkout at ``path``; ``n`` overrides the arrivals of some
    traffic mixes."""
    n = dict(TINY_N, **(n or {}))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), path)
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(path, "chipbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(path, "src"))
    tdir = os.path.join(path, "chipbench", "traffic")
    for name in os.listdir(tdir):
        p = os.path.join(tdir, name)
        with open(p) as fh:
            mix = json.load(fh)
        mix["n_arrivals"] = n.get(name[:-5], 300)
        mix["loads"] = mix["loads"][::max(len(mix["loads"]) // 2, 1)]
        mix["seeds_per_load"] = 4 // len(mix["loads"])
        with open(p, "w") as fh:
            json.dump(mix, fh)
    cdir = os.path.join(path, "chipbench", "configs")
    for name in os.listdir(cdir):
        p = os.path.join(cdir, name)
        with open(p) as fh:
            cfg = json.load(fh)
        if "chunk" in cfg["engine"]:
            cfg["engine"]["chunk"] = 256
        with open(p, "w") as fh:
            json.dump(cfg, fh)
    p = os.path.join(path, "chipbench", "peaks.json")
    with open(p) as fh:
        peaks = json.load(fh)
    peaks["devices"]["cpu"] = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                               "hbm_bytes": 1e10}
    with open(p, "w") as fh:
        json.dump(peaks, fh)
    return path


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("checkout")))


@pytest.fixture
def on_cpu(monkeypatch):
    import jax

    from chipbench import run
    monkeypatch.setattr(run, "require_chips",
                        lambda n: jax.devices()[:n])
    return run


def cells(chips: int | None = None) -> list:
    """The benchmark's cells, or those that ask for ``chips`` chips."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return [w["name"] for w in json.load(fh)["workloads"]
                if chips in (None, w["chips"])]


def result_of(capsys) -> dict:
    """The result line a run printed last on standard output."""
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1])
