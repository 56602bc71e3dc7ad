"""Each cell's unit of work runs end to end at a tiny size on the CPU, and
the command refuses to report anywhere but on a TPU checkout."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench.tests.chipbench_fixtures import (REPO, cells, on_cpu,
                                                result_of, tiny_root)


@pytest.mark.parametrize("cell", cells(chips=1))
def test_unit_runs_end_to_end_and_is_correct(cell, tiny_root, on_cpu,
                                             capsys):
    rc = on_cpu.main(["--workload", cell, "--seed", str(2**31 + 17),
                      "--seconds", "0.3", "--trace", "0"], root=tiny_root)
    res = result_of(capsys)
    assert rc == 0
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"arrivals_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_reports_layers_and_a_breakdown(tiny_root, on_cpu,
                                                   capsys):
    rc = on_cpu.main(["--workload", "scale-ll.diurnal-stream", "--seed", "5",
                      "--seconds", "0.3", "--trace", "1"], root=tiny_root)
    res = result_of(capsys)
    assert rc == 0 and res["correct"] is True
    # the CPU has no device plane and this process has compiled the
    # engine already: the host feed is what there is to read
    assert "host_feed_ms" in res["metrics"]
    assert set(res["metrics"]) <= {"host_feed_ms", "backend_compile_s",
                                   "trace_lower_s"}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0
    assert not os.path.exists(os.path.join(
        tiny_root, "experiments", "chipbench", "scale-ll.diurnal-stream",
        "profile"))


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "testbed-hermes.diurnal-sweep", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_on_cpu_exits_non_zero_with_no_result():
    p = _command(REPO)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not a TPU" in p.stderr


def test_command_without_the_program_exits_non_zero(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(str(tmp_path))
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_unknown_device_kind_is_an_error(tiny_root, on_cpu, capsys,
                                         tmp_path):
    root = str(tmp_path / "co")
    shutil.copytree(tiny_root, root, symlinks=True)
    peaks = os.path.join(root, "chipbench", "peaks.json")
    with open(peaks) as fh:
        doc = json.load(fh)
    del doc["devices"]["cpu"]
    with open(peaks, "w") as fh:
        json.dump(doc, fh)
    rc = on_cpu.main(["--workload", "testbed-hermes.diurnal-sweep",
                      "--seed", "1", "--seconds", "0.1", "--trace", "0"],
                     root=root)
    out = capsys.readouterr()
    assert rc != 0 and "{" not in out.out
    assert "peaks.json" in out.err
