"""A run whose timed path is broken underneath comes out not correct.

Each test skips only the harness's look for a chip and drives the rest
of a run, with the program's compiled engine wrapped so that it makes
one fault: a step that returns its state unchanged, half of the batch
left out (its results copied from the other half), or one answer
altered where it is produced.  On four chips (a cell the benchmark
does not hold yet, run here on four virtual devices) the replications
run with no exchange between chips, so the fault there is one chip's
block left out (copied from another's).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from chipbench.tests.chipbench_fixtures import (CONTROL_N, REPO, on_cpu,
                                                result_of, tiny_root)

FAULTS = ("unchanged", "half", "altered")


def _halve(x):
    h = x.shape[0] // 2
    return x.at[h:2 * h].set(x[:h])


def _quarter(x):
    q = x.shape[0] // 4
    return x.at[q:2 * q].set(x[:q])


def _broken_sweep(fault, get_engine):
    """``_get_engine`` whose batched engine makes ``fault``."""
    def wrapped(*a, **kw):
        run, fresh = get_engine(*a, **kw)

        def bad(*args):
            st = run(*args)
            if fault == "unchanged":
                return st._replace(
                    resp=jnp.full_like(st.resp, jnp.nan),
                    cold=jnp.zeros_like(st.cold),
                    rejected=jnp.zeros_like(st.rejected),
                    worker_of=jnp.full_like(st.worker_of, -1),
                    server_time=jnp.zeros_like(st.server_time),
                    core_time=jnp.zeros_like(st.core_time),
                    now=jnp.zeros_like(st.now))
            if fault == "half":
                return jax.tree_util.tree_map(_halve, st)
            n = st.worker_of.shape[1] // 2
            w = st.worker_of[:, n]
            return st._replace(worker_of=st.worker_of.at[:, n].set(
                jnp.where(w >= 0, w + 1, w)))
        return bad, fresh
    return wrapped


def _broken_stream(fault, get_engine):
    """``_get_stream_engine`` whose chunk program makes ``fault``."""
    def wrapped(*a, **kw):
        (init, step, drain), fresh = get_engine(*a, **kw)

        def bad(st, gids, valid, *ins):
            if fault == "unchanged":
                r, k = ins[0].shape
                ys = (jnp.zeros((r, k), bool), jnp.zeros((r, k), bool),
                      jnp.full((r, k), -1, jnp.int32))
                return st, ys
            st, ys = step(st, gids, valid, *ins)
            if fault in ("half", "block"):
                cut = _halve if fault == "half" else _quarter
                return jax.tree_util.tree_map(cut, st), ys
            tel = dict(st.tel, decisions=st.tel["decisions"].at[:, 0].add(1))
            return st._replace(tel=tel), ys
        return (init, bad, drain), fresh
    return wrapped


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", ["testbed-hermes.diurnal-sweep",
                                  "scale-ll.diurnal-stream"])
def test_broken_timed_path_is_not_correct(cell, fault, tiny_root, on_cpu,
                                          monkeypatch, capsys):
    from repro.core import simulator, streaming
    if cell.startswith("testbed"):
        monkeypatch.setattr(simulator, "_get_engine",
                            _broken_sweep(fault, simulator._get_engine))
    else:
        monkeypatch.setattr(streaming, "_get_stream_engine",
                            _broken_stream(fault,
                                           streaming._get_stream_engine))
    rc = on_cpu.main(["--workload", cell, "--seed", "2147483777",
                      "--seconds", "0.2", "--trace", "0"], root=tiny_root)
    res = result_of(capsys)
    assert rc == 0
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


FOUR_CHIPS = """
import contextlib, io, json, sys, tempfile
sys.path[:0] = [{repo!r}]
import jax
from chipbench import run
from chipbench.tests.chipbench_fixtures import make_tiny_root
from chipbench.tests.test_chipbench_faults import _broken_stream
from repro.core import streaming
run.require_chips = lambda n: jax.devices()[:n]
real = streaming._get_stream_engine
out = {{}}
with tempfile.TemporaryDirectory() as tmp:
    root = make_tiny_root(tmp)
    # the four-chip cell as a later PR would add it: a traffic file and
    # an entry (one replication per chip here, each one checked), with
    # horizons long enough for the float32 control to go astray
    with open(tmp + "/chipbench/traffic/diurnal-stream.json") as fh:
        mix = json.load(fh)
    mix["n_arrivals"] = {control_n}
    with open(tmp + "/chipbench/traffic/diurnal-stream-r32.json", "w") as fh:
        json.dump(mix, fh)
    with open(tmp + "/BENCHMARK.json") as fh:
        bench = json.load(fh)
    bench["workloads"].append({{"name": "scale-ll.diurnal-stream-4chip",
                               "config": "scale-ll", "chips": 4,
                               "traffic": "diurnal-stream-r32", "why": "x"}})
    with open(tmp + "/BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    for fault in ("none", "half", "block"):
        streaming._get_stream_engine = real if fault == "none" \
            else _broken_stream(fault, real)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = run.main(["--workload", "scale-ll.diurnal-stream-4chip",
                           "--seed", "2147483901", "--seconds", "0.2",
                           "--trace", "0"], root=root)
        res = json.loads(buf.getvalue().strip().splitlines()[-1])
        out[fault] = [rc, res["correct"], res["device"]["count"]]
    streaming._get_stream_engine = real
    from chipbench import control
    ctl = control.collect(root, "scale-ll.diurnal-stream-4chip", [5],
                          [21, 22, 23], require=run.require_chips)
    lim = ctl["limits"]
    out["control"] = [all(v <= lim[n] for n, v in ctl["lower"].items()),
                      all(any(v > lim[n] for n, v in rd.items())
                          for rd in ctl["control"].values())]
print("RESULT " + json.dumps(out))
"""


def test_four_chip_cell_runs_and_catches_a_chip_left_out():
    """The four-chip cell on four virtual CPU devices: a sound run is
    correct, one with half the batch or one chip's block left out is
    not, and the control fails where the program passes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c",
                        FOUR_CHIPS.format(repo=REPO, control_n=CONTROL_N)],
                       env=env, capture_output=True, text=True, timeout=600)
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, p.stderr[-3000:]
    out = json.loads(line[0][len("RESULT "):])
    assert out == {"none": [0, True, 4], "half": [0, False, 4],
                   "block": [0, False, 4], "control": [True, True]}
