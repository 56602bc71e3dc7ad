"""chip_smoke.py: refuses to report off the chip; its phases and checks
run end to end on the CPU at the rehearsal size."""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
#: the one check that must fail off the chip: the kernel is interpreted
CPU_ONLY_FAILURE = "E/H/PS engine HLO holds tpu_custom_call"


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _smoke():
    sys.path.insert(0, REPO)
    import chip_smoke
    return chip_smoke


def test_refuses_without_a_tpu():
    p = _run(SCRIPT, REPO)
    assert p.returncode != 0, p.stdout
    assert '"ok"' not in p.stdout
    assert "not a TPU" in p.stderr


def test_refuses_outside_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    p = _run(str(alone), str(tmp_path))
    assert p.returncode != 0, p.stdout
    assert '"ok"' not in p.stdout


@pytest.mark.parametrize("phase", ["kernel", "testbed", "day"])
def test_one_chip_phases_pass_their_checks_on_cpu(phase, capsys):
    cs = _smoke()
    sm = cs.Smoke("cpu")
    sm.phase(phase, getattr(cs, f"phase_{phase}"), cs.SIZES["small"])
    out = capsys.readouterr().out
    assert sm.failed == ([CPU_ONLY_FAILURE] if phase == "testbed" else [])
    assert "check" in out and "FAIL" not in out.replace(
        f"check {CPU_ONLY_FAILURE}: FAIL", "")


def test_four_chip_phase_passes_on_four_cpu_devices(devices_script):
    out = devices_script(f"""
import sys
sys.path.insert(0, {REPO!r})
import chip_smoke as cs
sm = cs.Smoke("cpu")
sm.phase("four_chips", cs.phase_four_chips, cs.SIZES["small"])
assert not sm.failed, sm.failed
print("four-ok")
""", n_devices=4)
    assert "four-ok" in out
    assert out.count("spread over 4 devices: PASS") == 2
