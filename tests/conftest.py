"""Shared fixtures.  NOTE: no global device-count override here — smoke
tests and benches must see 1 device; sharded tests spawn subprocesses
with their own ``--xla_force_host_platform_device_count`` (see
test_distributed.py)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_devices_script(body: str, n_devices: int = 8,
                       timeout: int = 560) -> str:
    """Run a Python snippet in a fresh process with N host devices.

    The child is pinned to the CPU: a parent that holds an accelerator
    cannot hand it to a child.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr}")
    return proc.stdout


@pytest.fixture(scope="session")
def devices_script():
    return run_devices_script
