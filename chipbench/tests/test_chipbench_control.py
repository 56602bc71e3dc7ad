"""The control comes out not correct: the reference computed in float32,
put in the program's place, fails a limit of each cell that the
program's own runs keep (a small size of each cell on the CPU)."""
import jax
import pytest

from chipbench import control
from chipbench.tests.chipbench_fixtures import (CONTROL_N, cells,
                                                make_tiny_root)


@pytest.mark.parametrize("cell", cells(chips=1))
def test_control_fails_where_the_program_passes(cell, tmp_path):
    # stream horizons long enough for float32 to go astray, as at the
    # cell's own size on the chip
    root = make_tiny_root(str(tmp_path),
                          {"diurnal-stream": CONTROL_N})
    res = control.collect(root, cell, [11, 2**31 + 12], [21, 22, 23],
                          require=lambda n: jax.devices()[:n])
    limits = res["limits"]
    for seed, rd in res["program"].items():
        assert all(rd[n] <= limits[n] for n in rd), (seed, rd)
    for seed, rd in res["control"].items():
        assert any(rd[n] > limits[n] for n in rd), (seed, rd)
