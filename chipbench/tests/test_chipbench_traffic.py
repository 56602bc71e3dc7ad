"""The benchmark's traffic generator is pinned by digests of its own
seeded output, so a change to it shows; it is deterministic in the seed,
takes seeds beyond 32 bits, and yields well-formed replications."""
import hashlib
import json
import os

import numpy as np
import pytest

from chipbench import traffic
from chipbench.tests.chipbench_fixtures import REPO

GOLDEN = {
    "diurnal":
        "ce5239f4d2613d08fd423276224cd1c6154c1117017c4ebac813398469581252",
    "bursty":
        "bdccdd9ea043fdc0e8acf58f9ff1040fecfe99fd79beecb074f6d561a3b5ca6f",
    "cold-heavy":
        "0e6937cdb1b2864dc68377f2cd9d174f8714a8b3a2ad08e2097971070aadb490",
    "flash-crowd":
        "42869310dc2493eaa5bac3c2bf742de19c9774b7c8fa93d123c2f0a299f9e679",
}


def _digest(unit: dict) -> str:
    h = hashlib.sha256()
    for k in ("arrival", "func", "service", "u_lb", "func_home"):
        h.update(np.ascontiguousarray(unit[k]).tobytes())
    return h.hexdigest()


def _mix(profile: str) -> dict:
    return {"profile": profile, "n_arrivals": 500, "loads": [0.4, 0.8],
            "seeds_per_load": 2}


@pytest.mark.parametrize("profile", sorted(GOLDEN))
def test_output_matches_its_golden_digest(profile):
    unit = traffic.make_unit(_mix(profile), 8, 12, 2**31 + 12345, 3)
    assert _digest(unit) == GOLDEN[profile]


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 3])
def test_deterministic_in_the_seed_and_well_formed(seed):
    mix = _mix("diurnal")
    a = traffic.make_unit(mix, 100, 12, seed, 0)
    b = traffic.make_unit(mix, 100, 12, seed, 0)
    assert _digest(a) == _digest(b)
    assert _digest(a) != _digest(traffic.make_unit(mix, 100, 12, seed, 1))
    assert a["arrival"].shape == (4, 500) and a["loads"] == (0.4, 0.4,
                                                             0.8, 0.8)
    assert (np.diff(a["arrival"], axis=1) >= 0).all()
    assert a["func"].min() >= 0 and a["func"].max() < a["n_functions"]
    assert (a["service"] > 0).all() and (a["service"] <= 600.0).all()
    assert a["func_home"].max() < 100
    # one trace seed per column: the loads differ only in time compression
    np.testing.assert_array_equal(a["func"][0], a["func"][2])
    ratio = a["arrival"][0, 1:] / a["arrival"][2, 1:]
    np.testing.assert_allclose(ratio, 2.0, rtol=1e-12)


def test_benchmark_mixes_name_a_known_profile():
    tdir = os.path.join(REPO, "chipbench", "traffic")
    for name in os.listdir(tdir):
        with open(os.path.join(tdir, name)) as fh:
            mix = json.load(fh)
        one = dict(mix, n_arrivals=200, loads=mix["loads"][:1],
                   seeds_per_load=1)
        assert traffic.make_unit(one, 8, 12, 1, 0)["arrival"].shape == \
            (1, 200)
