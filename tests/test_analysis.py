"""repro.analysis: lint rules, jaxpr audit, contracts, budgets, CLI."""
import os

import pytest

from repro.analysis import (BASELINES, RULES, audit_cache_key, audit_engines,
                            audit_fn, check_contracts, lint_file, lint_paths)
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.budgets import (DEFAULT_BUDGET, budget_for,
                                    check_budgets)
from repro.analysis.jaxpr_audit import JaxprStats, iter_engine_specs

from conftest import REPO

FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")


def fixture(name: str) -> str:
    return os.path.join(FIXTURES, name)


# --------------------------------------------------------------------------
# AST lint: one fixture per rule, each fires exactly once
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fname,rule", [
    ("det001_case.py", "DET001"),
    ("det002_case.py", "DET002"),
    ("hot001_case.py", "HOT001"),
    ("hot002_case.py", "HOT002"),
    ("hot003_case.py", "HOT003"),
    ("par001_case.py", "PAR001"),
    ("par002_case.py", "PAR002"),
])
def test_fixture_fires_exactly_once(fname, rule):
    findings = lint_file(fixture(fname))
    assert [f.rule for f in findings] == [rule], findings


def test_unparseable_file_reports_lnt000():
    text = open(fixture("lnt000_case.py.txt")).read()
    findings = lint_file("lnt000_case.py", text=text)
    assert [f.rule for f in findings] == ["LNT000"]


def test_disable_comments_silence_findings():
    assert lint_file(fixture("disabled_case.py")) == []


def test_file_wide_disable():
    text = ("# repro-lint: disable-file=DET001\n"
            "import numpy as np\n"
            "x = np.random.rand(3)\n"
            "y = np.random.randn(3)\n")
    assert lint_file("mod.py", text=text) == []


def test_unknown_rule_id_in_disable_is_ignored():
    text = ("import numpy as np\n"
            "x = np.random.rand(3)  # repro-lint: disable=NOPE123\n")
    findings = lint_file("mod.py", text=text)
    assert [f.rule for f in findings] == ["DET001"]


def test_static_argnames_not_traced():
    text = ("from functools import partial\n"
            "import jax\n"
            "@partial(jax.jit, static_argnames=('flag',))\n"
            "def f(x, *, flag):\n"
            "    assert flag\n"
            "    return x\n")
    assert lint_file("mod.py", text=text) == []


def test_findings_carry_hints_and_format():
    (f,) = lint_file(fixture("det001_case.py"))
    assert f.hint == RULES["DET001"].hint
    assert "det001_case.py" in f.format() and "DET001" in f.format()


def test_clean_pass_golden_over_tree():
    findings = lint_paths([os.path.join(REPO, "src"),
                           os.path.join(REPO, "benchmarks")])
    assert findings == [], "\n".join(f.format() for f in findings)


# --------------------------------------------------------------------------
# jaxpr audit: toy programs
# --------------------------------------------------------------------------

def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype)


def test_jaxpr_audit_clean_toy():
    import jax.numpy as jnp

    def toy(x):
        return x + x

    stats, findings = audit_fn(toy, _sds((4,), jnp.float32),
                               label="toy", allow_64=False)
    assert findings == []
    assert stats.eqns >= 1 and stats.label == "toy"


def test_jaxpr_audit_flags_x64_promotion():
    import repro.core.simulator  # noqa: F401 — enables x64 on import
    import jax.numpy as jnp

    def toy(x):
        return (x.astype(jnp.float64) * jnp.float64(2.0)).sum()

    _, findings = audit_fn(toy, _sds((4,), jnp.float32), allow_64=False)
    assert any(f.rule == "JXP003" for f in findings)


def test_jaxpr_audit_flags_host_callback():
    import jax

    def toy(x):
        jax.debug.print("x={x}", x=x)
        return x + x

    _, findings = audit_fn(toy, _sds((4,), jax.numpy.float32))
    assert any(f.rule == "JXP004" for f in findings)


def test_jaxpr_audit_flags_weak_scan_carry():
    import jax

    def toy(x):
        def body(c, xi):
            return c * 2.0, c
        c, _ = jax.lax.scan(body, 1.0, x)
        return c

    stats, findings = audit_fn(toy, _sds((4,), jax.numpy.float64),
                               allow_weak_outputs=True)
    assert stats.scans == 1
    assert any(f.rule == "JXP001" for f in findings)


def _split_in_loop(idx_dtype):
    """A scan whose body splits an index into row and column."""
    import jax

    def toy(x):
        def body(c, xi):
            j = xi.astype(idx_dtype)
            return c + j // 3 + j % 3, None
        c, _ = jax.lax.scan(body, jax.numpy.zeros((), idx_dtype), x)
        return c
    return toy


def test_jaxpr_audit_flags_int64_division_in_loop():
    import repro.core.simulator  # noqa: F401 — enables x64 on import
    import jax.numpy as jnp

    _, findings = audit_fn(_split_in_loop(jnp.int64),
                           _sds((4,), jnp.int32))
    assert [f.rule for f in findings] == ["JXP006"], findings


def test_jaxpr_audit_int32_or_unlooped_division_is_clean():
    import repro.core.simulator  # noqa: F401 — enables x64 on import
    import jax.numpy as jnp

    _, findings = audit_fn(_split_in_loop(jnp.int32),
                           _sds((4,), jnp.int32))
    assert findings == []
    _, findings = audit_fn(lambda x: x.astype(jnp.int64) // 3,
                           _sds((4,), jnp.int32))
    assert findings == []


# --------------------------------------------------------------------------
# jaxpr audit: the real engines
# --------------------------------------------------------------------------

def test_audit_engines_smoke():
    stats, findings = audit_engines(balancers=["LL"])
    assert [s.label for s in stats] == ["E/LL/PS|jax", "E/LL/PS|pallas"]
    assert findings == [], "\n".join(f.format() for f in findings)
    assert all(s.eqns > 0 and s.scans >= 1 for s in stats)


def test_audit_all_engines_clean():
    stats, findings = audit_engines()
    assert len(stats) == len(BASELINES)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_engine_specs_cover_every_balancer_and_backend():
    from repro.policy import balancer_names
    labels = {label for label, *_ in iter_engine_specs()}
    for bname in balancer_names():
        for backend in ("jax", "pallas"):
            assert f"E/{bname}/PS|{backend}" in labels


def test_cache_key_covers_every_config_field():
    findings = audit_cache_key()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_lifecycle_subfield_does_not_share_cached_engine():
    from repro.core.cluster import ClusterCfg
    from repro.core.simulator import build_simulator
    from repro.core.taxonomy import parse_policy
    from repro.lifecycle import LifecycleCfg

    pol = parse_policy("E/LL/PS")
    c1 = ClusterCfg(n_workers=2, cores=2, capacity_factor=2,
                    lifecycle=LifecycleCfg(ttl_s=60.0))
    c2 = c1._replace(lifecycle=c1.lifecycle._replace(ttl_s=61.0))
    e1 = build_simulator(pol, c1, n_arrivals=4, n_functions=2)
    e1b = build_simulator(pol, c1, n_arrivals=4, n_functions=2)
    e2 = build_simulator(pol, c2, n_arrivals=4, n_functions=2)
    assert e1 is e1b
    assert e1 is not e2


# --------------------------------------------------------------------------
# contracts
# --------------------------------------------------------------------------

def test_contracts_clean_on_current_registries():
    findings = check_contracts()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_contract_flags_stateless_pair_mismatch():
    from repro.analysis.contracts import check_balancers
    from repro.policy.registry import BALANCERS, register_balancer

    # a stateful-shaped return from a stateless balancer is a violation
    register_balancer(
        "XCONTRACT",
        make_np=lambda cores, slots: (lambda *a: 0, lambda *a: None),
        make_jax=lambda cores, slots: (lambda *a: 0, lambda *a: None))
    try:
        findings = check_balancers()
        mine = [f for f in findings if "XCONTRACT" in f.path]
        assert mine and all(f.rule == "CON001" for f in mine)
    finally:
        del BALANCERS["XCONTRACT"]


# --------------------------------------------------------------------------
# budgets
# --------------------------------------------------------------------------

def test_baselines_cover_all_current_engines():
    labels = {label for label, *_ in iter_engine_specs()}
    assert labels == set(BASELINES)


def test_budget_for_unknown_engine_uses_default():
    assert budget_for("E/NOPE/PS|jax") == DEFAULT_BUDGET


def test_over_budget_engine_yields_bgt001():
    st = JaxprStats(label="E/LL/PS|jax", eqns=10 ** 6, scans=1, whiles=2,
                    carry_leaves=14, carry_bytes=0, outputs=1)
    rows, findings = check_budgets([st])
    assert rows[0]["ok"] is False
    assert [f.rule for f in findings] == ["BGT001"]


def test_within_budget_engine_is_clean():
    st = JaxprStats(label="E/LL/PS|jax", eqns=BASELINES["E/LL/PS|jax"],
                    scans=1, whiles=2, carry_leaves=14, carry_bytes=0,
                    outputs=1)
    rows, findings = check_budgets([st])
    assert rows[0]["ok"] is True and findings == []


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def test_cli_strict_passes_on_clean_file(tmp_path, capsys):
    clean = tmp_path / "ok.py"
    clean.write_text("x = 1\n")
    assert analysis_main([str(clean), "--strict", "--no-jaxpr"]) == 0
    assert "0 findings" in capsys.readouterr().out


def test_cli_strict_fails_on_violation(capsys):
    rc = analysis_main([fixture("det001_case.py"), "--strict",
                        "--no-jaxpr"])
    assert rc == 1
    assert "DET001" in capsys.readouterr().out


def test_cli_non_strict_reports_but_passes(capsys):
    rc = analysis_main([fixture("det001_case.py"), "--no-jaxpr"])
    assert rc == 0
    assert "DET001" in capsys.readouterr().out


def test_cli_list_rules(capsys):
    assert analysis_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("DET001", "HOT002", "PAR001", "JXP005", "CON004",
                "BGT001"):
        assert rid in out
