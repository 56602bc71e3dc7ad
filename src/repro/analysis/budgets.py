"""Per-engine jaxpr complexity budgets (``BGT001``).

Fusion regressions show up as equation-count blowups long before they
show up as wall-clock noise: a carry dtype drift de-fuses the scan
body, a new host sync splits the program, an accidental ``vmap`` of a
scalar path multiplies the eqn count.  This pass traces every
registered engine (see :func:`repro.analysis.jaxpr_audit
.iter_engine_specs`), counts jaxpr equations recursively, and fails if
any engine exceeds its recorded budget.

Budgets are *measured baselines × headroom* — loose enough to allow
normal drift (new policy features change counts by a few eqns), tight
enough that a structural break (≥ ~50%) trips.  An engine with no
recorded baseline gets :data:`DEFAULT_BUDGET`; re-baseline with
``python -m repro.analysis --print-baselines`` after intentional
engine surgery.

:func:`bench_rows` is the ``benchmarks/run.py`` hook: it returns the
rows recorded under ``analysis`` in ``BENCH_report.json`` alongside a
REPRO-CHECK-style ``(ok, detail)`` verdict.
"""
from __future__ import annotations

from .findings import Finding
from .rules import RULES

#: Measured eqn-count baselines per engine label (AUDIT_N=8 arrivals,
#: AUDIT_F=3 functions, AUDIT_W=3 workers — counts are shape-dependent,
#: keep in sync with :mod:`repro.analysis.jaxpr_audit`).
BASELINES: dict[str, int] = {
    "E/LOC/PS|jax": 575,
    "E/LOC/PS|pallas": 575,
    "E/R/PS|jax": 562,
    "E/R/PS|pallas": 562,
    "E/LL/PS|jax": 549,
    "E/LL/PS|pallas": 549,
    "E/H/PS|jax": 571,
    "E/H/PS|pallas": 605,
    "E/JSQ2/PS|jax": 577,
    "E/JSQ2/PS|pallas": 577,
    "E/RR/PS|jax": 584,
    "E/RR/PS|pallas": 584,
    "E/HIKU/PS|jax": 723,
    "E/HIKU/PS|pallas": 723,
    "E/DD/PS|jax": 641,
    "E/DD/PS|pallas": 641,
    "E/SWARM/PS|jax": 682,
    "E/SWARM/PS|pallas": 682,
    "E/LL/PS|jax|ka=NONE": 724,
    "E/LL/PS|jax|ka=FIXED_TTL": 724,
    "E/LL/PS|jax|ka=HYBRID_HIST": 822,
    "L/LL/FCFS|jax": 1231,
    # telemetry-on lanes (streaming histogram/counter carry in the
    # scan); the telemetry-off baselines above are unchanged — the
    # disabled path traces the identical pre-telemetry program
    "E/LL/PS|jax|tel": 759,
    "E/H/PS|jax|tel": 781,
    "E/HIKU/PS|jax|tel": 933,
    "E/H/PS|pallas|tel": 815,
    "E/LL/PS|jax|ka=FIXED_TTL|tel": 934,
    "L/LL/FCFS|jax|tel": 1491,
    # heterogeneous-fleet lanes: the speed-vector divide costs ~4 eqns
    # on a speed-blind engine; SWARM's learned-state carry and the
    # TARGET_P99 autoscaler+telemetry lane are budgeted on top
    "E/LL/PS|jax|fleet": 553,
    "E/SWARM/PS|jax|fleet": 698,
    "E/LL/PS|jax|fleet|auto|tel": 859,
    # streaming chunk-engine lanes: one segment's scan traced on the
    # engine's own init carry (slot mirrors + exact counters, no (N,)
    # output planes — hence smaller than the monolithic twins)
    "E/LL/PS|jax|chunk": 362,
    "E/LL/PS|jax|tel|chunk": 473,
    "E/LL/PS|jax|ka=HYBRID_HIST|tel|chunk": 657,
    "E/LL/PS|jax|fleet|auto|tel|chunk": 566,
    # windowed-timeline lanes: the flight-recorder plane scatters into
    # K-window counters/sketches on every arrival and completion, so
    # it costs more than the telemetry sketch alone; timeline-off
    # baselines above are unchanged (the disabled path traces the
    # identical pre-timeline program — locked by
    # test_timeline_off_is_bit_identical)
    "E/LL/PS|jax|tl": 1036,
    "E/LL/PS|jax|tel|tl": 1246,
    "E/H/PS|jax|tel|tl": 1302,
    "E/LL/PS|jax|fleet|auto|tel|tl": 1421,
    "E/LL/PS|jax|tel|tl|chunk": 753,
}

#: Headroom multiplier over the measured baseline.
HEADROOM: float = 1.5

#: Budget for engines with no recorded baseline (new policies land
#: before re-baselining; this only guards against gross blowups).
DEFAULT_BUDGET: int = 2000


def budget_for(label: str) -> int:
    base = BASELINES.get(label)
    if base is None:
        return DEFAULT_BUDGET
    return int(base * HEADROOM)


def check_budgets(stats=None) -> tuple[list[dict], list[Finding]]:
    """Trace every engine, compare eqn counts against budgets.

    Returns ``(rows, findings)`` where ``rows`` are JSON-ready dicts
    (one per engine: label, eqns, budget, baseline, ok) and
    ``findings`` carry a ``BGT001`` per over-budget engine.
    """
    if stats is None:
        from .jaxpr_audit import audit_engines
        stats, _ = audit_engines()
    rows: list[dict] = []
    findings: list[Finding] = []
    for st in stats:
        budget = budget_for(st.label)
        row = st.row()
        row["baseline"] = BASELINES.get(st.label)
        row["budget"] = budget
        row["ok"] = st.eqns <= budget
        rows.append(row)
        if not row["ok"]:
            findings.append(Finding(
                path=f"<engine:{st.label}>", line=0, rule="BGT001",
                message=(f"jaxpr has {st.eqns} eqns, budget {budget} "
                         f"(baseline {row['baseline']}) — a fusion or "
                         f"carry-structure regression"),
                hint=RULES["BGT001"].hint))
    return rows, findings


def bench_rows() -> tuple[list[dict], bool, str]:
    """Budget gate for ``benchmarks/run.py``: (rows, ok, detail)."""
    rows, findings = check_budgets()
    over = [f.path for f in findings]
    detail = (f"{len(rows)} engines traced, "
              + (f"over budget: {', '.join(over)}" if over
                 else "all within eqn budgets"))
    return rows, not over, detail


def format_baselines(stats) -> str:
    """Render measured stats as a paste-ready ``BASELINES`` literal."""
    lines = ["BASELINES: dict[str, int] = {"]
    for st in stats:
        lines.append(f'    "{st.label}": {st.eqns},')
    lines.append("}")
    return "\n".join(lines)
