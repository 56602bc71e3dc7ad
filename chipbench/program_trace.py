"""What the per-layer metrics read from the program's own tracer.

A traced run installs the program's process-wide tracer
(:class:`repro.telemetry.spans.Tracer`), and the program records into
it where the work happens:

* spans, each with the span it was opened in: ``engine.put``,
  ``engine.run``, ``engine.readout`` (``simulate_many`` and
  ``simulate_stream``), ``stream.init``, ``stream.feed`` (one per
  chunk), ``stream.drain`` and ``metrics.summarize_batch``;
* one ``drain.iterations`` counter sample per call: the completion
  drain's loop iterations ``needed`` (the sum over scan steps of the
  mean over replications) and ``executed`` (the sum over steps of the
  max, which the vmapped loop runs), over ``steps`` scan steps;
* ``scopes``: per compiled scan-step program, each HLO instruction's
  innermost named scope (``advance``, ``select``, ``place``,
  ``lifecycle``, ``tel``, ``tl``; ``""`` for none).

Only the window counts: a span or a sample under one of the harness's
``setup.*`` spans belongs to set-up.  These numbers are read only in a
run whose profile saw a device: there they say what the chip waits for
and what it spends a step on; a host-only rehearsal reports none of
them, as it reports no device metric.  A program that records none of
them reads ``None``.
"""
from __future__ import annotations

import sys
from collections import defaultdict

SETUP = "setup."
COUNTER = "drain.iterations"


def tracer():
    """The program's process-wide tracer."""
    from repro.telemetry.spans import get_tracer
    return get_tracer()


def on_device(run) -> bool:
    """Whether the run's profile held a device's operations."""
    return any(red["devices"] for red in (run.slices or {}).values())


def window_events(events: list, ph: str, name: str) -> list:
    """The events of phase ``ph`` named ``name`` that no ``setup.*`` span
    holds, following each event's chain of parent spans."""
    spans = {e["id"]: e for e in events if e.get("ph") == "X" and "id" in e}

    def in_setup(e) -> bool:
        seen = set()
        p = e.get("parent")
        while p in spans and p not in seen:
            seen.add(p)
            if spans[p]["name"].startswith(SETUP):
                return True
            p = spans[p].get("parent")
        return False
    return [e for e in events if e.get("ph") == ph and e["name"] == name
            and not in_setup(e)]


def span_ms(run, name: str) -> float | None:
    """Mean duration, in milliseconds, of the window's spans ``name``."""
    if not on_device(run):
        return None
    evs = window_events(tracer().events, "X", name)
    if not evs:
        return None
    return sum(e["dur"] for e in evs) / len(evs) / 1e3


def drain_iterations(run) -> dict | None:
    """``needed``, ``executed`` and ``steps`` of the drain counter, summed
    over the window's units."""
    if not on_device(run):
        return None
    evs = window_events(tracer().events, "C", COUNTER)
    if not evs:
        return None
    return {k: sum(e["args"][k] for e in evs)
            for k in ("needed", "executed", "steps")}


def step_scopes(run) -> dict | None:
    """Microseconds per scan step of each named scope, ``""`` for the
    operations in none, over the slice inside a unit's scan.

    Of the program maps the tracer holds, the one whose operations take
    the most of the slice's time is the scan's.  Each scope's share of
    the time of that program's leaf operations (control flow holds other
    operations and is left out) times ``step_device_us`` is its time
    per step, so the parts add up to ``step_device_us``.  The parts are
    printed on standard error once per run.
    """
    from chipbench.metrics.step_device_us import read as step_us
    if getattr(run, "step_scopes", None) is not None:
        return run.step_scopes
    maps = getattr(tracer(), "scopes", None)
    if not maps or not on_device(run):
        return None
    step = step_us(run)
    if step is None:
        return None
    red = run.slices["steady"]
    self_ns = red["self_ns"]
    label, ops = max(maps.items(), key=lambda kv: sum(
        t for op, t in self_ns.items() if op in kv[1]))
    parts: dict[str, float] = defaultdict(float)
    for op, t in self_ns.items():
        if op in ops:
            parts[ops[op]] += t
    leaf_ns = sum(parts.values())
    if not leaf_ns:
        return None
    run.step_scopes = {s: step * t / leaf_ns for s, t in parts.items()}
    busy = [d["busy_ns"] for d in red["devices"].values()]
    named = {s or "unscoped": v for s, v in run.step_scopes.items()}
    print(f"chipbench: scopes of a scan step of {step} us ({label!r}), us "
          f"per step: {named}; unscoped share "
          f"{parts.get('', 0.0) / leaf_ns}; leaf operations {leaf_ns} ns "
          f"of the slice's busy {busy} ns", file=sys.stderr, flush=True)
    return run.step_scopes


def scope_us(run, scope: str) -> float | None:
    """Microseconds per scan step of the named ``scope``, or ``None``
    where the program's map holds no operation of it."""
    parts = step_scopes(run)
    if parts is None or scope not in parts:
        return None
    return parts[scope]
