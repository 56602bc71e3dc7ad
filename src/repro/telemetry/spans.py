"""Host-side span tracing (observability plane 2).

A zero-dependency tracer: nested :meth:`Tracer.span` context managers
record wall-clock intervals on one integer ``time.perf_counter_ns``
clock and export them as Chrome trace-event JSON — load the file at
https://ui.perfetto.dev (or ``chrome://tracing``) to see engine
build/compile/dispatch phases, benchmark figures and serving-platform
task lifecycles on one timeline.

Design constraints:

* **Opt-in and near-free when off.**  The process-wide tracer starts
  disabled; a disabled ``span()`` returns one shared null context
  manager (no allocation, no clock read), so instrumented hot paths —
  the engine-cache lookup, every ``simulate`` call — cost nothing in
  ordinary runs.
* **Host-side only.**  Spans never enter jitted code (a span inside a
  ``lax.scan`` would be a host callback — exactly what the ``JXP004``
  audit rule forbids).  Device-side visibility comes from two places:
  with ``jax_bridge=True`` every span also opens a
  ``jax.profiler.TraceAnnotation``, so spans show up inside XLA
  profiles; and :attr:`Tracer.scopes` maps each compiled program's
  HLO instructions to the ``jax.named_scope`` that holds them
  (:func:`op_scopes`), so profiled device time can be split by scope.
* **Causes are kept.**  Every span and counter sample records the span
  that was open when it began (``id``/``parent`` on the event), so a
  reader can tell the warm-up call of a benchmark from its timed ones.
* **Two clock domains.**  ``span()`` measures real wall time in
  microseconds since :attr:`Tracer.t0_ns`; :meth:`Tracer.event_at`
  records *virtual-time* events (the serving platform's simulated task
  lifecycles) under a separate pid so the two timelines never
  interleave confusingly in Perfetto.

Typical use::

    from repro.telemetry import configure_tracing, get_tracer, span

    configure_tracing(True)
    with span("fig2", loads=7):
        ...
    get_tracer().export("experiments/trace_bench.json")
"""
from __future__ import annotations

import contextlib
import json
import os
import re
import time
from typing import Any, Iterable

#: pid used for real wall-clock spans in the exported trace.
WALL_PID = 1
#: pid used for virtual-time events (simulated task lifecycles).
VIRTUAL_PID = 2

#: what a disabled tracer's ``span()`` returns: one shared, stateless
#: null context, so a disabled span allocates nothing
_NULL_SPAN = contextlib.nullcontext()


class _Span:
    """One wall-clock span of :meth:`Tracer.span`; its event is written
    when the span closes."""

    __slots__ = ("tracer", "name", "args", "id", "parent", "t_ns",
                 "bridge")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer, self.name, self.args = tracer, name, args
        self.bridge = None

    def __enter__(self) -> "_Span":
        tr = self.tracer
        self.id = tr._next_id
        tr._next_id += 1
        self.parent = tr._open[-1] if tr._open else None
        tr._open.append(self.id)
        if tr._annotation is not None:
            self.bridge = tr._annotation(self.name)
            self.bridge.__enter__()
        self.t_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end_ns = time.perf_counter_ns()
        tr = self.tracer
        if self.bridge is not None:
            self.bridge.__exit__(*exc)
        tr._open.pop()
        tr._events.append({
            "name": self.name, "ph": "X", "ts": tr._us(self.t_ns),
            "dur": (end_ns - self.t_ns) / 1e3,
            "pid": WALL_PID, "tid": 0, "id": self.id, "parent": self.parent,
            "args": {k: _jsonable(v) for k, v in self.args.items()},
        })


class Tracer:
    """Collects spans/events; exports Chrome trace-event JSON.

    Spans of one tracer nest on the thread that opens them: each records
    the innermost span open at its start as its ``parent``.
    """

    def __init__(self, enabled: bool = True, jax_bridge: bool = False):
        self.enabled = enabled
        self.jax_bridge = jax_bridge
        self._annotation = None
        if enabled and jax_bridge:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        self._events: list[dict] = []
        #: the wall clock's zero (``perf_counter_ns``): wall-clock event
        #: times are microseconds since it
        self.t0_ns = time.perf_counter_ns()
        self._epoch0 = time.time()
        self._next_id = 0
        self._open: list[int] = []
        #: per compiled program (by a label naming it): each HLO
        #: instruction's innermost named scope, ``""`` for none (see
        #: :func:`op_scopes`)
        self.scopes: dict[str, dict[str, str]] = {}

    # ------------------------------------------------------------------
    def _us(self, t_ns: int) -> float:
        """A ``perf_counter_ns`` reading as a wall-clock event time."""
        return (t_ns - self.t0_ns) / 1e3

    def span(self, name: str, **args: Any):
        """A context manager recording a nested wall-clock span around
        the ``with`` body."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def count(self, name: str, **values: float) -> None:
        """A counter sample on the wall-clock timeline: one Chrome
        ``"C"`` event whose ``args`` hold each named series' value."""
        if not self.enabled:
            return
        self._events.append({
            "name": name, "ph": "C",
            "ts": self._us(time.perf_counter_ns()),
            "pid": WALL_PID, "tid": 0,
            "parent": self._open[-1] if self._open else None,
            "args": {k: float(v) for k, v in values.items()},
        })

    def event_at(self, name: str, ts_s: float, dur_s: float, *,
                 tid: int = 0, **args: Any) -> None:
        """A retrospective *virtual-time* complete event.

        Used for simulated timelines (e.g. one event per serving-platform
        task: ``ts_s`` = arrival, ``dur_s`` = response time, ``tid`` =
        worker).  Virtual seconds map 1:1 onto trace microseconds×1e6
        under :data:`VIRTUAL_PID`, separate from the wall-clock track.
        """
        if not self.enabled:
            return
        self._events.append({
            "name": name, "ph": "X", "ts": ts_s * 1e6,
            "dur": max(dur_s, 0.0) * 1e6,
            "pid": VIRTUAL_PID, "tid": int(tid),
            "args": {k: _jsonable(v) for k, v in args.items()},
        })

    def counter_at(self, name: str, ts_s: float, value: float, *,
                   tid: int = 0) -> None:
        """A *virtual-time* counter sample (Perfetto counter track).

        Used by the timeline exporter to merge per-window metrics into
        the span trace: one ``ph: "C"`` sample per window start renders
        as a stepped counter track under :data:`VIRTUAL_PID`, aligned
        with the serving platform's task events."""
        if not self.enabled:
            return
        self._events.append({
            "name": name, "ph": "C", "ts": ts_s * 1e6,
            "pid": VIRTUAL_PID, "tid": int(tid),
            "args": {"value": float(value)},
        })

    # ------------------------------------------------------------------
    @property
    def events(self) -> list[dict]:
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()

    def aggregate(self) -> dict:
        """``{span name: {"count": n, "total_s": s}}`` over wall spans."""
        agg: dict[str, dict] = {}
        for ev in self._events:
            if ev.get("ph") != "X" or ev.get("pid") != WALL_PID:
                continue
            a = agg.setdefault(ev["name"], {"count": 0, "total_s": 0.0})
            a["count"] += 1
            a["total_s"] += ev.get("dur", 0.0) / 1e6
        for a in agg.values():
            a["total_s"] = round(a["total_s"], 6)
        return agg

    def export(self, path: str) -> str:
        """Write Chrome trace-event JSON (Perfetto-loadable)."""
        doc = {
            "traceEvents": self._events,
            "displayTimeUnit": "ms",
            "otherData": {
                "epoch0": self._epoch0,
                "process_names": {str(WALL_PID): "wall-clock",
                                  str(VIRTUAL_PID): "virtual-time"},
            },
        }
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


def _jsonable(v: Any):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: the computations an instruction calls: fused, loop condition and
#: body, reducers, branches
_CALLED = re.compile(r"\b(?:calls|condition|body|to_apply|true_computation"
                     r"|false_computation)=%?([\w.\-]+)"
                     r"|\bbranch_computations=\{([^}]*)\}")
_CONTROL = re.compile(r"\s(?:while|conditional|call)\(")
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def _innermost(op_name: str, names: frozenset) -> str:
    # the last component of an op_name is the primitive; the ones before
    # it are scopes and transforms, a transform wrapping the scope it
    # was applied under (``vmap(advance)``)
    for part in reversed(op_name.split("/")[:-1]):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in names:
            return part
    return ""


def op_scopes(hlo_text: str, names: Iterable[str]) -> dict[str, str]:
    """Each HLO instruction of a compiled module's text
    (``compiled.as_text()``) mapped to the innermost of the
    ``jax.named_scope`` names ``names`` that holds it, ``""`` for none.

    The keys are the names a device profile gives the operations.  An
    instruction's scope is read from its ``op_name``; a fusion's from
    its own, which XLA copies from the fused root, or else its root's;
    an instruction with neither (such as one XLA added to a loop's
    condition) takes that of the instruction calling its computation.
    Control flow (``while``, ``conditional``, ``call``) is left out: in
    a profile it holds other operations.
    """
    names = frozenset(names)
    op_of: dict[str, str | None] = {}
    comp_of: dict[str, str] = {}
    called: dict[str, list[str]] = {}
    root_of: dict[str, str] = {}
    caller: dict[str, str] = {}
    control: set[str] = set()
    comp = ""
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            head = line.split()
            comp = head[1 if head[0] == "ENTRY" and len(head) > 1 else 0]
            comp = comp.lstrip("%")
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        root, name, rest = m.groups()
        op = _OP_NAME.search(rest)
        op_of[name], comp_of[name] = (op.group(1) if op else None), comp
        if root:
            root_of[comp] = name
        body = rest.split(", metadata=")[0]
        comps = [c.strip().lstrip("%") for one, many in _CALLED.findall(body)
                 for c in ([one] if one else many.split(","))]
        called[name] = comps
        for c in comps:
            caller.setdefault(c, name)
        if _CONTROL.search(" " + body):
            control.add(name)

    def op_name(name: str, hops: int = 0) -> str:
        if op_of[name] is not None:
            return op_of[name]
        for c in called[name]:
            r = root_of.get(c)
            if r is not None and op_of[r] is not None:
                return op_of[r]
        up = caller.get(comp_of[name])
        return op_name(up, hops + 1) if up and hops < 64 else ""

    return {name: _innermost(op_name(name), names)
            for name in op_of if name not in control}


# --------------------------------------------------------------------------
# Process-wide default tracer (disabled until configured).
# --------------------------------------------------------------------------

_TRACER = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    global _TRACER
    old = _TRACER
    _TRACER = tracer
    return old


def configure_tracing(enabled: bool = True, *,
                      jax_bridge: bool = False) -> Tracer:
    """Swap in a fresh process-wide tracer; returns it."""
    tracer = Tracer(enabled=enabled, jax_bridge=jax_bridge)
    set_tracer(tracer)
    return tracer


def span(name: str, **args: Any):
    """Convenience: a span on the process-wide tracer."""
    return _TRACER.span(name, **args)
