"""Host spans, the profiler slice and its reduction to device numbers.

A traced run profiles two short slices of the window, not a whole unit:
one unit is tens of thousands of scan steps and millions of device
events.  One lies inside a unit's scan; the other reaches across the
boundary between two units, so it holds the host's work between them.
Each is reduced to one plain record (``devices``: per device the ``[name,
start_ns, dur_ns]`` of every operation it ran; ``programs``: the same of
every execution of a compiled program; ``t0``/``t1``: the slice's
bounds; ``boundary_ns``: when the second unit started on the host), all
on the harness's ``perf_counter_ns`` clock, so the host spans and the
device events share one time line.  The reduction from that record to
busy time, idle shares, scan steps, gaps and kernel times is
:func:`reduce`; the per-layer metric readers take their numbers from it.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import threading
import time
from collections import defaultdict

CLOCK_MARK = "chipbench.clock"
#: the device trace lines that hold one event per executed operation and
#: one per execution of a compiled program
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
#: the profiler runs on this long after the slice, so that the device's
#: last operations of the slice reach the trace
SETTLE_S = 0.02


class Spans:
    """The harness's host spans: ``(name, start_ns, end_ns)`` on the
    ``perf_counter_ns`` clock.  With a ``tracer`` (the program's
    :class:`repro.telemetry.spans.Tracer`) each span is also opened
    there, so it reaches the profiler through its bridge."""

    def __init__(self, tracer=None):
        self.events: list[tuple[str, int, int]] = []
        self.tracer = tracer

    @contextlib.contextmanager
    def __call__(self, name: str):
        cm = self.tracer.span(name) if self.tracer is not None \
            else contextlib.nullcontext()
        t0 = time.perf_counter_ns()
        try:
            with cm:
                yield
        finally:
            self.events.append((name, t0, time.perf_counter_ns()))


class Slicer(threading.Thread):
    """Profile two slices of the window, one after the other.

    ``boundary`` opens at the time the window loop gives
    :meth:`boundary_at`, a little before one unit's device work is due to
    end, and closes ``tail_s`` after the next unit starts (the loop calls
    :meth:`unit_started` at every unit's start), or ``max_s`` after it
    opened: it holds the end of one unit's device work, the host's work
    between the units and the start of the next unit's.  ``steady`` lasts
    ``length_s`` from ``delay_s`` into the first unit that starts once
    the boundary slice has been collected, so that neither slice sees the
    profiler at work on the other.  Runs beside the window's own thread,
    which is blocked in the program's calls meanwhile.  After each
    ``start_trace`` it opens one annotation whose host-clock start fixes
    the offset between the profile's clock and ``perf_counter_ns``; each
    trace is stopped ``SETTLE_S`` after its slice closes, so that the
    device's last operations of the slice are in it.
    """

    def __init__(self, log_dir: str, delay_s: float, length_s: float,
                 tail_s: float):
        super().__init__(daemon=True)
        self.log_dir, self.delay_s, self.length_s = log_dir, delay_s, length_s
        self.tail_s = tail_s
        #: per slice: its directory, clock mark, bounds, unit boundary and
        #: the seconds ``stop_trace`` took
        self.slices: dict[str, dict] = {}
        self.error: BaseException | None = None
        #: set once both slices have closed; collecting them takes longer
        self.sliced = threading.Event()
        self._begin: dict[str, int] = {}
        self._armed = {"boundary": threading.Event(),
                       "steady": threading.Event()}
        self._next_unit = threading.Event()
        self.max_s = 0.0

    def boundary_at(self, begin_ns: int, max_s: float) -> None:
        """Open the boundary slice at ``begin_ns``, for ``max_s`` at most."""
        self._begin["boundary"], self.max_s = begin_ns, max_s
        self._armed["boundary"].set()

    def unit_started(self, t_ns: int) -> None:
        """The window loop starts a unit at ``t_ns``."""
        b = self.slices.get("boundary")
        if b is None:
            return
        if b["t0"] is not None and t_ns > b["t0"] \
                and b["boundary_ns"] is None:
            b["boundary_ns"] = t_ns
            self._next_unit.set()
        elif b["stop_s"] is not None and "steady" not in self._begin:
            self._begin["steady"] = t_ns + int(self.delay_s * 1e9)
            self._armed["steady"].set()

    def _profile(self, name: str, wait) -> None:
        import jax
        rec = self.slices[name] = {
            "log_dir": os.path.join(self.log_dir, name), "t0": None,
            "t1": None, "mark": None, "boundary_ns": None, "stop_s": None}
        self._armed[name].wait()
        _sleep_until(self._begin[name])
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0            # host spans, not every call
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(rec["log_dir"], profiler_options=opts)
        try:
            rec["mark"] = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(CLOCK_MARK):
                pass
            rec["t0"] = time.perf_counter_ns()
            wait(rec)
            rec["t1"] = time.perf_counter_ns()
            time.sleep(SETTLE_S)
        finally:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            rec["stop_s"] = time.perf_counter() - t

    def _until_next_unit(self, rec: dict) -> None:
        last = rec["t0"] + int(self.max_s * 1e9)
        self._next_unit.wait(self.max_s)
        if rec["boundary_ns"] is not None:
            last = min(last, rec["boundary_ns"] + int(self.tail_s * 1e9))
        _sleep_until(last)

    def run(self) -> None:
        try:
            self._profile("boundary", self._until_next_unit)
            self._profile("steady", lambda rec: time.sleep(self.length_s))
        except BaseException as e:          # reported by the caller
            self.error = e
        finally:
            self.sliced.set()


def _sleep_until(t_ns: int) -> None:
    time.sleep(max(0.0, (t_ns - time.perf_counter_ns()) / 1e9))


def read_slice(rec: dict) -> dict:
    """The plain record of a finished slice (one of
    :attr:`Slicer.slices`): per device the operations and the program
    executions it ran, on the host clock, clipped to the slice; the
    slice's bounds; the host time of the unit boundary in it."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        rec["log_dir"], "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no profile written under {rec['log_dir']}")
    pd = ProfileData.from_file(paths[-1])
    offset = None
    lines: dict[str, dict[str, list]] = {OPS_LINE: {}, PROGRAMS_LINE: {}}
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:") and line.name in lines:
                # an operation's name is its HLO instruction's text: keep
                # the instruction's own name
                lines[line.name][plane.name] = [
                    [ev.name.split(" = ", 1)[0].lstrip("%"), ev.start_ns,
                     ev.duration_ns] for ev in line.events]
            elif plane.name.startswith("/host:") and offset is None:
                for ev in line.events:
                    if ev.name == CLOCK_MARK:
                        offset = rec["mark"] - ev.start_ns
                        break
    if offset is None:
        raise RuntimeError("the profile holds no clock mark")
    t0, t1 = rec["t0"], rec["t1"]

    def clip(evs):
        out = []
        for name, start, dur in evs:
            s, e = int(start + offset), int(start + offset + dur)
            if e > t0 and s < t1:
                out.append([name, max(s, t0), min(e, t1) - max(s, t0)])
        return out
    devices = {d: clip(evs) for d, evs in lines[OPS_LINE].items()}
    return {"devices": devices,
            "programs": {d: clip(evs)
                         for d, evs in lines[PROGRAMS_LINE].items()},
            "t0": t0, "t1": t1, "boundary_ns": rec["boundary_ns"]}


def union(intervals) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def nest(ops) -> tuple[list, list]:
    """Leaf intervals and per-operation self time of one device line.

    Operations on a line nest: a loop or a conditional holds the
    operations of its body.  Busy time is the union of the leaves;
    an operation's self time is its duration less its direct children's.
    """
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    self_ns = [d for _, _, d in ops]
    parent_of_any = [False] * len(ops)
    stack: list[int] = []
    for i, (_, s, d) in enumerate(ops):
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent_of_any[stack[-1]] = True
            self_ns[stack[-1]] -= d
        stack.append(i)
    leaves = [(s, s + d) for (_, s, d), p in zip(ops, parent_of_any)
              if not p]
    return leaves, [(name, t) for (name, _, _), t in zip(ops, self_ns)]


def time_before(intervals):
    """``f(t)``: nanoseconds of the disjoint sorted ``intervals`` before
    ``t``."""
    starts = [s for s, _ in intervals]
    cum = [0]
    for s, e in intervals:
        cum.append(cum[-1] + e - s)

    def f(t: int) -> int:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0
        s, e = intervals[i]
        return cum[i] + min(t, e) - s
    return f


def step_marker(ops, t0: int, t1: int) -> str | None:
    """The operation that marks a scan step on one device line.

    Of the operations run at least twice wholly inside the slice, the one
    with the most time.  An operation's events lie inside those of the
    operation that holds it, so this is one that the scan's body runs
    itself, once per step: the program's outermost loop spans an edge of
    the slice and is left out, and anything nested in a step has less
    time than the step's own operation that holds it.
    """
    total: dict[str, int] = defaultdict(int)
    count: dict[str, int] = defaultdict(int)
    for name, s, d in ops:
        if s > t0 and s + d < t1:
            total[name] += d
            count[name] += 1
    cands = [n for n in total if count[n] >= 2]
    return max(cands, key=lambda n: (total[n], n)) if cands else None


def steps(ops, programs, busy_iv, t0: int, t1: int) -> tuple:
    """``(marker, steps, busy_ns)``: the scan steps seen whole in the
    slice and the device's busy time in them.  A step runs from one
    start of the marker to the next within one program execution, so
    the host's time between programs is in no step."""
    marker = step_marker(ops, t0, t1)
    starts = sorted(s for n, s, d in ops
                    if n == marker and s > t0 and s + d < t1)
    before = time_before(busy_iv)
    n = busy = 0
    for _, ps, pd in programs:
        inside = [s for s in starts if ps <= s < ps + pd]
        n += max(len(inside) - 1, 0)
        if len(inside) > 1:
            busy += before(inside[-1]) - before(inside[0])
    return marker, n, busy


def reduce(record: dict, spans, top: int = 10) -> dict:
    """Busy time, idle shares, scan steps, operation self times and
    attributed gaps, per device of the slice.

    A device is busy while a leaf operation runs on it (the time a loop
    spends between its body's operations counts as idle).  ``idle`` is
    the idle share of the time in which a program ran; ``unit_gap_ns``
    the time in which none ran, where the slice reaches from inside one
    program across the unit boundary to inside another (else ``None``).
    ``spans`` are ``(name, start_ns, end_ns)`` host spans on the same
    clock; each of the ``top`` longest idle gaps is named after the
    innermost span covering its middle (``"none"`` where the host was in
    no span).
    """
    t0, t1 = record["t0"], record["t1"]
    window = t1 - t0
    boundary = record.get("boundary_ns")
    per_dev = {}
    gaps = []
    self_total: dict[str, int] = defaultdict(int)
    count: dict[str, int] = defaultdict(int)
    for dev, ops in sorted(record["devices"].items()):
        leaves, self_ns = nest(ops)
        busy_iv = union(leaves)
        busy = sum(e - s for s, e in busy_iv)
        programs = record.get("programs", {}).get(dev, [])
        prog_iv = union((s, s + d) for _, s, d in programs)
        in_prog = sum(e - s for s, e in prog_iv)
        spans_edge = (prog_iv and prog_iv[0][0] == t0
                      and prog_iv[-1][1] == t1)
        marker, n_steps, step_busy = steps(ops, programs, busy_iv, t0, t1)
        per_dev[dev] = {
            "busy_ns": busy, "program_ns": in_prog, "ops": len(ops),
            "idle": 1.0 - busy / in_prog if in_prog else None,
            "marker": marker, "steps": n_steps, "step_busy_ns": step_busy,
            "unit_gap_ns": (window - in_prog
                            if spans_edge and boundary is not None
                            and t0 < boundary < t1 else None)}
        edges = [t0] + [x for iv in busy_iv for x in iv] + [t1]
        gaps.extend((b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a)
        for name, t in self_ns:
            self_total[name] += t
            count[name] += 1
    gaps = sorted(gaps, reverse=True)[:top]
    # per-op totals are over every device; count and mean per device
    n_dev = max(len(per_dev), 1)

    def host_at(t: int) -> str:
        inside = [(s, n) for n, s, e in spans if s <= t < e]
        return max(inside)[1] if inside else "none"

    return {
        "window_ns": window,
        "devices": per_dev,
        "self_ns": {k: v / n_dev for k, v in self_total.items()},
        "count": {k: v / n_dev for k, v in count.items()},
        "gaps": [(host_at((a + b) // 2), g) for g, a, b in gaps],
    }
