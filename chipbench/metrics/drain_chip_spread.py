"""How far the slowest chip's completion drain ran beyond the average
chip's, over the window's units: the program's ``drain.iterations``
counter (:mod:`chipbench.program_trace`) under a ``rep`` mesh, its
``executed_max`` (the largest chip's sum over scan steps of the max
over that chip's block of replications) over its ``executed`` (the same
sum, the mean over the chips).  1 where every chip's block drained
alike; above 1 the slowest chip holds the horizon.  A program whose
counter has no ``executed_max`` reads ``None``."""
from chipbench.program_trace import COUNTER, on_device, tracer, window_events


def read(run):
    if not on_device(run):
        return None
    evs = window_events(tracer().events, "C", COUNTER)
    if not evs or any("executed_max" not in e["args"] for e in evs):
        return None
    executed = sum(e["args"]["executed"] for e in evs)
    if not executed:
        return None
    return sum(e["args"]["executed_max"] for e in evs) / executed
