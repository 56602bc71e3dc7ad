"""Device busy time of one scan step, in microseconds, from the trace
alone: over the slice inside a unit's scan, the busy time of the steps
seen whole over their count, the mean over the chips.  A step runs from
one start of the operation that marks it
(:func:`chipbench.devtrace.step_marker`) to the next within one program
execution; one step advances every replication on the chip by one
arrival."""


def read(run):
    devs = run.slices["steady"]["devices"] if run.slices else {}
    per = [d["step_busy_ns"] / d["steps"] for d in devs.values()
           if d["steps"]]
    if not per:
        return None
    return sum(per) / len(per) / 1e3
