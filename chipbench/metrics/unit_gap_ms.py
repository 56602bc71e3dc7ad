"""Device time, in milliseconds, in which no program ran, over the slice
that reaches from inside one unit's program across the unit boundary to
inside the next unit's: the host's hold on the chip once per unit (the
read-out, the summary, the next unit's inputs and dispatch), the mean
over the chips.  Nothing is read where the slice missed the boundary."""


def read(run):
    devs = run.slices["boundary"]["devices"] if run.slices else {}
    gap = [d["unit_gap_ns"] for d in devs.values()
           if d["unit_gap_ns"] is not None]
    if not gap or len(gap) < len(devs):
        return None
    return sum(gap) / len(gap) / 1e6
