"""Share of the time in which a program ran on the device that no
operation of it ran, over the slice inside a unit's scan: the gaps
between a program's operations, the mean over the chips of the cell
(each chip's share is on an earlier stderr line).  The host's time
between programs is not in it: that is ``unit_gap_ms``."""


def read(run):
    devs = run.slices["steady"]["devices"] if run.slices else {}
    idle = [d["idle"] for d in devs.values() if d["idle"] is not None]
    if not idle:
        return None
    return sum(idle) / len(idle)
