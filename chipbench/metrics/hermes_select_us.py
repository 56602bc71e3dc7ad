"""Mean device duration of one launch of the Hermes Pallas kernel
(``name="hermes_select"``) in the slice inside a unit's scan, in
microseconds.  The scan runs one launch per step, vmapped over the
replications on a chip."""

KERNEL = "hermes_select"


def read(run):
    if not run.slices:
        return None
    red = run.slices["steady"]
    names = [n for n in red["count"] if KERNEL in n]
    count = sum(red["count"][n] for n in names)
    if not count:
        return None
    return sum(red["self_ns"][n] for n in names) / count / 1e3
