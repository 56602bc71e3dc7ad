"""Share of the completion-drain iterations the device ran that some
replication needed, over the window's units: the sum over scan steps of
the mean over replications of each one's iterations, over the sum of the
max (the program's ``drain.iterations`` counter,
:mod:`chipbench.program_trace`).  1 where every replication drains
alike; low where a few busy replications hold the rest."""
from chipbench.program_trace import drain_iterations


def read(run):
    it = drain_iterations(run)
    if it is None or not it["executed"]:
        return None
    return it["needed"] / it["executed"]
