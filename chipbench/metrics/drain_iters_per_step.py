"""Completion-drain loop iterations the device ran per scan step, over
the window's units: under ``vmap`` the loop runs until the busiest
replication is done, so a step runs the max over replications of the
iterations each needed (the program's ``drain.iterations`` counter,
:mod:`chipbench.program_trace`)."""
from chipbench.program_trace import drain_iterations


def read(run):
    it = drain_iterations(run)
    if it is None or not it["steps"]:
        return None
    return it["executed"] / it["steps"]
