"""Device time of the balancer's select per scan step, in microseconds:
the operations whose innermost named scope is ``select`` (the Hermes
kernel launch included), as a share of the leaf operations' time in the
slice inside a unit's scan, times ``step_device_us``
(:func:`chipbench.program_trace.step_scopes`)."""
from chipbench.program_trace import scope_us


def read(run):
    return scope_us(run, "select")
