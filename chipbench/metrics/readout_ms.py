"""Mean host time, in milliseconds, of one unit's read-out: the
program's ``engine.readout`` span, its outputs from the device to the
host (:mod:`chipbench.program_trace`)."""
from chipbench.program_trace import span_ms


def read(run):
    return span_ms(run, "engine.readout")
