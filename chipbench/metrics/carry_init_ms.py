"""Mean host time, in milliseconds, of one horizon's initial carry: the
program's ``stream.init`` span, the eager programs that build the batched
carry before the first chunk (:mod:`chipbench.program_trace`)."""
from chipbench.program_trace import span_ms


def read(run):
    return span_ms(run, "stream.init")
