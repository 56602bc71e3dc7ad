"""Mean host time, in milliseconds, of one chunk's feed: the program's
``stream.feed`` span, slicing the chunk's host inputs, moving them to
the device and sharding them, without the dispatch
(:mod:`chipbench.program_trace`)."""
from chipbench.program_trace import span_ms


def read(run):
    return span_ms(run, "stream.feed")
