"""Mean host time, in milliseconds, of one placement on the ``rep``
mesh: the program's ``stream.shard`` span around each ``shard_reps``
call of a horizon (the function homes, the initial carry, each chunk's
inputs), which puts every leaf with its replication axis split over the
chips (:mod:`chipbench.program_trace`).  A program that records no such
span, as without a mesh, reads ``None``."""
from chipbench.program_trace import span_ms


def read(run):
    return span_ms(run, "stream.shard")
