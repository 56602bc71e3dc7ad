"""Mean host time, in milliseconds, of one sweep's summary: the
program's ``metrics.summarize_batch`` span, per-replication and pooled
percentiles on the host (:mod:`chipbench.program_trace`)."""
from chipbench.program_trace import span_ms


def read(run):
    return span_ms(run, "metrics.summarize_batch")
