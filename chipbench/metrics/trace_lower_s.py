"""Seconds of jaxpr tracing and MLIR lowering in set-up, as
``jax.monitoring`` reports them.  Paid on every run, cache hit or not."""


def read(run):
    keys = [("setup", "jaxpr_trace"), ("setup", "lower")]
    if not any(run.monitor.counts.get(k) for k in keys):
        return None
    return sum(run.monitor.seconds.get(k, 0.0) for k in keys)
