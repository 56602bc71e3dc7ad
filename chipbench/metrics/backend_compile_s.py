"""Seconds of backend compilation in set-up, as ``jax.monitoring``
reports them; on a persistent-cache hit this is the time to load the
compiled program.  Cache hits and misses are on an earlier stderr line."""


def read(run):
    key = ("setup", "backend_compile")
    if not run.monitor.counts.get(key):
        return None
    return run.monitor.seconds[key]
