"""Mean host wall time, in milliseconds, between successive chunk
dispatches of ``simulate_stream`` (its ``chunk_callback``): slicing the
host inputs, moving them to the device and dispatching one chunk."""


def read(run):
    if not run.feed_s:
        return None
    return sum(run.feed_s) / len(run.feed_s) * 1e3
