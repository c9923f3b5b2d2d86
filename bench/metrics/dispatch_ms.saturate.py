"""dispatch_ms.saturate: mean wall time of a steady dispatch in the window,
from the server's dispatch-latency histogram."""
from bench import stats


def read(ctx):
    mean = stats.histogram_mean(ctx.counters, "medoid_dispatch_seconds",
                                phase="steady")
    return None if mean is None else 1e3 * mean
