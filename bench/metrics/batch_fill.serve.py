"""batch_fill.serve: real requests over batch slots per dispatch in the
window, in %, from the server's occupancy histogram."""
from bench import stats


def read(ctx):
    mean = stats.histogram_mean(ctx.counters, "medoid_batch_occupancy")
    return None if mean is None else 100.0 * mean
