"""queue_wait_ms.serve: mean time, over the requests dispatched in the
window, from a request's admission to the start of the dispatch that
answers it, from the server's ``medoid_queue_wait_seconds`` histogram
(nothing where the server keeps no such histogram)."""
from bench import stats


def read(ctx):
    mean = stats.histogram_mean(ctx.counters, "medoid_queue_wait_seconds")
    return None if mean is None else 1e3 * mean
