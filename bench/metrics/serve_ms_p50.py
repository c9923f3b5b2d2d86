"""serve_ms_p50: median, over every request due in the window, of the time
from its due time to its answer on the host (host clock)."""
from bench import loadgen, stats


def read(ctx):
    return stats.percentile(loadgen.latency_ms(ctx.run), 50)
