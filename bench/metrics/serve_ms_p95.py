"""serve_ms_p95: 95th percentile of the same latencies as serve_ms_p50; a
request still open at the window's close counts with its age then."""
from bench import loadgen, stats


def read(ctx):
    return stats.percentile(loadgen.latency_ms(ctx.run), 95)
