"""dot_centrality.roofline: the least time the requests served in the
traced window need (max of 2 x multiply-adds at the bf16 peak and bytes at
the HBM bandwidth, from each request's own n, bench/counts.py) over the
device time of the Gram centrality kernel, in %."""
from bench import peaks

# the Pallas Gram kernel, named in the trace after its jitted wrapper
KERNELS = (("kernel_centrality_sums", "tpu_custom_call"),)


def read(ctx):
    if ctx.trace is None:
        return None
    served = ctx.counters[1]["served"] - ctx.counters[0]["served"]
    least = 0.0
    for _, s in ctx.entry.served[len(ctx.entry.served) - served:]:
        w = ctx.entry.work[s]
        least += peaks.least_seconds(ctx.device_kind, flops=2.0 * w["terms"],
                                     bytes_=w["bytes"])
    return peaks.share_pct(least, ctx.trace.kernel_s(KERNELS))
