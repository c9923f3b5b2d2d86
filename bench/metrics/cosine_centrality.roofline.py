"""cosine_centrality.roofline: the least time the queries of the traced
window need (max of 2 x the scheduled multiply-adds at the bf16 peak and
the scheduled pulls' bytes at the HBM bandwidth, bench/counts.py) over the
device time of the Gram centrality kernel, in %."""
from bench import peaks

# the Pallas Gram kernel, named in the trace after its jitted wrapper
KERNELS = (("kernel_centrality_sums", "tpu_custom_call"),)


def read(ctx):
    if ctx.trace is None:
        return None
    queries = sum(r["finish"] is not None for r in ctx.run["records"])
    w = ctx.entry.work
    least = queries * peaks.least_seconds(
        ctx.device_kind, flops=2.0 * w["terms"], bytes_=w["bytes"])
    return peaks.share_pct(least, ctx.trace.kernel_s(KERNELS))
