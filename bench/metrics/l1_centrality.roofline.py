"""l1_centrality.roofline: the least time the queries of the traced window
need (the scheduled pulls' bytes at the HBM bandwidth, bench/counts.py;
no VPU peak has a source yet) over the device time of the l1 centrality
kernel, in %."""
from bench import peaks

# the Pallas l1 kernel, named in the trace after its jitted wrapper
KERNELS = (("kernel_centrality_sums", "tpu_custom_call"),)


def read(ctx):
    if ctx.trace is None:
        return None
    queries = sum(r["finish"] is not None for r in ctx.run["records"])
    least = queries * peaks.least_seconds(
        ctx.device_kind, bytes_=ctx.entry.work["bytes"])
    return peaks.share_pct(least, ctx.trace.kernel_s(KERNELS))
