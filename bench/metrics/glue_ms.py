"""glue_ms: device busy time per query outside the distance kernels
(gathers, transposes, reference sampling, sorts and top-k, reductions)."""

# the Pallas distance kernels, named in the trace after their jitted wrapper
KERNELS = (("kernel_centrality_sums", "tpu_custom_call"),)


def read(ctx):
    if ctx.trace is None:
        return None
    queries = sum(r["finish"] is not None for r in ctx.run["records"])
    if not queries:
        return None
    return (ctx.trace.busy_s - ctx.trace.kernel_s(KERNELS)) * 1e3 / queries
