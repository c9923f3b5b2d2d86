"""idle_share.pipeline: share of the traced window in which no operation
ran on the device, in %."""


def read(ctx):
    return ctx.trace.idle_share_pct() if ctx.trace is not None else None
