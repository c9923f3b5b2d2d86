"""query_ms: the window's length over the queries completed in it (host
clock). Closed loop, one caller: each query ends when its answer is on the
host, and the window ends with the last query."""


def read(ctx):
    done = sum(r["finish"] is not None for r in ctx.run["records"])
    return ctx.run["window_s"] * 1e3 / done if done else None
