"""served_per_s: requests answered inside the window over its seconds."""


def read(ctx):
    end = ctx.run["t_end"]
    done = sum(r["finish"] is not None and r["finish"] <= end
               for r in ctx.run["records"])
    return done / ctx.run["window_s"]
