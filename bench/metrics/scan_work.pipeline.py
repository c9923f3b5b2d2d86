"""scan_work.pipeline: distance terms the round loop asks the estimator for
per query (the program's work odometer, ``called``: scan bands score their
full width against their widest reference buffer), over the terms the
round schedule needs (bench/counts.py)."""
from bench import work


def read(ctx):
    per = work.per_dispatch("medoid")
    return None if per is None else per[0] / ctx.entry.work["terms"]
