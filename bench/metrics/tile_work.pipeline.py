"""tile_work.pipeline: distance terms the kernel evaluates per query once
each call is padded to its tiles (the program's work odometer,
``computed``), over the terms the round loop asks for (``called``)."""
from bench import work


def read(ctx):
    per = work.per_dispatch("medoid")
    return None if per is None else per[1] / per[0]
