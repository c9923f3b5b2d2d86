"""norm_rows.netflix: operand rows whose norms the centrality calls take per
query (the program's work odometer, ``normed``: cosine's unit rows, the
candidates' and the references' of every call), over n. Reads nothing
where the program's odometer keeps no such tally."""


def read(ctx):
    try:
        from repro.engine import instrument

        normed = instrument.work_counters()["normed"].get("medoid", 0)
        dispatches = instrument.dispatch_count("medoid")
    except (ImportError, AttributeError, KeyError):
        return None
    if not dispatches or not normed:
        return None
    return normed / dispatches / int(ctx.config["n"])
