"""gather_rows.pipeline: rows the round loop copies out of the corpus per
query (the program's work odometer, ``gathered``: the candidate and
reference rows of every estimator call that takes rows rather than
indices), over n. 0 where every call fetches its rows by index; nothing
where the odometer keeps no such tally or no query ran."""


def read(ctx):
    try:
        from repro.engine import instrument

        gathered = instrument.work_counters()["gathered"].get("medoid", 0)
        dispatches = instrument.dispatch_count("medoid")
    except (ImportError, AttributeError, KeyError):
        return None
    if not dispatches:
        return None
    return gathered / dispatches / int(ctx.config["n"])
