"""Distance work per dispatch, from the program's work odometer.

``repro.engine.instrument`` tallies, for every dispatch of a medoid
program, the ``|x - y|`` terms its round loop asks the estimator for
(``called``: band widths and reference buffers included) and the terms
the kernel evaluates once each call is padded to its tiles
(``computed``). Both are static per signature, so totals over the
process divided by its dispatches give one dispatch's work whenever every
dispatch has the same shape, as in a closed loop on one corpus.
"""
from __future__ import annotations


def per_dispatch(kind: str):
    """``(called, computed)`` terms per dispatch of the ``kind`` programs;
    None where the program keeps no work odometer or ran no dispatch."""
    try:
        from repro.engine import instrument

        work = instrument.work_counters()
        dispatches = instrument.dispatch_count(kind)
    except (ImportError, AttributeError):
        return None
    called = work["called"].get(kind, 0)
    if not dispatches or not called:
        return None
    return called / dispatches, work["computed"].get(kind, 0) / dispatches
