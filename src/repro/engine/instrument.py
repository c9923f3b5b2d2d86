"""Engine-wide dispatch/trace odometers.

PR 2's ragged engine carried a single module-global compile counter
(``_RAGGED_TRACES``) so tests and benchmarks could assert the bucketing
invariant ("mixed-n traffic compiles at most one program per bucket"). This
module generalizes that into one small instrument shared by every jitted
engine entry point (:mod:`repro.engine.programs`):

* ``note_trace(kind)`` — called *inside* a jitted body, so it runs exactly
  once per XLA program traced for that entry point (retraces for new shapes
  count; cached same-shape calls don't);
* ``note_dispatch(kind)`` — called in the host-side wrapper, once per call.

Both are monotone odometers (never reset): consumers assert on *deltas*,
so independent test files and servers can't clobber each other. The
steady-state claim of the one-program refactor — "repeated same-shape calls
never retrace" — is exactly ``trace delta == 0`` while ``dispatch delta``
grows, and ``counters()`` emits the full snapshot into ``BENCH_engine.json``
so the dispatch-bound -> compute-bound shift is visible per PR.

A third odometer tallies distance work, in ``|x - y|`` terms (candidate
rows x reference rows x width). At trace time the round loop reports the
static block shape of every estimator call (:func:`note_score`) into the
open :func:`tally`; the program keeps that per-dispatch :class:`Work` by
signature, and the host wrapper adds it on each dispatch
(:func:`note_work`). ``called`` is the block the engine asks the estimator
for (band width and reference buffer included); ``computed`` is the same
block padded to the kernel's tile — what the kernel actually evaluates
(a tile that the kernel sizes from the call's shape, as the ℓ1 centrality
kernel does, is given as that rule). ``normed`` counts the operand rows
whose norms a Gram metric takes before the kernel on every call (cosine's
unit rows, ℓ2's squared norms; none for ℓ1): ``rows + refs`` per call.
``gathered`` counts the rows the round loop copies out of the corpus for
a call (``rows + refs``), where the estimator takes rows and not indices.
No device work, no host sync.
"""
from __future__ import annotations

import contextlib
from collections import Counter
from dataclasses import dataclass
from typing import Callable, Optional, Union

# A kernel's (row, reference, width) block, fixed or a rule of the call's
# (rows, refs, width).
Block = tuple[int, int, int]
Tile = Union[Block, Callable[[int, int, int], Block]]

_TRACES: Counter = Counter()
_DISPATCHES: Counter = Counter()
_CALLED: Counter = Counter()
_COMPUTED: Counter = Counter()
_NORMED: Counter = Counter()
_GATHERED: Counter = Counter()
_WORK = (_CALLED, _COMPUTED, _NORMED, _GATHERED)  # Work's fields, in order
_TALLIES: list = []          # open trace-time (Work, copies) collectors


@dataclass
class Work:
    """Distance terms of one dispatch: asked for (``called``) and evaluated
    after tile padding (``computed``); operand rows whose norms the calls
    take (``normed``); rows copied out of the corpus for them
    (``gathered``)."""
    called: int = 0
    computed: int = 0
    normed: int = 0
    gathered: int = 0


def _padded(size: int, block: int) -> int:
    return -(-size // block) * block


def note_score(rows: int, refs: int, width: int, *, runs: int = 1,
               tile: Optional[Tile] = None, norms: bool = False,
               gathers: bool = False) -> None:
    """Record one estimator call over a ``(rows, width) x (refs, width)``
    block that runs ``runs`` times per program run (call at trace time,
    outside any scan body: a scan body is traced once but runs once per
    scanned round). ``tile`` = (row, reference, width) block of the kernel
    that evaluates it, or the rule that gives that block for the call's
    ``(rows, refs, width)``; ``None`` = no padding. ``norms``: the call
    takes the norm of every operand row first. ``gathers``: its rows are
    copied out of the corpus for it. Does nothing outside a
    :func:`tally`."""
    if not _TALLIES:
        return
    work, copies = _TALLIES[-1]
    called = rows * refs * width
    if callable(tile):
        tile = tile(rows, refs, width)
    computed = called if tile is None else (
        _padded(rows, tile[0]) * _padded(refs, tile[1])
        * _padded(width, tile[2]))
    work.called += copies * runs * called
    work.computed += copies * runs * computed
    if norms:
        work.normed += copies * runs * (rows + refs)
    if gathers:
        work.gathered += copies * runs * (rows + refs)


@contextlib.contextmanager
def tally(copies: int = 1):
    """Collect the :func:`note_score` calls traced inside the block into a
    fresh :class:`Work`, each counted ``copies`` times (the batch size of a
    vmapped body, whose estimator calls are traced at per-query shapes)."""
    work = Work()
    _TALLIES.append((work, copies))
    try:
        yield work
    finally:
        _TALLIES.pop()


def note_work(kind: str, work: Work) -> None:
    """Add one dispatch's distance work to the ``kind`` odometer (host
    side, next to :func:`note_dispatch`)."""
    _CALLED[kind] += work.called
    _COMPUTED[kind] += work.computed
    _NORMED[kind] += work.normed
    _GATHERED[kind] += work.gathered


def work_counters() -> dict:
    """Snapshot of the work odometer (per kind: distance terms, rows
    normed and rows gathered)."""
    return {"called": dict(sorted(_CALLED.items())),
            "computed": dict(sorted(_COMPUTED.items())),
            "normed": dict(sorted(_NORMED.items())),
            "gathered": dict(sorted(_GATHERED.items()))}


def note_trace(kind: str) -> None:
    """Record one XLA trace of the ``kind`` entry point (call at trace time,
    i.e. from inside the jitted body)."""
    _TRACES[kind] += 1


def note_dispatch(kind: str) -> None:
    """Record one host-side call into the ``kind`` entry point."""
    _DISPATCHES[kind] += 1


def trace_count(kind: str | None = None) -> int:
    """Programs traced so far — for ``kind``, or in total."""
    return _TRACES[kind] if kind is not None else sum(_TRACES.values())


def dispatch_count(kind: str | None = None) -> int:
    """Dispatches so far — for ``kind``, or in total."""
    return _DISPATCHES[kind] if kind is not None else sum(_DISPATCHES.values())


def counters() -> dict:
    """Snapshot of both odometers (per kind), for benchmark emission."""
    return {"traces": dict(sorted(_TRACES.items())),
            "dispatches": dict(sorted(_DISPATCHES.items()))}


class deltas:
    """Context helper over the monotone odometers: snapshot on enter, deltas
    on demand — so consumers stop hand-rolling ``before = trace_count(...)``
    / ``after - before`` arithmetic::

        with instrument.deltas() as d:
            find_medoid(data, key)
        assert d.trace("medoid") <= 1      # programs traced inside the block
        assert d.dispatch("medoid") == 1   # dispatches inside the block

    Deltas are readable both mid-block and after exit (exit freezes them, so
    work done later never contaminates a recorded measurement). ``counters()``
    returns the per-kind nonzero deltas in the same shape as the module-level
    :func:`counters` snapshot — that per-block form is what benchmark cells
    emit, keeping ``BENCH_*.json`` rows independent of execution order.
    """

    def __enter__(self) -> "deltas":
        self._t0 = Counter(_TRACES)
        self._d0 = Counter(_DISPATCHES)
        self._w0 = tuple(map(Counter, _WORK))
        self._t1 = self._d1 = self._w1 = None
        return self

    def __exit__(self, *exc) -> None:
        self._t1 = Counter(_TRACES)
        self._d1 = Counter(_DISPATCHES)
        self._w1 = tuple(map(Counter, _WORK))

    def _now(self) -> tuple[Counter, Counter]:
        if self._t1 is not None:
            return self._t1, self._d1
        return _TRACES, _DISPATCHES

    def trace(self, kind: str | None = None) -> int:
        """Programs traced since enter — for ``kind``, or in total."""
        cur, _ = self._now()
        if kind is not None:
            return cur[kind] - self._t0[kind]
        return sum(cur.values()) - sum(self._t0.values())

    def dispatch(self, kind: str | None = None) -> int:
        """Dispatches since enter — for ``kind``, or in total."""
        _, cur = self._now()
        if kind is not None:
            return cur[kind] - self._d0[kind]
        return sum(cur.values()) - sum(self._d0.values())

    def work(self, kind: str) -> Work:
        """Distance work dispatched since enter by the ``kind`` programs."""
        now = self._w1 or _WORK
        return Work(*(c[kind] - c0[kind] for c, c0 in zip(now, self._w0)))

    def counters(self) -> dict:
        """Per-kind nonzero deltas, same shape as the module snapshot."""
        t, d = self._now()
        return {"traces": {k: v for k, v in sorted((t - self._t0).items())},
                "dispatches": {k: v
                               for k, v in sorted((d - self._d0).items())}}
