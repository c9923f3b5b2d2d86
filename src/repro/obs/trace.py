"""Structured trace events: profiler spans, JSONL records, profiler hooks.

:func:`span` is the one host-span primitive: a
``jax.profiler.TraceAnnotation`` named ``medoid.<name>`` with its ids as
annotation metadata, so host phases land on the host plane of the same
profiler trace as the device's ``XLA Ops``. It is entered identically
whether or not a profiler is attached (Pallas kernels carry the Python
frames they were traced under into their compiled program, so a span
present only under a profiler would change the compile cache's key);
with none attached it costs one C++ ``TraceMe`` that records nothing.

A :class:`TraceSession` turns the engine's device-resident telemetry buffers
(:mod:`repro.obs.telemetry`) and the trace/dispatch odometers
(:mod:`repro.engine.instrument`) into an append-only JSONL event stream a
human (or the CI validator, :mod:`repro.obs.validate`) can read back:

    {"event": "session", "seq": 0, "ts": ..., "version": 1, ...}
    {"event": "span", "name": "dispatch", "dur_s": ..., "traces": {...}, ...}
    {"event": "round", "r": 0, "survivors": 512, "num_refs": 23, ...}
    {"event": "select", "winner": 318, "pulls": 15402, ...}

Every record carries ``event`` (its type), a monotone ``seq``, and a wall
``ts``. Session spans (``TraceSession.span(name)``, built on :func:`span`)
wrap host-side phases — trace, compile, dispatch, select — and record their
duration plus the *deltas* of the engine odometers while the span was open
(so ``traces > 0`` inside a dispatch span is exactly "this dispatch compiled
something"). Round events are emitted
from a telemetry dict by :meth:`TraceSession.record_rounds`; their per-round
``pulls`` sum to the scheduled totals the facade reports, which the
validator checks against the enclosing ``select`` event.

``profiler_dir=...`` (off by default) brackets the whole session in
``jax.profiler.start_trace`` / ``stop_trace`` (written on ``close()``).
"""
from __future__ import annotations

import contextlib
import json
import math
import time
from typing import IO, Optional

import jax

from repro.engine import instrument

SCHEMA_VERSION = 1
SPAN_PREFIX = "medoid."


def span(name: str, **ids):
    """Context manager: the host span ``medoid.<name>`` on the profiler's
    clock, carrying ``ids`` (scalars or strings without commas) as its
    metadata. Always entered; it records only while a profiler runs."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **ids)


def _jsonable(v):
    """Coerce numpy / jax scalars and non-finite floats to JSON-safe values
    (NaN/Inf become null — JSON has no spelling for them)."""
    if hasattr(v, "item") and not isinstance(v, (str, bytes)):
        try:
            v = v.item()
        except (TypeError, ValueError):
            v = str(v)
    if isinstance(v, float) and not math.isfinite(v):
        return None
    return v


class TraceSession:
    """One JSONL trace stream (events also kept in memory for programmatic
    consumers). Usable as a context manager; ``close()`` is idempotent."""

    def __init__(self, path: Optional[str] = None, *,
                 profiler_dir: Optional[str] = None,
                 meta: Optional[dict] = None):
        self._fh: Optional[IO[str]] = open(path, "w") if path else None
        self.path = path
        self.profiler_dir = profiler_dir
        self.events: list[dict] = []
        self._seq = 0
        self._closed = False
        self._profiling = False
        if profiler_dir:
            jax.profiler.start_trace(profiler_dir)
            self._profiling = True
        self.event("session", version=SCHEMA_VERSION, **(meta or {}))

    # ------------------------------- emission -------------------------------
    def event(self, event: str, **fields) -> dict:
        """Append one record to the stream (and the in-memory list)."""
        if self._closed:
            raise RuntimeError("TraceSession is closed")
        rec = {"event": event, "seq": self._seq, "ts": round(time.time(), 6)}
        rec.update({k: _jsonable(v) for k, v in fields.items()})
        self._seq += 1
        self.events.append(rec)
        if self._fh is not None:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        return rec

    @contextlib.contextmanager
    def span(self, name: str, **fields):
        """Wrap a host-side phase in :func:`span` (``fields`` as its ids) and
        emit one ``span`` record on exit with ``dur_s`` and the engine
        odometer deltas observed while open."""
        t0 = time.perf_counter()
        with instrument.deltas() as d, span(name, **fields):
            yield
        self.event("span", name=name, dur_s=round(time.perf_counter() - t0, 6),
                   traces=d.counters()["traces"],
                   dispatches=d.counters()["dispatches"], **fields)

    def record_rounds(self, telemetry: dict, *, slot: Optional[int] = None,
                      **fields) -> None:
        """Emit one ``round`` event per telemetry row. ``telemetry`` is the
        host-side dict from :func:`repro.obs.telemetry_to_host` (leaves
        ``(R,)``, or ``(B, R)`` from the batched/ragged engines — pass
        ``slot`` to pick one query's rows; batched rows share their schedule
        columns, so slot 0 is representative for pull accounting)."""
        tel = telemetry
        if slot is not None:
            tel = {k: v[slot] for k, v in telemetry.items()}
        rows = len(next(iter(tel.values()))) if tel else 0
        for r in range(rows):
            self.event("round", r=r,
                       **{k: tel[k][r] for k in tel}, **fields)

    def record_result(self, result, **fields) -> None:
        """Emit a :class:`repro.api.MedoidResult`: its per-round telemetry
        (when the query ran with ``telemetry=True``) followed by the
        ``select`` record whose ``pulls`` the round rows sum to."""
        if getattr(result, "telemetry", None) is not None:
            self.record_rounds(result.telemetry)
        self.event("select", winner=result.medoid, pulls=result.pulls,
                   n=result.n, algo=result.algo, metric=result.metric,
                   backend=result.backend, **fields)

    # ------------------------------- lifecycle ------------------------------
    def close(self) -> None:
        if self._closed:
            return
        self.event("session_end", events=self._seq)
        self._closed = True
        if self._profiling:
            jax.profiler.stop_trace()
            self._profiling = False
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self) -> "TraceSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
