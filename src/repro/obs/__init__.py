"""Observability: device-resident telemetry, trace events, serving metrics.

Three layers, lowest first:

* :mod:`repro.obs.telemetry` — the fixed-shape per-round telemetry pytree
  the engine carries through its banded ``lax.scan`` (device-path: pure jnp,
  host-sync-guarded alongside the engine package);
* :mod:`repro.obs.trace` — :func:`span`, the ``medoid.<name>`` host span
  on the profiler's clock, and :class:`TraceSession`, JSONL
  span/round/select events built on it;
* :mod:`repro.obs.metrics` — counters/histograms with a Prometheus text
  exposition, the :class:`ServerMetrics` bundle of the medoid server, and
  the engine-odometer exposition.

``repro.engine.halving`` imports :mod:`repro.obs.telemetry` from inside the
round loop, so this package sits BELOW the engine in the layering — the
host-side modules (which import :mod:`repro.engine.instrument`) are loaded
lazily to keep that edge acyclic.
"""
from __future__ import annotations

from repro.obs import telemetry

__all__ = ["MetricsRegistry", "ServerMetrics", "TraceSession",
           "instrument_exposition", "span", "telemetry", "telemetry_to_host"]

_LAZY = {
    "TraceSession": ("repro.obs.trace", "TraceSession"),
    "span": ("repro.obs.trace", "span"),
    "MetricsRegistry": ("repro.obs.metrics", "MetricsRegistry"),
    "ServerMetrics": ("repro.obs.metrics", "ServerMetrics"),
    "instrument_exposition": ("repro.obs.metrics", "instrument_exposition"),
}


def __getattr__(name: str):
    try:
        modname, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}"
                             ) from None
    import importlib

    return getattr(importlib.import_module(modname), attr)


def telemetry_to_host(tel) -> dict:
    """Fetch a device telemetry pytree to host numpy arrays (one transfer
    per leaf, after the answer is already on host — never inside a jitted
    body)."""
    import numpy as np

    return {k: np.asarray(v) for k, v in tel.items()}
