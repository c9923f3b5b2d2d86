"""Correlated Sequential Halving (Algorithm 1 of the paper) — engine adapters.

As of PR 4 the round loop itself lives in :mod:`repro.engine.halving`
(:func:`~repro.engine.run_halving`, parameterized by an
:class:`~repro.engine.ArmEstimator`); this module keeps the paper-facing
medoid entry points as thin adapters over it:

* :func:`correlated_sequential_halving` — the research-level function
  returning the full :class:`CorrSHResult` (medoid, pulls, rounds, final
  estimates);
* ``_medoid_impl`` / ``_batch_impl`` / :func:`ragged_medoids` — the
  internal entry points the facade (:mod:`repro.api`), the serving layer,
  and the clustering refiners dispatch to. Since PR 6 these are thin
  wrappers over the cached jitted programs of
  :mod:`repro.engine.programs` — keyed by (bucket, schedule config,
  backend), so repeated same-shape calls never retrace;
* :func:`corr_sh_medoid`, :func:`corr_sh_medoid_batch`,
  :func:`corr_sh_medoid_ragged` — the pre-facade public names, kept
  signature-compatible as deprecated shims (one ``DeprecationWarning`` per
  process; use :mod:`repro.api`).

Everything the old in-module loops guaranteed still holds — static shapes
from :func:`~repro.engine.schedule.round_schedule`, shared per-round
reference draws, bit-exact full-bucket parity between the ragged and dense
paths — and is now pinned against verbatim pre-refactor loop snapshots by
``tests/test_engine.py``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import DistanceBackend
from repro.core.bucketing import DEFAULT_MIN_BUCKET, bucket_n
from repro.deprecation import warn_once
from repro.engine import (HalvingProblem, Round, medoid_centrality,
                          round_schedule, run_halving, schedule_pulls)
from repro.engine import instrument, programs

PairwiseFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
BackendLike = Union[str, DistanceBackend, None]

__all__ = [
    "CorrSHResult", "Round", "corr_sh_medoid", "corr_sh_medoid_batch",
    "corr_sh_medoid_ragged", "correlated_sequential_halving",
    "ragged_compile_count", "ragged_medoids", "round_schedule",
    "schedule_pulls",
]


@dataclass
class CorrSHResult:
    medoid: jnp.ndarray                 # scalar int32 index
    pulls: int                          # total distance computations (static)
    rounds: list[Round] = field(default_factory=list)
    theta_hat: Optional[jnp.ndarray] = None  # final-round estimates


def correlated_sequential_halving(
    data: jnp.ndarray,
    budget: int,
    key: jax.Array,
    metric: str = "l2",
    pairwise_fn: Optional[PairwiseFn] = None,
    backend: BackendLike = "reference",
) -> CorrSHResult:
    """Run Algorithm 1. ``data: (n, d)``; returns the medoid index.

    ``backend`` selects the distance implementation from the registry in
    :mod:`repro.core.backend` (``"reference"``, ``"pallas_pairwise"``,
    ``"pallas_fused"``, ``"pallas_fused_topk"``). ``pairwise_fn`` still
    overrides the distance block directly (legacy hook; takes precedence
    over ``backend``).
    """
    n = int(data.shape[0])
    rounds = round_schedule(n, budget)
    if not rounds:  # n == 1
        return CorrSHResult(medoid=jnp.zeros((), jnp.int32), pulls=0)
    problem = HalvingProblem(
        data, medoid_centrality(backend, metric, pairwise_fn=pairwise_fn))
    out = run_halving(problem, rounds, backend, key=key)
    return CorrSHResult(
        medoid=out.winner,
        pulls=sum(x.pulls for x in rounds[: out.r_stop + 1]),
        rounds=rounds[: out.r_stop + 1],
        theta_hat=out.theta,
    )


def _medoid_impl(data: jnp.ndarray, key: jax.Array, *, budget: int,
                 metric: str = "l2", backend: str = "reference",
                 telemetry: bool = False,
                 precision: str = "fp32", error_model: str = "probe"):
    """Single-query medoid (the facade's ``find_medoid`` kernel): dispatch
    the cached jitted program for this (budget, metric, backend) config.
    With ``telemetry`` the program returns ``(index, per-round telemetry)``
    — same single dispatch (see :mod:`repro.obs.telemetry`). Quantized
    programs (``precision != "fp32"``) additionally return the traced
    ``verified`` certificate right after the index."""
    instrument.note_dispatch("medoid")
    fn = programs.medoid_program(budget=budget, metric=metric,
                                 backend=backend, telemetry=telemetry, precision=precision,
                                 error_model=error_model)
    return fn(data, key)


def _batch_impl(data: jnp.ndarray, key: jax.Array, *, budget: int,
                metric: str = "l2", backend: str = "reference",
                telemetry: bool = False,
                precision: str = "fp32", error_model: str = "probe"):
    """Batched multi-query medoid: ``data (B, n, d) -> (B,)`` indices
    (``((B,), telemetry)`` with ``telemetry``).

    All queries share one static round schedule (shapes depend only on
    ``(n, budget)``), so the whole batch is a single ``vmap`` of the round
    loop — one XLA program, B independent reference draws (the key is split
    per query; estimates stay independent across the batch). This is the
    k-medoids / multi-tenant serving workload: B candidate sets answered in
    one device dispatch.
    """
    if data.ndim != 3:
        raise ValueError(f"expected (B, n, d) batch, got shape {data.shape}")
    instrument.note_dispatch("batch")
    fn = programs.batch_program(budget=budget, metric=metric,
                                backend=backend, telemetry=telemetry, precision=precision,
                                error_model=error_model)
    return fn(data, key)


# ---------------------------------------------------------------------------
# ragged multi-query engine: per-query n via padding + validity masking
# ---------------------------------------------------------------------------

def ragged_compile_count() -> int:
    """Number of distinct XLA programs traced by the ragged engine so far
    (the ``"ragged"`` odometer of :mod:`repro.engine.instrument` — bumped at
    *trace* time, exactly once per compiled program). The bucketing
    invariants ("a sweep over mixed-n traffic compiles at most one program
    per bucket") are asserted against this counter by the service tests and
    bench_ragged."""
    return instrument.trace_count("ragged")


def ragged_medoids(data: jnp.ndarray, lengths, key: jax.Array, *,
                   budget: int, metric: str = "l2",
                   backend: str = "reference",
                   min_bucket: int = DEFAULT_MIN_BUCKET,
                   telemetry: bool = False,
                   precision: str = "fp32", error_model: str = "probe"):
    """Ragged multi-query medoid: ``data (B, n_max, d)`` + per-query
    ``lengths (B,)`` -> ``(B,)`` medoid indices (each < its query's length);
    ``((B,) indices, telemetry)`` with ``telemetry``.

    Queries of heterogeneous sizes ride one XLA program: ``n_max`` is rounded
    up to a power-of-two bucket (see :mod:`repro.core.bucketing` — this caps
    compilations across arbitrary traffic), one static round schedule is
    computed from ``(n_bucket, budget)``, and per-query padding is handled by
    in-round validity masking — padded arms take +inf centrality and are
    never counted as references. A query occupying its full bucket
    (``length == n_bucket``) follows the exact same schedule, reference draws
    and arithmetic as a single-query ``find_medoid(data[i], split(key, B)[i])``.

    Raises ``ValueError`` on an all-padding query (``length < 1``) or a
    length exceeding ``n_max`` — rejected at admission, before any dispatch.
    """
    if data.ndim != 3:
        raise ValueError(f"expected (B, n_max, d) batch, got shape {data.shape}")
    lengths = jnp.asarray(lengths, jnp.int32)
    if lengths.shape != (data.shape[0],):
        raise ValueError(f"lengths must be ({data.shape[0]},), "
                         f"got {lengths.shape}")
    try:                      # host-side admission checks (concrete lengths)
        lens = np.asarray(lengths)
    except jax.errors.TracerArrayConversionError:
        lens = None           # called under an outer trace: caller's problem
    if lens is not None:
        if (lens < 1).any():
            raise ValueError("all-padding query rejected: every query needs "
                             f"length >= 1, got lengths={lens.tolist()}")
        if (lens > data.shape[1]).any():
            raise ValueError(f"length exceeds padded arm count "
                             f"{data.shape[1]}: lengths={lens.tolist()}")
    # Bucket-pad OUTSIDE the jitted impl: the raw n_max must never reach the
    # jit cache key, or every distinct caller padding would compile its own
    # program and the per-bucket compile cap would silently evaporate.
    n_bucket = bucket_n(data.shape[1], min_bucket)
    if data.shape[1] < n_bucket:
        data = jnp.pad(data, ((0, 0), (0, n_bucket - data.shape[1]), (0, 0)))
    instrument.note_dispatch("ragged")
    fn = programs.ragged_program(n_bucket=n_bucket, budget=budget,
                                 metric=metric, backend=backend,
                                 telemetry=telemetry, precision=precision,
                                 error_model=error_model)
    return fn(data, lengths, key)


# ---------------------------------------------------------------------------
# deprecated pre-facade entry points (use repro.api)
# ---------------------------------------------------------------------------

def corr_sh_medoid(data: jnp.ndarray, key: jax.Array, *, budget: int,
                   metric: str = "l2",
                   backend: str = "reference") -> jnp.ndarray:
    """Deprecated: use :func:`repro.api.find_medoid`."""
    warn_once("repro.core.corr_sh.corr_sh_medoid", "repro.api.find_medoid")
    return _medoid_impl(data, key, budget=budget, metric=metric,
                        backend=backend)


def corr_sh_medoid_batch(data: jnp.ndarray, key: jax.Array, *, budget: int,
                         metric: str = "l2",
                         backend: str = "reference") -> jnp.ndarray:
    """Deprecated: use :func:`repro.api.find_medoids_batch`."""
    warn_once("repro.core.corr_sh.corr_sh_medoid_batch",
              "repro.api.find_medoids_batch")
    return _batch_impl(data, key, budget=budget, metric=metric,
                       backend=backend)


def corr_sh_medoid_ragged(data: jnp.ndarray, lengths, key: jax.Array, *,
                          budget: int, metric: str = "l2",
                          backend: str = "reference",
                          min_bucket: int = DEFAULT_MIN_BUCKET) -> jnp.ndarray:
    """Deprecated: use :func:`repro.api.find_medoids_ragged`."""
    warn_once("repro.core.corr_sh.corr_sh_medoid_ragged",
              "repro.api.find_medoids_ragged")
    return ragged_medoids(data, lengths, key, budget=budget, metric=metric,
                          backend=backend, min_bucket=min_bucket)
