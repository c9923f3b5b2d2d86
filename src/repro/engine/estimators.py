"""Arm-loss estimators: how a batch of reference pulls scores each arm.

The estimator is the extension axis of the unified engine. The round loop
(:func:`repro.engine.halving.run_halving`) owns reference draws, masking,
halving, and selection; an :class:`ArmEstimator` owns only the mapping

    (candidate rows (C, d), reference rows (R, d)) -> per-arm raw sums (C,)

plus an optional auxiliary output (any pytree) that the engine threads
through to the outcome — the SWAP estimator returns its ``(C, k)``
per-medoid delta block this way. Sums are *pre-division*: the engine
normalizes by the (static) reference count, or the drawn valid count under a
``ref_mask``, so estimators never reimplement ragged denominators.

Built-in estimators (the three bandit workloads of BanditPAM/BanditPAM++):

``medoid_centrality``
    ``sum_j d(x_i, y_j)`` — the paper's problem. Rides the backend's fused
    centrality kernels when available (no ``(C, R)`` block in HBM).
``build_delta``
    BanditPAM BUILD: ``sum_j min(d1_j, d(x_i, y_j))`` against the cached
    nearest-medoid distance ``d1``.
``swap_delta``
    FasterPAM SWAP: one shared draw prices all k swaps of every candidate
    via a ``(C, t)`` block + ``(t, k)`` one-hot segment sum; the arm value
    is ``min_i delta(c, i)`` and the full delta block is the aux output.

A backend can register a fused implementation of any estimator in its
``fused_estimators`` mapping (next to ``centrality_sums`` — see
:class:`repro.core.backend.DistanceBackend`); the factories below pick it up
automatically, so a new Pallas kernel for, say, ``build_delta`` plugs in
without touching any engine or workload code. Third-party estimators
register by name via :func:`register_estimator` (see the README's
trimmed-mean example).

**Scan-body-safe contract** (required since the round loop became a
``lax.scan``): ``score`` must be a pure traced function of its array inputs
— no host round-trips (item / host-array conversion / device fetches), no
branching on concrete array *values*, no reliance on the number of rounds.
``ref_mask``, when given, is a float *weight* vector over the reference
axis and must enter multiplicatively (weight-0 references contribute
exactly nothing to the sums): inside a scan band the engine passes
positional validity (``position < t_r``) as weights over a fixed-width
reference buffer, so any non-multiplicative mask handling would corrupt
every scanned round. ``aux`` is only consumed from the *output* round (the
engine discards it in scanned rounds), so it may be arbitrarily large.
All built-in estimators and the fused Pallas paths satisfy this contract.
"""
from __future__ import annotations

import inspect
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.engine.instrument import Tile

# NOTE: repro.core is imported lazily inside the factories — the engine
# package sits BELOW repro.core in the layering (repro.core.__init__ pulls in
# corr_sh, which is built on this package), so module-level imports here
# would be circular. Factories run at trace time only; the cost is nil.

# score(cand_rows, ref_rows, *, refs, ref_mask=None) -> (sums (C,), aux).
# ``refs`` are the drawn global reference indices (for gathering cached
# per-point state like d1/d2/nearest); ``ref_mask`` is the (R,) float
# validity mask over the drawn references, or None on the dense path.
ScoreFn = Callable[..., Tuple[jnp.ndarray, Any]]


@dataclass(frozen=True)
class ArmEstimator:
    """One arm-loss estimator: a name (for registries/telemetry) + score fn.
    ``tile`` is the (candidate, reference, width) block its kernel pads a
    call to, or the rule that gives it from the call's shape (``None``: no
    padding), and ``norms`` whether each call takes the norm of every
    operand row first (the Gram metrics), for the engine's work tally.
    ``score_at``, where set, is the backend's ``centrality_sums_at``: the
    same sums by index into the corpus, bitwise those of ``score`` on the
    gathered rows at its own block, and the round loop then copies no rows
    out of the corpus."""
    name: str
    score: ScoreFn
    tile: Optional[Tile] = None
    norms: bool = False
    score_at: Optional[Callable] = None


# ------------------------- estimator factory registry -----------------------

# name -> factory(backend, metric, **params) -> ArmEstimator
_ESTIMATORS: dict[str, Callable[..., ArmEstimator]] = {}


def register_estimator(name: str, factory: Callable[..., ArmEstimator],
                       ) -> Callable[..., ArmEstimator]:
    """Register an estimator factory (last registration wins on a name)."""
    _ESTIMATORS[name] = factory
    return factory


def get_estimator(name: str) -> Callable[..., ArmEstimator]:
    try:
        return _ESTIMATORS[name]
    except KeyError:
        raise ValueError(f"unknown estimator {name!r}; "
                         f"one of {list_estimators()}") from None


def list_estimators() -> tuple[str, ...]:
    return tuple(sorted(_ESTIMATORS))


# --------------------------- masked-call resolution -------------------------

def _masked_centrality_fn(be, fn, metric: str) -> Callable:
    """Mask-aware form of a backend centrality fn: built-in backends take
    ``ref_mask`` natively (the fused kernels apply it in VMEM); a registered
    backend that predates the keyword falls back to masking its pairwise
    block."""
    from repro.core import distances

    try:
        params = inspect.signature(fn).parameters
        mask_native = "ref_mask" in params or any(
            p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())
    except (TypeError, ValueError):   # builtins / odd callables: probe-free
        mask_native = False
    if mask_native:
        return lambda x, y, m: fn(x, y, ref_mask=m)
    pw = be.pairwise(metric)
    return lambda x, y, m: distances.masked_rowsum(pw(x, y), m)


# ----------------------------- built-in factories ---------------------------

def _takes_norms(metric: str) -> bool:
    """Whether ``metric``'s distance path takes row norms on every call:
    cosine's unit rows and the squared norms of ℓ2 and squared ℓ2."""
    return metric in ("l2", "sql2", "cosine")


def medoid_centrality(backend=None, metric: str = "l2", *,
                      pairwise_fn: Optional[Callable] = None) -> ArmEstimator:
    """The paper's estimator: ``sum_j d(x_i, y_j)``.

    Uses the backend's fused path when registered (``fused_estimators`` or
    the fused ``centrality_sums`` kernels), and scores by index where the
    backend's kernel fetches its own rows (``centrality_sums_at``: ℓ1 on
    the fused Pallas backends). ``pairwise_fn`` overrides the distance
    block directly (the legacy hook of ``correlated_sequential_halving``;
    takes precedence over ``backend``, and takes gathered rows).
    """
    from repro.core import distances
    from repro.core.backend import get_backend

    tile = score_at = None
    if pairwise_fn is not None:
        def plain(x, y):
            return jnp.sum(pairwise_fn(x, y), axis=1)

        def masked(x, y, m):
            return distances.masked_rowsum(pairwise_fn(x, y), m)
    else:
        be = get_backend(backend)
        fused = be.fused_estimators.get("medoid_centrality")
        fn = fused(metric) if fused is not None else be.centrality_sums(metric)
        plain = fn
        masked = _masked_centrality_fn(be, fn, metric)
        tile = be.tile if be.centrality_tile is None \
            else be.centrality_tile(metric)
        if be.centrality_sums_at is not None:
            score_at = be.centrality_sums_at(metric)

    def score(cand, ref_rows, *, refs, ref_mask=None):
        if ref_mask is None:
            return plain(cand, ref_rows), None
        return masked(cand, ref_rows, ref_mask), None

    # a caller's own pairwise_fn takes whatever norms it takes, unseen
    return ArmEstimator("medoid_centrality", score, tile,
                        pairwise_fn is None and _takes_norms(metric),
                        score_at)


def build_delta(backend=None, metric: str = "l2", *,
                d1: jnp.ndarray) -> ArmEstimator:
    """BanditPAM BUILD estimator: ``sum_j min(d1_j, d(x_i, y_j))`` — the
    cached nearest-medoid distance ``d1`` caps every reference's
    contribution, so an arm's value is the total cost were it added as the
    next medoid (up to the constant ``sum_j d1_j``)."""
    from repro.core import distances
    from repro.core.backend import get_backend

    be = get_backend(backend)
    fused = be.fused_estimators.get("build_delta")
    if fused is not None:
        fn = fused(metric)

        def score(cand, ref_rows, *, refs, ref_mask=None):
            return fn(cand, ref_rows, d1[refs], ref_mask=ref_mask), None
    else:
        pw = be.pairwise(metric)

        def score(cand, ref_rows, *, refs, ref_mask=None):
            blk = jnp.minimum(pw(cand, ref_rows), d1[refs][None, :])
            return distances.masked_rowsum(blk, ref_mask), None

    return ArmEstimator("build_delta", score, be.tile, _takes_norms(metric))


def swap_delta(backend=None, metric: str = "l2", *, d1: jnp.ndarray,
               d2: jnp.ndarray, nearest: jnp.ndarray, k: int) -> ArmEstimator:
    """FasterPAM SWAP estimator. Per candidate c and medoid slot i, over a
    shared reference draw J:

        delta(c, i) = sum_{j in J} min(d(c,j) - d1_j, 0)
                    + sum_{j in J, nearest_j = i} [ min(d(c,j), d2_j) - d1_j
                                                    - min(d(c,j) - d1_j, 0) ]

    (a (C, t) block, a (t, k) one-hot segment sum — entirely on-device).
    The arm value is ``min_i delta(c, i)``; the full ``(C, k)`` delta block
    is returned as aux so the winner's slot falls out after the loop."""
    from repro.core.backend import get_backend

    be = get_backend(backend)
    fused = be.fused_estimators.get("swap_delta")
    if fused is not None:
        fn = fused(metric)

        def score(cand, ref_rows, *, refs, ref_mask=None):
            delta = fn(cand, ref_rows, d1[refs], d2[refs], nearest[refs],
                       k, ref_mask=ref_mask)
            return jnp.min(delta, axis=1), delta
    else:
        pw = be.pairwise(metric)

        def score(cand, ref_rows, *, refs, ref_mask=None):
            blk = pw(cand, ref_rows)                          # (C, t)
            d1r, d2r = d1[refs][None, :], d2[refs][None, :]
            gain = jnp.minimum(blk - d1r, 0.0)                # (C, t)
            term = jnp.minimum(blk, d2r) - d1r - gain         # (C, t)
            if ref_mask is not None:
                m = ref_mask.reshape(-1).astype(blk.dtype)[None, :]
                gain = gain * m
                term = term * m
            onehot = jax.nn.one_hot(nearest[refs], k, dtype=blk.dtype)
            delta = (jnp.sum(gain, axis=1, keepdims=True)
                     + term @ onehot)                         # (C, k)
            return jnp.min(delta, axis=1), delta

    return ArmEstimator("swap_delta", score, be.tile, _takes_norms(metric))


register_estimator("medoid_centrality", medoid_centrality)
register_estimator("build_delta", build_delta)
register_estimator("swap_delta", swap_delta)
