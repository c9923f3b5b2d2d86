"""The stable public facade of the repro medoid system.

Every workload the repo serves — single-query medoid identification
(the paper's Algorithm 1), batched and ragged multi-query serving,
distributed execution, baseline algorithms, and bandit k-medoids
clustering — enters through the four functions here:

    from repro.api import (MedoidConfig, KMedoidsConfig, find_medoid,
                           find_medoids_batch, find_medoids_ragged, kmedoids)

    res = find_medoid(data, key)                          # MedoidResult
    res = find_medoid(data, key, backend="pallas_fused", budget_per_arm=32)
    meds = find_medoids_batch(batch, key)                 # (B,) indices
    meds = find_medoids_ragged([q1, q2, q3], key=key)     # any sizes
    clust = kmedoids(data, k=8, key=key)                  # KMedoidsResult
    live = maintain_medoid(data)                          # MaintainedMedoid
    live.insert(x); live.delete(slot); live.query()       # mutable corpus

Configuration is a frozen dataclass (:class:`MedoidConfig` /
:class:`KMedoidsConfig`); every entry point also accepts the config fields
directly as keyword overrides (``find_medoid(x, key, metric="l1")`` is
``find_medoid(x, key, config=MedoidConfig(metric="l1"))``).

All of these are thin adapters over ONE engine —
:func:`repro.engine.run_halving`, the estimator-parameterized correlated-SH
round loop — so masking, bucketed batching, fused Pallas paths, the on-chip
top-k epilogue, and the compile odometer apply uniformly. ``algo=`` swaps
the algorithm itself (``corr_sh`` | ``meddit`` | ``rand`` | ``exact``)
behind the same call, and ``mesh=`` routes ``find_medoid`` through the
shard_map distributed engines. The pre-facade entry points
(``corr_sh_medoid*``, ``bandit_kmedoids``) still work as deprecated shims.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.bucketing import DEFAULT_MIN_BUCKET, pack_queries
from repro.core.corr_sh import _batch_impl, _medoid_impl, ragged_medoids
from repro.core.exact import exact_medoid
from repro.core.meddit import meddit_medoid
from repro.core.rand import rand_medoid
from repro.engine import round_schedule, stop_round
from repro.obs import telemetry_to_host

ALGOS = ("corr_sh", "meddit", "rand", "exact")

__all__ = [
    "ALGOS", "KMedoidsConfig", "MedoidConfig", "MedoidResult", "find_medoid",
    "find_medoids_batch", "find_medoids_ragged", "kmedoids",
    "maintain_medoid",
]


# --------------------------------- configs ----------------------------------

@dataclass(frozen=True)
class MedoidConfig:
    """How a medoid query runs. ``budget_per_arm`` scales the paper's pull
    budget (``budget = budget_per_arm * n``; for ragged traffic, ``n`` is the
    power-of-two bucket). ``algo`` selects the algorithm behind the facade:
    ``corr_sh`` (the paper; the only one with batch/ragged modes), the
    ``meddit`` UCB baseline, the ``rand`` non-adaptive baseline
    (``budget_per_arm`` references), or the ``exact`` O(n^2) oracle.

    ``telemetry`` additionally returns the fixed-shape per-round trace of
    :mod:`repro.obs.telemetry` (host numpy, one row per executed round) —
    same single dispatch, bit-identical answers; ``corr_sh`` only.

    ``precision`` selects the distance arithmetic: ``"fp32"`` (default,
    bit-identical to every previous release), or the quantized ``"bf16"`` /
    ``"int8"`` paths of :mod:`repro.quant` — halving runs margin-widened
    against the quantization error model (``quant_error_model``: measured
    ``"probe"`` or certified-worst-case ``"analytic"``) and the finalists
    are re-verified in exact fp32; a run whose widened margins overflowed
    capacity falls back to a same-key fp32 re-run, so answers are exact
    either way. ``corr_sh`` only."""
    metric: str = "l2"
    backend: str = "reference"
    budget_per_arm: int = 24
    algo: str = "corr_sh"
    min_bucket: int = DEFAULT_MIN_BUCKET
    seed: int = 0          # key when the caller passes none
    telemetry: bool = False
    precision: str = "fp32"
    quant_error_model: str = "probe"


@dataclass(frozen=True)
class KMedoidsConfig:
    """How a k-medoids clustering job runs (BUILD -> ragged per-cluster
    refinement -> bandit SWAP, all on the unified engine)."""
    metric: str = "l2"
    backend: str = "reference"
    build_budget_per_arm: int = 16
    swap_budget_per_arm: int = 16
    refine_budget_per_arm: int = 20
    refine_sweeps: int = 1
    max_swap_rounds: int = 8
    min_bucket: int = DEFAULT_MIN_BUCKET
    seed: int = 0


@dataclass(frozen=True)
class MedoidResult:
    """One answered medoid query: the winning index plus exact (scheduled)
    pull accounting and the round plan that produced it.

    ``precision`` echoes the config. ``verified`` is ``None`` for fp32 runs;
    for quantized runs it is ``True`` when the widened margins held all the
    way down (the quantized answer carries the exact-fp32-finalist
    certificate) and ``False`` when capacity overflowed — the reported
    ``medoid`` then came from the same-key fp32 fallback re-run and is exact
    regardless. ``hardness`` (telemetry runs only) carries the instance
    hardness stats of :mod:`repro.core.hardness` — Δ₂ gap, σ spread, and
    the paper's H₂/H̃₂ hardness sums."""
    medoid: int
    pulls: int
    n: int
    algo: str
    metric: str
    backend: str
    rounds: tuple = ()     # (survivors, num_refs) per executed round
    telemetry: Optional[dict] = None   # per-round trace (host numpy) when
    #                                    MedoidConfig.telemetry is set
    precision: str = "fp32"
    verified: Optional[bool] = None
    hardness: Optional[dict] = None


def _resolve(config, overrides, cls):
    cfg = config if config is not None else cls()
    if not isinstance(cfg, cls):
        raise TypeError(f"config must be a {cls.__name__}, got {type(cfg)!r}")
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def _key_of(key, cfg):
    return jax.random.key(cfg.seed) if key is None else key


# ------------------------------- single query -------------------------------

def find_medoid(data: jnp.ndarray, key: Optional[jax.Array] = None, *,
                config: Optional[MedoidConfig] = None, mesh=None,
                distributed_impl: str = "v2", **overrides) -> MedoidResult:
    """Find the medoid of ``data (n, d)``.

    The default (``algo="corr_sh"``) runs the paper's correlated sequential
    halving through the unified engine on the configured distance backend.
    Pass ``mesh=`` (a ``jax.sharding.Mesh``; rows of ``data`` sharded over
    all its axes) to run the distributed shard_map engine instead
    (``distributed_impl="v2"`` communication-optimal, ``"v1"`` replicated).
    """
    cfg = _resolve(config, overrides, MedoidConfig)
    if cfg.algo not in ALGOS:
        raise ValueError(f"unknown algo {cfg.algo!r}; one of {ALGOS}")
    data = jnp.asarray(data)
    if data.ndim != 2:
        raise ValueError(f"expected (n, d) data, got shape {data.shape}")
    n = int(data.shape[0])
    key = _key_of(key, cfg)
    budget = cfg.budget_per_arm * n

    if cfg.telemetry and (cfg.algo != "corr_sh" or mesh is not None):
        raise ValueError("telemetry=True requires algo='corr_sh' without "
                         "mesh= (only the engine round loop is instrumented)")
    if cfg.precision != "fp32":
        from repro import quant
        quant.check_precision(cfg.precision)
        if cfg.algo != "corr_sh" or mesh is not None:
            raise ValueError("precision != 'fp32' requires algo='corr_sh' "
                             "without mesh= (only the engine round loop has "
                             "the widened-margin + verification path)")

    if mesh is not None:
        if cfg.algo != "corr_sh":
            raise ValueError(f"mesh= requires algo='corr_sh', got {cfg.algo!r}")
        from repro.core.distributed import distributed_corr_sh
        from repro.core.distributed_v2 import distributed_corr_sh_v2
        impls = {"v1": distributed_corr_sh, "v2": distributed_corr_sh_v2}
        try:
            fn = impls[distributed_impl]
        except KeyError:
            raise ValueError(f"distributed_impl must be one of "
                             f"{sorted(impls)}, got {distributed_impl!r}"
                             ) from None
        medoid = int(fn(data, key, mesh, budget=budget, metric=cfg.metric,
                        backend=cfg.backend))
        rounds = round_schedule(n, budget)
        return MedoidResult(medoid=medoid,
                            pulls=sum(r.pulls for r in rounds), n=n,
                            algo=f"corr_sh_distributed_{distributed_impl}",
                            metric=cfg.metric, backend=cfg.backend,
                            rounds=tuple((r.survivors, r.num_refs)
                                         for r in rounds))

    if cfg.algo == "exact":
        return MedoidResult(medoid=int(exact_medoid(data, cfg.metric)),
                            pulls=n * n, n=n, algo="exact",
                            metric=cfg.metric, backend=cfg.backend)
    if cfg.algo == "rand":
        refs = max(1, cfg.budget_per_arm)
        m = rand_medoid(data, key, num_refs=refs, metric=cfg.metric)
        return MedoidResult(medoid=int(m), pulls=n * refs, n=n, algo="rand",
                            metric=cfg.metric, backend=cfg.backend)
    if cfg.algo == "meddit":
        res = meddit_medoid(data, key, metric=cfg.metric)
        return MedoidResult(medoid=int(res.medoid), pulls=int(res.pulls),
                            n=n, algo="meddit", metric=cfg.metric,
                            backend=cfg.backend)

    if n == 1:
        tel = None
        if cfg.telemetry:
            from repro.obs import telemetry as obs_telemetry
            tel = telemetry_to_host(obs_telemetry.empty())
        return MedoidResult(medoid=0, pulls=0, n=1, algo="corr_sh",
                            metric=cfg.metric, backend=cfg.backend,
                            telemetry=tel, precision=cfg.precision,
                            verified=None if cfg.precision == "fp32"
                            else True)
    out = _medoid_impl(data, key, budget=budget, metric=cfg.metric,
                       backend=cfg.backend, telemetry=cfg.telemetry,
                       precision=cfg.precision,
                       error_model=cfg.quant_error_model)
    rounds = round_schedule(n, budget)
    executed = rounds[: stop_round(rounds) + 1]
    pulls = sum(r.pulls for r in executed)
    tel = None
    verified = None
    if cfg.precision == "fp32":
        if cfg.telemetry:
            out, tel = out
        medoid = int(out)
    else:
        from repro import quant
        if cfg.telemetry:
            out, ver, tel = out
        else:
            out, ver = out
        verified = bool(ver)
        pulls += quant.verify_pulls(n, rounds)
        if verified:
            medoid = int(out)
        else:
            # Widened margins overflowed their buffers somewhere — the
            # quantized answer lost its certificate. Re-run in fp32 with the
            # SAME key: identical draws, exact estimates, exact answer (and
            # the exact telemetry replaces the quantized trace).
            fout = _medoid_impl(data, key, budget=budget, metric=cfg.metric,
                                backend=cfg.backend, telemetry=cfg.telemetry)
            if cfg.telemetry:
                fout, tel = fout
            medoid = int(fout)
            pulls += sum(r.pulls for r in executed)
    if tel is not None:
        tel = telemetry_to_host(tel)
    hardness = None
    if cfg.telemetry:
        from repro.core.hardness import hardness_stats
        hs = hardness_stats(data, metric=cfg.metric)
        hardness = {"delta2": float(hs.delta[1]), "sigma": float(hs.sigma),
                    "h2": float(hs.h2), "h2_tilde": float(hs.h2_tilde)}
    return MedoidResult(medoid=medoid, pulls=pulls, n=n,
                        algo="corr_sh", metric=cfg.metric,
                        backend=cfg.backend,
                        rounds=tuple((r.survivors, r.num_refs)
                                     for r in executed),
                        telemetry=tel, precision=cfg.precision,
                        verified=verified, hardness=hardness)


# -------------------------------- multi query -------------------------------

def find_medoids_batch(data: jnp.ndarray, key: Optional[jax.Array] = None, *,
                       config: Optional[MedoidConfig] = None,
                       **overrides) -> jnp.ndarray:
    """Answer a ``(B, n, d)`` batch of independent medoid queries in one XLA
    dispatch (one shared static schedule, per-query reference draws).
    Returns the ``(B,)`` int32 medoid indices — or, with
    ``telemetry=True``, ``(indices, telemetry)`` where the telemetry leaves
    are host ``(B, R)`` arrays (one row per query per executed round)."""
    cfg = _resolve(config, overrides, MedoidConfig)
    if cfg.algo != "corr_sh":
        raise ValueError(f"batched mode requires algo='corr_sh', "
                         f"got {cfg.algo!r}")
    data = jnp.asarray(data)
    n = int(data.shape[1]) if data.ndim == 3 else 0
    key = _key_of(key, cfg)
    out = _batch_impl(data, key,
                      budget=cfg.budget_per_arm * max(n, 1),
                      metric=cfg.metric, backend=cfg.backend,
                      telemetry=cfg.telemetry, precision=cfg.precision,
                      error_model=cfg.quant_error_model)
    tel = None
    if cfg.precision == "fp32":
        if cfg.telemetry:
            medoids, tel = out
        else:
            medoids = out
    else:
        if cfg.telemetry:
            medoids, verified, tel = out
        else:
            medoids, verified = out
        if not bool(jnp.all(verified)):
            # Unverified queries fall back to the exact same-key fp32 batch
            # (one extra dispatch, shared by every overflowed query).
            fout = _batch_impl(data, key,
                               budget=cfg.budget_per_arm * max(n, 1),
                               metric=cfg.metric, backend=cfg.backend,
                               telemetry=False)
            medoids = jnp.where(verified, medoids, fout)
    if tel is not None:
        return medoids, telemetry_to_host(tel)
    return medoids


def find_medoids_ragged(data, lengths=None,
                        key: Optional[jax.Array] = None, *,
                        config: Optional[MedoidConfig] = None,
                        **overrides) -> jnp.ndarray:
    """Answer mixed-size medoid queries through one bucketed XLA program.

    Accepts either a pre-packed ``(B, n_max, d)`` array with per-query
    ``lengths (B,)``, or simply a list of ``(n_i, d)`` arrays (packed via
    :func:`repro.core.bucketing.pack_queries`). The bucket's budget is
    ``budget_per_arm * n_bucket``; padding is masked inside every round, and
    a query filling its bucket is bit-identical to the single-query path.
    Returns the ``(B,)`` int32 medoid indices (each < its query's length) —
    or ``(indices, telemetry)`` with ``telemetry=True`` (host ``(B, R)``
    leaves; schedule columns are the bucket's).
    """
    cfg = _resolve(config, overrides, MedoidConfig)
    if cfg.algo != "corr_sh":
        raise ValueError(f"ragged mode requires algo='corr_sh', "
                         f"got {cfg.algo!r}")
    if isinstance(data, (list, tuple)):
        if lengths is not None:
            raise ValueError("pass lengths only with pre-packed array data")
        data, lengths = pack_queries(list(data), min_bucket=cfg.min_bucket)
    elif lengths is None:
        raise ValueError("pre-packed array data needs explicit lengths")
    data = jnp.asarray(data)
    n_bucket = int(data.shape[1]) if data.ndim == 3 else 1
    from repro.core.bucketing import bucket_n
    n_bucket = bucket_n(n_bucket, cfg.min_bucket)
    key = _key_of(key, cfg)
    out = ragged_medoids(data, lengths, key,
                         budget=cfg.budget_per_arm * n_bucket,
                         metric=cfg.metric, backend=cfg.backend,
                         min_bucket=cfg.min_bucket,
                         telemetry=cfg.telemetry, precision=cfg.precision,
                         error_model=cfg.quant_error_model)
    tel = None
    if cfg.precision == "fp32":
        if cfg.telemetry:
            medoids, tel = out
        else:
            medoids = out
    else:
        if cfg.telemetry:
            medoids, verified, tel = out
        else:
            medoids, verified = out
        if not bool(jnp.all(verified)):
            fout = ragged_medoids(data, lengths, key,
                                  budget=cfg.budget_per_arm * n_bucket,
                                  metric=cfg.metric, backend=cfg.backend,
                                  min_bucket=cfg.min_bucket,
                                  telemetry=False)
            medoids = jnp.where(verified, medoids, fout)
    if tel is not None:
        return medoids, telemetry_to_host(tel)
    return medoids


# ------------------------------ mutable corpus ------------------------------

def maintain_medoid(data=None, *, d: Optional[int] = None,
                    config: Optional[MedoidConfig] = None, **overrides):
    """Build a live, incrementally-maintained medoid over a mutable corpus.

    Returns a :class:`repro.serve.MaintainedMedoid`: ``insert(x)`` /
    ``delete(slot)`` mutate the corpus at O(n) distance evaluations each
    (one exact n-vector updates every live point's centrality), ``query()``
    serves the maintained answer for the current corpus version for free,
    and only a dethroned (or deleted) incumbent triggers a full
    correlated-SH re-run — dispatched through the same cached programs as
    :func:`find_medoids_ragged`, keyed by corpus version for bit-exact
    reproducibility. Pass ``data (n, d)`` to bootstrap from an existing
    corpus, or ``d=`` alone to start empty. Config fields (``metric``,
    ``backend``, ``budget_per_arm``, ``min_bucket``, ``seed``) mean what
    they mean everywhere else in this facade.
    """
    from repro.serve import CorpusStore, MaintainedMedoid

    cfg = _resolve(config, overrides, MedoidConfig)
    if cfg.algo != "corr_sh":
        raise ValueError(f"maintain_medoid requires algo='corr_sh', "
                         f"got {cfg.algo!r}")
    if data is not None:
        store = CorpusStore.from_points(jnp.asarray(data), metric=cfg.metric,
                                        backend=cfg.backend,
                                        min_bucket=cfg.min_bucket,
                                        precision=cfg.precision)
    elif d is not None:
        store = CorpusStore(d, metric=cfg.metric, backend=cfg.backend,
                            min_bucket=cfg.min_bucket,
                            precision=cfg.precision)
    else:
        raise ValueError("pass data (n, d) or d= to start an empty corpus")
    return MaintainedMedoid(store, budget_per_arm=cfg.budget_per_arm,
                            seed=cfg.seed)


# -------------------------------- clustering --------------------------------

def kmedoids(data: jnp.ndarray, k: int, key: Optional[jax.Array] = None, *,
             config: Optional[KMedoidsConfig] = None, refiner=None,
             **overrides):
    """Bandit k-medoids (BUILD -> ragged refinement -> bandit SWAP) on the
    unified engine. Returns a :class:`repro.cluster.KMedoidsResult` (point
    indices, labels, cost, exact pull accounting). ``refiner`` overrides how
    the per-cluster subproblems are answered — see
    :func:`repro.cluster.service.kmedoids_via_service` for the
    continuous-batching route."""
    from repro.cluster.kmedoids import _kmedoids_impl

    cfg = _resolve(config, overrides, KMedoidsConfig)
    return _kmedoids_impl(
        data, k, _key_of(key, cfg), metric=cfg.metric, backend=cfg.backend,
        build_budget_per_arm=cfg.build_budget_per_arm,
        swap_budget_per_arm=cfg.swap_budget_per_arm,
        refine_budget_per_arm=cfg.refine_budget_per_arm,
        refine_sweeps=cfg.refine_sweeps,
        max_swap_rounds=cfg.max_swap_rounds,
        min_bucket=cfg.min_bucket, refiner=refiner)
