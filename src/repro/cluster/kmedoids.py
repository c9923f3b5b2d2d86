"""Bandit k-medoids clustering on the correlated-SH engine.

The paper's primitive — adaptive medoid identification in O(n log n)
distance evaluations — is exactly the inner loop of k-medoids, which is how
BanditPAM (Tiwari et al., NeurIPS 2020) and BanditPAM++ (2023) framed the
clustering problem. This module builds the full pipeline out of the layers
the repo already has, instead of re-deriving any of them:

* **BUILD** (greedy seeding): k correlated-SH argmin problems. Step 0 *is*
  the single-medoid problem and literally calls the same jitted single-query
  engine as :func:`repro.api.find_medoid` (so a k=1 BUILD is bit-identical
  to the paper engine by construction). Steps t >= 1 run
  :func:`repro.engine.run_halving` with the BanditPAM ``build_delta``
  estimator: an arm i's value over a shared reference draw J is
  ``sum_{j in J} min(d1_j, d(x_i, x_j))`` where ``d1`` is the cached
  distance to the nearest already-chosen medoid — the correlation trick
  applies unchanged because all arms share J (and the ``d1_J`` gather).

* **Ragged per-cluster refinement**: alternate-style sweeps. Each cluster's
  medoid update is a pure single-medoid problem over its members, and
  cluster sizes are heterogeneous — so the per-cluster subproblems are
  routed through :func:`repro.core.corr_sh.ragged_medoids` via the
  power-of-two bucketing planner (clusters are just another ragged traffic
  source; the compile odometer bounds hold here too). Per-cluster caching:
  only clusters whose membership changed since the previous sweep recompute.

* **SWAP** (FasterPAM-style bandit local search): swap-in candidates are the
  arms; one shared reference draw J yields, per candidate c, the swap deltas
  against ALL k medoids at once from the cached nearest/second-nearest
  distances:

      delta(c, i) = sum_{j in J} min(d(c,j) - d1_j, 0)
                  + sum_{j in J, nearest_j = i} [ min(d(c,j), d2_j) - d1_j
                                                  - min(d(c,j) - d1_j, 0) ]

  (a (C, t) block, a (t, k) one-hot segment sum — entirely on-device). The
  arm value is ``min_i delta(c, i)`` and correlated sequential halving prunes
  candidates round by round. The winning swap is verified with an *exact*
  delta (one n-vector of distances) before being applied; the ``(n, k)``
  medoid-distance cache then updates incrementally — only the swapped
  column is recomputed, and nearest/second-nearest fall out of a top-2.

Pull accounting is explicit and scheduled (never estimated), so benchmarks
and tests can assert the O(n log n)-vs-O(n^2) gap against exact PAM
(:mod:`repro.cluster.pam_exact`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (get_backend, plan_buckets, pack_queries,
                        round_schedule, schedule_pulls)
from repro.core.bucketing import DEFAULT_MIN_BUCKET, bucket_n, next_pow2
from repro.core.corr_sh import _medoid_impl, ragged_medoids
from repro.deprecation import warn_once
from repro.engine import (HalvingProblem, build_delta, run_halving,
                          swap_delta)

# refiner hook: (cluster member arrays, key) -> (local medoid indices, pulls).
# The default runs bucketed ragged dispatches in-process; the service layer
# (repro.cluster.service) substitutes a continuous-batching MedoidServer.
Refiner = Callable[[list, jax.Array], tuple[list, int]]


@dataclasses.dataclass
class KMedoidsResult:
    medoids: list[int]            # k point indices (cluster slot order)
    labels: np.ndarray            # (n,) cluster slot per point
    cost: float                   # sum of distances to assigned medoids
    pulls: int                    # total scheduled distance evaluations
    build_pulls: int
    assign_pulls: int
    refine_pulls: int
    swap_pulls: int
    swaps: int                    # accepted SWAP moves
    refine_updates: int           # per-cluster medoid changes during sweeps
    k: int = 0
    metric: str = "l2"
    backend: str = "reference"


# --------------------------------------------------------------------------
# jitted phase kernels — one compilation per (n, d, k, budget, metric,
# backend) signature, reused across BUILD steps / SWAP rounds / sweeps.
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("budget", "metric", "backend"))
def _build_step(data: jnp.ndarray, d1: jnp.ndarray, chosen: jnp.ndarray,
                key: jax.Array, *, budget: int, metric: str,
                backend: str) -> jnp.ndarray:
    """One BUILD greedy step: ``run_halving`` with the BanditPAM
    ``build_delta`` estimator (``sum_j min(d1_j, d(i, j))`` — the cached
    nearest-medoid distance caps every reference's contribution). Arms
    already chosen as medoids are masked out via ``arm_mask``."""
    rounds = round_schedule(data.shape[0], budget)
    problem = HalvingProblem(data, build_delta(backend, metric, d1=d1),
                             arm_mask=~chosen)
    return run_halving(problem, rounds, backend, key=key).winner


@functools.partial(jax.jit,
                   static_argnames=("k", "budget", "metric", "backend"))
def _build_scan(data: jnp.ndarray, m0: jnp.ndarray, key_build: jax.Array, *,
                k: int, budget: int, metric: str, backend: str):
    """BUILD steps 1..k-1 as ONE device-resident program: a ``lax.scan``
    whose carry is the ``(d1, chosen)`` cache pair. Each step runs the same
    traced round loop as :func:`_build_step` (``fold_in(key_build, t)`` per
    step, identical to the per-step host loop it replaces), updates the
    nearest-medoid distance cache ``d1`` from the winner's distance row, and
    marks the winner chosen — per-step winners never visit the host.
    Returns ``(meds (k,), d1 (n,))``."""
    n = data.shape[0]
    pw = get_backend(backend).pairwise(metric)
    rounds = round_schedule(n, budget)
    d1 = jnp.minimum(jnp.full((n,), jnp.inf, jnp.float32),
                     pw(data[m0][None, :], data)[0])
    chosen = jnp.zeros((n,), bool).at[m0].set(True)

    def step(carry, t):
        d1, chosen = carry
        kt = jax.random.fold_in(key_build, t)
        problem = HalvingProblem(data, build_delta(backend, metric, d1=d1),
                                 arm_mask=~chosen)
        m = run_halving(problem, rounds, backend, key=kt).winner
        d1 = jnp.minimum(d1, pw(data[m][None, :], data)[0])
        chosen = chosen.at[m].set(True)
        return (d1, chosen), m

    (d1, _), ms = jax.lax.scan(step, (d1, chosen),
                               jnp.arange(1, k, dtype=jnp.int32))
    return jnp.concatenate([m0[None].astype(jnp.int32), ms]), d1


@functools.partial(jax.jit, static_argnames=("metric", "backend"))
def _assign(data: jnp.ndarray, med_idx: jnp.ndarray, *, metric: str,
            backend: str):
    """Full (n, k) medoid-distance cache + nearest/second-nearest summary."""
    pw = get_backend(backend).pairwise(metric)
    dmat = pw(data, data[med_idx])                            # (n, k)
    return (dmat,) + _top2_of(dmat)


def _top2_of(dmat: jnp.ndarray):
    """(d1, d2, nearest) from the (n, k) cache — d2 = +inf when k == 1."""
    if dmat.shape[1] == 1:
        d1 = dmat[:, 0]
        return d1, jnp.full_like(d1, jnp.inf), jnp.zeros(d1.shape, jnp.int32)
    vals, ids = jax.lax.top_k(-dmat, 2)
    return -vals[:, 0], -vals[:, 1], ids[:, 0].astype(jnp.int32)


_top2 = jax.jit(_top2_of)


@functools.partial(jax.jit, static_argnames=("metric", "backend"))
def _assign_points(points: jnp.ndarray, med_rows: jnp.ndarray, *,
                   metric: str, backend: str):
    """Nearest medoid per row of ``points (m, k-free)``: ``(labels (m,),
    d1 (m,))`` against the medoid rows ``(k, d)``."""
    pw = get_backend(backend).pairwise(metric)
    dmat = pw(points, med_rows)                               # (m, k)
    return jnp.argmin(dmat, axis=1).astype(jnp.int32), jnp.min(dmat, axis=1)


def assign_to_medoids(points, med_rows, *, metric: str = "l2",
                      backend: str = "reference",
                      min_bucket: int = DEFAULT_MIN_BUCKET):
    """Assign arriving points to their nearest medoid, padded to a
    power-of-two arrival bucket so any arrival-size stream reuses one
    compiled program per ``(m_bucket, k, d)`` signature (the streaming
    analogue of the serving layer's shape buckets). Returns
    ``(labels (m,) np.int32, d1 (m,) np.float32, pulls)`` — pulls charge
    the padded rows too (they ran)."""
    points = jnp.asarray(points, jnp.float32)
    med_rows = jnp.asarray(med_rows, jnp.float32)
    if points.ndim != 2 or med_rows.ndim != 2:
        raise ValueError(f"expected (m, d) points and (k, d) medoid rows, "
                         f"got {points.shape} and {med_rows.shape}")
    m = int(points.shape[0])
    mb = bucket_n(max(1, m), min_bucket)
    padded = jnp.zeros((mb, points.shape[1]), jnp.float32).at[:m].set(points)
    labels, d1 = _assign_points(padded, med_rows, metric=metric,
                                backend=backend)
    return (np.asarray(labels[:m]), np.asarray(d1[:m]),
            mb * int(med_rows.shape[0]))


@functools.partial(jax.jit,
                   static_argnames=("budget", "k", "metric", "backend"))
def _swap_argmin(data: jnp.ndarray, d1: jnp.ndarray, d2: jnp.ndarray,
                 nearest: jnp.ndarray, chosen: jnp.ndarray, key: jax.Array,
                 *, budget: int, k: int, metric: str, backend: str):
    """One correlated-SH pass over swap-in candidates: ``run_halving`` with
    the FasterPAM ``swap_delta`` estimator (one shared reference draw prices
    all k swaps of every surviving candidate). Returns ``(candidate, medoid
    slot, estimated per-reference delta)`` for the best pair — the winner's
    ``(C, k)`` delta block rides the outcome's ``aux``."""
    rounds = round_schedule(data.shape[0], budget)
    problem = HalvingProblem(
        data, swap_delta(backend, metric, d1=d1, d2=d2, nearest=nearest, k=k),
        arm_mask=~chosen)
    out = run_halving(problem, rounds, backend, key=key)
    slot = jnp.argmin(out.aux[out.winner_pos]).astype(jnp.int32)
    return out.winner, slot, out.theta[out.winner_pos]


@functools.partial(jax.jit, static_argnames=("metric", "backend"))
def _exact_swap_delta(data: jnp.ndarray, cand: jnp.ndarray,
                      slot: jnp.ndarray, d1: jnp.ndarray, d2: jnp.ndarray,
                      nearest: jnp.ndarray, *, metric: str, backend: str):
    """Exact cost delta of swapping medoid ``slot`` for point ``cand`` — one
    n-vector of distances (the verification step before any swap is
    applied). Returns ``(delta, d(cand, .))``; the distance row is reused to
    update the cache column when the swap is accepted."""
    pw = get_backend(backend).pairwise(metric)
    dc = pw(data[cand][None, :], data)[0]                     # (n,)
    mine = nearest == slot
    delta = jnp.sum(jnp.where(mine, jnp.minimum(dc, d2) - d1,
                              jnp.minimum(dc - d1, 0.0)))
    return delta, dc


def _swap_sweep_impl(data: jnp.ndarray, dmat: jnp.ndarray, meds: jnp.ndarray,
                     key_swap: jax.Array, *, max_rounds: int, k: int,
                     budget: int, metric: str, backend: str):
    """The whole SWAP phase as ONE device-resident program.

    A ``lax.scan`` over ``max_rounds`` candidate sweeps carrying the
    ``(n, k)`` assignment cache ``dmat``, the medoid slots, and the accept/reject state machine of the
    host loop it replaces: a round's bandit winner is verified against the
    exact incumbent-delta vector on device, an accepted swap rewrites one
    cache column and resets the rejection counter, and two consecutive
    rejections latch ``done`` (later rounds are masked no-ops; their keys
    are per-round ``fold_in``\\ s, so skipping costs nothing and perturbs
    nothing). Winners, deltas, and the acceptance tolerance never visit the
    host. Returns ``(meds, labels, cost, swaps, executed)`` — ``executed``
    is the number of non-masked rounds, for exact pull accounting.
    """
    n = data.shape[0]
    pw = get_backend(backend).pairwise(metric)
    rounds = round_schedule(n, budget)

    def body(carry, rnd):
        dmat, meds, swaps, rejections, executed, done = carry
        d1, d2, nearest = _top2_of(dmat)
        chosen = jnp.zeros((n,), bool).at[meds].set(True)
        problem = HalvingProblem(
            data, swap_delta(backend, metric, d1=d1, d2=d2, nearest=nearest,
                             k=k), arm_mask=~chosen)
        out = run_halving(problem, rounds, backend,
                          key=jax.random.fold_in(key_swap, rnd))
        cand = out.winner
        slot = jnp.argmin(out.aux[out.winner_pos]).astype(jnp.int32)
        # exact incumbent verification (one n-vector of distances), with the
        # same relative tolerance the host loop used
        dc = pw(data[cand][None, :], data)[0]
        mine = nearest == slot
        delta = jnp.sum(jnp.where(mine, jnp.minimum(dc, d2) - d1,
                                  jnp.minimum(dc - d1, 0.0)))
        tol = -1e-6 * jnp.maximum(1.0, jnp.sum(d1) / n)
        accept = (delta < tol) & ~done
        reject = (delta >= tol) & ~done
        executed = executed + jnp.where(done, 0, 1)
        rejections = jnp.where(accept, 0, rejections + reject)
        done = done | (rejections >= 2)
        meds = jnp.where(accept, meds.at[slot].set(cand.astype(jnp.int32)),
                         meds)
        dmat = jnp.where(accept, dmat.at[:, slot].set(dc), dmat)
        swaps = swaps + accept
        return (dmat, meds, swaps, rejections, executed, done), None

    carry = (dmat, meds, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
             jnp.zeros((), jnp.int32), jnp.zeros((), bool))
    (dmat, meds, swaps, _, executed, _), _ = jax.lax.scan(
        body, carry, jnp.arange(max_rounds, dtype=jnp.int32))
    d1, _, nearest = _top2_of(dmat)
    return meds, nearest, jnp.sum(d1), swaps, executed


_swap_sweep = jax.jit(
    _swap_sweep_impl,
    static_argnames=("max_rounds", "k", "budget", "metric", "backend"))


# --------------------------------------------------------------------------
# ragged per-cluster refinement
# --------------------------------------------------------------------------

def make_direct_refiner(*, metric: str, backend: str, budget_per_arm: int,
                        min_bucket: int = DEFAULT_MIN_BUCKET) -> Refiner:
    """The in-process refiner: coalesce the cluster subproblems into
    power-of-two buckets and answer each bucket with ONE
    ``ragged_medoids`` dispatch — heterogeneous cluster sizes share
    the per-bucket compiled programs with every other ragged traffic
    source. Per-bucket key: ``fold_in(key, n_bucket)``. Batch slots are
    padded to the next power of two (dummy length-1 queries), so the number
    of compiled programs stays bounded no matter how cluster counts shift
    between sweeps — the same fixed-slot trick the MedoidServer uses."""
    def refine(arrays: list, key: jax.Array) -> tuple[list, int]:
        plan = plan_buckets([a.shape[0] for a in arrays], min_bucket)
        locals_: list = [None] * len(arrays)
        pulls = 0
        for nb, idxs in plan.items():
            group = [arrays[i] for i in idxs]
            slots = next_pow2(len(group))
            packed, lens = pack_queries(group, min_bucket,
                                        pad_batch_to=slots)
            meds = ragged_medoids(
                packed, lens, jax.random.fold_in(key, nb),
                budget=budget_per_arm * nb, metric=metric, backend=backend,
                min_bucket=min_bucket)
            # honest accounting: padded slots run the schedule too
            pulls += schedule_pulls(nb, budget_per_arm * nb) * slots
            for s, i in enumerate(idxs):
                locals_[i] = int(meds[s])
        return locals_, pulls
    return refine


# --------------------------------------------------------------------------
# the full pipeline
# --------------------------------------------------------------------------

def _kmedoids_impl(data, k: int, key: jax.Array, *, metric: str = "l2",
                   backend: str = "reference",
                   build_budget_per_arm: int = 16,
                   swap_budget_per_arm: int = 16,
                   refine_budget_per_arm: int = 20,
                   refine_sweeps: int = 1, max_swap_rounds: int = 8,
                   min_bucket: int = DEFAULT_MIN_BUCKET,
                   refiner: Optional[Refiner] = None) -> KMedoidsResult:
    """BUILD -> ragged per-cluster refinement -> bandit SWAP.

    ``data (n, d)``; returns a :class:`KMedoidsResult` whose ``medoids`` are
    point indices (slot order fixed by BUILD) and whose pull counters are
    exact scheduled distance-evaluation counts. Keys derive per phase
    (``fold_in(key, 0/1/2)`` for BUILD / refine / SWAP) so any phase is
    reproducible in isolation. ``refiner`` overrides how the per-cluster
    subproblems are answered (default: in-process bucketed ragged
    dispatches; see :class:`repro.cluster.service.ServiceRefiner` for the
    continuous-batching route).
    """
    data = jnp.asarray(data)
    if data.ndim != 2:
        raise ValueError(f"expected (n, d) data, got shape {data.shape}")
    n = int(data.shape[0])
    if not 1 <= k <= n:
        raise ValueError(f"need 1 <= k <= n, got k={k}, n={n}")
    get_backend(backend)                  # fail before any work
    if refiner is None:
        refiner = make_direct_refiner(metric=metric, backend=backend,
                                      budget_per_arm=refine_budget_per_arm,
                                      min_bucket=min_bucket)

    build_budget = build_budget_per_arm * n
    swap_budget = swap_budget_per_arm * n

    # ---------------- BUILD: k correlated-SH argmin steps ----------------
    # Step 0 IS the paper's problem (same cached program as find_medoid);
    # steps 1..k-1 run as ONE device-resident scan program — the d1/chosen
    # caches and every per-step winner stay on device, and the only host
    # sync of the whole phase is reading the final (k,) medoid vector out
    # for the (host-side) refinement bookkeeping below.
    key_build = jax.random.fold_in(key, 0)
    m0 = _medoid_impl(data, jax.random.fold_in(key_build, 0),
                      budget=build_budget, metric=metric, backend=backend)
    if k > 1:
        meds_dev, _ = _build_scan(data, m0, key_build, k=k,
                                  budget=build_budget, metric=metric,
                                  backend=backend)
    else:   # k == 1: an empty scan would still trace the step body, whose
        meds_dev = m0[None].astype(jnp.int32)   # n==1 schedule is empty

    meds: list[int] = [int(m) for m in meds_dev]     # one post-phase sync
    build_pulls = k * (schedule_pulls(n, build_budget) + n)

    dmat, d1, d2, nearest = _assign(data, meds_dev, metric=metric,
                                    backend=backend)
    assign_pulls = n * k

    # ------- ragged per-cluster refinement with affected-set caching -------
    key_refine = jax.random.fold_in(key, 1)
    refine_pulls = refine_updates = 0
    changed = set(range(k))
    for sweep in range(refine_sweeps):
        if not changed:
            break
        labels_np = np.asarray(nearest)
        which = [(c, np.flatnonzero(labels_np == c)) for c in sorted(changed)]
        which = [(c, mem) for c, mem in which if mem.size > 0]
        if not which:
            break
        locals_, p = refiner([data[mem] for _, mem in which],
                             jax.random.fold_in(key_refine, sweep))
        refine_pulls += p
        updates = 0
        for (c, mem), loc in zip(which, locals_):
            g = int(mem[int(loc)])
            if g != meds[c]:
                meds[c] = g
                updates += 1
        refine_updates += updates
        if updates == 0:
            break
        dmat, d1, d2, nearest = _assign(data, jnp.asarray(meds, jnp.int32),
                                        metric=metric, backend=backend)
        assign_pulls += n * k
        moved = np.asarray(nearest) != labels_np
        changed = (set(np.asarray(nearest)[moved].tolist())
                   | set(labels_np[moved].tolist())) if moved.any() else set()

    # ---------------- SWAP: bandit FasterPAM local search ----------------
    # The whole phase is ONE device-resident program (see _swap_sweep): the
    # bandit argmin, the exact incumbent verification, the accept/reject
    # state machine, and the incremental one-column cache updates all run
    # inside a single lax.scan — a round that doesn't verify re-draws
    # references under the next round's key (estimator noise, not
    # convergence) and the sweep latches off after two consecutive
    # rejections, exactly like the host loop it replaces.
    key_swap = jax.random.fold_in(key, 2)
    swap_pulls = swaps = 0
    # k == n leaves no swap-in candidates (every point is a medoid) — and
    # covers n == 1, whose empty round schedule the argmin couldn't handle
    if k < n and max_swap_rounds > 0:
        meds_dev, nearest, cost_dev, swaps_dev, executed = _swap_sweep(
            data, dmat, jnp.asarray(meds, jnp.int32), key_swap,
            max_rounds=max_swap_rounds, k=k, budget=swap_budget,
            metric=metric, backend=backend)
        meds = [int(m) for m in meds_dev]          # one post-phase sync
        swaps = int(swaps_dev)
        swap_pulls = int(executed) * (schedule_pulls(n, swap_budget) + n)
        cost = float(cost_dev)
        labels = np.asarray(nearest)
    else:
        cost = float(jnp.sum(d1))
        labels = np.asarray(nearest)

    pulls = build_pulls + assign_pulls + refine_pulls + swap_pulls
    return KMedoidsResult(
        medoids=meds, labels=labels, cost=cost,
        pulls=pulls, build_pulls=build_pulls, assign_pulls=assign_pulls,
        refine_pulls=refine_pulls, swap_pulls=swap_pulls, swaps=swaps,
        refine_updates=refine_updates, k=k, metric=metric, backend=backend)


def bandit_kmedoids(data, k: int, key: jax.Array, **kwargs) -> KMedoidsResult:
    """Deprecated: use :func:`repro.api.kmedoids` (same pipeline, config-
    driven). Signature-compatible with the pre-facade entry point."""
    warn_once("repro.cluster.kmedoids.bandit_kmedoids", "repro.api.kmedoids")
    return _kmedoids_impl(data, k, key, **kwargs)
