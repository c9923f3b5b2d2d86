"""THE correlated-SH round loop — one copy, estimator-parameterized.

Before PR 4 the skeleton (draw shared references -> score every surviving
arm -> halve via top-k) existed four times, once per workload: single-query
medoid, masked/ragged medoid, k-medoids BUILD, k-medoids SWAP. BanditPAM
(Tiwari et al., 2020/2023) frames all of these as the *same* bandit argmin
with different arm-loss estimators, and :func:`run_halving` says that in
code: the workload plugs in an :class:`~repro.engine.estimators.ArmEstimator`
and inherits masking, vmapped batching, fused selection, and the
one-XLA-program property for free.

As of PR 6 the loop is **one program by construction**, not by unrolling:
the halving rounds before the output round run as ``lax.scan`` over the
schedule's stacked array form (:meth:`repro.engine.schedule.Schedule.stacked`)
— a fixed-width survivor buffer kept sorted by estimate replaces the
shrinking ``idx``, per-round live counts are positional masks, and
reference draws are fixed-width permutation prefixes weighted by a
positional validity mask. Rounds are grouped into *bands* (default 3 rounds
per scan body) so XLA compiles O(log n / band) round bodies instead of
O(log n), at a bounded fixed-width compute overhead. The **output round**
(``r_stop``) still executes at its exact static legacy shapes outside the
scan, so the outcome's ``theta``/``aux``/winner arithmetic is bit-identical
to the pre-scan loop (scan rounds only make *selection* decisions, which are
invariant to the sub-ulp reduction-order differences fixed-width masking
introduces, except on exact ties already below estimator noise).

Unified semantics, pinned by ``tests/test_engine.py`` against verbatim
snapshots of the four pre-refactor loops (``tests/_legacy_loops.py``):

* **key folding**: one sequential ``key, sub = jax.random.split(key)`` per
  round (inside the scan carry — the same key sequence as the Python loop);
* **reference draws**: uniform without replacement via permutation prefix
  (:func:`sample_refs`); with a ``ref_mask``, the valid-first stable
  partition (:func:`sample_refs_masked`) which degenerates to the unmasked
  draw when every point is valid — the full-bucket bit-exactness theorem;
* **estimates**: the estimator returns raw per-arm *sums*; the engine
  divides by the (static) reference count, or by the drawn *valid* count
  under a ``ref_mask``;
* **arm masking**: ineligible arms (padding, already-chosen medoids) take
  ``+inf`` estimates — they never survive a halving ahead of an eligible arm
  and never win the final argmin;
* **tie-break**: survivor selection and the final argmin resolve ties toward
  the smaller *buffer position* (XLA's stable total-order sort — identical
  to ``jax.lax.top_k`` on negated values, for every ``keep`` at once), for
  every backend including the fused on-chip rank epilogue.

The loop is a pure array program with static shapes only — safe under
``jax.vmap`` (the batched and ragged engines map it over a leading batch
axis) and under ``jax.jit``; :mod:`repro.engine.programs` provides the
cached jitted entry points everything dispatches through.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING, Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.engine.schedule import Round, StackedBand, as_schedule
from repro.obs import telemetry as obs_telemetry

if TYPE_CHECKING:   # repro.core is imported lazily (see resolve_order_fn)
    from repro.core.backend import DistanceBackend
    from repro.engine.estimators import ArmEstimator

BackendLike = Union[str, "DistanceBackend", None]
SelectFn = Callable[[jnp.ndarray, int], jnp.ndarray]
OrderFn = Callable[[jnp.ndarray], jnp.ndarray]

# Rounds per scan body (the compile-vs-compute knob; see Schedule.stacked).
DEFAULT_BAND_ROUNDS = 3

# Buffer-width slack factor for margin-widened halving (``widen=``): every
# band (and the output round's survivor set) gets ``min(n, WIDEN_SLACK *
# scheduled_size)`` slots, so a round may retain up to 2x its scheduled
# survivor count before capacity truncation falsifies ``margin_ok``.
WIDEN_SLACK = 2


# ----------------------------- reference draws ------------------------------

def sample_refs(key: jax.Array, n: int, t: int) -> jnp.ndarray:
    """t reference indices, uniform without replacement (permutation prefix)."""
    if t >= n:
        return jnp.arange(n, dtype=jnp.int32)
    return jax.random.permutation(key, n)[:t].astype(jnp.int32)


def sample_refs_masked(key: jax.Array, n: int, t: int,
                       valid: jnp.ndarray) -> jnp.ndarray:
    """t reference indices favoring valid points: a uniform permutation of
    [0, n) stably partitioned so valid indices come first (still in random
    order — sampling without replacement among the valid points), invalid
    ones trail. When every point is valid this is exactly ``sample_refs``
    (the stable partition of an all-zero rank is the identity), which is what
    makes the masked engine bit-identical to the dense one on full buckets.
    """
    if t >= n:
        return jnp.arange(n, dtype=jnp.int32)
    perm = jax.random.permutation(key, n).astype(jnp.int32)
    order = jnp.argsort(jnp.where(valid[perm], 0, 1))  # jnp sort is stable
    return perm[order][:t]


# --------------------------- survivor selection -----------------------------

def default_select(theta: jnp.ndarray, keep: int) -> jnp.ndarray:
    """Survivor selection: indices of the ``keep`` smallest estimates,
    ascending, ties stable toward the smaller index (top_k on negated
    values, static k). Kept for the distributed engines and as the
    ``keep``-parameterized view of :func:`default_order`."""
    return jax.lax.top_k(-theta, keep)[1]


def default_order(theta: jnp.ndarray) -> jnp.ndarray:
    """Full stable ascending ordering of ``theta`` — ``default_select`` for
    every ``keep`` simultaneously (XLA's sort and top_k share the same
    stable float total order, including ``-0.0 < +0.0``). The scan-based
    round loop reorders its fixed-width survivor buffer with this, and the
    next round's positional live mask *is* the halving."""
    return jnp.argsort(theta).astype(jnp.int32)


def resolve_select_fn(backend: BackendLike) -> SelectFn:
    """The static-``keep`` top-k of a backend (fused ``survivor_topk``
    epilogue when registered, XLA top_k otherwise). The scan loop itself
    selects via full orderings (:func:`resolve_order_fn`); this resolver
    remains for API compatibility and the distributed engines."""
    # Imported at call (trace) time: the engine package sits BELOW repro.core
    # in the layering — repro.core.__init__ pulls in corr_sh, which is built
    # on this module, so a module-level import here would be circular.
    from repro.core.backend import get_backend

    fn = get_backend(backend).survivor_topk
    return fn if fn is not None else default_select


def resolve_order_fn(backend: BackendLike) -> OrderFn:
    """The halving step's survivor ordering: a backend with a fused on-chip
    rank epilogue (``survivor_order``, e.g. ``pallas_fused_topk``) keeps it
    on-chip; everyone else gets the default XLA stable sort. Both have
    identical stable-tie semantics, so the choice never changes survivors."""
    from repro.core.backend import get_backend

    fn = get_backend(backend).survivor_order
    return fn if fn is not None else default_order


# ------------------------------- the engine ---------------------------------

@dataclass(frozen=True)
class HalvingProblem:
    """One bandit-argmin instance: the arms, how pulls score, who's eligible.

    ``data``
        ``(n, d)`` arm rows; row i is both arm i and (potential) reference i.
    ``estimator``
        The :class:`ArmEstimator` scoring a reference batch per arm.
    ``arm_mask``
        Optional ``(n,)`` bool — arms eligible to survive / win (``False``
        arms take ``+inf`` estimates). ``None`` = all eligible, and no
        masking ops are traced at all (the dense path stays bit-identical).
    ``ref_mask``
        Optional ``(n,)`` bool — points eligible as references. Draws use the
        valid-first partition, estimator sums are restricted to drawn valid
        references, and estimates divide by the drawn *valid* count. ``None``
        = every point may serve as a reference (static denominator).
    """
    data: jnp.ndarray
    estimator: ArmEstimator
    arm_mask: Optional[jnp.ndarray] = None
    ref_mask: Optional[jnp.ndarray] = None


@dataclass(frozen=True)
class HalvingOutcome:
    """What one ``run_halving`` pass produced.

    ``winner`` is the global arm index (scalar int32); ``winner_pos`` its
    position within ``survivors`` (the final surviving global indices), so
    estimator ``aux`` — whose leading axis tracks survivors — can be indexed
    at the winner (the SWAP estimator reads its ``(C, k)`` delta this way).
    ``theta`` holds the output round's estimates over ``survivors`` and
    ``r_stop`` the (static) index of that round, for pull accounting.
    ``telemetry`` is ``None`` unless the run carried round telemetry — then
    it is the fixed-shape per-round dict of :mod:`repro.obs.telemetry` (one
    row per executed round, scanned rounds + the output round).

    Margin-widened runs (``run_halving(widen=...)``) additionally report
    ``live`` — the traced count of live finalists in the (slack-widened)
    ``survivors`` prefix — and ``margin_ok``, a traced bool that is ``True``
    iff every widened survivor set fit its static buffer all the way down
    (no margin-retained arm was ever capacity-truncated). Plain runs leave
    both ``None``.
    """
    winner: jnp.ndarray
    winner_pos: jnp.ndarray
    survivors: jnp.ndarray
    theta: jnp.ndarray
    aux: Any
    r_stop: int
    telemetry: Any = None
    live: Any = None
    margin_ok: Any = None


def _scan_band(problem: HalvingProblem, band: StackedBand, order_fn: OrderFn,
               key: jax.Array, buf: jnp.ndarray, telemetry: bool = False):
    """Run one band of halving rounds as a single ``lax.scan``.

    ``buf`` is the fixed-width survivor buffer (``band.width`` global arm
    indices, survivors in the sorted prefix). Each scanned round draws a
    full permutation, takes its static ``ref_cap`` prefix as the reference
    buffer, weights references by ``position < t_r`` (times the problem's
    ``ref_mask`` validity, if any), masks arms at ``position >= s_r`` (the
    live prefix) to ``+inf``, and re-sorts the buffer by estimate — the
    next round's tighter live prefix *is* the halving.

    With ``telemetry`` the scan additionally stacks one
    :func:`repro.obs.telemetry.round_stats` row per round (computed on the
    exact masked ``theta`` selection sees) as its ys — pure extra outputs,
    so the carry (and every selection decision) is untouched.
    """
    data, est = problem.data, problem.estimator
    n = data.shape[0]
    width, cap = band.width, band.ref_cap
    xs = (jnp.asarray(band.survivors, jnp.int32),
          jnp.asarray(band.num_refs, jnp.int32))

    def body(carry, sr_tr):
        key, buf = carry
        s_r, t_r = sr_tr
        key, sub = jax.random.split(key)
        perm = jax.random.permutation(sub, n).astype(jnp.int32)
        if problem.ref_mask is not None:
            perm = perm[jnp.argsort(jnp.where(problem.ref_mask[perm], 0, 1))]
        refs = perm[:cap]                                 # static prefix
        pos_ok = jnp.arange(cap, dtype=jnp.int32) < t_r   # this round's t_r
        if problem.ref_mask is not None:
            w = (pos_ok & problem.ref_mask[refs]).astype(jnp.float32)
            denom = jnp.maximum(jnp.sum(w), 1.0)
        else:
            w = pos_ok.astype(jnp.float32)
            denom = t_r.astype(jnp.float32)
        sums, _ = est.score(data[buf], data[refs], refs=refs, ref_mask=w)
        theta = sums / denom                              # (width,)
        alive = jnp.arange(width, dtype=jnp.int32) < s_r
        theta = jnp.where(alive, theta, jnp.inf)
        if problem.arm_mask is not None:
            theta = jnp.where(problem.arm_mask[buf], theta, jnp.inf)
        ys = obs_telemetry.round_stats(theta) if telemetry else None
        buf = buf[order_fn(theta)]        # stable: live ascending, dead last
        return (key, buf), ys

    (key, buf), rows = jax.lax.scan(body, (key, buf), xs)
    return key, buf, rows


def _scan_band_widened(problem: HalvingProblem, band: StackedBand,
                       keeps: Sequence[int], order_fn: OrderFn,
                       key: jax.Array, buf: jnp.ndarray, live: jnp.ndarray,
                       widen: jnp.ndarray, telemetry: bool = False):
    """One band of *margin-widened* halving rounds as a single ``lax.scan``.

    Identical to :func:`_scan_band` (same key sequence, draws, scoring, and
    sort) except the live prefix is a traced carried count instead of the
    scheduled static ``s_r``: after sorting, the round's cut is the
    ``keep_r``-th smallest estimate (``keep_r`` = the scheduled next-round
    survivor count) and every finite arm within ``widen`` of the cut is
    retained — ``live`` becomes ``clip(#inband, keep_r, width)``. Because
    the counted arms always fit the band's (slack-inflated) buffer, no arm
    is ever lost *inside* a band; capacity truncation can only happen at the
    static band-boundary slices, which the caller accounts in ``margin_ok``.
    """
    data, est = problem.data, problem.estimator
    n = data.shape[0]
    width, cap = band.width, band.ref_cap
    xs = (jnp.asarray(band.num_refs, jnp.int32),
          jnp.asarray(tuple(keeps), jnp.int32))

    def body(carry, tr_keep):
        key, buf, live = carry
        t_r, keep_r = tr_keep
        key, sub = jax.random.split(key)
        perm = jax.random.permutation(sub, n).astype(jnp.int32)
        if problem.ref_mask is not None:
            perm = perm[jnp.argsort(jnp.where(problem.ref_mask[perm], 0, 1))]
        refs = perm[:cap]
        pos_ok = jnp.arange(cap, dtype=jnp.int32) < t_r
        if problem.ref_mask is not None:
            w = (pos_ok & problem.ref_mask[refs]).astype(jnp.float32)
            denom = jnp.maximum(jnp.sum(w), 1.0)
        else:
            w = pos_ok.astype(jnp.float32)
            denom = t_r.astype(jnp.float32)
        sums, _ = est.score(data[buf], data[refs], refs=refs, ref_mask=w)
        theta = sums / denom                              # (width,)
        alive = jnp.arange(width, dtype=jnp.int32) < live
        theta = jnp.where(alive, theta, jnp.inf)
        if problem.arm_mask is not None:
            theta = jnp.where(problem.arm_mask[buf], theta, jnp.inf)
        ys = obs_telemetry.round_stats(theta) if telemetry else None
        order = order_fn(theta)
        # The cut: the keep_r-th smallest estimate. An +inf cut (fewer than
        # keep_r finite arms — heavy masking) keeps every finite arm.
        cut = theta[order][keep_r - 1]
        inband = jnp.isfinite(theta) & (theta <= cut + widen)
        live = jnp.clip(jnp.sum(inband.astype(jnp.int32)), keep_r, width)
        buf = buf[order]                  # stable: live ascending, dead last
        return (key, buf, live), ys

    (key, buf, live), rows = jax.lax.scan(body, (key, buf, live), xs)
    return key, buf, live, rows


def _run_halving_widened(problem: HalvingProblem, sched, order_fn: OrderFn,
                         *, key: jax.Array, band_rounds: int,
                         telemetry: bool,
                         widen: jnp.ndarray) -> HalvingOutcome:
    """The ``widen is not None`` body of :func:`run_halving` — see there."""
    data, est = problem.data, problem.estimator
    n = data.shape[0]
    stk = sched.stacked(n, band_rounds=band_rounds, slack=WIDEN_SLACK)
    widen = jnp.asarray(widen, jnp.float32)
    idx = jnp.arange(n, dtype=jnp.int32)
    live = jnp.asarray(n, jnp.int32)
    ok = jnp.asarray(True)
    scanned_rows = []
    for band in stk.bands:
        # Static boundary slice: the ONLY place a margin-retained arm can be
        # dropped. The dropped arms are the worst-ranked of the widened set,
        # but soundness needs all of them — record the overflow.
        ok = ok & (live <= band.width)
        live = jnp.minimum(live, band.width)
        idx = idx[:band.width]
        keeps = tuple(stk.sizes[band.start + i + 1] for i in range(len(band)))
        key, idx, live, rows = _scan_band_widened(
            problem, band, keeps, order_fn, key, idx, live, widen,
            telemetry=telemetry)
        if telemetry:
            scanned_rows.append(rows)

    out_cap = min(n, WIDEN_SLACK * stk.sizes[stk.r_stop])
    ok = ok & (live <= out_cap)
    live = jnp.minimum(live, out_cap)
    survivors = idx[:out_cap]
    rd = sched[stk.r_stop]
    key, sub = jax.random.split(key)
    if problem.ref_mask is not None:
        refs = sample_refs_masked(sub, n, rd.num_refs, problem.ref_mask)
        ref_mask = problem.ref_mask[refs].astype(jnp.float32)    # (t,)
        denom = jnp.maximum(jnp.sum(ref_mask), 1.0)
    else:
        refs = sample_refs(sub, n, rd.num_refs)
        ref_mask = None
        denom = refs.shape[0]              # static Python int
    sums, aux = est.score(data[survivors], data[refs], refs=refs,
                          ref_mask=ref_mask)
    theta = sums / denom
    theta = jnp.where(jnp.arange(out_cap, dtype=jnp.int32) < live,
                      theta, jnp.inf)
    if problem.arm_mask is not None:
        theta = jnp.where(problem.arm_mask[survivors], theta, jnp.inf)
    pos = jnp.argmin(theta)
    tel = None
    if telemetry:
        rows = scanned_rows + [jax.tree_util.tree_map(
            lambda x: x[None], obs_telemetry.round_stats(theta))]
        measured = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs), *rows)
        tel = obs_telemetry.assemble(sched[: stk.r_stop + 1], measured)
    return HalvingOutcome(winner=survivors[pos], winner_pos=pos,
                          survivors=survivors, theta=theta, aux=aux,
                          r_stop=stk.r_stop, telemetry=tel,
                          live=live, margin_ok=ok)


def run_halving(problem: HalvingProblem, schedule: Sequence[Round],
                backend: BackendLike = None, *, key: jax.Array,
                survivor_order: Optional[OrderFn] = None,
                band_rounds: int = DEFAULT_BAND_ROUNDS,
                telemetry: bool = False,
                widen: Optional[jnp.ndarray] = None) -> HalvingOutcome:
    """Run correlated sequential halving over ``schedule`` — the one round
    loop every workload shares, as one scanned array program.

    ``backend`` only resolves the survivor-ordering epilogue (pass
    ``survivor_order`` explicitly to skip the registry lookup, e.g. when
    vmapping many problems over one resolved backend); the distance path
    itself lives inside ``problem.estimator``. ``schedule`` must be
    non-empty (``n == 1`` has an empty schedule — handle it at the call
    site, the answer is arm 0). ``band_rounds`` groups the pre-output rounds
    into scan bodies (see :meth:`repro.engine.schedule.Schedule.stacked`).

    ``telemetry`` additionally carries the fixed-shape per-round telemetry
    buffer of :mod:`repro.obs.telemetry` through the scan (one row per
    executed round) into ``HalvingOutcome.telemetry``. Telemetry is pure
    extra outputs over the same key sequence, draws, and estimates — the
    winner, survivors, ``theta``, and ``aux`` are bitwise identical with it
    on or off (pinned by ``tests/test_obs.py``).

    Estimators must honor the scan-body-safe contract (see
    :mod:`repro.engine.estimators`): pure traced functions of their inputs
    whose ``ref_mask`` weighting is multiplicative, since scanned rounds
    pass positional validity as weights over fixed-width reference buffers.

    ``widen`` (a device scalar, e.g. :func:`repro.quant.error.margin`)
    switches halving to the *margin-widened* rule for perturbed estimators
    (quantized distance paths): each round keeps its scheduled count PLUS
    every finite arm within ``widen`` of the cut, buffers carry
    :data:`WIDEN_SLACK`-fold slack, and the outcome reports the traced
    ``live`` finalist count and a ``margin_ok`` capacity certificate (see
    :class:`HalvingOutcome`). ``widen=None`` (the default) traces the plain
    scheduled-count path, byte-identical to before the option existed — a
    zero-valued ``widen`` is NOT the same thing (the widened rule still
    retains exact ties at the cut and changes buffer shapes).
    """
    sched = as_schedule(schedule)
    if not len(sched):
        raise ValueError("empty schedule: n == 1 needs no halving — the "
                         "caller should short-circuit to arm 0")
    order_fn = survivor_order if survivor_order is not None \
        else resolve_order_fn(backend)
    if widen is not None:
        return _run_halving_widened(problem, sched, order_fn, key=key,
                                    band_rounds=band_rounds,
                                    telemetry=telemetry, widen=widen)
    data, est = problem.data, problem.estimator
    n = data.shape[0]
    stk = sched.stacked(n, band_rounds=band_rounds)
    idx = jnp.arange(n, dtype=jnp.int32)
    scanned_rows = []
    for band in stk.bands:
        idx = idx[:band.width]            # static slice: sorted live prefix
        key, idx, rows = _scan_band(problem, band, order_fn, key, idx,
                                    telemetry=telemetry)
        if telemetry:
            scanned_rows.append(rows)

    # Output round r_stop at its exact static legacy shapes — every value in
    # the outcome (theta, aux, winner arithmetic) is computed here, outside
    # the scan, bit-identically to the pre-scan loop.
    rd = sched[stk.r_stop]
    survivors = idx[:stk.sizes[stk.r_stop]]
    key, sub = jax.random.split(key)
    if problem.ref_mask is not None:
        refs = sample_refs_masked(sub, n, rd.num_refs, problem.ref_mask)
        ref_mask = problem.ref_mask[refs].astype(jnp.float32)    # (t,)
        denom = jnp.maximum(jnp.sum(ref_mask), 1.0)
    else:
        refs = sample_refs(sub, n, rd.num_refs)
        ref_mask = None
        denom = refs.shape[0]              # static Python int
    sums, aux = est.score(data[survivors], data[refs], refs=refs,
                          ref_mask=ref_mask)
    theta = sums / denom
    if problem.arm_mask is not None:
        theta = jnp.where(problem.arm_mask[survivors], theta, jnp.inf)
    pos = jnp.argmin(theta)
    tel = None
    if telemetry:
        rows = scanned_rows + [jax.tree_util.tree_map(
            lambda x: x[None], obs_telemetry.round_stats(theta))]
        measured = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs), *rows)
        tel = obs_telemetry.assemble(sched[: stk.r_stop + 1], measured)
    return HalvingOutcome(winner=survivors[pos], winner_pos=pos,
                          survivors=survivors, theta=theta, aux=aux,
                          r_stop=stk.r_stop, telemetry=tel)
