"""Device-resident round telemetry — the per-round numbers the paper plots.

Meddit-style bandit algorithms live or die on *per-round* behavior: how fast
the confidence gap between the incumbent and the runner-up closes, and where
the pull budget goes round by round. This module defines the fixed-shape
telemetry pytree the engine (:func:`repro.engine.run_halving`) optionally
carries through its banded ``lax.scan`` — one row per *executed* round
(scanned rounds plus the output round), every leaf a fixed-shape array, so
telemetry rides the same single XLA program as the answer and never adds a
host sync (this module is under the same host-sync grep guard as the engine
package).

Schema — a dict of arrays, each with leading axis ``R`` = executed rounds
(under ``vmap`` a batch axis is prepended: ``(B, R)``):

======================  =======  ==============================================
key                     dtype    meaning (row r)
======================  =======  ==============================================
``survivors``           int32    scheduled arm count entering round r (s_r)
``num_refs``            int32    scheduled reference draws (t_r)
``pulls``               int32    scheduled distance evaluations (s_r * t_r)
``budget_frac``         float32  cumulative pulls through round r / total
                                 scheduled pulls (reaches 1.0 at the last row)
``alive``               int32    arms with finite estimates (eligible + live;
                                 < s_r under arm masking / ragged padding)
``theta_min``           float32  smallest estimate this round (the incumbent)
``theta_med``           float32  median estimate over the alive arms
``theta_max``           float32  largest finite estimate
``gap``                 float32  runner-up minus incumbent — the quantity
                                 halving must outpace; NaN if < 2 alive arms
======================  =======  ==============================================

``survivors``/``num_refs``/``pulls``/``budget_frac`` are trace-time constants
from the static schedule (so per-round pull sums reconcile *exactly* with
:class:`repro.api.MedoidResult`'s scheduled pull accounting); the theta rows
are measured inside the scan body on the exact masked estimates selection
sees. Pull counts are int32 — fine for every CI-scale workload; past ~2^31
scheduled pulls per round read ``budget_frac`` instead.
"""
from __future__ import annotations

import jax.numpy as jnp

# The telemetry dict's keys, in emission order (shared by the host-side
# consumers in repro.obs.trace / repro.obs.validate).
FIELDS = ("survivors", "num_refs", "pulls", "budget_frac", "alive",
          "theta_min", "theta_med", "theta_max", "gap")

_SCHEDULE_FIELDS = ("survivors", "num_refs", "pulls", "budget_frac")
_DTYPES = {"survivors": jnp.int32, "num_refs": jnp.int32, "pulls": jnp.int32,
           "budget_frac": jnp.float32, "alive": jnp.int32,
           "theta_min": jnp.float32, "theta_med": jnp.float32,
           "theta_max": jnp.float32, "gap": jnp.float32}


def round_stats(theta: jnp.ndarray) -> dict:
    """Summary of one round's masked estimates (pure jnp — scan-body safe).

    ``theta`` is the per-arm estimate vector *after* live/eligibility
    masking (+inf at dead or ineligible positions) — exactly what survivor
    selection sees. Statistics are computed over the finite entries; ``gap``
    is the runner-up minus the incumbent (NaN when fewer than two arms are
    alive — +inf - +inf — which the host layer renders as null).
    """
    st = jnp.sort(theta)                       # ascending, +inf trail
    alive = jnp.sum(jnp.isfinite(st)).astype(jnp.int32)
    last = jnp.maximum(alive - 1, 0)
    return {
        "alive": alive,
        "theta_min": st[0].astype(jnp.float32),
        "theta_med": jnp.take(st, last // 2).astype(jnp.float32),
        "theta_max": jnp.take(st, last).astype(jnp.float32),
        "gap": (st[1] - st[0]).astype(jnp.float32),
    }


def winner_gap(theta: jnp.ndarray) -> jnp.ndarray:
    """Runner-up minus incumbent of one round's masked estimates: the
    ``gap`` of :func:`round_stats`, bit for bit, computed alone (the
    serving path's per-query hardness signal for the output round)."""
    st = jnp.sort(theta)
    return (st[1] - st[0]).astype(jnp.float32)


def schedule_constants(executed) -> dict:
    """The static (trace-time constant) telemetry columns for the executed
    rounds — scheduled survivor/reference/pull counts and the cumulative
    budget fraction. ``executed`` is the ``Round`` sequence ``[0 .. r_stop]``
    the engine actually runs, so ``sum(pulls)`` here IS the scheduled pull
    count the facade reports."""
    pulls = [r.pulls for r in executed]
    total = max(1, sum(pulls))
    cum, acc = [], 0
    for p in pulls:
        acc += p
        cum.append(acc / total)
    return {
        "survivors": jnp.asarray([r.survivors for r in executed], jnp.int32),
        "num_refs": jnp.asarray([r.num_refs for r in executed], jnp.int32),
        "pulls": jnp.asarray(pulls, jnp.int32),
        "budget_frac": jnp.asarray(cum, jnp.float32),
    }


def empty() -> dict:
    """The zero-round telemetry buffer (n == 1: nothing to halve)."""
    return {k: jnp.zeros((0,), _DTYPES[k]) for k in FIELDS}


def assemble(executed, measured: dict) -> dict:
    """Combine the static schedule columns with the measured theta rows into
    the full telemetry dict (all leaves shape ``(R,)``), ordered by
    :data:`FIELDS`."""
    out = dict(schedule_constants(executed))
    out.update(measured)
    return {k: out[k] for k in FIELDS}
