"""Cached jitted entry points: every workload dispatches one XLA program.

This is the execution layer of the one-program refactor. Each ``*_program``
factory returns a jitted callable closed over its static configuration
(``budget``/``metric``/``backend``/bucket), memoized in a module-level
table — so the facade (:mod:`repro.api`), the serving layer, and the
clustering pipeline all share literally the same compiled programs, keyed by
``(kind, schedule config, backend, telemetry)`` plus jax's own
shape key. Repeated same-shape calls never retrace (asserted counter-based
in ``tests/test_oneprogram.py`` via :mod:`repro.engine.instrument`); a
telemetry-carrying variant is its own cached program (more outputs), so
turning telemetry on costs one extra trace per signature — once — and
nothing per call thereafter.

**Buffer donation**: only the corpus insert/delete programs donate, because
only their outputs have the donated buffers' shapes and can reuse them in
place. A medoid program returns indices, so a donated arm buffer could
never be aliased (XLA would ignore it). On CPU, where XLA ignores donation,
the flag is folded away; :func:`donation_enabled` reports the effective
behavior.

**Persistent compile cache**: :func:`enable_compile_cache` turns on jax's
compilation cache (``JAX_COMPILATION_CACHE_DIR`` where set, else the fixed
``<checkout>/.jax_cache``), so a restarted server re-*traces* known buckets
but never re-*compiles* them.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Callable

import jax
import jax.numpy as jnp

from repro.engine import instrument
from repro.engine.estimators import medoid_centrality
from repro.engine.halving import HalvingProblem, resolve_order_fn, run_halving
from repro.engine.schedule import round_schedule
from repro.obs import telemetry as obs_telemetry

_PROGRAMS: dict[tuple, Callable] = {}


def donation_enabled() -> bool:
    """Whether buffer donation actually takes effect on this backend (jax
    silently ignores donations on CPU; we fold the flag away there so the
    donating and plain paths share one compiled program)."""
    return jax.default_backend() not in ("cpu",)


def device_info() -> dict:
    """The device this process runs on, as jax reports it — every CLI's JSON
    line carries it, so no result is read without its device."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def program_cache_info() -> dict:
    """Snapshot of the program table: kind -> number of cached callables."""
    info: dict[str, int] = {}
    for key in _PROGRAMS:
        info[key[0]] = info.get(key[0], 0) + 1
    return dict(sorted(info.items()))


def _memo(key: tuple, build: Callable[[], Callable]) -> Callable:
    fn = _PROGRAMS.get(key)
    if fn is None:
        fn = _PROGRAMS[key] = build()
    return fn


# ------------------------------ medoid programs -----------------------------

def _quant_config(precision: str, error_model: str, backend: str):
    """Resolve (effective backend, normalized error model) for a precision.

    For ``precision="fp32"`` the error model is folded to ``None`` so every
    fp32 caller shares one cached program regardless of its quant settings;
    otherwise the quantized backend replaces the caller's (a fused base
    backend keeps a fused quantized path — see ``repro.quant.backends``).
    Imports :mod:`repro.quant` lazily: the engine sits below it in layering.
    """
    if precision == "fp32":
        return backend, None
    from repro import quant

    return quant.backend_for(precision, base=backend), error_model


def medoid_program(*, budget: int, metric: str = "l2",
                   backend: str = "reference",
                   telemetry: bool = False, precision: str = "fp32",
                   error_model: str = "probe") -> Callable:
    """Jitted single-query medoid: ``(data (n, d), key) -> scalar index`` —
    or ``(index, telemetry dict)`` with ``telemetry`` (the per-round buffer
    of :mod:`repro.obs.telemetry` rides the same single program).

    With ``precision`` in {"bf16", "int8"} the whole pipeline changes:
    distances run through the quantized backend, halving runs margin-widened
    (``widen`` from the ``error_model`` of :mod:`repro.quant.error`, traced
    into the same program), and the finalists are re-scored in exact fp32
    (:func:`repro.quant.verify.exact_winner`) — the program returns
    ``(index, verified)`` (plus telemetry), where ``verified`` is the traced
    margin-capacity certificate."""
    eff_backend, eff_err = _quant_config(precision, error_model, backend)

    def build():
        def impl(data: jnp.ndarray, key: jax.Array):
            instrument.note_trace("medoid")
            rounds = round_schedule(data.shape[0], budget)
            if precision == "fp32":
                if not rounds:                    # n == 1
                    winner = jnp.zeros((), jnp.int32)
                    return (winner, obs_telemetry.empty()) if telemetry \
                        else winner
                problem = HalvingProblem(
                    data, medoid_centrality(eff_backend, metric))
                out = run_halving(problem, rounds, eff_backend, key=key,
                                  telemetry=telemetry)
                return (out.winner, out.telemetry) if telemetry \
                    else out.winner
            from repro import quant

            if not rounds:                        # n == 1: trivially exact
                winner = jnp.zeros((), jnp.int32)
                verified = jnp.ones((), bool)
                return (winner, verified, obs_telemetry.empty()) \
                    if telemetry else (winner, verified)
            problem = HalvingProblem(
                data, medoid_centrality(eff_backend, metric))
            widen = quant.margin(data, metric, precision, model=eff_err)
            out = run_halving(problem, rounds, eff_backend, key=key,
                              telemetry=telemetry, widen=widen)
            winner, verified = quant.exact_winner(problem, out, metric)
            return (winner, verified, out.telemetry) if telemetry \
                else (winner, verified)
        return jax.jit(impl)

    return _memo(("medoid", budget, metric, eff_backend, telemetry,
                  precision, eff_err), build)


def batch_program(*, budget: int, metric: str = "l2",
                  backend: str = "reference",
                  telemetry: bool = False, precision: str = "fp32",
                  error_model: str = "probe") -> Callable:
    """Jitted batched medoid: ``(data (B, n, d), key) -> (B,) indices`` —
    or ``((B,) indices, telemetry)`` with ``telemetry`` (per-query rows,
    leaves ``(B, R)``; the shared static schedule columns broadcast).

    One shared static round schedule, per-query reference draws (the key is
    split per query); the whole batch is a single vmap of the scanned round
    loop — one XLA program, one dispatch. Quantized (``precision != "fp32"``)
    programs vmap the widened run + exact fp32 verification per query and
    return ``((B,) indices, (B,) verified[, telemetry])`` — see
    :func:`medoid_program`.
    """
    eff_backend, eff_err = _quant_config(precision, error_model, backend)

    def build():
        def impl(data: jnp.ndarray, key: jax.Array):
            instrument.note_trace("batch")
            if data.ndim != 3:
                raise ValueError(f"expected (B, n, d) batch, "
                                 f"got shape {data.shape}")
            b, n, _ = data.shape
            rounds = round_schedule(n, budget)
            keys = jax.random.split(key, b)
            if not rounds:                        # n == 1
                winners = jnp.zeros((b,), jnp.int32)
                outs = (winners,) if precision == "fp32" \
                    else (winners, jnp.ones((b,), bool))
                if telemetry:
                    outs = outs + (jax.tree_util.tree_map(
                        lambda x: jnp.broadcast_to(x, (b,) + x.shape),
                        obs_telemetry.empty()),)
                return outs[0] if len(outs) == 1 else outs
            est = medoid_centrality(eff_backend, metric)
            order_fn = resolve_order_fn(eff_backend)

            def one(x: jnp.ndarray, k: jax.Array):
                problem = HalvingProblem(x, est)
                if precision == "fp32":
                    out = run_halving(problem, rounds, key=k,
                                      survivor_order=order_fn,
                                      telemetry=telemetry)
                    return (out.winner, out.telemetry) if telemetry \
                        else out.winner
                from repro import quant

                widen = quant.margin(x, metric, precision, model=eff_err)
                out = run_halving(problem, rounds, key=k,
                                  survivor_order=order_fn,
                                  telemetry=telemetry, widen=widen)
                winner, verified = quant.exact_winner(problem, out, metric)
                return (winner, verified, out.telemetry) if telemetry \
                    else (winner, verified)

            return jax.vmap(one)(data, keys)
        return jax.jit(impl)

    return _memo(("batch", budget, metric, eff_backend, telemetry,
                  precision, eff_err), build)


def ragged_program(*, n_bucket: int, budget: int, metric: str = "l2",
                   backend: str = "reference",
                   telemetry: bool = False, precision: str = "fp32",
                   error_model: str = "probe") -> Callable:
    """Jitted ragged medoid: ``(data (B, n_bucket, d), lengths (B,), key) ->
    (B,) indices`` — or ``((B,) indices, telemetry)`` with ``telemetry``
    (leaves ``(B, R)``; the measured rows differ per query through its
    ``alive`` count and masked estimates, the schedule columns are the
    bucket's and broadcast). Padded arms are masked out of every round (arm
    and reference roles both); a query filling its bucket is bit-identical
    to the single-query program. Quantized programs additionally return the
    per-query ``(B,) verified`` certificate — see :func:`medoid_program`."""
    eff_backend, eff_err = _quant_config(precision, error_model, backend)

    def build():
        def impl(data: jnp.ndarray, lengths: jnp.ndarray,
                 key: jax.Array):
            instrument.note_trace("ragged")
            b = data.shape[0]
            rounds = round_schedule(n_bucket, budget)
            if not rounds:                        # n_bucket == 1
                winners = jnp.zeros((b,), jnp.int32)
                outs = (winners,) if precision == "fp32" \
                    else (winners, jnp.ones((b,), bool))
                if telemetry:
                    outs = outs + (jax.tree_util.tree_map(
                        lambda x: jnp.broadcast_to(x, (b,) + x.shape),
                        obs_telemetry.empty()),)
                return outs[0] if len(outs) == 1 else outs
            valid = (jnp.arange(n_bucket, dtype=jnp.int32)[None, :]
                     < lengths[:, None])
            keys = jax.random.split(key, b)
            est = medoid_centrality(eff_backend, metric)
            order_fn = resolve_order_fn(eff_backend)

            def one(x: jnp.ndarray, v: jnp.ndarray, k: jax.Array):
                # padded arms: ineligible to win (arm_mask) AND dropped from
                # every reference draw / denominator (ref_mask) — one
                # validity mask plays both roles.
                problem = HalvingProblem(x, est, arm_mask=v, ref_mask=v)
                if precision == "fp32":
                    out = run_halving(problem, rounds, key=k,
                                      survivor_order=order_fn,
                                      telemetry=telemetry)
                    return (out.winner, out.telemetry) if telemetry \
                        else out.winner
                from repro import quant

                widen = quant.margin(x, metric, precision, model=eff_err)
                out = run_halving(problem, rounds, key=k,
                                  survivor_order=order_fn,
                                  telemetry=telemetry, widen=widen)
                winner, verified = quant.exact_winner(problem, out, metric)
                return (winner, verified, out.telemetry) if telemetry \
                    else (winner, verified)

            return jax.vmap(one)(data, valid, keys)
        return jax.jit(impl)

    return _memo(("ragged", n_bucket, budget, metric, eff_backend,
                  telemetry, precision, eff_err), build)


# ------------------------------ corpus programs -----------------------------
# Device-resident mutation kernels for the live corpus store
# (:mod:`repro.serve.corpus`). All of them operate on the full power-of-two
# *capacity* bucket — a slot freelist on the host decides which row a
# mutation touches, but the compiled signature depends only on the bucket —
# so an arbitrary insert/delete stream inside one capacity bucket reuses one
# compiled program per mutation kind ("no retrace on mutate", asserted by
# tests/test_serve.py against the "corpus" trace odometer). The centrality
# vector ``cent`` holds the EXACT summed distance of every live slot to all
# live slots (+inf at dead slots); each mutation maintains it with the one
# n-vector of distances the incumbent re-verification needs anyway — the
# same one-vector trick the SWAP phase uses before applying a swap.

def _pairwise_of(backend: str, metric: str):
    from repro.core.backend import get_backend

    return get_backend(backend).pairwise(metric)


def corpus_init_program(*, metric: str = "l2",
                        backend: str = "reference") -> Callable:
    """Jitted centrality bootstrap: ``(buf (cap, d), alive (cap,)) ->
    (cent (cap,), winner)`` — the one O(cap^2) pass that seeds the exact
    centrality vector when a store is built from an existing point set
    (mutations after it are all O(cap))."""
    def build():
        def impl(buf: jnp.ndarray, alive: jnp.ndarray):
            instrument.note_trace("corpus")
            pw = _pairwise_of(backend, metric)
            dmat = pw(buf, buf)                               # (cap, cap)
            sums = jnp.sum(jnp.where(alive[None, :], dmat, 0.0), axis=1)
            cent = jnp.where(alive, sums, jnp.inf)
            return cent, jnp.argmin(cent).astype(jnp.int32)
        return jax.jit(impl)

    return _memo(("corpus_init", metric, backend), build)


def corpus_insert_program(*, metric: str = "l2",
                          backend: str = "reference") -> Callable:
    """Jitted insert: ``(buf, cent, alive, x (d,), slot) -> (buf', cent',
    alive', winner)``. One n-vector of distances prices the new point
    exactly AND updates every live slot's exact centrality (``cent[j] +=
    d(x, j)``); ``winner`` is the exact argmin after the mutation, so the
    caller can tell a kept incumbent from a dethroned one without any
    further device work. The store's buffers are donated (folded away on
    CPU)."""
    eff_donate = donation_enabled()

    def build():
        def impl(buf: jnp.ndarray, cent: jnp.ndarray, alive: jnp.ndarray,
                 x: jnp.ndarray, slot: jnp.ndarray):
            instrument.note_trace("corpus")
            pw = _pairwise_of(backend, metric)
            buf = buf.at[slot].set(x)
            row = pw(x[None, :], buf)[0]                      # (cap,)
            cent_x = jnp.sum(jnp.where(alive, row, 0.0))
            cent = jnp.where(alive, cent + row, jnp.inf).at[slot].set(cent_x)
            alive = alive.at[slot].set(True)
            winner = jnp.argmin(cent).astype(jnp.int32)
            return buf, cent, alive, winner
        return jax.jit(impl,
                       donate_argnums=(0, 1, 2) if eff_donate else ())

    return _memo(("corpus_insert", metric, backend, eff_donate), build)


def corpus_delete_program(*, metric: str = "l2",
                          backend: str = "reference") -> Callable:
    """Jitted delete: ``(buf, cent, alive, slot) -> (cent', alive',
    winner)``. The deleted slot's one n-vector of distances backs its
    contribution out of every surviving centrality; the point data stays in
    the (now dead, freelisted) row and is simply masked everywhere."""
    eff_donate = donation_enabled()

    def build():
        def impl(buf: jnp.ndarray, cent: jnp.ndarray, alive: jnp.ndarray,
                 slot: jnp.ndarray):
            instrument.note_trace("corpus")
            pw = _pairwise_of(backend, metric)
            row = pw(buf[slot][None, :], buf)[0]              # (cap,)
            alive = alive.at[slot].set(False)
            cent = jnp.where(alive, cent - row, jnp.inf)
            winner = jnp.argmin(cent).astype(jnp.int32)
            return cent, alive, winner
        return jax.jit(impl, donate_argnums=(1, 2) if eff_donate else ())

    return _memo(("corpus_delete", metric, backend, eff_donate), build)


def corpus_grow_program() -> Callable:
    """Jitted capacity doubling: ``(buf (cap, d), cent, alive) -> the same
    triple at 2*cap``. The new tail starts dead (+inf centrality, freelisted
    by the host store)."""
    def build():
        def impl(buf: jnp.ndarray, cent: jnp.ndarray, alive: jnp.ndarray):
            instrument.note_trace("corpus")
            cap = buf.shape[0]
            return (jnp.pad(buf, ((0, cap), (0, 0))),
                    jnp.pad(cent, (0, cap), constant_values=jnp.inf),
                    jnp.pad(alive, (0, cap)))
        return jax.jit(impl)

    return _memo(("corpus_grow",), build)


def corpus_gather_program() -> Callable:
    """Jitted snapshot gather: ``(buf (cap, d), idx (n_bucket,)) ->
    (n_bucket, d)`` — packs the live slots (host-ordered, zero-padded index
    vector) into the dense prefix form the ragged engine consumes, so a full
    ``run_halving`` re-run rides the exact same cached
    :func:`ragged_program` as every other ragged tenant."""
    def build():
        def impl(buf: jnp.ndarray, idx: jnp.ndarray):
            instrument.note_trace("corpus")
            return jnp.take(buf, idx, axis=0)
        return jax.jit(impl)

    return _memo(("corpus_gather",), build)


# --------------------------- persistent compile cache ------------------------

# <checkout>/.jax_cache: a fixed path (the path is part of the cache key, so
# a per-process or temporary directory would never hit), listed in .gitignore.
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache for this process; returns
    its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already
    uses that directory and no other is set here; otherwise the cache lives
    at :data:`CHECKOUT_CACHE_DIR`. Thresholds are dropped so every engine
    program is cached: a restarted process re-*traces* known signatures but
    never re-*compiles* them. Called by the entry points (CLIs, benchmark
    runner, chip smoke), never at import time."""
    from jax.experimental.compilation_cache import compilation_cache

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()   # re-read the settings on next compile
    return path
