"""Quantized distance subsystem (PR 10): the soundness + exactness contracts.

What the subsystem promises, and what this suite pins:

* **certified error model** — the analytic per-distance bound of
  :func:`repro.quant.analytic_distance_bound` actually dominates the
  observed ``max |d_q - d_f|`` on full pairwise blocks, for every metric
  and both quantized precisions;
* **soundness of the widened halving** (the hypothesis property of the
  issue): on adversarial near-tie instances, whenever the capacity
  certificate ``margin_ok`` holds, the margin-widened quantized run NEVER
  drops the arm the same-draw fp32 run selects — it is always among the
  live finalists the exact epilogue scores;
* **exactness of the served answer** — the quantized facade's medoid is
  never worse (in exact fp32 centrality) than the fp32 facade's answer for
  the same key: verified runs return the exact-centrality argmin of a
  finalist superset, unverified runs fall back to the same-key fp32 run;
* **plumbing parity** — batch/ragged quantized dispatches match the
  single-query quantized facade under the engine's key-splitting contract;
  pulls account for the verification epilogue; the quantized
  ``CorpusStore`` / ``maintain_medoid`` / k-medoids / ``MedoidServer``
  paths run the quantized backends end to end (with warmup pre-tracing
  every variant a live dispatch can select).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import quant
from repro.api import (MedoidConfig, find_medoid, find_medoids_batch,
                       find_medoids_ragged, maintain_medoid)
from repro.core import METRICS, exact_medoid, pairwise
from repro.engine import (HalvingProblem, medoid_centrality, round_schedule,
                          run_halving)

pytestmark = pytest.mark.quant

QUANT = ("bf16", "int8")


def _near_tie_data(seed: int, n_base: int = 24, d: int = 6,
                   jitter: float = 1e-3):
    """Adversarial near-ties: every point has a twin ``jitter`` away, so
    survivor cuts land inside clusters of nearly-equal centralities — the
    regime where an unwidened quantized run evicts fp32 survivors."""
    key = jax.random.key(seed)
    base = jax.random.normal(jax.random.fold_in(key, 0), (n_base, d))
    pts = jnp.concatenate([base, base], axis=0)
    noise = jitter * jax.random.normal(jax.random.fold_in(key, 1),
                                       pts.shape)
    return pts + noise, jax.random.fold_in(key, 2)


# ------------------------------ error model ---------------------------------

@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("precision", QUANT)
def test_analytic_bound_dominates_observed_error(metric, precision):
    data = jax.random.normal(jax.random.key(17), (96, 12)) * 1.7
    dq = quant.quant_pairwise(metric, precision)(data, data)
    df = pairwise(metric)(data, data)
    observed = float(jnp.max(jnp.abs(dq - df)))
    bound = float(quant.analytic_distance_bound(data, metric, precision))
    assert observed <= bound * (1.0 + 1e-5), (metric, precision,
                                              observed, bound)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("precision", QUANT)
def test_probe_margin_positive_and_below_analytic(metric, precision):
    """The probe statistic measures mean-over-refs perturbation, so (at the
    shared safety factor) it must sit at or below the certified worst-case
    — that gap is exactly why the probe model's margins are usable."""
    data = jax.random.normal(jax.random.key(23), (200, 10))
    probe = float(quant.margin(data, metric, precision, model="probe"))
    analytic = float(quant.margin(data, metric, precision,
                                  model="analytic"))
    assert 0.0 < probe
    assert probe <= quant.DEFAULT_SAFETY * analytic


def test_margin_fp32_is_zero_and_model_validated():
    data = jnp.ones((8, 3))
    assert float(quant.margin(data, "l2", "fp32")) == 0.0
    with pytest.raises(ValueError, match="unknown error model"):
        quant.margin(data, "l2", "bf16", model="exact")
    with pytest.raises(ValueError, match="unknown precision"):
        quant.check_precision("fp16")


# --------------------- widened halving: soundness property -------------------

@given(seed=st.integers(0, 300), precision=st.sampled_from(QUANT))
@settings(max_examples=20, deadline=None)
def test_widened_halving_never_drops_fp32_winner_on_near_ties(seed,
                                                              precision):
    """THE soundness property: with the analytic (certified) margin, a
    margin-widened quantized run whose capacity certificate holds retains
    the arm the same-draw fp32 run selects among its live finalists."""
    data, key = _near_tie_data(seed)
    n = int(data.shape[0])
    rounds = round_schedule(n, 16 * n)
    backend = quant.backend_for(precision)
    widen = quant.margin(data, "l2", precision, model="analytic")
    out_q = run_halving(
        HalvingProblem(data, medoid_centrality(backend, "l2")),
        rounds, backend, key=key, widen=widen)
    out_f = run_halving(
        HalvingProblem(data, medoid_centrality("reference", "l2")),
        rounds, "reference", key=key)
    if bool(out_q.margin_ok):
        finalists = np.asarray(out_q.survivors)[: int(out_q.live)]
        assert int(out_f.winner) in set(finalists.tolist()), (
            seed, precision, int(out_f.winner), finalists)


@given(seed=st.integers(0, 300), precision=st.sampled_from(QUANT))
@settings(max_examples=15, deadline=None)
def test_facade_answer_never_worse_than_fp32_on_near_ties(seed, precision):
    """End-to-end exactness: the quantized facade's answer has exact fp32
    centrality <= the fp32 facade's answer for the same key — verified runs
    return the exact argmin of a finalist superset; unverified runs ARE the
    same-key fp32 run."""
    data, key = _near_tie_data(seed)
    f = find_medoid(data, key, budget_per_arm=16)
    q = find_medoid(data, key, budget_per_arm=16, precision=precision,
                    quant_error_model="analytic")
    assert q.verified in (True, False)
    if q.verified is False:
        assert q.medoid == f.medoid          # same-key fp32 fallback
    cent = jnp.sum(pairwise("l2")(data, data), axis=1)
    assert float(cent[q.medoid]) <= float(cent[f.medoid]) * (1 + 1e-6)


def test_unwidened_runs_carry_no_certificate():
    data = jax.random.normal(jax.random.key(5), (64, 8))
    rounds = round_schedule(64, 16 * 64)
    out = run_halving(HalvingProblem(data, medoid_centrality()), rounds,
                      key=jax.random.key(1))
    assert out.live is None and out.margin_ok is None


# --------------------------- exact fp32 epilogue -----------------------------

def test_exact_winner_is_exact_argmin_of_live_finalists():
    data = jax.random.normal(jax.random.key(31), (80, 7))
    n = int(data.shape[0])
    rounds = round_schedule(n, 16 * n)
    widen = quant.margin(data, "l2", "int8", model="probe")
    problem = HalvingProblem(data, medoid_centrality("quant_int8", "l2"))
    out = run_halving(problem, rounds, "quant_int8",
                      key=jax.random.key(3), widen=widen)
    winner, verified = quant.exact_winner(problem, out, "l2")
    finalists = np.asarray(out.survivors)[: int(out.live)]
    cent = np.asarray(jnp.sum(pairwise("l2")(data, data), axis=1))
    assert int(winner) == int(finalists[np.argmin(cent[finalists])])
    assert bool(verified) == bool(out.margin_ok)
    assert quant.verify_pulls(n, rounds) == \
        quant.verify_width(n, rounds) * n


# ------------------------------ facade plumbing ------------------------------

def test_facade_validation():
    data = jnp.ones((8, 3))
    with pytest.raises(ValueError, match="unknown precision"):
        find_medoid(data, jax.random.key(0), precision="fp16")
    with pytest.raises(ValueError, match="requires algo='corr_sh'"):
        find_medoid(data, jax.random.key(0), precision="bf16", algo="exact")


@pytest.mark.parametrize("precision", QUANT)
def test_facade_pulls_account_for_verification(precision):
    n = 64
    data = jax.random.normal(jax.random.key(n), (n, 8))
    key = jax.random.key(1000 + n)
    f = find_medoid(data, key, budget_per_arm=16)
    q = find_medoid(data, key, budget_per_arm=16, precision=precision)
    rounds = round_schedule(n, 16 * n)
    assert q.precision == precision
    want = f.pulls + quant.verify_pulls(n, rounds)
    if q.verified:
        assert q.pulls == want
    else:
        assert q.pulls == want + f.pulls      # + the fp32 fallback re-run
    assert 0 <= q.medoid < n


@pytest.mark.parametrize("precision", QUANT)
def test_batch_matches_single_query_quantized(precision):
    b, n, d = 3, 64, 8
    data = jax.random.normal(jax.random.key(6), (b, n, d))
    key = jax.random.key(8)
    got = find_medoids_batch(data, key, budget_per_arm=16,
                             precision=precision)
    keys = jax.random.split(key, b)
    singles = [find_medoid(data[i], keys[i], budget_per_arm=16,
                           precision=precision).medoid for i in range(b)]
    assert [int(m) for m in got] == singles


@pytest.mark.parametrize("precision", QUANT)
def test_ragged_full_bucket_matches_single_query_quantized(precision):
    n, d = 64, 8
    qs = [jax.random.normal(jax.random.fold_in(jax.random.key(42), i),
                            (n, d)) for i in range(2)]
    key = jax.random.key(77)
    got = find_medoids_ragged(qs, key=key, budget_per_arm=16,
                              precision=precision)
    keys = jax.random.split(key, 2)
    singles = [find_medoid(qs[i], keys[i], budget_per_arm=16,
                           precision=precision).medoid for i in range(2)]
    assert [int(m) for m in got] == singles


def test_single_point_short_circuit():
    res = find_medoid(jnp.ones((1, 4)), jax.random.key(0), precision="int8")
    assert (res.medoid, res.pulls, res.verified) == (0, 0, True)


def test_telemetry_carries_hardness_and_certificate():
    data = jax.random.normal(jax.random.key(64), (64, 8))
    res = find_medoid(data, jax.random.key(1064), budget_per_arm=16,
                      precision="bf16", telemetry=True)
    assert res.verified in (True, False)
    assert res.telemetry is not None
    assert set(res.hardness) == {"delta2", "sigma", "h2", "h2_tilde"}
    assert res.hardness["delta2"] >= 0.0 and res.hardness["h2"] > 0.0


# ----------------------- downstream consumers (serving) ----------------------

def test_corpus_store_and_maintained_medoid_quantized():
    from repro.serve.corpus import CorpusStore

    data = np.asarray(jax.random.normal(jax.random.key(3), (60, 5)))
    store = CorpusStore.from_points(data, precision="int8",
                                    metric="l2")
    assert store.precision == "int8" and store.backend == "quant_int8"
    assert store.n == 60

    mm = maintain_medoid(data, config=MedoidConfig(precision="int8"))
    slot, version = mm.query()
    # quantized-exact incremental centralities on generic-position data:
    # the maintained winner is the exact fp32 medoid
    assert slot == int(exact_medoid(jnp.asarray(data), "l2"))
    mm.insert(np.zeros((5,), np.float32))
    slot2, version2 = mm.query()
    assert version2 > version and mm.store.is_live(slot2)


def test_kmedoids_runs_on_quant_backend():
    from repro.api import KMedoidsConfig, kmedoids

    data = jax.random.normal(jax.random.key(12), (96, 6))
    res = kmedoids(data, 4, jax.random.key(13),
                   config=KMedoidsConfig(backend="quant_bf16"))
    meds = sorted(res.medoids)
    assert len(set(meds)) == 4 and all(0 <= m < 96 for m in meds)


def test_server_quant_warmup_pretraces_every_variant():
    """The warmup satellite: a quantized server's warmup traces its live
    quantized variant AND the exact fp32 fallback program, so live traffic
    on warmed buckets never retraces."""
    from repro.launch.serve_medoid import MedoidServer

    srv = MedoidServer(precision="bf16", seed=0, max_batch=4)
    srv.warmup([(48, 6)])
    c0 = srv.recompiles
    for i in range(3):
        # n in 40..42: same power-of-two bucket (64) warmup pre-traced
        srv.submit(jax.random.normal(jax.random.fold_in(
            jax.random.key(9), i), (40 + i, 6)))
    srv.drain()
    stats = srv.stats()
    assert srv.recompiles == c0 == 0          # all variants were pre-traced
    assert stats["answered"] == 3
    assert stats["precision"] == "bf16"
    assert stats["quant_fallbacks"] >= 0


def test_server_rejects_bad_precision():
    from repro.launch.serve_medoid import MedoidServer

    with pytest.raises(ValueError, match="unknown precision"):
        MedoidServer(precision="fp16")
    with pytest.raises(ValueError, match="unknown error model"):
        MedoidServer(precision="bf16", quant_error_model="exact")
