"""The round loop reaches its rows in one of two ways: by index, where the
estimator's kernel fetches them from the corpus itself (ℓ1 on the fused
Pallas backends), or gathered, ``data[idx]``, for every other estimator.
Both give the same run bit for bit; the work odometer counts the rows that
only the second copies out of the corpus."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import instrument, programs
from repro.engine.estimators import build_delta, medoid_centrality, swap_delta
from repro.engine.halving import HalvingProblem, run_halving
from repro.engine.schedule import as_schedule, round_schedule

N, D = 200, 300


def _corpus(n=N, d=D, seed=0):
    k = jax.random.key(seed)
    x = jax.random.uniform(jax.random.fold_in(k, 1), (n, d))
    return jnp.where(jax.random.bernoulli(jax.random.fold_in(k, 2), 0.3,
                                          (n, d)), 0.0, x)


def _gathering(est):
    """The same estimator without its indexed path: every call gathers."""
    return dataclasses.replace(est, score_at=None)


def _run(est, data, key, *, arm_mask=None, ref_mask=None, widen=None):
    rounds = round_schedule(data.shape[0], 20 * data.shape[0])

    def one(x, k):
        out = run_halving(HalvingProblem(x, est, arm_mask, ref_mask), rounds,
                          "pallas_fused", key=k, widen=widen)
        extra = () if widen is None else (out.live, out.margin_ok)
        return (out.winner, out.survivors, out.theta) + extra
    return jax.jit(one)(data, key)


def _same(a, b):
    for u, v in zip(a, b):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


@pytest.mark.parametrize("backend", ["pallas_fused", "pallas_fused_topk"])
def test_indexed_path_is_set_for_l1_on_the_fused_backends_only(backend):
    assert medoid_centrality(backend, "l1").score_at is not None
    assert medoid_centrality(backend, "cosine").score_at is None
    assert medoid_centrality(backend, "l2").score_at is None
    # a caller's own distance block takes rows
    assert medoid_centrality(backend, "l1", pairwise_fn=lambda x, y: x @ y.T
                             ).score_at is None
    d1 = jnp.zeros((N,))
    assert build_delta(backend, "l1", d1=d1).score_at is None
    assert swap_delta(backend, "l1", d1=d1, d2=d1,
                      nearest=jnp.zeros((N,), jnp.int32), k=2).score_at is None


@pytest.mark.parametrize("backend", ["reference", "pallas_pairwise",
                                     "quant_bf16", "quant_bf16_fused",
                                     "quant_int8"])
def test_other_backends_gather(backend):
    assert medoid_centrality(backend, "l1").score_at is None


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_indexed_run_is_the_gathered_run(seed):
    est = medoid_centrality("pallas_fused", "l1")
    data = _corpus(seed=seed)
    key = jax.random.key(100 + seed)
    _same(_run(est, data, key), _run(_gathering(est), data, key))


@pytest.mark.parametrize("seed", [0, 1])
def test_indexed_run_is_the_gathered_run_when_masked(seed):
    """The ragged problem: padded arms neither win nor serve as
    references, and estimates divide by the drawn valid count."""
    est = medoid_centrality("pallas_fused", "l1")
    data = _corpus(seed=10 + seed)
    valid = jnp.arange(N) < 143
    key = jax.random.key(200 + seed)
    kw = dict(arm_mask=valid, ref_mask=valid)
    _same(_run(est, data, key, **kw), _run(_gathering(est), data, key, **kw))


def test_indexed_run_is_the_gathered_run_widened():
    est = medoid_centrality("pallas_fused", "l1")
    data = _corpus(seed=20)
    key = jax.random.key(300)
    widen = jnp.float32(0.01)
    _same(_run(est, data, key, widen=widen),
          _run(_gathering(est), data, key, widen=widen))


def test_indexed_run_is_the_gathered_run_under_vmap():
    """The batched programs vmap the whole round loop over queries."""
    est = medoid_centrality("pallas_fused", "l1")
    data = jnp.stack([_corpus(n=64, d=130, seed=s) for s in range(3)])
    keys = jax.random.split(jax.random.key(400), 3)
    rounds = round_schedule(64, 20 * 64)

    def batch(e):
        return jax.jit(jax.vmap(lambda x, k: run_halving(
            HalvingProblem(x, e), rounds, "pallas_fused", key=k).theta))(
                data, keys)
    _same([batch(est)], [batch(_gathering(est))])


def _gathered_rows(n, budget):
    """Rows a run copies out of the corpus where every call gathers: each
    band's width and reference buffer per scanned round, then the output
    round's survivors and references."""
    sched = as_schedule(round_schedule(n, budget))
    stk = sched.stacked(n, band_rounds=3)
    scanned = sum(len(b) * (b.width + b.ref_cap) for b in stk.bands)
    return scanned + stk.sizes[stk.r_stop] + sched[stk.r_stop].num_refs


@pytest.mark.parametrize("backend,metric,gathers", [
    ("pallas_fused", "l1", False), ("pallas_fused_topk", "l1", False),
    ("pallas_fused", "cosine", True), ("reference", "l1", True)])
def test_odometer_counts_the_rows_the_loop_gathers(backend, metric, gathers):
    n = 256
    rounds = round_schedule(n, 30 * n)
    est = medoid_centrality(backend, metric)
    with instrument.tally() as work:
        jax.eval_shape(lambda x, k: run_halving(
            HalvingProblem(x, est), rounds, backend, key=k).winner,
            jax.ShapeDtypeStruct((n, 64), jnp.float32),
            jax.eval_shape(lambda: jax.random.key(0)))
    want = _gathered_rows(n, 30 * n) if gathers else 0
    assert work.gathered == want
    # n = 256 at 30 pulls per arm: bands 256 x 15, 32 x 120 (3 trips
    # each) and 4 x 240 (1 trip), then 2 arms x 256 references
    assert _gathered_rows(n, 30 * n) == 3 * 271 + 3 * 152 + 244 + 258


def test_odometer_counts_the_indexed_kernels_own_block():
    """Past about 32,000 wide the indexed kernel's candidate tiles are
    smaller than the gathered kernel's (whole rows in VMEM): the odometer
    pads each call to the block of the kernel that runs it."""
    from repro.kernels import ops

    n, d = 256, 60000
    rounds = round_schedule(n, 30 * n)
    est = medoid_centrality("pallas_fused", "l1")

    def computed(e):
        with instrument.tally() as work:
            jax.eval_shape(lambda x, k: run_halving(
                HalvingProblem(x, e), rounds, "pallas_fused", key=k).winner,
                jax.ShapeDtypeStruct((n, d), jnp.float32),
                jax.eval_shape(lambda: jax.random.key(0)))
        return work.computed
    indexed = computed(est)
    assert indexed == computed(dataclasses.replace(
        _gathering(est), tile=ops.L1_TILE_AT))
    assert indexed != computed(_gathering(est))


def test_medoid_program_dispatch_gathers_no_rows():
    data = _corpus(n=128, d=200, seed=30)
    fn = programs.medoid_program(budget=20 * 128, metric="l1",
                                 backend="pallas_fused")
    with instrument.deltas() as d:
        winner = fn(data, jax.random.key(7))
        programs.charge_work("medoid", fn, data)
        instrument.note_dispatch("medoid")
    work = d.work("medoid")
    assert work.called > 0 and work.gathered == 0
    assert 0 <= int(winner) < 128
