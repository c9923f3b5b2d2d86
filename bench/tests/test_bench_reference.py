"""The plain reference against a brute-force NumPy medoid, and the
benchmark's generators' determinism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.generators import mnist_zeros_like, rnaseq_like


def brute_theta(x: np.ndarray, metric: str) -> np.ndarray:
    x = x.astype(np.float64)
    diff = x[:, None, :] - x[None, :, :]
    if metric == "l1":
        d = np.abs(diff).sum(-1)
    elif metric == "l2":
        d = np.sqrt((diff ** 2).sum(-1))
    else:
        n = np.linalg.norm(x, axis=1)
        d = 1.0 - (x @ x.T) / np.outer(n, n)
    return d.mean(1)


@pytest.mark.parametrize("metric,gen", [("l1", rnaseq_like),
                                        ("l2", mnist_zeros_like),
                                        ("cosine", mnist_zeros_like)])
def test_reference_matches_brute_force(metric, gen):
    x = gen.generate(jax.random.key(3), (300,), 40)[0]
    want = brute_theta(np.asarray(x), metric)
    got = np.asarray(reference.centrality(x, jnp.int32(300), metric=metric,
                                          block=64))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
    assert reference.medoid(x, metric)[0] == int(np.argmin(want))


def test_reference_masks_rows_past_count():
    x = mnist_zeros_like.generate(jax.random.key(4), (200,), 16)[0]
    pad = jnp.concatenate([x, jnp.full((56, 16), 9.0)])
    got = np.asarray(reference.centrality(pad, jnp.int32(200), metric="l2"))
    assert np.all(np.isinf(got[200:]))
    np.testing.assert_allclose(got[:200], brute_theta(np.asarray(x), "l2"),
                               rtol=2e-5)


def test_slice_medoid_reads_one_set_of_a_flat_pool():
    sets = mnist_zeros_like.generate(jax.random.key(5), (50, 130, 77), 24)
    flat = jnp.concatenate(list(sets) + [jnp.zeros((256, 24))])
    i, th = reference.slice_medoid(flat, jnp.int32(50), jnp.int32(130),
                                   rows=256, metric="l2")
    want = brute_theta(np.asarray(sets[1]), "l2")
    assert int(i) == int(np.argmin(want))
    assert float(th) == pytest.approx(float(want.min()), rel=2e-5)


def test_bf16_control_differs_from_fp32():
    x = mnist_zeros_like.generate(jax.random.key(6), (300,), 64)[0]
    t32 = reference.centrality(x, jnp.int32(300), metric="l2")
    t16 = reference.centrality(x, jnp.int32(300), metric="l2",
                               precision="bf16")
    assert float(jnp.max(jnp.abs(t32 - t16))) > 1e-4


@pytest.mark.parametrize("gen", [rnaseq_like, mnist_zeros_like])
def test_generators_are_deterministic_per_seed(gen):
    a = gen.generate(jax.random.key(7), (33, 20), 12)
    b = gen.generate(jax.random.key(7), (33, 20), 12)
    c = gen.generate(jax.random.key(8), (33, 20), 12)
    assert [s.shape for s in a] == [(33, 12), (20, 12)]
    for u, v, w in zip(a, b, c):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
        assert not np.array_equal(np.asarray(u), np.asarray(w))


def test_rnaseq_rows_lie_on_the_simplex():
    x = np.asarray(rnaseq_like.generate(jax.random.key(9), (40,), 300)[0])
    assert np.all(x > 0)
    np.testing.assert_allclose(x.sum(1), 1.0, rtol=1e-5)
