"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


def _data(c, r, d, dtype, seed=0):
    k = jax.random.key(seed)
    x = jax.random.normal(jax.random.fold_in(k, 1), (c, d), dtype)
    y = jax.random.normal(jax.random.fold_in(k, 2), (r, d), dtype)
    return x, y


SHAPES = [(1, 1, 1), (5, 3, 2), (128, 128, 256), (130, 257, 300),
          (64, 512, 100), (333, 65, 129)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dot_kernel(shape, dtype):
    x, y = _data(*shape, dtype)
    got = ops.kernel_dot(x, y)
    want = ref.ref_dot_pairwise(x, y)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l1_kernel(shape, dtype):
    x, y = _data(*shape, dtype)
    got = ops.kernel_l1(x, y)
    want = ref.ref_l1_pairwise(x, y)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("shape", SHAPES)
def test_l1_centrality_fused(shape):
    x, y = _data(*shape, jnp.float32)
    got = ops.kernel_l1_centrality(x, y)
    want = ref.ref_l1_centrality(x, y)[:, 0] / y.shape[0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("metric", ["l2", "sql2", "cosine"])
@pytest.mark.parametrize("shape", SHAPES[:4])
def test_gram_metrics(metric, shape):
    x, y = _data(*shape, jnp.float32)
    got = ops.pairwise_kernel(metric)(x, y)
    want = ref.ref_pairwise(metric, x, y)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


@given(c=st.integers(1, 200), r=st.integers(1, 200), d=st.integers(1, 300),
       metric=st.sampled_from(["l1", "l2", "sql2", "cosine"]))
@settings(max_examples=25, deadline=None)
def test_kernels_hypothesis(c, r, d, metric):
    x, y = _data(c, r, d, jnp.float32, seed=c * 1000 + r)
    got = ops.pairwise_kernel(metric)(x, y)
    want = ref.ref_pairwise(metric, x, y)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    assert got.shape == (c, r)


@given(c=st.integers(1, 64), d=st.integers(1, 64))
@settings(max_examples=20, deadline=None)
def test_distance_properties(c, d):
    """Metric axioms on the kernel outputs: symmetry + zero diagonal."""
    x, _ = _data(c, c, d, jnp.float32, seed=d)
    for metric in ("l1", "l2"):
        m = np.asarray(ops.pairwise_kernel(metric)(x, x))
        np.testing.assert_allclose(m, m.T, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.diag(m), 0.0, atol=1e-2)
        assert (m >= -1e-3).all()


@pytest.mark.parametrize("platform,interpret", [("cpu", True), ("tpu", False),
                                                ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, platform, interpret):
    """Kernels interpret on the CPU backend, compile on a TPU, and raise on
    any other backend instead of interpreting silently."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
    if interpret is not None:
        assert ops.interpret_mode() is interpret
        return
    with pytest.raises(RuntimeError, match="TPU"):
        ops.interpret_mode()
    x = jnp.ones((3, 517), jnp.float32)   # a shape no other test traces
    with pytest.raises(RuntimeError, match="TPU"):
        ops.kernel_centrality_sums(x, x, metric="l1")


# ------------------ fused top-k survivor-selection epilogue -----------------

@pytest.mark.parametrize("c,keep", [(1, 1), (5, 2), (8, 8), (128, 64),
                                    (130, 65), (257, 100), (300, 3),
                                    (513, 257)])
def test_topk_smallest_matches_lax_top_k(c, keep):
    """The on-chip rank/select pair must replicate jax.lax.top_k(-theta, k)
    bit-exactly — ascending values, stable index tie-break — because the
    round loop's survivor ORDER seeds the next round's gathers."""
    theta = jax.random.normal(jax.random.key(c * 7 + keep), (c,))
    got = ops.kernel_topk_smallest(theta, keep=keep)
    want = jax.lax.top_k(-theta, keep)[1]
    assert got.tolist() == want.tolist()


def test_topk_smallest_ties_and_inf():
    """Duplicate values and +inf entries (the ragged engine's masked arms)
    keep top_k's stable ordering."""
    theta = jnp.array([3.0, 1.0, jnp.inf, 1.0, 2.0, jnp.inf, 1.0, 0.5])
    got = ops.kernel_topk_smallest(theta, keep=6)
    want = jax.lax.top_k(-theta, 6)[1]
    assert got.tolist() == want.tolist() == [7, 1, 3, 6, 4, 0]


def test_topk_smallest_validates_keep():
    with pytest.raises(ValueError, match="keep"):
        ops.kernel_topk_smallest(jnp.zeros((4,)), keep=5)
    with pytest.raises(ValueError, match="keep"):
        ops.kernel_topk_smallest(jnp.zeros((4,)), keep=0)


@given(c=st.integers(1, 300), frac=st.integers(1, 100))
@settings(max_examples=25, deadline=None)
def test_topk_smallest_hypothesis(c, frac):
    keep = max(1, min(c, (c * frac) // 100))
    key = jax.random.key(c * 101 + frac)
    # quantized values force plenty of exact ties
    theta = jnp.round(jax.random.normal(key, (c,)) * 4.0) / 4.0
    got = ops.kernel_topk_smallest(theta, keep=keep)
    want = jax.lax.top_k(-theta, keep)[1]
    assert got.tolist() == want.tolist()
