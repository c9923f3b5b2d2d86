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


# The round loop makes one axis of every ℓ1 call small: few references in
# early rounds, few candidates late. The fused kernel sizes its tile from
# the shape, so these miniatures cover both extremes and d off the lane.
@pytest.mark.parametrize("c", [2, 5, 40, 300])
@pytest.mark.parametrize("r", [2, 8, 64, 500])
def test_l1_centrality_sums_at_schedule_shapes(c, r):
    x, y = _data(c, r, 300, jnp.float32, seed=c * 1000 + r)
    got = ops.kernel_centrality_sums(x, y, metric="l1")
    want = ref.ref_l1_centrality(x, y)[:, 0]
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("c,r", [(40, 64), (5, 500), (300, 8)])
def test_l1_centrality_sums_weigh_each_reference(c, r):
    """A positional prefix (the scan bands' ``position < t_r``) and an
    arbitrary weight vector (the ragged engine's valid arms) multiply each
    reference's contribution; weight-0 references add nothing."""
    x, y = _data(c, r, 300, jnp.float32, seed=c + r)
    prefix = (jnp.arange(r) < (r + 1) // 2).astype(jnp.float32)
    ragged = jax.random.bernoulli(jax.random.key(r), 0.6, (r,)) \
        .astype(jnp.float32)
    for w in (prefix, ragged):
        got = ops.kernel_centrality_sums(x, y, metric="l1", ref_mask=w)
        want = jnp.sum(ref.ref_l1_pairwise(x, y) * w[None, :], axis=1)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


def test_l1_centrality_sums_under_vmap():
    """The ragged engine vmaps the fused kernel over queries, each with its
    own reference weights."""
    k = jax.random.key(3)
    xs = jax.random.normal(jax.random.fold_in(k, 1), (3, 5, 300))
    ys = jax.random.normal(jax.random.fold_in(k, 2), (3, 64, 300))
    ws = (jnp.arange(64)[None, :] < jnp.array([[64], [17], [1]])) \
        .astype(jnp.float32)
    got = jax.vmap(lambda a, b, m: ops.kernel_centrality_sums(
        a, b, metric="l1", ref_mask=m))(xs, ys, ws)
    want = jnp.stack([jnp.sum(ref.ref_l1_pairwise(a, b) * m[None, :], axis=1)
                      for a, b, m in zip(xs, ys, ws)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


def test_l1_centrality_sums_accuracy_against_float64():
    """Many references into one sum: 2 candidates x 4,096 references at
    d = 1,024. The f32 sums keep within the relative error the 128 x 128 x
    256-tiled kernel gave on these inputs (1.3133e-7)."""
    k = jax.random.key(16)
    x = jax.random.uniform(jax.random.fold_in(k, 1), (2, 1024))
    y = jax.random.uniform(jax.random.fold_in(k, 2), (4096, 1024))
    got = np.asarray(ops.kernel_centrality_sums(x, y, metric="l1"),
                     np.float64)
    x64, y64 = np.asarray(x, np.float64), np.asarray(y, np.float64)
    want = np.abs(x64[:, None, :] - y64[None]).sum(axis=(1, 2))
    assert np.max(np.abs(got - want) / want) <= 1.3134e-7


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
