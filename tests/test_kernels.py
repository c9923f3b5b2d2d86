"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels import pairwise_distance as pk


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


# ------------- the ℓ1 kernel that fetches its rows by index ----------------

def _corpus(n, d, seed):
    """A corpus whose rows hold exact zeros, as count data does."""
    k = jax.random.key(seed)
    x = jax.random.uniform(jax.random.fold_in(k, 1), (n, d))
    return jnp.where(jax.random.bernoulli(jax.random.fold_in(k, 2), 0.3,
                                          (n, d)), 0.0, x)


def _draws(n, c, r, seed):
    """Candidate and reference indices drawn with replacement (so rows
    repeat), and reference weights with zeros among them."""
    k = jax.random.key(seed)
    ci = jax.random.randint(jax.random.fold_in(k, 3), (c,), 0, n)
    ri = jax.random.randint(jax.random.fold_in(k, 4), (r,), 0, n)
    w = jax.random.bernoulli(jax.random.fold_in(k, 5), 0.7, (r,))
    return ci, ri, w.astype(jnp.float32)


# widths off the lane multiple, row counts off the sublane multiple, and
# both extreme tilings: many candidates against few references, and few
# against many (several reference tiles, several width blocks)
@pytest.mark.parametrize("n,d,c,r", [
    (60, 300, 70, 13), (37, 1000, 9, 45), (300, 129, 300, 8),
    (700, 300, 5, 1100), (50, 1000, 3, 520)])
def test_l1_centrality_at_is_bitwise_the_gathered_kernel(n, d, c, r):
    data = _corpus(n, d, seed=n + d)
    ci, ri, w = _draws(n, c, r, seed=c * 7 + r)
    rows = ops.l1_rows(data)
    for mask in (None, w):
        want = ops.kernel_centrality_sums(data[ci], data[ri], metric="l1",
                                          ref_mask=mask)
        got = ops.kernel_centrality_sums_at(rows, ci, ri, width=d,
                                            ref_mask=mask)
        assert got.shape == (c,)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,d,r", [(37, 300, 13), (300, 129, 8),
                                   (130, 1000, 70)])
def test_l1_centrality_at_every_row_in_storage_order(n, d, r):
    """The first band's call: every row a candidate, read from the corpus
    as it lies (its partial last tile and lanes past d included)."""
    data = _corpus(n, d, seed=n + r)
    _, ri, w = _draws(n, 1, r, seed=r)
    want = ops.kernel_centrality_sums(data, data[ri], metric="l1",
                                      ref_mask=w)
    got = ops.kernel_centrality_sums_at(ops.l1_rows(data), None, ri,
                                        width=d, ref_mask=w, corpus=data)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    batch = jnp.stack([data, data[::-1]])
    got = jax.vmap(lambda x: ops.kernel_centrality_sums_at(
        ops.l1_rows(x), None, ri, width=d, ref_mask=w, corpus=x))(batch)
    for x, g in zip(batch, got):
        want = ops.kernel_centrality_sums(x, x[ri], metric="l1", ref_mask=w)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want))


def test_l1_centrality_at_under_vmap():
    """The batched and ragged programs vmap the kernel: each query its own
    corpus, draws and weights, or one corpus shared by every query."""
    b, n, d = 3, 40, 300
    data = jnp.stack([_corpus(n, d, seed=s) for s in range(b)])
    draws = [_draws(n, 11, 30, seed=s) for s in range(b)]
    ci, ri, w = (jnp.stack(a) for a in zip(*draws))
    want = jax.vmap(lambda x, c, r, m: ops.kernel_centrality_sums(
        x[c], x[r], metric="l1", ref_mask=m))(data, ci, ri, w)
    got = jax.vmap(lambda x, c, r, m: ops.kernel_centrality_sums_at(
        ops.l1_rows(x), c, r, width=d, ref_mask=m))(data, ci, ri, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    rows = ops.l1_rows(data[0])
    shared = jax.vmap(lambda c, r, m: ops.kernel_centrality_sums_at(
        rows, c, r, width=d, ref_mask=m))(ci, ri, w)
    want = jax.vmap(lambda c, r, m: ops.kernel_centrality_sums(
        data[0][c], data[0][r], metric="l1", ref_mask=m))(ci, ri, w)
    np.testing.assert_array_equal(np.asarray(shared), np.asarray(want))


@pytest.mark.parametrize("budget", [3000, 700])
def test_l1_centrality_at_splits_what_smem_cannot_hold(monkeypatch, budget):
    """Index vectors beyond the kernel's SMEM go in several kernel calls:
    two whole queries per call (3000 bytes), or one query's candidates in
    pieces (700); the sums stay bitwise those of the gathered kernel."""
    b, n, d, c, r = 3, 50, 130, 300, 20
    data = jnp.stack([_corpus(n, d, seed=40 + s) for s in range(b)])
    draws = [_draws(n, c, r, seed=50 + s) for s in range(b)]
    ci, ri, w = (jnp.stack(a) for a in zip(*draws))
    want = jax.vmap(lambda x, cc, rr, m: ops.kernel_centrality_sums(
        x[cc], x[rr], metric="l1", ref_mask=m))(data, ci, ri, w)
    monkeypatch.setattr(pk, "L1_INDEX_BYTES", budget)
    jax.clear_caches()
    got = jax.vmap(lambda x, cc, rr, m: ops.kernel_centrality_sums_at(
        ops.l1_rows(x), cc, rr, width=d, ref_mask=m))(data, ci, ri, w)
    jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not ops.l1_index_fits(n)


def test_l1_centrality_at_accuracy_against_float64():
    """The float64 check of the gathered kernel, with the rows named by
    index into one corpus: within the same relative error."""
    k = jax.random.key(16)
    x = jax.random.uniform(jax.random.fold_in(k, 1), (2, 1024))
    y = jax.random.uniform(jax.random.fold_in(k, 2), (4096, 1024))
    data = jnp.concatenate([x, y])
    got = np.asarray(ops.kernel_centrality_sums_at(
        ops.l1_rows(data), jnp.arange(2), jnp.arange(2, 4098), width=1024),
        np.float64)
    x64, y64 = np.asarray(x, np.float64), np.asarray(y, np.float64)
    want = np.abs(x64[:, None, :] - y64[None]).sum(axis=(1, 2))
    assert np.max(np.abs(got - want) / want) <= 1.3134e-7


@pytest.mark.parametrize("n,d", [(37, 300), (16, 256), (5, 1000), (33, 1)])
def test_l1_rows_is_the_zero_padded_chunk_view(n, d):
    data = _corpus(n, d, seed=n * d)
    nch = pk.l1_row_chunks(d)
    want = jnp.pad(data, ((0, 0), (0, nch * 128 - d))).reshape(n * nch, 128)
    np.testing.assert_array_equal(np.asarray(ops.l1_rows(data)),
                                  np.asarray(want))


def test_l1_tile_fits_whole_rows_and_the_indexed_kernel_knows_its_limit():
    """The indexed kernel's candidate block leaves room for whole rows in
    VMEM: the gathered kernel's at RNA-Seq width, fewer rows where rows are
    wider, and no indexed kernel where not even 8 fit (those calls gather,
    as before). The gathered kernel's block never shrinks for it."""
    assert pk.l1_row_chunks(27998) == 224
    for indexed in (False, True):
        assert pk.l1_centrality_tile(20000, 8, 27998, indexed=indexed) \
            == (128, 8, 7040)
        assert pk.l1_centrality_tile(40, 4000, 27998, indexed=indexed) \
            == (40, 504, 1408)
    for d in (60000, 150000):
        bc = pk.l1_centrality_tile(20000, 8, d, indexed=True)[0]
        assert bc < 128
        assert 4 * 128 * pk.l1_row_chunks(d) \
            * (2 * bc + pk.L1_REF_SLOTS * 8) <= pk.L1_ROW_BYTES
        assert pk.l1_centrality_tile(20000, 8, d)[0] == 128
    # past the indexed kernel's widths the gathered calls keep their blocks
    assert pk.l1_rows_cap(200000) == 0
    assert pk.l1_centrality_tile(313, 506, 200000) == (112, 512, 1664)
    assert pk.l1_centrality_tile(20000, 8, 200000) == (128, 8, 7424)
    wide = jax.ShapeDtypeStruct((10, 300000), jnp.float32)
    assert pk.l1_rows_cap(300000) == 0
    assert ops.centrality_kernel_at("l1")(wide) is None
    assert all(ops.centrality_kernel_at(m) is None
               for m in ("l2", "sql2", "cosine"))


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
