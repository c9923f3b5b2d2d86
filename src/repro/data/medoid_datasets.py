"""Synthetic datasets with the statistics of the paper's benchmarks.

The paper evaluates on RNA-Seq (simplex rows, ℓ1), Netflix (sparse ratings,
cosine) and MNIST-zeros (dense images, ℓ2). The property that makes
correlated sampling win on those datasets is *reference heterogeneity*: a
reference point x_J contributes a shared "remoteness" term β_J to every
distance d(x_i, x_J) (Appendix B's additive model), which cancels in
d(x_1,x_J) − d(x_i,x_J). We synthesize lookalikes that carry this structure
explicitly (per-point lognormal spread / Dirichlet concentration / noise
level), calibrated so ρ_near ≈ 0.05–0.3 and H2/H̃2 ≈ 3–50, bracketing the
paper's measured 4.8 (MNIST) and 6.6 (RNA-Seq 20k).

``planted_medoid`` keeps controllable Δ gaps for property tests.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rnaseq_like(key, n: int, d: int = 4096, radial: float = 1.5,
                sparsity: float = 0.3) -> jnp.ndarray:
    """Probability-simplex rows (ℓ1): Dirichlet with per-point concentration.

    Low-concentration rows are spiky and ℓ1-far from everything (large β_j);
    high-concentration rows sit near the base measure (candidate medoids).
    Measured on this generator: rho_near ~ 0.23, variance reduction ~ 38x —
    matching the paper's Fig 3(b) (rho = 0.25 on RNA-Seq 20k).
    """
    kb, ka, kg, ks = jax.random.split(key, 4)
    base = jax.random.gamma(kb, 0.3, (d,)) + 1e-3
    base = base / base.sum()
    alpha_pt = jnp.exp(jax.random.normal(ka, (n,)) * radial - 1.0)  # lognormal

    def rows(args):   # one key per row: the sampler's state scales with rows
        k, a = args
        return jax.random.gamma(k, jnp.maximum(a * base * d, 1e-3))

    # A single (n, d) gamma call holds ~80 B of rejection-sampler state per
    # element (~45 GB at 20,000 x 27,998); 256-row blocks bound it.
    g = jax.lax.map(rows, (jax.random.split(kg, n), alpha_pt), batch_size=256)
    mask = jax.random.bernoulli(ks, 1.0 - sparsity, (n, d))
    g = g * mask + 1e-6
    return g / g.sum(axis=1, keepdims=True)


def netflix_like(key, n: int, d: int = 2048, radial: float = 1.2
                 ) -> jnp.ndarray:
    """Sparse nonnegative 'ratings' (cosine): a dominant taste direction with
    per-user angular spread, plus Zipf item popularity x per-user activity
    driving the (correlated) sparsity pattern — β_j here is the reference
    user's angle/activity. Density falls with the width, as popularity
    decays along the titles. Measured on 1,000-2,000 rows (seeds 0-2, 7):
    3.0-3.1% at the default d = 2048 (median 33-35 ratings a user), 0.53%
    at the Netflix Prize's 17,770 titles (median 48); rho_near ~ 0.32."""
    ku, kn, ke, ks, ka = jax.random.split(key, 5)
    u0 = jax.nn.relu(jax.random.normal(ku, (1, d))) + 0.1
    r = jnp.exp(jax.random.normal(ke, (n,)) * radial) * 0.5
    vals = jax.nn.relu(u0 + r[:, None] * jax.random.normal(kn, (n, d)))
    pop = 1.0 / (1.0 + jnp.arange(d) * 0.05)             # item popularity
    act = jnp.exp(jax.random.normal(ka, (n,)) * radial)  # user activity
    p = jnp.clip(pop[None, :] * act[:, None] * 0.5, 0.0, 1.0)
    x = vals * jax.random.bernoulli(ks, p)
    # guard all-zero rows (cosine undefined): give them one tiny coordinate
    return x.at[:, 0].add(1e-3)


def mnist_zeros_like(key, n: int, d: int = 784, radial: float = 0.4
                     ) -> jnp.ndarray:
    """Dense one-cluster images (ℓ2): prototype + lognormal per-image spread."""
    kb, kn, kr = jax.random.split(key, 3)
    proto = jax.nn.sigmoid(jax.random.normal(kb, (1, d)) * 2.0)
    r = jnp.exp(jax.random.normal(kr, (n,)) * radial) * 0.25
    return jnp.clip(proto + r[:, None] * jax.random.normal(kn, (n, d)),
                    0.0, 1.0)


def planted_medoid(key, n: int, d: int = 64, gap: float = 0.5) -> jnp.ndarray:
    """Gaussian cloud + one point pulled toward the centroid: index 0 is the
    medoid with controllable margin (for property tests)."""
    kx, _ = jax.random.split(key)
    x = jax.random.normal(kx, (n, d))
    centroid = jnp.mean(x, axis=0)
    x = x.at[0].set(centroid * (1.0 - gap * 0.1))
    return x


DATASETS = {
    "rnaseq20k_like": ("l1", rnaseq_like),
    "netflix20k_like": ("cosine", netflix_like),
    "mnist_zeros_like": ("l2", mnist_zeros_like),
}


# ---------------------------------------------------------------------------
# planted-cluster variants (the k-medoids workload): same per-metric structure
# as the single-medoid generators, but with k planted groups and ground-truth
# labels. Cluster sizes are deliberately UNEVEN (log-spaced) so the per-cluster
# subproblems span several power-of-two buckets — the ragged engine's traffic.
# ---------------------------------------------------------------------------

def uneven_sizes(n: int, k: int, spread: float = 2.0) -> list[int]:
    """k log-spaced cluster sizes summing to n (largest ~ e^spread x the
    smallest) — heterogeneous on purpose, to exercise bucketed dispatch."""
    if not 1 <= k <= n:
        raise ValueError(f"need 1 <= k <= n, got k={k}, n={n}")
    w = [math.exp(spread * i / max(1, k - 1)) for i in range(k)]
    sizes = [max(1, int(n * wi / sum(w))) for wi in w]
    diff = n - sum(sizes)      # clamping can overshoot either way
    if diff > 0:
        sizes[-1] += diff
    i = k - 1
    while diff < 0:            # shrink from the largest, never below 1
        take = min(sizes[i] - 1, -diff)
        sizes[i] -= take
        diff += take
        i -= 1
    return sizes


def _labels(sizes) -> jnp.ndarray:
    return jnp.concatenate([jnp.full((s,), c, jnp.int32)
                            for c, s in enumerate(sizes)])


def planted_clusters(key, n: int, d: int = 64, k: int = 8, gap: float = 4.0,
                     spread: float = 2.0):
    """k well-separated Gaussian blobs (ℓ2), uneven sizes; returns
    ``(data (n, d), labels (n,))``. ``gap`` scales the center separation
    relative to the unit within-cluster noise."""
    sizes = uneven_sizes(n, k, spread)
    kc, kx = jax.random.split(key)
    centers = gap * jax.random.normal(kc, (k, d))
    labels = _labels(sizes)
    return centers[labels] + jax.random.normal(kx, (n, d)), labels


def rnaseq_clusters(key, n: int, d: int = 1024, k: int = 8,
                    concentration: float = 80.0, spread: float = 2.0):
    """Simplex rows (ℓ1) with k planted expression programs: each cluster's
    Dirichlet base measure concentrates on its own coordinate block (plus a
    small shared background), so between-cluster ℓ1 is near the maximal 2
    while within-cluster rows stay near their base."""
    sizes = uneven_sizes(n, k, spread)
    labels = _labels(sizes)
    kb, kg, kw = jax.random.split(key, 3)
    blk = d // k
    base = jax.random.gamma(kb, 0.5, (k, d)) * 0.02 + 1e-4   # background
    block_mask = (jnp.arange(d)[None, :] // blk) == jnp.arange(k)[:, None]
    base = base + block_mask * (jax.random.gamma(kw, 2.0, (k, d)) + 0.5)
    base = base / base.sum(axis=1, keepdims=True)            # (k, d) simplex
    alpha = concentration * base[labels] * d / k
    g = jax.random.gamma(kg, jnp.maximum(alpha, 1e-3)) + 1e-8
    return g / g.sum(axis=1, keepdims=True), labels


def netflix_clusters(key, n: int, d: int = 512, k: int = 8,
                     noise: float = 0.25, spread: float = 2.0):
    """Sparse nonnegative ratings (cosine) with k taste communities: each
    cluster rides its own (near-orthogonal in high d) taste direction, with
    per-user noise and popularity-driven sparsity."""
    sizes = uneven_sizes(n, k, spread)
    labels = _labels(sizes)
    ku, kn, ks = jax.random.split(key, 3)
    tastes = jax.nn.relu(jax.random.normal(ku, (k, d))) + 0.05
    vals = jax.nn.relu(tastes[labels]
                       + noise * jax.random.normal(kn, (n, d)))
    pop = 1.0 / (1.0 + jnp.arange(d) * 0.02)
    x = vals * jax.random.bernoulli(ks, jnp.clip(pop, 0.05, 1.0), (n, d))
    return x.at[:, 0].add(1e-3), labels     # guard all-zero rows


def mnist_clusters(key, n: int, d: int = 784, k: int = 8,
                   noise: float = 0.15, spread: float = 2.0):
    """Dense images (ℓ2): k digit prototypes + small per-image noise."""
    sizes = uneven_sizes(n, k, spread)
    labels = _labels(sizes)
    kp, kn = jax.random.split(key)
    protos = jax.nn.sigmoid(jax.random.normal(kp, (k, d)) * 2.0)
    x = jnp.clip(protos[labels] + noise * jax.random.normal(kn, (n, d)),
                 0.0, 1.0)
    return x, labels


# name -> (metric, generator(key, n, d, k) -> (data, labels))
CLUSTER_DATASETS = {
    "planted": ("l2", planted_clusters),
    "rnaseq_like": ("l1", rnaseq_clusters),
    "netflix_like": ("cosine", netflix_clusters),
    "mnist_like": ("l2", mnist_clusters),
}
