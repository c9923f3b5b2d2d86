"""Distance functions for the medoid engine.

All functions compute *blocked* pairwise distances ``D[c, r] = d(X[c], Y[r])``
for ``X: (C, d)``, ``Y: (R, d)`` in pure jnp. These are the reference
implementations; the Pallas kernels in ``repro.kernels`` implement the same
contract with explicit VMEM tiling (and are validated against these).

Supported metrics (paper uses l1, l2, cosine; squared-l2 included because the
paper's Remark 2 covers non-metric divergences):

- ``l1``      : sum |x - y|
- ``l2``      : sqrt(sum (x - y)^2)
- ``sql2``    : sum (x - y)^2            (Bregman; not a metric)
- ``cosine``  : 1 - <x, y> / (|x||y|)
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

METRICS = ("l1", "l2", "sql2", "cosine")


def _gram(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    # (C, d) @ (d, R) in f32 accumulation — MXU path on TPU. HIGHEST keeps
    # the multiplies f32 there too (the TPU default rounds f32 operands to
    # bf16), so the reference and the exact oracle are fp32 on every backend.
    return jax.lax.dot_general(
        x, y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def pairwise_l1(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    # (C, 1, d) - (1, R, d) -> (C, R, d); reduce over d. VPU-bound.
    return jnp.sum(jnp.abs(x[:, None, :].astype(jnp.float32)
                           - y[None, :, :].astype(jnp.float32)), axis=-1)


def pairwise_sql2(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=-1)  # (C,)
    y2 = jnp.sum(yf * yf, axis=-1)  # (R,)
    g = _gram(xf, yf)               # (C, R)
    # Clamp: the Gram trick can go slightly negative from rounding.
    return jnp.maximum(x2[:, None] + y2[None, :] - 2.0 * g, 0.0)


def pairwise_l2(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(pairwise_sql2(x, y))


def pairwise_cosine(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    xn = jnp.sqrt(jnp.sum(xf * xf, axis=-1))
    yn = jnp.sqrt(jnp.sum(yf * yf, axis=-1))
    g = _gram(xf, yf)
    denom = jnp.maximum(xn[:, None] * yn[None, :], 1e-12)
    return 1.0 - g / denom


_PAIRWISE: dict[str, Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]] = {
    "l1": pairwise_l1,
    "l2": pairwise_l2,
    "sql2": pairwise_sql2,
    "cosine": pairwise_cosine,
}


def pairwise(metric: str) -> Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]:
    """Return the blocked pairwise-distance function for ``metric``."""
    try:
        return _PAIRWISE[metric]
    except KeyError:
        raise ValueError(f"unknown metric {metric!r}; one of {METRICS}") from None


def masked_rowsum(block: jnp.ndarray,
                  ref_mask: jnp.ndarray | None) -> jnp.ndarray:
    """Row sums of a (C, R) distance block over the valid reference columns
    (``ref_mask`` broadcastable to (R,), nonzero = valid; None = all valid).
    The single definition of out-of-kernel mask semantics — the pairwise
    backends and the ragged engine's legacy-backend fallback all route here."""
    if ref_mask is not None:
        block = block * ref_mask.reshape(-1).astype(block.dtype)[None, :]
    return jnp.sum(block, axis=1)


@functools.partial(jax.jit, static_argnames=("metric",))
def full_distance_matrix(x: jnp.ndarray, metric: str = "l2") -> jnp.ndarray:
    """All-pairs (n, n) distance matrix — used by exact computation & oracles."""
    return pairwise(metric)(x, x)


def centrality_sums(x: jnp.ndarray, refs: jnp.ndarray, metric: str,
                    ref_block: int = 32, d_chunk: int = 256,
                    ref_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """sum_j d(x_i, refs_j) without materializing the (C, R) matrix — the
    memory-bounded form the distributed engine scores rounds with.

    For ℓ1 (no matmul form) the broadcast intermediate is bounded to
    (C, ref_block, d_chunk); Gram-trick metrics just take the row-sum of the
    (cheap) pairwise matrix. ``ref_mask`` (shape (R,), nonzero = valid)
    restricts the sum to valid references — the ragged engine's padded arms
    contribute nothing.
    """
    if metric != "l1":
        return masked_rowsum(pairwise(metric)(x, refs), ref_mask)
    C, d = x.shape
    R = refs.shape[0]
    rb = min(ref_block, R)
    pad = (-R) % rb
    refs_p = jnp.pad(refs, ((0, pad), (0, 0)))
    nb = refs_p.shape[0] // rb
    mask = (jnp.arange(nb * rb) < R).astype(jnp.float32)
    if ref_mask is not None:
        mask = mask * jnp.pad(ref_mask.reshape(-1).astype(jnp.float32),
                              (0, pad))
    mask = mask.reshape(nb, rb)
    xf = x.astype(jnp.float32)

    def body(acc, inp):
        blk, m = inp                                 # (rb, d), (rb,)
        blk = blk.astype(jnp.float32)
        tot = jnp.zeros((C,), jnp.float32)
        for c0 in range(0, d, d_chunk):              # static unroll
            a = jnp.abs(xf[:, None, c0:c0 + d_chunk]
                        - blk[None, :, c0:c0 + d_chunk])
            tot = tot + jnp.einsum("crk,r->c", a, m)
        return acc + tot, 0

    acc, _ = jax.lax.scan(body, jnp.zeros((C,), jnp.float32),
                          (refs_p.reshape(nb, rb, d), mask))
    return acc
