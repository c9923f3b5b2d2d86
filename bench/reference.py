"""The plain exact medoid: the yardstick that decides ``correct``.

Independent of the program under test: plain ``jax.numpy``, float32,
matrix products at ``Precision.HIGHEST``. The centrality of row i is the
mean distance from i to every valid row (itself included, at distance 0),
computed in blocks of rows so that no (n, n) matrix is ever held.

``precision="bf16"`` is the control: the same computation a step lower,
as a later change might be tempted to run it. For l1 the inputs are
rounded to bfloat16 and |x - y| is taken in bfloat16 (sums stay float32);
for the Gram metrics the products take bfloat16 operands with float32
accumulation (the TPU's default precision for a float32 matmul).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISIONS = ("fp32", "bf16")


def _l1(rows, data, precision):
    if precision == "bf16":
        rows, data = rows.astype(jnp.bfloat16), data.astype(jnp.bfloat16)
    diff = jnp.abs(rows[:, None, :] - data[None, :, :])
    return jnp.sum(diff, axis=-1, dtype=jnp.float32)


def _gram(rows, data, precision):
    if precision == "bf16":
        rows, data = rows.astype(jnp.bfloat16), data.astype(jnp.bfloat16)
        prec = jax.lax.Precision.DEFAULT
    else:
        prec = jax.lax.Precision.HIGHEST
    return jax.lax.dot_general(rows, data, (((1,), (1,)), ((), ())),
                               precision=prec,
                               preferred_element_type=jnp.float32)


def _sq_l2(rows, data, precision):
    r2 = jnp.sum(rows * rows, axis=1)
    d2 = jnp.sum(data * data, axis=1)
    g = _gram(rows, data, precision)
    return jnp.maximum(r2[:, None] + d2[None, :] - 2.0 * g, 0.0)


def _cosine(rows, data, precision):
    rn = jnp.sqrt(jnp.sum(rows * rows, axis=1))
    dn = jnp.sqrt(jnp.sum(data * data, axis=1))
    g = _gram(rows, data, precision)
    return 1.0 - g / jnp.maximum(rn[:, None] * dn[None, :], 1e-12)


DISTANCES = {
    "l1": _l1,
    "l2": lambda r, x, p: jnp.sqrt(_sq_l2(r, x, p)),
    "sql2": _sq_l2,
    "cosine": _cosine,
}


@functools.partial(jax.jit, static_argnames=("metric", "precision", "block"))
def centrality(data, count, *, metric: str, precision: str = "fp32",
               block: int = 256):
    """Mean distance of each row of ``data (P, d)`` to its first ``count``
    rows; rows at or past ``count`` read +inf. Returns (P,) float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    dist = DISTANCES[metric]
    p = data.shape[0]
    data = data.astype(jnp.float32)
    valid = jnp.arange(p) < count
    pad = (-p) % block
    padded = jnp.pad(data, ((0, pad), (0, 0)))

    def one_block(i):
        rows = jax.lax.dynamic_slice_in_dim(padded, i * block, block, axis=0)
        d = dist(rows, data, precision)
        return jnp.sum(jnp.where(valid[None, :], d, 0.0), axis=1)

    sums = jax.lax.map(one_block, jnp.arange((p + pad) // block)).reshape(-1)
    theta = sums[:p] / count.astype(jnp.float32)
    return jnp.where(valid, theta, jnp.inf)


@functools.partial(jax.jit,
                   static_argnames=("rows", "metric", "precision", "block"))
def slice_medoid(flat, offset, count, *, rows: int, metric: str,
                 precision: str = "fp32", block: int = 256):
    """The medoid of ``flat[offset : offset + count]``, read through a
    window of ``rows`` rows (so one program serves every count up to it).
    Returns ``(index within the set, its centrality)``."""
    data = jax.lax.dynamic_slice_in_dim(flat, offset, rows, axis=0)
    theta = centrality(data, count, metric=metric, precision=precision,
                       block=block)
    i = jnp.argmin(theta)
    return i, theta[i]


def medoid(data, metric: str, precision: str = "fp32"):
    """Index of the medoid of ``data (n, d)`` and its centrality (host)."""
    theta = centrality(data, jnp.int32(data.shape[0]), metric=metric,
                       precision=precision)
    i = int(jnp.argmin(theta))
    return i, float(theta[i])
