"""RNA-Seq-like rows for the l1 metric, drawn on the device from a key.

The benchmark's own copy of the repo's ``rnaseq_like`` generator, so that
a change to the program cannot change the benchmark's data. Each row is a
point of the probability simplex: Dirichlet draws with a lognormal
per-row concentration (spiky rows lie far from everything, concentrated
rows near the base measure, where the medoid is), then 30% of the
coordinates zeroed and the row renormalised.

A single (n, d) gamma call holds about 80 bytes of sampler state per
element (45 GB at 20,000 x 27,998), so rows are drawn in 256-row blocks,
one key per row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "d", "radial", "sparsity"))
def _rows(key, n: int, d: int, radial: float, sparsity: float):
    kb, ka, kg, ks = jax.random.split(key, 4)
    base = jax.random.gamma(kb, 0.3, (d,)) + 1e-3
    base = base / base.sum()
    alpha = jnp.exp(jax.random.normal(ka, (n,)) * radial - 1.0)

    def row(args):
        k, a = args
        return jax.random.gamma(k, jnp.maximum(a * base * d, 1e-3))

    g = jax.lax.map(row, (jax.random.split(kg, n), alpha), batch_size=256)
    g = g * jax.random.bernoulli(ks, 1.0 - sparsity, (n, d)) + 1e-6
    return g / g.sum(axis=1, keepdims=True)


def generate(key, sizes, d: int, radial: float = 1.5,
             sparsity: float = 0.3) -> list:
    """One (n, d) float32 point set per entry of ``sizes``."""
    return [_rows(jax.random.fold_in(key, i), int(n), int(d), float(radial),
                  float(sparsity)) for i, n in enumerate(sizes)]
