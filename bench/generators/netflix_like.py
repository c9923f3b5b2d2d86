"""Netflix-like ratings rows for the cosine metric, drawn on the device from
a key.

The benchmark's own copy of the repo's ``netflix_like`` generator, so that
a change to the program cannot change the benchmark's data. Each user row
is a dominant taste direction plus normal noise with a lognormal per-user
spread, kept where it is positive; a title is rated with a probability of
its Zipf-like popularity times the user's lognormal activity, so the
sparsity pattern is correlated across users. Column 0 carries a 1e-3
guard, so that no row is all zero (cosine is undefined there). At 17,770
titles about 0.53% of the entries are ratings, a median of 48 a user.

One (n, d) call holds a few (n, d) float32 intermediates at its peak,
which fits one chip at 20,000 x 17,770, so rows are drawn in one call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "d", "radial"))
def _rows(key, n: int, d: int, radial: float):
    ku, kn, ke, ks, ka = jax.random.split(key, 5)
    u0 = jax.nn.relu(jax.random.normal(ku, (1, d))) + 0.1
    r = jnp.exp(jax.random.normal(ke, (n,)) * radial) * 0.5
    vals = jax.nn.relu(u0 + r[:, None] * jax.random.normal(kn, (n, d)))
    pop = 1.0 / (1.0 + jnp.arange(d) * 0.05)
    act = jnp.exp(jax.random.normal(ka, (n,)) * radial)
    p = jnp.clip(pop[None, :] * act[:, None] * 0.5, 0.0, 1.0)
    x = vals * jax.random.bernoulli(ks, p)
    return x.at[:, 0].add(1e-3)


def generate(key, sizes, d: int, radial: float = 1.2) -> list:
    """One (n, d) float32 point set per entry of ``sizes``."""
    return [_rows(jax.random.fold_in(key, i), int(n), int(d), float(radial))
            for i, n in enumerate(sizes)]
