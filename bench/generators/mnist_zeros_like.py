"""MNIST-zeros-like images for the l2 metric, drawn on the device from a key.

The benchmark's own copy of the repo's ``mnist_zeros_like`` generator:
each point set has its own prototype image (sigmoid of a scaled normal),
and each row is that prototype plus normal noise with a lognormal
per-row scale, clipped to [0, 1]. Many sets of different sizes are drawn
in one compiled call: the rows of all sets are drawn together, each with
its set's prototype, and split into one array per set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit,
                   static_argnames=("sizes", "d", "radial", "scale"))
def _sets(key, sizes: tuple, d: int, radial: float, scale: float):
    kb, kn, kr = jax.random.split(key, 3)
    owner = jnp.asarray(np.repeat(np.arange(len(sizes)), sizes), jnp.int32)
    rows = int(sum(sizes))
    proto = jax.nn.sigmoid(jax.random.normal(kb, (len(sizes), d)) * 2.0)
    r = jnp.exp(jax.random.normal(kr, (rows,)) * radial) * scale
    x = jnp.clip(proto[owner] + r[:, None] * jax.random.normal(kn, (rows, d)),
                 0.0, 1.0)
    return tuple(jnp.split(x, np.cumsum(sizes)[:-1].tolist()))


def generate(key, sizes, d: int, radial: float = 0.4,
             scale: float = 0.25) -> list:
    """One (n, d) float32 point set per entry of ``sizes``."""
    return list(_sets(key, tuple(int(n) for n in sizes), int(d),
                      float(radial), float(scale)))
