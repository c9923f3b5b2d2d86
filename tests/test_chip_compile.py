"""Ahead-of-time compiles of the main path for a TPU v5e, without a chip.

Interpret mode runs the Pallas kernels through XLA:CPU and cannot see what
the TPU compiler refuses: 1-D vector layouts, lanes padded past the scoped
VMEM limit, unaligned slices. These tests compile the kernels at
1024x512x1024 tiles, the fused l1 kernel at both extremes of its
shape-sized tile at RNA-Seq width, and the l1 ``pallas_fused`` medoid
program at 4096x512 for a described ``v5e:2x2`` topology, and check that
the compiled program holds the Mosaic kernel (``tpu_custom_call``) rather
than an interpreted one.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file. Keep all chip compiles in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine import programs
from repro.kernels import ops

C, D, R = 1024, 512, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer ``ops`` to the compiled kernels (the CPU backend interprets
    them) with the persistent compile cache off. jit caches are cleared on
    both sides, so no interpreted trace leaks in and no compiled one out."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(programs, "_PROGRAMS", {})
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_l1_pairwise_compiles(compiled_kernels, one_chip):
    x = _spec((C, D), jnp.float32, one_chip)
    y = _spec((R, D), jnp.float32, one_chip)
    _compile(ops.kernel_l1, x, y)


def test_l1_centrality_compiles(compiled_kernels, one_chip):
    x = _spec((C, D), jnp.float32, one_chip)
    y = _spec((R, D), jnp.float32, one_chip)
    mask = _spec((R,), jnp.float32, one_chip)
    _compile(lambda a, b, m: ops.kernel_centrality_sums(
        a, b, metric="l1", ref_mask=m), x, y, mask)


@pytest.mark.parametrize("c,r", [(8, 8192), (4096, 8)])
def test_l1_centrality_compiles_at_both_extreme_tilings(compiled_kernels,
                                                        one_chip, c, r):
    """RNA-Seq width (d = 27,998): few candidates against many references
    (the late rounds) and the reverse (the early ones), each at the tile
    its shape gives, inside the scoped VMEM limit."""
    x = _spec((c, 27998), jnp.float32, one_chip)
    y = _spec((r, 27998), jnp.float32, one_chip)
    mask = _spec((r,), jnp.float32, one_chip)
    _compile(lambda a, b, m: ops.kernel_centrality_sums(
        a, b, metric="l1", ref_mask=m), x, y, mask)


@pytest.mark.parametrize("metric", ["l2", "cosine"])
@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_dot_centrality_compiles(compiled_kernels, one_chip, metric,
                                 compute_dtype):
    x = _spec((C, D), jnp.float32, one_chip)
    y = _spec((R, D), jnp.float32, one_chip)
    _compile(lambda a, b: ops.kernel_centrality_sums(
        a, b, metric=metric, compute_dtype=compute_dtype), x, y)


def test_topk_smallest_compiles(compiled_kernels, one_chip):
    theta = _spec((C,), jnp.float32, one_chip)
    _compile(lambda t: ops.kernel_topk_smallest(t, keep=C // 2), theta)


def test_l1_fused_medoid_program_compiles(compiled_kernels, one_chip):
    n, d = 4096, 512
    fn = programs.medoid_program(budget=30 * n, metric="l1",
                                 backend="pallas_fused")
    data = _spec((n, d), jnp.float32, one_chip)
    key = _spec((), jax.eval_shape(lambda: jax.random.key(0)).dtype,
                one_chip)
    compiled = fn.lower(data, key).compile()
    assert "tpu_custom_call" in compiled.as_text()
