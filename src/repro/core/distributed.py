"""Distributed Correlated Sequential Halving via shard_map.

Dataset layout: rows sharded over the flattened mesh (every axis participates:
on the production mesh that is pod x data x model = 512-way row sharding).
Each round of corrSH becomes:

  1. reference *indices* for the round are computed from a replicated key —
     identical on every device (this IS the paper's correlation trick: one
     shared reference set for all surviving arms, here realized with zero
     communication because indices are derived, not exchanged);
  2. reference *rows* (t_r, d) are materialized everywhere with a
     masked-scatter + psum (an all-gather of unaligned rows);
  3. each device computes centrality partial-sums for its *candidate* slice
     (s_r / P candidates x t_r references) — compute is sharded on the
     candidate axis so the (s_r,) estimates come out locally;
  4. estimates are all-gathered ((s_r,) floats — tiny) and the halving top-k
     runs replicated.

Communication per round: one psum of (t_r, d) + one all-gather of (s_r,).
Compute per device: s_r * t_r / P distance evaluations — perfect scaling.

All shapes are static (see corr_sh.round_schedule), so the entire multi-round
algorithm lowers to a single XLA program under shard_map + jit.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.backend import get_backend
from repro.engine import default_select, round_schedule

# Outputs are replicated via psum/all_gather, so the replication check is off.
shard_map = functools.partial(jax.shard_map, check_vma=False)


def _gather_rows(x_local: jnp.ndarray, global_idx: jnp.ndarray,
                 shard_offset: jnp.ndarray, axes: Sequence[str]) -> jnp.ndarray:
    """Materialize rows of the (row-sharded) global array at ``global_idx``
    on every device: masked local scatter + psum.

    ``global_idx`` MUST be replicated (identical on every device) — each
    device contributes the rows it owns and the psum assembles the rest.
    """
    n_local = x_local.shape[0]
    local_pos = global_idx - shard_offset
    valid = (local_pos >= 0) & (local_pos < n_local)
    safe = jnp.clip(local_pos, 0, n_local - 1)
    rows = x_local[safe] * valid[:, None].astype(x_local.dtype)
    return jax.lax.psum(rows, axes)


def make_distributed_corr_sh(mesh: Mesh, *, n: int, d: int, budget: int,
                             metric: str = "l2", backend: str = "reference"):
    """Build the jitted distributed corrSH for a fixed (n, d, budget) — the
    lowerable artifact the dry-run compiles without allocating data."""

    def fn(x_global: jnp.ndarray, key: jax.Array) -> jnp.ndarray:
        return _distributed_corr_sh_impl(x_global, key, mesh, budget=budget,
                                         metric=metric, backend=backend)

    return jax.jit(fn)


def distributed_corr_sh(
    x_global: jnp.ndarray,
    key: jax.Array,
    mesh: Mesh,
    *,
    budget: int,
    metric: str = "l2",
    backend: str = "reference",
) -> jnp.ndarray:
    """Medoid of ``x_global: (n, d)`` on ``mesh`` (rows sharded over all axes).

    Returns the global medoid index (replicated scalar). n must be divisible by
    the total device count for the row sharding (pad upstream if needed).
    ``backend`` picks the per-device distance implementation (the Pallas
    backends run the same kernels inside each shard's round).
    """
    return make_distributed_corr_sh(
        mesh, n=int(x_global.shape[0]), d=int(x_global.shape[1]),
        budget=budget, metric=metric, backend=backend)(x_global, key)


def _distributed_corr_sh_impl(
    x_global: jnp.ndarray,
    key: jax.Array,
    mesh: Mesh,
    *,
    budget: int,
    metric: str = "l2",
    backend: str = "reference",
) -> jnp.ndarray:
    axes = tuple(mesh.axis_names)
    num_devices = math.prod(mesh.devices.shape)
    n, d = int(x_global.shape[0]), int(x_global.shape[1])
    if n % num_devices:
        raise ValueError(f"n={n} must be divisible by device count {num_devices}")
    n_local = n // num_devices
    theta_fn = get_backend(backend).centrality_sums(metric)
    rounds = round_schedule(n, budget)

    def shard_fn(x_local: jnp.ndarray, key: jnp.ndarray) -> jnp.ndarray:
        # linear shard id over all mesh axes -> row offset of this shard
        shard_id = jax.lax.axis_index(axes)
        offset = shard_id * n_local

        idx = jnp.arange(n, dtype=jnp.int32)   # surviving arms (replicated)
        theta_hat = None
        for r, rd in enumerate(rounds):
            rkey = jax.random.fold_in(key, r)  # replicated -> shared refs
            if rd.num_refs >= n:
                refs = jnp.arange(n, dtype=jnp.int32)
            else:
                refs = jax.random.permutation(rkey, n)[: rd.num_refs].astype(jnp.int32)
            ref_rows = _gather_rows(x_local, refs, offset, axes)  # (t_r, d) everywhere

            # gather the full (replicated) survivor rows once, then shard the
            # *compute* over devices by slicing candidates locally. NOTE:
            # _gather_rows requires replicated indices, so we gather all of
            # idx (replicated) rather than per-device slices of it.
            s = idx.shape[0]
            per_dev = -(-s // num_devices)
            pad = per_dev * num_devices - s
            idx_p = jnp.pad(idx, (0, pad), constant_values=-1)
            cand_all = _gather_rows(x_local, jnp.where(idx_p >= 0, idx_p, 0),
                                    offset, axes)                  # (s+pad, d)
            my = jax.lax.dynamic_slice_in_dim(idx_p, shard_id * per_dev, per_dev)
            my_valid = my >= 0
            cand_rows = jax.lax.dynamic_slice_in_dim(
                cand_all, shard_id * per_dev, per_dev)             # (per_dev, d)
            local_theta = theta_fn(cand_rows, ref_rows) / ref_rows.shape[0]
            local_theta = jnp.where(my_valid, local_theta, jnp.inf)
            theta_hat = jax.lax.all_gather(local_theta, axes, tiled=True)[:s]

            if rd.exact or s <= 2:
                return idx[jnp.argmin(theta_hat)]
            keep = math.ceil(s / 2)
            # replicated halving: same stable-tie selection as the unified
            # engine (repro.engine.default_select), so distributed survivors
            # match the single-host engine's round for round
            idx = idx[default_select(theta_hat, keep)]
        return idx[jnp.argmin(theta_hat)]

    specs = P(axes)  # rows sharded over all axes jointly
    fn = shard_map(shard_fn, mesh=mesh, in_specs=(specs, P()), out_specs=P())
    return fn(x_global, key)


def make_row_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding that shards axis 0 of a (n, d) dataset over all mesh axes."""
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))
