"""Pluggable distance backends for the medoid engines.

Every corrSH round boils down to two primitives over a candidate block
``x: (C, d)`` and a reference block ``y: (R, d)``:

* ``pairwise(metric)(x, y) -> (C, R)`` — the full distance block;
* ``centrality_sums(metric)(x, y, ref_mask=None) -> (C,)`` — row sums
  ``sum_j d(x_i, y_j)``, which is all the algorithm actually needs (estimates
  are means). The optional ``ref_mask`` keyword (shape (R,), nonzero = valid)
  restricts the sum to valid references: the ragged multi-query engine pads
  short queries up to a shared bucket size and masks the padded arms out of
  every round *inside* the distance path (the fused Pallas kernels apply the
  mask in VMEM, so invalid references cost no HBM traffic either).

A :class:`DistanceBackend` bundles one implementation of each, and the
single-host (:mod:`repro.core.corr_sh`), batched, and distributed
(:mod:`repro.core.distributed`, :mod:`repro.core.distributed_v2`) engines all
consume the backend instead of hardcoding a distance path. Registered
backends:

``reference``
    Pure-jnp blocked distances (:mod:`repro.core.distances`). The ground
    truth everything else is validated against; ℓ1 centrality is
    memory-bounded via the scan in ``distances.centrality_sums``.

``pallas_pairwise``
    Pallas kernels for the (C, R) block (MXU Gram kernel for l2/sql2/cosine,
    VPU kernel for ℓ1); centrality is a row-sum *outside* the kernel, so the
    block still round-trips through HBM.

``pallas_fused``
    Fused centrality kernels: the ℓ1 VPU kernel and the MXU
    ``dot_centrality`` kernel reduce over references *inside* the kernel —
    no round ever materializes the (s_r, t_r) block in HBM, for any metric.
    This is the memory-roofline-optimal production path.

``pallas_fused_topk``
    ``pallas_fused`` plus the fused top-k survivor-selection epilogue
    (:func:`repro.kernels.ops.kernel_topk_smallest`): the halving step's
    top-k runs as an on-chip rank/select kernel pair instead of XLA's
    generic sort, with bit-identical stable-tie semantics — no step of a
    round leaves the chip.

The Pallas backends compile their kernels on a TPU and interpret them on
the CPU backend (tests, small runs); on any other backend they raise (see
:func:`repro.kernels.ops.interpret_mode`).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Mapping, Optional, Union

import jax.numpy as jnp

from repro.core import distances
from repro.kernels import ops as kops

PairwiseFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
# (x, y) -> (C,) sums; built-in backends also take ref_mask= (see module doc).
CentralityFn = Callable[..., jnp.ndarray]


@dataclass(frozen=True)
class DistanceBackend:
    """One implementation of the round primitives, keyed by metric name.

    ``centrality_sums(metric)`` should return a function that also accepts an
    optional ``ref_mask=`` keyword; backends that don't are still usable —
    the ragged engine falls back to masking their ``pairwise`` block.
    """
    name: str
    pairwise: Callable[[str], PairwiseFn]
    centrality_sums: Callable[[str], CentralityFn]
    materializes_block: bool   # does centrality ever put (C, R) in HBM?
    description: str = ""
    # Optional fused survivor-selection epilogue: ``fn(theta, keep)`` returns
    # the indices of the ``keep`` smallest estimates with jax.lax.top_k's
    # exact stable-tie semantics. When set, the round loops route the halving
    # step through it instead of the default XLA top_k — the last off-chip
    # step of a round stays on-chip. ``None`` = default selection.
    survivor_topk: Optional[Callable[[jnp.ndarray, int], jnp.ndarray]] = None
    # Optional fused survivor-ordering epilogue: ``fn(theta)`` returns the
    # full stable ascending ordering of the estimates (``argsort`` with
    # jax.lax.top_k's exact total-order/stable-tie semantics). This is the
    # form the scan-based round loop consumes — the per-round keep is a
    # positional mask over the reordered buffer, so one full ordering serves
    # every halving ratio. ``None`` = XLA's stable sort.
    survivor_order: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None
    # Optional fused arm-loss estimator paths, keyed by estimator name
    # ("medoid_centrality", "build_delta", "swap_delta", ...). Each value is
    # a ``metric -> score-kernel`` factory; the estimator factories in
    # :mod:`repro.engine.estimators` consult this mapping first and fall back
    # to composing ``pairwise``/``centrality_sums``. This is how a backend
    # ships, say, an in-VMEM BUILD-delta kernel without any engine changes.
    fused_estimators: Mapping[str, Callable[[str], Callable]] = \
        field(default_factory=dict)


_REGISTRY: dict[str, DistanceBackend] = {}


def _ensure_plugins() -> None:
    """Pull in backend-registering packages that sit ABOVE this module in the
    layering (they import us, so they can't be imported at module scope).
    Called lazily from the resolvers — by the time anyone asks the registry
    for a name, importing :mod:`repro.quant` is cycle-free."""
    import repro.quant.backends  # noqa: F401  (registers quant_* backends)


def register_backend(backend: DistanceBackend) -> DistanceBackend:
    """Add ``backend`` to the registry (last registration wins on a name)."""
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(backend: Union[str, DistanceBackend, None]) -> DistanceBackend:
    """Resolve a backend name (or pass an instance through). ``None`` means
    the reference backend."""
    if backend is None:
        return _REGISTRY["reference"]
    if isinstance(backend, DistanceBackend):
        return backend
    if backend not in _REGISTRY:
        _ensure_plugins()
    try:
        return _REGISTRY[backend]
    except KeyError:
        raise ValueError(
            f"unknown backend {backend!r}; one of {list_backends()}") from None


def list_backends() -> tuple[str, ...]:
    _ensure_plugins()
    return tuple(sorted(_REGISTRY))


# --------------------------------------------------------------------------
# built-in backends
# --------------------------------------------------------------------------

def _reference_centrality(metric: str) -> CentralityFn:
    def fn(x: jnp.ndarray, y: jnp.ndarray,
           ref_mask: jnp.ndarray | None = None) -> jnp.ndarray:
        return distances.centrality_sums(x, y, metric, ref_mask=ref_mask)
    return fn


def _pairwise_rowsum_centrality(metric: str) -> CentralityFn:
    kernel = kops.pairwise_kernel(metric)

    def fn(x: jnp.ndarray, y: jnp.ndarray,
           ref_mask: jnp.ndarray | None = None) -> jnp.ndarray:
        return distances.masked_rowsum(kernel(x, y), ref_mask)
    return fn


register_backend(DistanceBackend(
    name="reference",
    pairwise=distances.pairwise,
    centrality_sums=_reference_centrality,
    materializes_block=True,
    description="pure-jnp blocked distances (ground truth)",
))

register_backend(DistanceBackend(
    name="pallas_pairwise",
    pairwise=kops.pairwise_kernel,
    centrality_sums=_pairwise_rowsum_centrality,
    materializes_block=True,
    description="Pallas (C, R) block kernels + out-of-kernel row sum",
))

# The fused centrality kernels double as the fused ``medoid_centrality``
# estimator path (same contract: (x, y, ref_mask=) -> (C,) sums in-kernel).
_FUSED_ESTIMATORS = {"medoid_centrality": kops.centrality_kernel}

register_backend(DistanceBackend(
    name="pallas_fused",
    pairwise=kops.pairwise_kernel,
    centrality_sums=kops.centrality_kernel,
    materializes_block=False,
    description="fused in-kernel reference reduction (no (C, R) in HBM)",
    fused_estimators=_FUSED_ESTIMATORS,
))


def _topk_epilogue(theta: jnp.ndarray, keep: int) -> jnp.ndarray:
    return kops.kernel_topk_smallest(theta, keep=keep)


def _order_epilogue(theta: jnp.ndarray) -> jnp.ndarray:
    # The full ordering is the keep == C case of the rank/select kernel
    # pair: padded rows carry int32-max keys, so the first C slots are
    # exactly the real arms in stable ascending order.
    return kops.kernel_topk_smallest(theta, keep=theta.shape[0])


register_backend(DistanceBackend(
    name="pallas_fused_topk",
    pairwise=kops.pairwise_kernel,
    centrality_sums=kops.centrality_kernel,
    materializes_block=False,
    description="pallas_fused + on-chip top-k survivor-selection epilogue",
    survivor_topk=_topk_epilogue,
    survivor_order=_order_epilogue,
    fused_estimators=_FUSED_ESTIMATORS,
))
