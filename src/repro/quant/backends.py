"""Reduced-precision distance backends: bf16 and AQT-style symmetric int8.

Production embedding corpora are stored and served in bf16/int8; the fp32
Gram path the engine runs by default leaves the MXU's low-precision rate on
the table. This module registers quantized :class:`~repro.core.backend.
DistanceBackend` implementations of the same two round primitives every
backend provides (``pairwise`` / ``centrality_sums``, plus the
``fused_estimators`` hook for ``medoid_centrality``), so every workload —
single/batch/ragged medoid, k-medoids BUILD and SWAP, corpus mutation
kernels — can run quantized through the existing registry without touching
a single call site:

``quant_bf16``
    Inputs are rounded to bfloat16 *at the Gram stage only*; products
    accumulate in fp32 (``preferred_element_type``), row norms and metric
    epilogues (sqrt / normalization / clamps) stay fp32. On TPU the bf16
    ``dot_general`` runs the MXU at its doubled bf16 rate. ℓ1 has no matmul
    form; it sees storage rounding only (bf16-cast inputs, fp32 sums).

``quant_int8``
    AQT-style symmetric per-row quantization (the MaxText idiom): each row
    is scaled by ``s_i = max|x_i| / 127``, rounded to int8, and the Gram
    block accumulates **exactly** in int32 before one fp32 dequantization
    ``G = (Q_x Q_y^T) * s_x s_y^T``. The only error is the per-element
    rounding ``|x - s q| <= s/2``; the int8 x int8 -> int32 matmul path is
    the MXU's highest-rate mode.

``quant_bf16_fused``
    ``quant_bf16``'s centrality routed through the Pallas ``dot_centrality``
    kernel at ``compute_dtype=bfloat16`` (the in-kernel cast added for this
    subsystem) — the memory-roofline-optimal quantized path on TPU; ℓ1
    rides the VPU kernel on bf16-rounded inputs.

Quantized estimates are *perturbed* estimates: the engine widens the
survivor margin by the error model of :mod:`repro.quant.error` and verifies
the final survivor set in exact fp32 (:mod:`repro.quant.verify`) — see
``MedoidConfig(precision=...)``. Using a quantized backend directly via
``backend="quant_bf16"`` runs plain (unwidened) halving on quantized
estimates, which is what BUILD/SWAP/corpus mutation consume.

All functions here are pure traced jnp/Pallas code — scan-body-safe per the
estimator contract (no host syncs), and deterministic: the same inputs
quantize to the same ints on every call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import distances
from repro.core.backend import DistanceBackend, register_backend
from repro.kernels import ops as kops

#: Facade-level precision names (``MedoidConfig.precision``).
PRECISIONS = ("fp32", "bf16", "int8")

#: precision -> registered quantized backend name (fp32 -> None: no override).
_QUANT_BACKEND = {"fp32": None, "bf16": "quant_bf16", "int8": "quant_int8"}


def check_precision(precision: str) -> str:
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; "
                         f"one of {PRECISIONS}")
    return precision


def backend_for(precision: str, base: str = "reference"):
    """The quantized backend name a precision maps to (None for fp32).

    ``base`` is the caller's fp32 backend choice: a fused Pallas base keeps
    a fused quantized path where one exists (bf16 — the in-kernel cast),
    everything else gets the jnp quantized backend for that precision.
    """
    name = _QUANT_BACKEND[check_precision(precision)]
    if name == "quant_bf16" and base in ("pallas_fused", "pallas_fused_topk"):
        return "quant_bf16_fused"
    return name


# ----------------------------- bf16 Gram path -------------------------------

def _bf16(a: jnp.ndarray) -> jnp.ndarray:
    """Storage rounding: fp32 -> bf16 (the quantization step, nothing else)."""
    return a.astype(jnp.float32).astype(jnp.bfloat16)


def gram_bf16(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """bf16-multiply / fp32-accumulate Gram block: the MXU's bf16 mode."""
    return jax.lax.dot_general(
        _bf16(x), _bf16(y),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


# ----------------------------- int8 AQT path --------------------------------

def quantize_rows_int8(x: jnp.ndarray):
    """Symmetric per-row int8 quantization: ``(q (n, d) int8, s (n,) f32)``
    with ``x ~= q * s[:, None]`` and ``|x - q s| <= s / 2`` per element."""
    xf = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    s = jnp.maximum(s, jnp.finfo(jnp.float32).tiny)  # all-zero rows: q = 0
    q = jnp.clip(jnp.round(xf / s[..., None]), -127.0, 127.0).astype(jnp.int8)
    return q, s


def gram_int8(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Per-row-scaled int8 Gram: exact int32 accumulation, one fp32
    dequantization — quantization error is pure input rounding."""
    qx, sx = quantize_rows_int8(x)
    qy, sy = quantize_rows_int8(y)
    g = jax.lax.dot_general(
        qx, qy,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return g.astype(jnp.float32) * sx[:, None] * sy[None, :]


def dequantize_rows_int8(x: jnp.ndarray) -> jnp.ndarray:
    """The int8 representation mapped back to fp32 (what the ℓ1 path and the
    error model's probe actually measure distances between)."""
    q, s = quantize_rows_int8(x)
    return q.astype(jnp.float32) * s[..., None]


# ------------------------- metric blocks per precision ----------------------

def _norms_sq(a: jnp.ndarray) -> jnp.ndarray:
    af = a.astype(jnp.float32)
    return jnp.sum(af * af, axis=-1)


def _unit_rows(a: jnp.ndarray) -> jnp.ndarray:
    af = a.astype(jnp.float32)
    return af / jnp.maximum(jnp.linalg.norm(af, axis=-1, keepdims=True),
                            1e-12)


def _quant_pairwise(metric: str, gram, l1_repr):
    """Pairwise block for ``metric`` with a quantized Gram stage. Row norms
    and the metric epilogue stay fp32, so the only perturbation relative to
    the reference block is the Gram error (ℓ1: the representation error)."""
    if metric == "l1":
        def l1(x, y):
            xq, yq = l1_repr(x), l1_repr(y)
            return jnp.sum(jnp.abs(xq[:, None, :] - yq[None, :, :]), axis=-1)
        return l1
    if metric == "cosine":
        def cos(x, y):
            return 1.0 - gram(_unit_rows(x), _unit_rows(y))
        return cos
    if metric in ("l2", "sql2"):
        def sq(x, y):
            g = gram(x, y)
            v = jnp.maximum(_norms_sq(x)[:, None] + _norms_sq(y)[None, :]
                            - 2.0 * g, 0.0)
            return jnp.sqrt(v) if metric == "l2" else v
        return sq
    raise ValueError(f"unknown metric {metric!r}; one of {distances.METRICS}")


def _bf16_repr(a: jnp.ndarray) -> jnp.ndarray:
    return _bf16(a).astype(jnp.float32)


def quant_pairwise(metric: str, precision: str):
    """The quantized pairwise block for ``(metric, precision)`` — also what
    the error model's probe compares against the reference block."""
    check_precision(precision)
    if precision == "fp32":
        return distances.pairwise(metric)
    if precision == "bf16":
        return _quant_pairwise(metric, gram_bf16, _bf16_repr)
    return _quant_pairwise(metric, gram_int8, dequantize_rows_int8)


def _centrality_of(pairwise_fn):
    def fn(x, y, ref_mask=None):
        return distances.masked_rowsum(pairwise_fn(x, y), ref_mask)
    return fn


def _make_backend(name: str, precision: str, description: str):
    def pairwise(metric: str):
        return quant_pairwise(metric, precision)

    def centrality(metric: str):
        return _centrality_of(quant_pairwise(metric, precision))

    return DistanceBackend(
        name=name,
        pairwise=pairwise,
        centrality_sums=centrality,
        materializes_block=True,
        description=description,
        fused_estimators={"medoid_centrality": centrality},
    )


register_backend(_make_backend(
    "quant_bf16", "bf16",
    "bf16-multiply / fp32-accumulate Gram (quantized storage rounding)"))

register_backend(_make_backend(
    "quant_int8", "int8",
    "AQT-style symmetric per-row int8 Gram, exact int32 accumulation"))


# --------------------- fused (Pallas) bf16 centrality -----------------------

def _fused_bf16_centrality(metric: str):
    if metric == "l1":
        kern = kops.centrality_kernel(metric)

        def l1(x, y, ref_mask=None):
            return kern(_bf16_repr(x), _bf16_repr(y), ref_mask=ref_mask)
        return l1
    return functools.partial(kops.kernel_centrality_sums, metric=metric,
                             compute_dtype="bfloat16")


_BF16_FUSED = {"medoid_centrality": _fused_bf16_centrality}

register_backend(DistanceBackend(
    name="quant_bf16_fused",
    pairwise=lambda metric: quant_pairwise(metric, "bf16"),
    centrality_sums=_fused_bf16_centrality,
    materializes_block=False,
    description="bf16 Gram centrality fused in the Pallas dot_centrality "
                "kernel (in-kernel cast, fp32 accumulation)",
    fused_estimators=_BF16_FUSED,
    tile=kops.TILE,
    centrality_tile=kops.centrality_tile,
))
