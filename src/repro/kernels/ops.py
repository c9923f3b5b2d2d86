"""Jit'd public wrappers around the Pallas kernels.

These handle padding to block multiples and metric plumbing (Gram trick for
ℓ2/sqℓ2/cosine). The kernels compile to Mosaic on a TPU. On the CPU backend
they run in Pallas interpret mode (same kernel code, executed by XLA:CPU), so
tests and small runs work without a chip; any other backend raises instead of
interpreting (see :func:`interpret_mode`). ``pairwise_kernel(metric)``
returns a drop-in replacement for ``repro.core.distances.pairwise(metric)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pairwise_distance as pk


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: True on the CPU backend,
    False on a TPU (compiled Mosaic kernels). Any other backend raises — the
    kernels are never interpreted silently where a compiled path is expected.
    Evaluated at trace time by the jitted wrappers below."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels need a TPU (or the CPU backend, interpreted); the "
        f"default backend is {platform!r}. Use backend='reference' here.")


def _pad_to(a: jnp.ndarray, m0: int, m1: int) -> jnp.ndarray:
    p0 = (-a.shape[0]) % m0
    p1 = (-a.shape[1]) % m1
    if p0 or p1:
        a = jnp.pad(a, ((0, p0), (0, p1)))
    return a


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel_dot(x: jnp.ndarray, y: jnp.ndarray, interpret: bool | None = None) -> jnp.ndarray:
    """Pairwise inner products via the MXU kernel. (C, d) x (R, d) -> (C, R)."""
    interp = interpret_mode() if interpret is None else interpret
    c, r = x.shape[0], y.shape[0]
    xp = _pad_to(x, pk.BC, pk.BD)
    yp = _pad_to(y, pk.BR, pk.BD)
    return pk.dot_pairwise(xp, yp, interpret=interp)[:c, :r]


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel_l1(x: jnp.ndarray, y: jnp.ndarray, interpret: bool | None = None) -> jnp.ndarray:
    """Pairwise ℓ1 distances via the VPU kernel."""
    interp = interpret_mode() if interpret is None else interpret
    c, r = x.shape[0], y.shape[0]
    xp = _pad_to(x, pk.BC, pk.BD)
    yp = _pad_to(y, pk.BR, pk.BD)
    return pk.l1_pairwise(xp, yp, interpret=interp)[:c, :r]


def _pad_ref_mask(ref_mask: jnp.ndarray | None, r: int,
                  r_pad: int) -> jnp.ndarray | None:
    """Pad a (r,) validity mask with zeros out to the kernel-padded length."""
    if ref_mask is None:
        return None
    m = ref_mask.reshape(-1).astype(jnp.float32)
    if r_pad > r:
        m = jnp.pad(m, (0, r_pad - r))
    return m


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel_l1_centrality(x: jnp.ndarray, y: jnp.ndarray,
                         interpret: bool | None = None,
                         ref_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Fused mean_j ℓ1(x_i, y_j): (C, d) x (R, d) -> (C,). Never materializes
    the (C, R) matrix — the memory-roofline optimization for big ref sets.
    With ``ref_mask`` (shape (R,), nonzero = valid) the mean runs over the
    valid references only."""
    interp = interpret_mode() if interpret is None else interpret
    c, r = x.shape[0], y.shape[0]
    xp = _pad_to(x, pk.BC, pk.BD)
    yp = _pad_to(y, pk.BR, pk.BD)
    mask = _pad_ref_mask(ref_mask, r, yp.shape[0])
    sums = pk.l1_centrality(xp, yp, r_true=r, ref_mask=mask,
                            interpret=interp)[:c, 0]
    denom = r if ref_mask is None else jnp.maximum(jnp.sum(mask), 1.0)
    return sums / denom


def _norms_sq(a: jnp.ndarray) -> jnp.ndarray:
    af = a.astype(jnp.float32)
    return jnp.sum(af * af, axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel_sql2(x: jnp.ndarray, y: jnp.ndarray, interpret: bool | None = None) -> jnp.ndarray:
    # NB: ``interpret`` is in kernel_dot's static_argnames — always forward it
    # by keyword so the static/traced split never depends on positional
    # signature resolution.
    g = kernel_dot(x, y, interpret=interpret)
    return jnp.maximum(_norms_sq(x)[:, None] + _norms_sq(y)[None, :] - 2.0 * g, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel_l2(x: jnp.ndarray, y: jnp.ndarray, interpret: bool | None = None) -> jnp.ndarray:
    return jnp.sqrt(kernel_sql2(x, y, interpret=interpret))


def _unit_rows(a: jnp.ndarray) -> jnp.ndarray:
    af = a.astype(jnp.float32)
    return af / jnp.maximum(jnp.linalg.norm(af, axis=-1, keepdims=True), 1e-12)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel_cosine(x: jnp.ndarray, y: jnp.ndarray, interpret: bool | None = None) -> jnp.ndarray:
    return 1.0 - kernel_dot(_unit_rows(x), _unit_rows(y), interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("metric", "interpret", "compute_dtype"))
def kernel_centrality_sums(x: jnp.ndarray, y: jnp.ndarray, *,
                           metric: str = "l2",
                           interpret: bool | None = None,
                           ref_mask: jnp.ndarray | None = None,
                           compute_dtype: str = "float32") -> jnp.ndarray:
    """Fused ``sum_j d(x_i, y_j)``: (C, d) x (R, d) -> (C,) distance sums.

    Every metric routes through a fused kernel (ℓ1 VPU kernel or the MXU
    ``dot_centrality`` kernel), so the (C, R) block never exists in HBM —
    the memory-roofline win, now for all four metrics. ``ref_mask`` (shape
    (R,), nonzero = valid) drops invalid references from the sum *inside*
    the kernel — the ragged engine's padded arms never contribute.

    ``compute_dtype="bfloat16"`` lowers the Gram-stage multiply precision
    inside the MXU kernel (norms, metric epilogue, and accumulation stay
    f32) — the quantized ``quant_bf16_fused`` backend's path. The ℓ1 VPU
    kernel has no matmul stage; its inputs are representation-rounded
    instead (the caller's job — see ``repro.quant.backends``).
    """
    interp = interpret_mode() if interpret is None else interpret
    c, r = x.shape[0], y.shape[0]
    if metric == "l1":
        xp = _pad_to(x, pk.BC, pk.BD)
        yp = _pad_to(y, pk.BR, pk.BD)
        mask = _pad_ref_mask(ref_mask, r, yp.shape[0])
        return pk.l1_centrality(xp, yp, r_true=r, ref_mask=mask,
                                interpret=interp)[:c, 0]
    if metric == "cosine":
        xf, yf = _unit_rows(x), _unit_rows(y)
        xn2 = jnp.zeros((c, 1), jnp.float32)   # unused by the cosine path
        yn2 = jnp.zeros((1, r), jnp.float32)
    elif metric in ("l2", "sql2"):
        xf = x.astype(jnp.float32)
        yf = y.astype(jnp.float32)
        xn2 = _norms_sq(xf)[:, None]
        yn2 = _norms_sq(yf)[None, :]
    else:
        raise ValueError(f"unknown metric {metric!r}")
    xp = _pad_to(xf, pk.BC, pk.BD)
    yp = _pad_to(yf, pk.BR, pk.BD)
    xn2p = _pad_to(xn2, pk.BC, 1)
    yn2p = _pad_to(yn2, 1, pk.BR)
    mask = _pad_ref_mask(ref_mask, r, yp.shape[0])
    return pk.dot_centrality(xp, yp, xn2p, yn2p, r, metric=metric,
                             ref_mask=mask, compute_dtype=compute_dtype,
                             interpret=interp)[:c, 0]


@functools.partial(jax.jit, static_argnames=("keep", "interpret"))
def kernel_topk_smallest(theta: jnp.ndarray, *, keep: int,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Fused survivor-selection epilogue: indices of the ``keep`` smallest
    entries of ``theta (C,)``, ordered ascending with ties broken toward the
    smaller index — drop-in for ``jax.lax.top_k(-theta, keep)[1]`` (the round
    loop's halving step), computed by the on-chip rank/select kernel pair so
    survivor selection never leaves the chip."""
    interp = interpret_mode() if interpret is None else interpret
    c = theta.shape[0]
    if not 0 < keep <= c:
        raise ValueError(f"keep must be in [1, {c}], got {keep}")
    cp = c + (-c) % pk.BC
    # IEEE-totalorder monotone int key (sign-flip bitcast): plain int
    # comparison then orders floats exactly like XLA's sort, including
    # -0.0 < +0.0 — plain float </== would merge the two zeros and diverge
    # from top_k on which one survives first.
    b = jax.lax.bitcast_convert_type(theta.astype(jnp.float32), jnp.int32)
    key = jnp.where(b >= 0, b, (~b) ^ jnp.int32(-(2 ** 31)))
    # int32-max-pad: padded rows rank strictly after every real arm (even
    # +inf estimates), so no real slot below ``c`` can point at padding.
    # kp <= cp always (keep <= c).
    v = jnp.pad(key, (0, cp - c), constant_values=jnp.iinfo(jnp.int32).max)
    kp = min(cp, keep + (-keep) % 128)
    return pk.topk_smallest(v, kp, interpret=interp)[0, :keep]


_KERNELS = {
    "l1": kernel_l1,
    "l2": kernel_l2,
    "sql2": kernel_sql2,
    "cosine": kernel_cosine,
}


def pairwise_kernel(metric: str):
    """Kernel-backed drop-in for ``repro.core.distances.pairwise(metric)``."""
    try:
        return _KERNELS[metric]
    except KeyError:
        raise ValueError(f"unknown metric {metric!r}") from None


def centrality_kernel(metric: str):
    """Fused row-sum centrality for ``metric``: ``f(x, y) -> (C,)`` sums."""
    if metric not in _KERNELS:
        raise ValueError(f"unknown metric {metric!r}")
    return functools.partial(kernel_centrality_sums, metric=metric)
