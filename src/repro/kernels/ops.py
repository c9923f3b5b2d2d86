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

# The (candidate, reference, width) block every wrapper below pads to, save
# the fused ℓ1 centrality, whose block follows the call's shape
# (``centrality_tile``): the shape of the work a kernel call evaluates.
TILE = (pk.BC, pk.BR, pk.BD)
# The indexed ℓ1 kernel's block rule: candidate tiles of whole rows.
L1_TILE_AT = functools.partial(pk.l1_centrality_tile, indexed=True)


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


def centrality_tile(metric: str):
    """The block the fused centrality kernel of ``metric`` pads a call to:
    a rule of the call's ``(rows, refs, width)`` for ℓ1, whose VPU kernel
    sizes its tile from the shape, and the fixed ``TILE`` of the MXU
    kernel for the Gram metrics."""
    return pk.l1_centrality_tile if metric == "l1" else TILE


def _l1_centrality_sums(x: jnp.ndarray, y: jnp.ndarray,
                        ref_mask: jnp.ndarray | None,
                        interpret: bool) -> jnp.ndarray:
    """(C,) ℓ1 distance sums over the valid references, through the fused
    kernel at the block ``pk.l1_centrality_tile`` gives the call. Inputs
    stream as f32, whose sublane multiple (8) the rule pads rows to."""
    (c, d), r = x.shape, y.shape[0]
    bc, br, bd = block = pk.l1_centrality_tile(c, r, d)
    xp = _pad_to(x.astype(jnp.float32), bc, bd)
    yp = _pad_to(y.astype(jnp.float32), br, bd)
    mask = _pad_ref_mask(ref_mask, r, yp.shape[0])
    return pk.l1_centrality(xp, yp, r_true=r, block=block, ref_mask=mask,
                            interpret=interpret)[:c, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel_l1_centrality(x: jnp.ndarray, y: jnp.ndarray,
                         interpret: bool | None = None,
                         ref_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Fused mean_j ℓ1(x_i, y_j): (C, d) x (R, d) -> (C,). Never materializes
    the (C, R) matrix — the memory-roofline optimization for big ref sets.
    With ``ref_mask`` (shape (R,), nonzero = valid) the mean runs over the
    valid references only."""
    interp = interpret_mode() if interpret is None else interpret
    sums = _l1_centrality_sums(x, y, ref_mask, interp)
    if ref_mask is None:
        return sums / y.shape[0]
    return sums / jnp.maximum(jnp.sum(ref_mask.astype(jnp.float32)), 1.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def l1_rows(data: jnp.ndarray, interpret: bool | None = None) -> jnp.ndarray:
    """The chunk view of an ``(n, d)`` corpus that the indexed ℓ1 kernel
    fetches rows from: ``(n * pk.l1_row_chunks(d), 128)`` f32, row i in
    rows ``[i * nch, (i + 1) * nch)``, zero past ``d``. One copy of the
    corpus, made once per run of the round loop."""
    interp = interpret_mode() if interpret is None else interpret
    return pk.l1_rows_view(data.astype(jnp.float32), interpret=interp)


def _within_smem(call, ci: jnp.ndarray, ri: jnp.ndarray, w: jnp.ndarray,
                 bc: int) -> jnp.ndarray:
    """``call(ci, ri, w)`` over (B, Cp) candidate and (B, Rp) reference
    index vectors in as few kernel calls as keep each call's vectors within
    ``pk.L1_INDEX_BYTES`` of SMEM: whole queries where one fits, else one
    query's candidate tiles in pieces. Every piece reads all its query's
    references, so each sum is the one a single call gives."""
    b, cp = ci.shape
    rp = ri.shape[1]
    query = 4 * (cp + 2 * rp)
    if b * query <= pk.L1_INDEX_BYTES:
        return call(ci, ri, w)
    if query <= pk.L1_INDEX_BYTES:
        q = pk.L1_INDEX_BYTES // query
        return jnp.concatenate([call(ci[i:i + q], ri[i:i + q], w[i:i + q])
                                for i in range(0, b, q)])
    piece = (pk.L1_INDEX_BYTES // 4 - 2 * rp) // bc * bc
    if piece <= 0:
        raise ValueError(f"{rp} references do not fit the indexed ℓ1 "
                         f"kernel's SMEM; gather the rows instead")
    return jnp.stack([
        jnp.concatenate([call(ci[i:i + 1, j:j + piece], ri[i:i + 1],
                              w[i:i + 1])[0] for j in range(0, cp, piece)])
        for i in range(b)])


def l1_index_fits(n: int) -> bool:
    """Whether one query's reference indices and weights over an ``n``-row
    corpus fit the indexed kernel's SMEM, beside a candidate tile."""
    return 4 * (2 * (n + pk.L1_REFS) + pk.L1_ROWS) <= pk.L1_INDEX_BYTES


@functools.cache
def _l1_sums_at(d: int, block: tuple[int, int, int], interpret: bool):
    """``pk.l1_centrality_at`` at one static width and block, batched by
    stacking: a vmapped call becomes one kernel call whose queries read
    one stacked corpus view, their indices offset to their own rows."""
    nch = pk.l1_row_chunks(d)

    @jax.custom_batching.custom_vmap
    def sums(rows, ci, ri, w):
        return _within_smem(functools.partial(
            pk.l1_centrality_at, rows, d=d, block=block,
            interpret=interpret), ci, ri, w, block[0])

    @sums.def_vmap
    def _stacked(size, batched, rows, ci, ri, w):
        rows_b, ci_b, ri_b, w_b = batched
        offset = 0
        if rows_b:
            offset = (jnp.arange(size, dtype=jnp.int32)
                      * (rows.shape[1] // nch))[:, None, None]
            rows = rows.reshape(-1, pk.LANES)

        def stack(a, a_b):
            a = a if a_b else jnp.broadcast_to(a, (size,) + a.shape)
            return a.reshape(-1, a.shape[-1])

        out = sums(rows, stack(ci + offset, ci_b or rows_b),
                   stack(ri + offset, ri_b or rows_b), stack(w, w_b))
        return out.reshape(size, -1, out.shape[-1]), True

    return sums


@functools.cache
def _l1_sums_all(d: int, block: tuple[int, int, int], interpret: bool):
    """``pk.l1_centrality_at`` with every corpus row a candidate, in
    storage order. Vmapped, it becomes the indexed call over each query's
    rows in order, stacked."""
    @jax.custom_batching.custom_vmap
    def sums(rows, corpus, ri, w):
        return pk.l1_centrality_at(rows, None, ri, w, d=d, block=block,
                                   corpus=corpus, interpret=interpret)

    @sums.def_vmap
    def _indexed(size, batched, rows, corpus, ri, w):
        m = corpus.shape[-2]
        cp = m + (-m) % block[0]
        ci = jnp.where(jnp.arange(cp) < m, jnp.arange(cp), 0)[None]
        axes = [0 if b else None for b in (batched[0], batched[2],
                                            batched[3])]
        out = jax.vmap(_l1_sums_at(d, block, interpret),
                       in_axes=(axes[0], None, axes[1], axes[2]),
                       axis_size=size)(rows, ci, ri, w)
        return out, True

    return sums


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def kernel_centrality_sums_at(rows: jnp.ndarray,
                              cand_idx: jnp.ndarray | None,
                              ref_idx: jnp.ndarray, *, width: int,
                              interpret: bool | None = None,
                              ref_mask: jnp.ndarray | None = None,
                              corpus: jnp.ndarray | None = None
                              ) -> jnp.ndarray:
    """ℓ1 ``kernel_centrality_sums(data[cand_idx], data[ref_idx])``
    without the gathers: the kernel fetches the rows from ``rows =
    l1_rows(data)`` itself (``width`` is ``data``'s). Index vectors pad
    with row 0 to the block ``L1_TILE_AT`` gives the call, the padded
    references weigh 0, and the sums are bitwise those of the gathered
    call at that block. ``cand_idx=None`` with ``corpus=data``: every row
    is a candidate, in storage order, read from ``data`` as it lies."""
    interp = interpret_mode() if interpret is None else interpret
    c = corpus.shape[0] if cand_idx is None else cand_idx.shape[0]
    r = ref_idx.shape[0]
    bc, br, _ = block = L1_TILE_AT(c, r, width)
    ri = jnp.pad(ref_idx.astype(jnp.int32), (0, (-r) % br))
    w = (jnp.arange(ri.shape[0]) < r).astype(jnp.float32)
    if ref_mask is not None:
        w = w * _pad_ref_mask(ref_mask, r, ri.shape[0])
    if cand_idx is None:
        return _l1_sums_all(width, block, interp)(
            rows, corpus.astype(jnp.float32), ri[None], w[None])[0, :c]
    ci = jnp.pad(cand_idx.astype(jnp.int32), (0, (-c) % bc))
    return _l1_sums_at(width, block, interp)(
        rows, ci[None], ri[None], w[None])[0, :c]


def _l1_centrality_on(data: jnp.ndarray):
    """Lay ``data`` out once for the indexed ℓ1 kernel and return
    ``((cand_idx, ref_idx, ref_mask=None) -> (C,) sums, block rule)`` over
    it (``cand_idx=None``: every row, in storage order), or ``None`` where
    its rows are too wide for the kernel's VMEM or its reference draws
    too long for its SMEM."""
    n, width = data.shape
    if not (pk.l1_rows_cap(width) and l1_index_fits(n)):
        return None
    return functools.partial(kernel_centrality_sums_at, l1_rows(data),
                             width=width, corpus=data), L1_TILE_AT


def centrality_kernel_at(metric: str):
    """The fused centrality kernel that fetches its rows by index, for
    ``metric``: ``data -> None or ((cand_idx, ref_idx, ref_mask=None) ->
    (C,) sums, its block rule)``. ℓ1 only; ``None`` for the Gram
    metrics."""
    return _l1_centrality_on if metric == "l1" else None


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
        return _l1_centrality_sums(x, y, ref_mask, interp)
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
