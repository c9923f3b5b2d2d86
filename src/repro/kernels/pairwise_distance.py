"""Pallas TPU kernels for the medoid engine's hot loop.

The paper's per-round hot spot is the rectangular distance block
``D[c, j] = d(X[S_r][c], X[J_r][j])`` plus its row-mean. On TPU we split the
metrics into two kernel families:

* **dot kernel** (MXU path): pairwise inner products ``G = X @ Y^T`` with f32
  accumulation. ℓ2 / squared-ℓ2 / cosine reduce to ``G`` plus O(nd) row norms
  computed outside the kernel (Gram trick), so the inner loop runs on the
  128x128 systolic array at full rate.

* **ℓ1 kernels** (VPU path): ``sum |x - y|`` has no matmul form. Two
  variants:
    - ``l1_pairwise``  -> (C, R) distance matrix. It tiles
      ``(BC, BD) x (BD, BR)`` (the reference block transposed) into VMEM and
      accumulates a lane-dense (BC, BR) tile, one d-column at a time.
    - ``l1_centrality``-> fused row-sum (C,): never materializes (C, R) in
      HBM. Both operands keep d on lanes, and its tile is sized from each
      call's shape (``l1_centrality_tile``), since the round loop makes one
      of its two row axes small in every call.

Grid layout: (i, j, k) with k (the d-axis) innermost so each output tile is
revisited across k steps and accumulated in place (standard Pallas reduction
pattern); the fused centrality kernels also fold j into the accumulation.

All wrappers in ``ops.py`` pad shapes to block multiples; padded d-columns are
zeros (contribute 0 to every metric), padded candidate rows are sliced off,
and padded reference rows are masked *inside* the kernels via a per-reference
validity mask streamed in as a kernel input. The mask generalizes the old
static ``col < r_true`` predicate: the ragged multi-query engine reuses the
same kernels with arbitrary validity patterns (padded arms of short queries),
while the dense wrappers pass the prefix mask and get bit-identical results.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block sizes: MXU-aligned (multiples of 128 in the matmul dims). The ℓ1
# pairwise kernel uses the same tiles: per grid step an X tile and a
# transposed Y tile of BC*BD*4B = 128 KiB each (f32), plus one (BC, BR) f32
# accumulator of 64 KiB — under 1 MiB of VMEM with double-buffered inputs.
# The fused ℓ1 centrality kernel sizes its own (``l1_centrality_tile``).
BC = 128   # candidate rows per tile
BR = 128   # reference rows per tile
BD = 256   # d-axis slab per grid step


# --------------------------------------------------------------------------
# dot kernel (MXU): G[c, r] = sum_d X[c, d] * Y[r, d]
# --------------------------------------------------------------------------

def _precision(compute_dtype) -> jax.lax.Precision:
    """f32 multiplies run at full f32 precision on the MXU (the fp32
    backends are exact fp32, like the reference path); bf16 at its rate."""
    return (jax.lax.Precision.HIGHEST if compute_dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _dot_kernel(x_ref, y_ref, o_ref, *, compute_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # In-kernel quantization cast (the VMEM tile is rounded, never the HBM
    # copy): bf16 multiplies run the MXU at its doubled rate; accumulation
    # stays f32 via preferred_element_type either way.
    x = x_ref[...].astype(compute_dtype)
    y = y_ref[...].astype(compute_dtype)
    o_ref[...] += jax.lax.dot_general(
        x, y, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_precision(compute_dtype),
        preferred_element_type=jnp.float32,
    )


def dot_pairwise(x: jnp.ndarray, y: jnp.ndarray, *,
                 compute_dtype: str = "float32",
                 interpret: bool = False) -> jnp.ndarray:
    """X: (C, d), Y: (R, d) — C, R, d already padded to block multiples.
    ``compute_dtype`` sets the multiply precision (f32 accumulation always).
    """
    c, d = x.shape
    r, _ = y.shape
    grid = (c // BC, r // BR, d // BD)
    kern = functools.partial(_dot_kernel,
                             compute_dtype=jnp.dtype(compute_dtype))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BC, BD), lambda i, j, k: (i, k)),
            pl.BlockSpec((BR, BD), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((BC, BR), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((c, r), jnp.float32),
        interpret=interpret,
    )(x, y)


# --------------------------------------------------------------------------
# ℓ1 kernels (VPU): D[c, r] = sum_d |X[c, d] - Y[r, d]|
#
# Every intermediate is a 2-D, lane-dense (BC, BR) tile. The reference block
# is streamed TRANSPOSED, (BD, BR), so reference r sits on lane r: each d step
# broadcasts one X column (BC, 1) against one Y^T row (1, BR) and adds
# |x - y| into the (BC, BR) f32 accumulator. No (BC, BR, chunk) broadcast
# ever exists (its minor chunk axis would pad to 128 lanes in VMEM).
# --------------------------------------------------------------------------

def _l1_slab(x_ref, yt_ref) -> jnp.ndarray:
    """(BC, BR) f32 partial ℓ1 sums of one d-slab: sum over the BD columns
    of X's tile (BC, BD) against the transposed reference tile (BD, BR)."""
    x = x_ref[...].astype(jnp.float32)
    yt = yt_ref[...].astype(jnp.float32)
    acc = jnp.zeros((BC, BR), jnp.float32)
    for dd in range(BD):                 # static unroll over the slab
        acc = acc + jnp.abs(x[:, dd:dd + 1] - yt[dd:dd + 1, :])
    return acc


def _l1_pairwise_kernel(x_ref, yt_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _l1_slab(x_ref, yt_ref)


def l1_pairwise(x: jnp.ndarray, y: jnp.ndarray, *,
                interpret: bool = False) -> jnp.ndarray:
    """X: (C, d), Y: (R, d) — C, R, d already padded to block multiples."""
    c, d = x.shape
    r, _ = y.shape
    grid = (c // BC, r // BR, d // BD)
    return pl.pallas_call(
        _l1_pairwise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BC, BD), lambda i, j, k: (i, k)),
            pl.BlockSpec((BD, BR), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((BC, BR), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((c, r), jnp.float32),
        interpret=interpret,
    )(x, y.T)


# --------------------------------------------------------------------------
# fused ℓ1 centrality kernel: S[c] = sum_{r valid} w_r sum_d |X[c,d] - Y[r,d]|
# Never materializes the (C, R) matrix in HBM. Unlike the pairwise kernels,
# both operands stream in their natural row-major layout with d on lanes:
# a (bc, bd) candidate tile and a (br, bd) reference tile. Per reference,
# its row is broadcast over sublanes and |X - y_r| is summed over the
# slab's 128-lane chunks into a lane-dense (bc, 128) f32 partial, so the
# inner loop is one sub, one abs and one add per vreg with no cross-lane
# broadcast. The partial is weighted by the reference's validity w_r (a
# streamed (br, 1) column: block padding and the ragged engine's invalid
# references weigh 0) into the grid step's own (bc, 128) sum, which folds
# into a VMEM accumulator; its lane reduction to (bc, 1) runs once, at the
# candidate tile's last step.
#
# The tile follows the call's shape (``l1_centrality_tile``): the round
# loop makes one axis of every call small (few references early, few
# candidates late), so each row axis pads only to the sublane multiple.
# --------------------------------------------------------------------------

SUBLANES, LANES = 8, 128
L1_ROWS = 128            # candidate rows per tile: a (128, 128) f32 partial
                         # is 16 vregs, and the step's sum 16 more
L1_REFS = 512            # reference rows per tile
L1_TILE_BYTES = 8 << 20  # both input tiles, double-buffered, f32


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _even_block(n: int, cap: int) -> int:
    """The least sublane-multiple block, at most ``cap``, that covers ``n``
    rows in as few equal tiles as ``cap`` allows."""
    return _cdiv(_cdiv(n, _cdiv(n, cap)), SUBLANES) * SUBLANES


def l1_centrality_tile(c: int, r: int, d: int) -> tuple[int, int, int]:
    """(candidate, reference, width) block of the fused ℓ1 kernel for a
    ``(c, d) x (r, d)`` call: a pure function of the static shape.

    Rows pad to the sublane multiple (8) in equal tiles of at most
    ``L1_ROWS`` candidates and ``L1_REFS`` references. The width block is
    the widest multiple of 128 whose double-buffered tiles fit
    ``L1_TILE_BYTES`` and that pads ``d`` by at most 1% (or one 128-lane
    chunk past its lane rounding, where 1% is less than that).
    """
    bc = _even_block(max(c, 1), L1_ROWS)
    br = _even_block(max(r, 1), L1_REFS)
    chunks = _cdiv(max(d, 1), LANES)
    most = max(1, min(chunks, L1_TILE_BYTES // (2 * 4 * (bc + br) * LANES)))
    slack = max(d // 100, (chunks + 1) * LANES - d)
    q = max(q for q in range(1, most + 1)
            if _cdiv(chunks, q) * q * LANES - d <= slack)
    return bc, br, q * LANES


def _l1_centrality_kernel(x_ref, y_ref, w_ref, o_ref, acc_ref, wl_ref):
    j = pl.program_id(1)
    k = pl.program_id(2)
    bc, bd = x_ref.shape

    @pl.when((j == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the weights, broadcast over lanes once a step
    wl_ref[...] = jnp.broadcast_to(w_ref[...], wl_ref.shape)

    def group(g, step):
        # references load in aligned groups of 8 rows; each row is then
        # broadcast over the candidate tile's sublanes
        r0 = pl.multiple_of(g * SUBLANES, SUBLANES)
        w = wl_ref[pl.ds(r0, SUBLANES), :]
        for rr in range(SUBLANES):
            part = jnp.zeros((bc, LANES), jnp.float32)
            for lo in range(0, bd, LANES):     # static unroll over the slab
                y = y_ref[pl.ds(r0, SUBLANES), lo:lo + LANES]
                part = part + jnp.abs(x_ref[:, lo:lo + LANES] - y[rr:rr + 1])
            step = step + part * w[rr:rr + 1]
        return step

    acc_ref[...] += jax.lax.fori_loop(
        0, y_ref.shape[0] // SUBLANES, group,
        jnp.zeros((bc, LANES), jnp.float32))

    @pl.when((j == pl.num_programs(1) - 1) & (k == pl.num_programs(2) - 1))
    def _finish():
        o_ref[...] = jnp.sum(acc_ref[...], axis=1, keepdims=True)


def l1_centrality(x: jnp.ndarray, y: jnp.ndarray, r_true: int, *,
                  block: tuple[int, int, int],
                  ref_mask: jnp.ndarray | None = None,
                  interpret: bool = False) -> jnp.ndarray:
    """Row sums of |X - Y| distances over the valid rows of Y.

    x: (C, d), y: (R, d), both padded to multiples of ``block`` (the
    ``l1_centrality_tile`` of the unpadded call); returns (C, 1) f32 sums
    (not yet divided). By default the first ``r_true`` rows are valid;
    ``ref_mask`` (any shape broadcastable to (R,), a multiplicative weight,
    nonzero = valid) further restricts them.
    """
    c, d = x.shape
    r, _ = y.shape
    bc, br, bd = block
    mask = (jnp.arange(r) < r_true).astype(jnp.float32)
    if ref_mask is not None:
        mask = mask * ref_mask.reshape(-1).astype(jnp.float32)
    return pl.pallas_call(
        _l1_centrality_kernel,
        grid=(c // bc, r // br, d // bd),
        in_specs=[
            pl.BlockSpec((bc, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((br, bd), lambda i, j, k: (j, k)),
            pl.BlockSpec((br, 1), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bc, 1), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bc, LANES), jnp.float32),
                        pltpu.VMEM((br, LANES), jnp.float32)],
        interpret=interpret,
    )(x, y, mask.reshape(r, 1))


# --------------------------------------------------------------------------
# fused dot-centrality kernel (MXU): S[c] = sum_{r valid} d(X[c], Y[r])
# for the Gram-trick metrics. The (BC, BR) distance tile lives only in a VMEM
# scratch accumulator — the (C, R) block is never materialized in HBM, which
# makes every metric's round memory-roofline-optimal, not just ℓ1.
#
# The d-axis (grid dim k, innermost) accumulates raw inner products into the
# scratch tile; at the last k step the metric's elementwise transform
# (sql2 / l2 / cosine) is applied to the *complete* Gram tile — sqrt does not
# commute with the d-reduction, hence the scratch carry — invalid reference
# rows (block padding or ragged-query padded arms) are zeroed by the streamed
# (1, R) validity mask, and the row-sum folds into o_ref.
# --------------------------------------------------------------------------

def _dot_centrality_kernel(x_ref, y_ref, xn_ref, yn_ref, m_ref, o_ref,
                           acc_ref, *, metric: str, nk: int, compute_dtype):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((j == 0) & (k == 0))
    def _init_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # In-kernel quantization cast (see _dot_kernel); norms, the metric
    # epilogue, and the accumulator stay f32.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(compute_dtype), y_ref[...].astype(compute_dtype),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_precision(compute_dtype),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _finish():
        g = acc_ref[...]                                   # (BC, BR) complete
        if metric == "cosine":
            # inputs pre-normalized outside: distance is 1 - <x̂, ŷ>
            v = 1.0 - g
        else:
            sq = jnp.maximum(xn_ref[...] + yn_ref[...] - 2.0 * g, 0.0)
            v = jnp.sqrt(sq) if metric == "l2" else sq
        v = v * m_ref[...]                                 # mask invalid refs
        o_ref[...] += jnp.sum(v, axis=1, keepdims=True)    # (BC, 1)


def dot_centrality(x: jnp.ndarray, y: jnp.ndarray, xn2: jnp.ndarray,
                   yn2: jnp.ndarray, r_true: int, *, metric: str,
                   ref_mask: jnp.ndarray | None = None,
                   compute_dtype: str = "float32",
                   interpret: bool = False) -> jnp.ndarray:
    """Row sums of ``d(X, Y)`` over the valid rows of Y for the MXU metrics,
    fused past the Gram stage.

    x: (C, d), y: (R, d) padded to block multiples; xn2: (C, 1), yn2: (1, R)
    squared row norms (ignored for cosine — pass zeros and pre-normalized
    x/y). By default the first ``r_true`` rows of Y are valid; ``ref_mask``
    (broadcastable to (R,), nonzero = valid) further restricts them — the
    ragged engine passes the per-draw arm-validity mask here. Returns (C, 1)
    f32 distance sums (not yet divided by the valid count).
    """
    if metric not in ("l2", "sql2", "cosine"):
        raise ValueError(f"dot_centrality does not support metric {metric!r}")
    c, d = x.shape
    r, _ = y.shape
    mask = (jnp.arange(r) < r_true).astype(jnp.float32)
    if ref_mask is not None:
        mask = mask * ref_mask.reshape(-1).astype(jnp.float32)
    grid = (c // BC, r // BR, d // BD)
    kern = functools.partial(_dot_centrality_kernel, metric=metric,
                             nk=d // BD,
                             compute_dtype=jnp.dtype(compute_dtype))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BC, BD), lambda i, j, k: (i, k)),
            pl.BlockSpec((BR, BD), lambda i, j, k: (j, k)),
            pl.BlockSpec((BC, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, BR), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, BR), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((BC, 1), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BC, BR), jnp.float32)],
        interpret=interpret,
    )(x, y, xn2, yn2, mask.reshape(1, r))


# --------------------------------------------------------------------------
# fused top-k survivor-selection epilogue: given the per-candidate centrality
# estimates a round's fused kernel just produced, pick the ``keep`` smallest
# arms ON-CHIP — the last remaining off-chip step of a round (XLA's generic
# sort over the (C,) estimates). Semantics replicate jax.lax.top_k(-theta, k)
# exactly, stable index tie-break included, so the survivor *order* (which
# seeds the next round's gather) is bit-identical to the default path.
#
# Two accumulation kernels in the house style (no sort network needed):
#
# * rank kernel, grid (i, j): rank[i] = #{j : theta[j] < theta[i]  or
#   (theta[j] == theta[i] and j < i)}. The strict total order makes `rank` a
#   permutation of [0, C), and the (BC, BC) comparison tile only ever lives
#   in VMEM/registers — the (C, C) comparison matrix is never materialized.
# * select kernel, grid (i,): out[s] = sum_i i * [rank[i] == s] — a one-hot
#   scatter of each index to its rank slot, accumulated over candidate tiles.
#
# Padded candidate rows carry +inf and indices above every real arm, so they
# rank strictly after all real arms (+inf ties break by index) and land in
# slots >= C that the wrapper slices off. Masked (+inf) *real* arms — the
# ragged engine's padded-arm estimates — get the same index-stable order
# top_k gives them.
# --------------------------------------------------------------------------

def _topk_rank_kernel(vc_ref, vr_ref, o_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    vi = vc_ref[...]                      # (BC, 1) this tile's arm estimates
    vj = vr_ref[...]                      # (1, BC) estimates being ranked against
    gi = i * BC + jax.lax.broadcasted_iota(jnp.int32, (BC, 1), 0)
    gj = j * BC + jax.lax.broadcasted_iota(jnp.int32, (1, BC), 1)
    beats = (vj < vi) | ((vj == vi) & (gj < gi))      # (BC, BC) broadcast
    o_ref[...] += jnp.sum(beats.astype(jnp.int32), axis=1, keepdims=True)


def _topk_select_kernel(r_ref, o_ref, *, kp: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    rank = r_ref[...]                     # (BC, 1) int32, a permutation slice
    gi = i * BC + jax.lax.broadcasted_iota(jnp.int32, (BC, 1), 0)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)
    hit = rank == slot                    # (BC, kp) one-hot over output slots
    o_ref[...] += jnp.sum(jnp.where(hit, gi, 0), axis=0, keepdims=True)


def topk_smallest(v: jnp.ndarray, kp: int, *,
                  interpret: bool = False) -> jnp.ndarray:
    """Indices of the ascending-sorted prefix of ``v``, on-chip.

    v: (Cp,) int32 *total-order keys* (see ``ops.kernel_topk_smallest`` —
    the float estimates are bitcast to the IEEE-totalorder monotone int so
    comparisons match XLA's sort exactly, -0.0 < +0.0 included), Cp a
    multiple of BC, padded with int32 max; kp: output slot count (multiple
    of 128, >= the ``keep`` the caller will slice, <= Cp). Returns (1, kp)
    int32 where slot s holds the index of the (s+1)-th smallest value,
    ties broken toward the smaller index — exactly
    ``jax.lax.top_k(-theta, kp)[1]`` restricted to the real arms.
    """
    cp = v.shape[0]
    grid_rank = (cp // BC, cp // BC)
    ranks = pl.pallas_call(
        _topk_rank_kernel,
        grid=grid_rank,
        in_specs=[
            pl.BlockSpec((BC, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, BC), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((BC, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, 1), jnp.int32),
        interpret=interpret,
    )(v.reshape(cp, 1), v.reshape(1, cp))
    return pl.pallas_call(
        functools.partial(_topk_select_kernel, kp=kp),
        grid=(cp // BC,),
        in_specs=[pl.BlockSpec((BC, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, kp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, kp), jnp.int32),
        interpret=interpret,
    )(ranks)
