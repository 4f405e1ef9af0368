"""Fused RBF Gram-matrix matvec — the paper's per-iteration hot-spot.

Every CG / def-CG iteration on the GP-classification Newton system costs
one product with the kernel Gram matrix ``K(X, X)``.  Materializing ``K``
(n² entries) and streaming it from HBM makes the matvec memory-bound at
~0.5 flop/byte.  This kernel instead *fuses* Gram formation and the matvec:

    tile (i, j):   S  = ‖xi‖² + ‖xj‖ᵀ² − 2·Xi Xjᵀ        (MXU: bm×d @ d×bn)
                   Kb = exp(−S/2)                          (VPU)
    r ≤ R_VPU:     Pc += Σ_chunks Kb[:, chunk] ⊙ Vᵀ[c, chunk]   (VPU, f32)
                   Yi[:, c] = Σ_lanes Pc                    (last j only)
    r > R_VPU:     Yi += Kb @ Vj                           (MXU: bm×bn @ bn×r)

so HBM traffic is O(n·d + n·r) per pass instead of O(n²), and arithmetic
intensity grows with the block size — the op becomes compute-bound, which
is the right regime for the MXU (DESIGN.md §3).

Which unit takes ``Kb·V`` is decided from ``r`` alone.  On the MXU a
product with r ≤ 128 columns fills one 128-lane weight tile whatever r
is, and at HIGHEST costs six bf16 passes of every Kb row: a third of the
tile's MXU pushes at d = 256 and an eighth at d = 784, for 1/(d+1) of its
flops at r = 1.  For narrow V the kernel instead keeps ``V`` with its n
axis on lanes (``(r_pad, n)``) and, per column c, an f32 accumulator ``Pc``
of lane-wide partial sums (bm × 128): each tile adds the exact f32
products ``Kb[:, chunk] · Vᵀ[c, chunk]`` over its 128-lane chunks, and
only the last j reduces ``Pc`` across lanes into column c of the output.

The square pass with narrow V (``x_cols`` is ``None``, r ≤ R_VPU, up to
2^17 rows: ``SYM_OUT_BYTES``) uses that K is symmetric: each unordered
pair of row blocks {i, j} is formed once and applied both ways,

    Yi += Kb·Vj          (lane partials, as above)
    Yj += Kbᵀ·Vi         (i ≠ j: Σ over sublanes of Kb ⊙ Vi[:, c], VPU)

which halves the distance GEMM and the exponentials of the pass.  Every
entry of K is still formed at HIGHEST in f32, once per pass; only the
order of the sums changes.

Parameter handling: the wrapper (ops.py) pre-scales ``X ← X/λ`` and
``V ← θ²·V``, so the kernel body is hyperparameter-free and never
recompiles during outer-loop kernel-hyperparameter optimization.

Multi-RHS (``V ∈ ℝ^{n×r}``) is native: recomputing ``A·W`` for a recycled
k-vector basis (the O(k·n²) overhead the paper accounts for in §2.2) is a
single fused pass with r = k instead of k separate matvecs.

Grid layout.  Rectangular calls (a mesh chip's row block against the
gathered columns) and wide V run ``(i, j)`` over every tile, j innermost
("arbitrary": row block i's output is accumulated in VMEM across j and
written back at the last j), i parallel across cores; the output block
is ``(bm, r_pad)``.  The symmetric square pass runs a circulant band
``(i, t)``, t ∈ [0, nb//2], j = (i + t) mod nb over nb = n_pad/bm row
blocks: for odd nb every unordered pair comes up once (nb·(nb+1)/2
steps); for even nb the t = nb/2 pairs come up twice and only rows
i < nb/2 take them.  Contributions to a row block arrive from many grid
rows, so its output is the whole ``(r_pad, n_pad)`` accumulator, V on
lanes, held in VMEM for the whole grid (1 MiB at n = 2^15), and both
axes are "arbitrary"; the wrapper transposes it back to (n, r), a
reshape at r = 1.  It stays one ``pallas_call`` with a rank-2 output, so
a pass is one ``rbf_gram_matvec`` event as before.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import F32, block_spec, round_up
from repro.runtime import spans

# The distance GEMM runs at full f32 precision (F32).  On a v5e at
# n = 2^15, d = 784 (float64 reference on 256 rows) the kernel's relative
# error is 3.7e-6 this way, 4.7e-3 with both contractions at the default
# one-bf16-pass precision and 5.4e-3 with only the distance term at
# HIGHEST: no cheaper choice is within a 1e-5 solve tolerance.  ``Kb·V``
# for r ≤ R_VPU is an f32 multiply-add on the VPU, each product exact to
# f32 rounding, so at least as exact as HIGHEST's six-pass bf16
# emulation, which wider V keeps on the MXU; so are the symmetric
# body's column sums ``Kbᵀ·Vi``.  The block stays 256: at d = 784 a 512
# block overflows VMEM on the MXU body and on the VPU body at r = 8 (it
# fits at r = 1).  The symmetric body forms an (i, j) tile for j ≠ i once
# instead of twice, so a square pass issues 8,256 distance GEMMs at
# n = 2^15 (nb = 128) where the full grid issued 16,384.

# Widest V whose product runs on the VPU.  On a v5e the VPU body is the
# faster at every r from 1 to 8, at d = 256 (n = 7291: 1.019 against
# 1.436 ms a pass at r = 1, 1.105 against 1.435 at r = 8) and at d = 784
# (n = 2^15: 63.01 against 70.69 ms, 64.41 against 70.64); 8 is the
# recycled basis's k, so the AW refresh takes the VPU body too.
R_VPU = 8
LANES = 128

# The symmetric body holds its whole (r_pad, n_pad) f32 output in VMEM.
# At d = 784 on a v5e it compiles at n_pad = 2^17 and overflows VMEM at
# 2^18 with r = 8; square passes whose output would pass 4 MiB (2^17
# rows at r ≤ 8) take the full (i, j) grid instead.
SYM_OUT_BYTES = 4 << 20


def _gram_tile(x_i_ref, x_j_ref):
    """``Kb = exp(−‖xi−xj‖²/2)`` for one (bm × bn) tile, in f32."""
    xi = x_i_ref[...].astype(jnp.float32)  # (bm, d)
    xj = x_j_ref[...].astype(jnp.float32)  # (bn, d)

    # Pairwise squared distances via one MXU matmul + rank-1 corrections.
    sq_i = jnp.sum(xi * xi, axis=1, keepdims=True)  # (bm, 1)
    sq_j = jnp.sum(xj * xj, axis=1, keepdims=True).T  # (1, bn)
    cross = jax.lax.dot_general(
        xi,
        xj,
        (((1,), (1,)), ((), ())),
        precision=F32,
        preferred_element_type=jnp.float32,
    )  # (bm, bn)
    dist2 = jnp.maximum(sq_i + sq_j - 2.0 * cross, 0.0)
    return jnp.exp(-0.5 * dist2)


def _rbf_matvec_kernel(x_i_ref, x_j_ref, v_ref, o_ref, acc_ref):
    """One (bm × bn) tile of y += exp(−‖xi−xj‖²/2) @ v on the MXU."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kb = _gram_tile(x_i_ref, x_j_ref)
    vj = v_ref[...].astype(jnp.float32)  # (bn, r)
    acc_ref[...] += jax.lax.dot_general(
        kb, vj, (((1,), (0,)), ((), ())),
        precision=F32, preferred_element_type=jnp.float32,
    )

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _rbf_matvec_vpu_kernel(x_i_ref, x_j_ref, vt_ref, o_ref, acc_ref):
    """The same tile with ``Kb·V`` on the VPU: ``vt_ref`` is (r_pad, bn),
    V with its n axis on lanes and zero rows below r, and ``acc_ref``
    (r, bm, lanes) holds each column's lane-wide partial sums."""
    j = pl.program_id(1)
    r, _, lanes = acc_ref.shape

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kb = _gram_tile(x_i_ref, x_j_ref)  # (bm, bn)
    vt = vt_ref[...].astype(jnp.float32)  # (r_pad, bn)
    for c in range(r):
        part = acc_ref[c]
        for s in range(0, kb.shape[1], lanes):
            part = part + kb[:, s:s + lanes] * vt[c:c + 1, s:s + lanes]
        acc_ref[c] = part

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        col = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
        out = jnp.zeros(o_ref.shape, jnp.float32)
        for c in range(r):
            y = jnp.sum(acc_ref[c], axis=1, keepdims=True)  # (bm, 1)
            out = jnp.where(col == c, y, out)
        o_ref[...] = out.astype(o_ref.dtype)


def _pair_is_new(i, t, nb: int):
    """Whether step (i, t) of the circulant band meets a pair of row
    blocks not met before: for even ``nb`` the pairs at t = nb/2 come up
    twice, and only rows i < nb/2 take them."""
    if nb % 2:
        return t >= 0
    return (2 * t != nb) | (i < nb // 2)


def _col_block(i, t, nb: int):
    """Column block ``j = (i + t) mod nb`` of step (i, t).  A step that
    meets no new pair repeats its row's previous j, so nothing is
    fetched for it."""
    t = jnp.where(_pair_is_new(i, t, nb), t, t - 1)
    j = i + t
    return jnp.where(j >= nb, j - nb, j)


def _rbf_matvec_sym_kernel(
    x_i_ref, x_j_ref, vt_ref, vt_i_ref, o_ref, acc_ref, vc_ref
):
    """The pair {i, j = (i + t) mod nb} of a square pass with narrow V.

    ``Kb = K(xi, xj)`` is formed once and applied both ways on the VPU:
    ``Yi += Kb·Vj`` into row block i's lane-wide partials ``acc_ref``
    (r, b, lanes), as the rectangular body does, and, off the diagonal,
    ``Yj += Kbᵀ·Vi`` as sublane sums of ``Kb ⊙ Vi[:, c]``.  ``vt_ref``
    and ``vt_i_ref`` (r_pad, b) are V's blocks j and i with n on lanes;
    ``vc_ref`` (b, lanes) holds block i transposed, n on sublanes, once
    per row; ``o_ref`` (r_pad, n_pad) is the whole output, V on lanes,
    held in VMEM for the whole grid."""
    i, t = pl.program_id(0), pl.program_id(1)
    r, b, lanes = acc_ref.shape
    r_pad, n_pad = o_ref.shape
    nb = n_pad // b
    sub = jax.lax.broadcasted_iota(jnp.int32, (r_pad, b), 0)

    def add_cols(blk_of_c, block):
        """``o[:, block] += rows c of blk_of_c(c)``, one (1, b) each."""
        out = jnp.zeros((r_pad, b), jnp.float32)
        for c in range(r):
            out = jnp.where(sub == c, blk_of_c(c), out)
        cols = pl.ds(pl.multiple_of(block * b, b), b)
        o_ref[:, cols] += out

    @pl.when((i == 0) & (t == 0))
    def _init_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(t == 0)
    def _init_row():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        vi = vt_i_ref[...].astype(jnp.float32)  # (r_pad, b)
        fill = jnp.zeros((lanes - r_pad, b), jnp.float32)
        vc_ref[...] = jnp.concatenate([vi, fill], axis=0).T

    @pl.when(_pair_is_new(i, t, nb))
    def _pair():
        kb = _gram_tile(x_i_ref, x_j_ref)  # (b, b)
        vt = vt_ref[...].astype(jnp.float32)  # (r_pad, b)
        for c in range(r):
            part = acc_ref[c]
            for s in range(0, b, lanes):
                part = part + kb[:, s:s + lanes] * vt[c:c + 1, s:s + lanes]
            acc_ref[c] = part

        @pl.when(t > 0)
        def _cols():
            vc = vc_ref[...]  # (b, lanes)
            add_cols(
                lambda c: jnp.sum(kb * vc[:, c:c + 1], axis=0, keepdims=True),
                _col_block(i, t, nb),
            )

    @pl.when(t == pl.num_programs(1) - 1)
    def _done():
        # Row block i is complete: its lane partials, transposed so that
        # n lies on lanes, summed over sublanes.
        add_cols(lambda c: jnp.sum(acc_ref[c].T, axis=0, keepdims=True), i)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)
def rbf_matvec_pallas(
    x_rows: jnp.ndarray,
    v_scaled: jnp.ndarray,
    x_cols: jnp.ndarray | None = None,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """``y = exp(−½‖xr_i − xc_j‖²) V`` over pre-scaled inputs.

    Args:
      x_rows: (m, d) row data, already divided by the lengthscale.
      v_scaled: (n, r) right-hand sides, already scaled by θ².
      x_cols: (n, d) column data, pre-scaled like ``x_rows``; ``None``
        for the square Gram matvec, whose columns are its rows.  The
        sharded operator passes its local row block as ``x_rows`` and the
        all-gathered data as ``x_cols``: each shard forms the K-tiles of
        (local rows × all columns) in VMEM, never in HBM.
      block_m/block_n: VMEM tile rows/cols; multiples of 128 on real TPUs.
      interpret: run the kernel body in Python on CPU (validation mode).

    Shapes are padded internally: j-padding is exact because padded V rows
    are zero; padded i-rows are sliced off the output.  The square case
    pads ``x`` once, to one length for rows and columns, and hands the
    same array to both.
    """
    m, d = x_rows.shape
    n, r = v_scaled.shape

    bm = min(block_m, max(round_up(m, 8), 8))
    bn = min(block_n, max(round_up(n, 8), 8))
    d_pad = round_up(d, 128)
    r_pad = round_up(r, 8)
    vpu = r <= R_VPU
    spans.count("gram_kv_vpu" if vpu else "gram_kv_mxu")
    b = min(bm, bn)
    if x_cols is None and vpu and 4 * r_pad * round_up(n, b) <= SYM_OUT_BYTES:
        spans.count("gram_sym")
        return _symmetric_pass(x_rows, v_scaled, b, d_pad, r_pad, interpret)
    if x_cols is None:
        m_pad = n_pad = max(round_up(n, bm), round_up(n, bn))
        xr_p = xc_p = jnp.pad(x_rows, ((0, n_pad - n), (0, d_pad - d)))
    else:
        m_pad, n_pad = round_up(m, bm), round_up(n, bn)
        xr_p = jnp.pad(x_rows, ((0, m_pad - m), (0, d_pad - d)))
        xc_p = jnp.pad(x_cols, ((0, n_pad - n), (0, d_pad - d)))
    if vpu:
        # n on lanes: for r = 1 the transpose is a reshape.
        lanes = LANES if bn % LANES == 0 else bn
        kernel = _rbf_matvec_vpu_kernel
        v_p = jnp.pad(v_scaled.T, ((0, r_pad - r), (0, n_pad - n)))
        v_spec = block_spec((r_pad, bn), lambda i, j: (0, j))
        acc = pltpu.VMEM((r, bm, lanes), jnp.float32)
    else:
        kernel = _rbf_matvec_kernel
        v_p = jnp.pad(v_scaled, ((0, n_pad - n), (0, r_pad - r)))
        v_spec = block_spec((bn, r_pad), lambda i, j: (j, 0))
        acc = pltpu.VMEM((bm, r_pad), jnp.float32)

    out = pl.pallas_call(
        kernel,
        grid=(m_pad // bm, n_pad // bn),
        in_specs=[
            block_spec((bm, d_pad), lambda i, j: (i, 0)),
            block_spec((bn, d_pad), lambda i, j: (j, 0)),
            v_spec,
        ],
        out_specs=block_spec((bm, r_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, r_pad), v_scaled.dtype),
        scratch_shapes=[acc],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="rbf_gram_matvec",
    )(xr_p, xc_p, v_p)
    return out[:m, :r]


def _symmetric_pass(x, v_scaled, b, d_pad, r_pad, interpret):
    """``K(X, X)·V`` for r ≤ R_VPU over the circulant band of unordered
    row-block pairs: grid (i, t), t ∈ [0, nb//2], j = (i + t) mod nb."""
    n, d = x.shape
    r = v_scaled.shape[1]
    n_pad = round_up(n, b)
    nb = n_pad // b
    lanes = LANES if b % LANES == 0 else b
    x_p = jnp.pad(x, ((0, n_pad - n), (0, d_pad - d)))
    # V once, n on lanes: block j for the row sums, block i for the
    # column sums (an (n, r_pad) copy would pad its lanes to 128 in HBM).
    vt = jnp.pad(v_scaled.T, ((0, r_pad - r), (0, n_pad - n)))

    def col(i, t):
        return _col_block(i, t, nb)

    out = pl.pallas_call(
        _rbf_matvec_sym_kernel,
        grid=(nb, nb // 2 + 1),
        in_specs=[
            block_spec((b, d_pad), lambda i, t: (i, 0)),
            block_spec((b, d_pad), lambda i, t: (col(i, t), 0)),
            block_spec((r_pad, b), lambda i, t: (0, col(i, t))),
            block_spec((r_pad, b), lambda i, t: (0, i)),
        ],
        out_specs=block_spec((r_pad, n_pad), lambda i, t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, n_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((r, b, lanes), jnp.float32),
            pltpu.VMEM((b, lanes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="rbf_gram_matvec",
    )(x_p, x_p, vt, vt)
    # n back on the leading axis: for r = 1 a reshape.
    return out[:r, :n].T.astype(v_scaled.dtype)
