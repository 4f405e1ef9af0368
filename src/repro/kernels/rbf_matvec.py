"""Fused RBF Gram-matrix matvec — the paper's per-iteration hot-spot.

Every CG / def-CG iteration on the GP-classification Newton system costs
one product with the kernel Gram matrix ``K(X, X)``.  Materializing ``K``
(n² entries) and streaming it from HBM makes the matvec memory-bound at
~0.5 flop/byte.  This kernel instead *fuses* Gram formation and the matvec:

    tile (i, j):   S  = ‖xi‖² + ‖xj‖ᵀ² − 2·Xi Xjᵀ        (MXU: bm×d @ d×bn)
                   Kb = exp(−S/2)                          (VPU)
                   Yi += Kb @ Vj                           (MXU: bm×bn @ bn×r)

so HBM traffic is O(n·d + n·r) per pass instead of O(n²), and arithmetic
intensity grows with the block size — the op becomes compute-bound, which
is the right regime for the MXU (DESIGN.md §3).

Parameter handling: the wrapper (ops.py) pre-scales ``X ← X/λ`` and
``V ← θ²·V``, so the kernel body is hyperparameter-free and never
recompiles during outer-loop kernel-hyperparameter optimization.

Multi-RHS (``V ∈ ℝ^{n×r}``) is native: recomputing ``A·W`` for a recycled
k-vector basis (the O(k·n²) overhead the paper accounts for in §2.2) is a
single fused pass with r = k instead of k separate matvecs.

Grid layout: ``(i, j)`` with j innermost ("arbitrary" semantics — the
output tile for row-block i is revisited across j and accumulated in VMEM;
only the final j writes back).  i is parallel across cores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import F32, block_spec, round_up

# Both contractions run at full f32 precision (F32).  On a v5e at n = 2^15,
# d = 784 (float64 reference on 256 rows) the kernel's relative error is
# 3.7e-6 this way, 4.7e-3 with both at the default one-bf16-pass
# precision and 5.4e-3 with only the distance term at HIGHEST: neither
# cheaper choice is within a 1e-5 solve tolerance.  HIGHEST on the K·v
# term is what limits the block to 256 (512 overflows VMEM).


def _rbf_matvec_kernel(x_i_ref, x_j_ref, v_ref, o_ref, acc_ref):
    """One (bm × bn) tile of y += exp(−‖xi−xj‖²/2) @ v."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xi = x_i_ref[...].astype(jnp.float32)  # (bm, d)
    xj = x_j_ref[...].astype(jnp.float32)  # (bn, d)
    vj = v_ref[...].astype(jnp.float32)  # (bn, r)

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
    kb = jnp.exp(-0.5 * dist2)

    acc_ref[...] += jax.lax.dot_general(
        kb, vj, (((1,), (0,)), ((), ())),
        precision=F32, preferred_element_type=jnp.float32,
    )

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)
def rbf_matvec_pallas(
    x_scaled: jnp.ndarray,
    v_scaled: jnp.ndarray,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """``y = exp(−½‖x_i − x_j‖²) V`` over pre-scaled inputs.

    Args:
      x_scaled: (n, d) data, already divided by the lengthscale.
      v_scaled: (n, r) right-hand sides, already scaled by θ².
      block_m/block_n: VMEM tile rows/cols; multiples of 128 on real TPUs.
      interpret: run the kernel body in Python on CPU (validation mode).

    Shapes are padded internally: j-padding is exact because padded V rows
    are zero; padded i-rows are sliced off the output.
    """
    n, d = x_scaled.shape
    _, r = v_scaled.shape

    bm = min(block_m, max(round_up(n, 8), 8))
    bn = min(block_n, max(round_up(n, 8), 8))
    n_m = round_up(n, bm)
    n_n = round_up(n, bn)
    n_pad = max(n_m, n_n)
    d_pad = round_up(d, 128)
    r_pad = round_up(r, 8)

    x_p = jnp.pad(x_scaled, ((0, n_pad - n), (0, d_pad - d)))
    v_p = jnp.pad(v_scaled, ((0, n_pad - n), (0, r_pad - r)))

    grid = (n_pad // bm, n_pad // bn)
    out = pl.pallas_call(
        _rbf_matvec_kernel,
        grid=grid,
        in_specs=[
            block_spec((bm, d_pad), lambda i, j: (i, 0)),
            block_spec((bn, d_pad), lambda i, j: (j, 0)),
            block_spec((bn, r_pad), lambda i, j: (j, 0)),
        ],
        out_specs=block_spec((bm, r_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, r_pad), v_scaled.dtype),
        scratch_shapes=[pltpu.VMEM((bm, r_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="rbf_gram_matvec",
    )(x_p, x_p, v_p)
    return out[:n, :r]


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)
def rbf_matvec_rect_pallas(
    x_rows: jnp.ndarray,
    x_cols: jnp.ndarray,
    v_scaled: jnp.ndarray,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Rectangular Gram matvec ``y = exp(−½‖xr_i − xc_j‖²) V``.

    The sharded-operator building block: each shard holds a ROW block of
    the data (``x_rows``, its local (m, d) slice) and applies the full
    column set (``x_cols``, the all-gathered (n, d) data) to the gathered
    right-hand sides — the K-tile for (local rows × all columns) is
    formed and consumed in VMEM, never materialized.  The kernel body is
    :func:`_rbf_matvec_kernel` unchanged (the square wrapper just passes
    the same array for both row and column data); only the padding and
    grid differ.
    """
    m, d = x_rows.shape
    n, _ = x_cols.shape
    _, r = v_scaled.shape

    bm = min(block_m, max(round_up(m, 8), 8))
    bn = min(block_n, max(round_up(n, 8), 8))
    m_pad = round_up(m, bm)
    n_pad = round_up(n, bn)
    d_pad = round_up(d, 128)
    r_pad = round_up(r, 8)

    xr_p = jnp.pad(x_rows, ((0, m_pad - m), (0, d_pad - d)))
    xc_p = jnp.pad(x_cols, ((0, n_pad - n), (0, d_pad - d)))
    v_p = jnp.pad(v_scaled, ((0, n_pad - n), (0, r_pad - r)))

    grid = (m_pad // bm, n_pad // bn)
    out = pl.pallas_call(
        _rbf_matvec_kernel,
        grid=grid,
        in_specs=[
            block_spec((bm, d_pad), lambda i, j: (i, 0)),
            block_spec((bn, d_pad), lambda i, j: (j, 0)),
            block_spec((bn, r_pad), lambda i, j: (j, 0)),
        ],
        out_specs=block_spec((bm, r_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, r_pad), v_scaled.dtype),
        scratch_shapes=[pltpu.VMEM((bm, r_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="rbf_gram_matvec_rect",
    )(xr_p, xc_p, v_p)
    return out[:m, :r]

