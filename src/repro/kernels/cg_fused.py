"""Fused CG/def-CG iteration updates — the solver's non-matvec hot path.

One def-CG iteration on flat ``(n,)`` state does, besides the matvec:

    x  += α p                    r  -= α ap
    rr  = rᵀr                    awr = (AW)ᵀ r          (deflation GEMV)
    p   = β p + r − W μ          P[idx], AP[idx] = p, ap (recording)

Issued as separate ops these are ~8 HBM passes over n-sized data; in the
memory-bound regime the paper targets (cheap matvec, large n) they dominate
the iteration.  This module fuses them into two passes (DESIGN.md §8):

* :func:`fused_cg_update_pallas` — ``x/r`` AXPYs plus *both* reductions
  (``rᵀr`` and ``(AW)ᵀr``) in one read of ``x, r, p, ap, AW``;
* :func:`fused_deflate_direction_pallas` — the deflated direction update
  ``p ← βp + r − Wμ`` plus the guarded ring-buffer write of ``(p, Ap)``
  (a dynamic output row selected by scalar-prefetched ``idx``, buffers
  aliased in/out so untouched rows never move).

Layout: a flat vector of length n is viewed as ``(n/128, 128)`` and the
grid walks row-blocks; bases ``(k, n)`` become ``(k, n/128, 128)`` with the
k axis resident per block.  Scalars (α, β, μ) ride in SMEM; the reductions
accumulate in SMEM across the sequential grid.

The ``chunked`` twins are the pure-jnp same-math forms.  They deliberately
have *no* scan blocking: all operands are O(n), nothing materializes, and a
single jnp expression lets XLA fuse each group into one loop — that is the
CPU/GPU fast path the solver uses off-TPU.

Per the repo kernel contract: oracles live in ``ref.py``, dispatch in
``ops.py`` (pallas | interpret | reference | chunked | auto).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import F32, block_spec, round_up

_LANES = 128


def _acc(dtype):
    """Accumulation dtype (mirrors core.pytree): f64 stays, else ≥ f32."""
    if dtype == jnp.float64:
        return jnp.float64
    return jnp.promote_types(dtype, jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=F32)


def _pad_rows(v: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    """(n,) → (n_pad/128, 128), zero-padded (identity when n == n_pad)."""
    n = v.shape[-1]
    if v.ndim == 1:
        return jnp.pad(v, (0, n_pad - n)).reshape(-1, _LANES)
    return jnp.pad(v, ((0, 0), (0, n_pad - n))).reshape(
        v.shape[0], -1, _LANES
    )


# ---------------------------------------------------------------------------
# fused_cg_update: x += αp, r −= αap, rr = rᵀr, awr = AW·r — one pass
# ---------------------------------------------------------------------------


def _cg_update_kernel(
    alpha_ref, x_ref, r_ref, p_ref, ap_ref, xo_ref, ro_ref, rr_ref
):
    i = pl.program_id(0)
    alpha = alpha_ref[0, 0]
    rn = r_ref[...].astype(jnp.float32) - alpha * ap_ref[...].astype(
        jnp.float32
    )
    xo_ref[...] = (
        x_ref[...].astype(jnp.float32) + alpha * p_ref[...].astype(jnp.float32)
    ).astype(xo_ref.dtype)
    ro_ref[...] = rn.astype(ro_ref.dtype)

    @pl.when(i == 0)
    def _init():
        rr_ref[0, 0] = jnp.float32(0.0)

    rr_ref[0, 0] += jnp.sum(rn * rn)


def _cg_update_aw_kernel(
    alpha_ref, x_ref, r_ref, p_ref, ap_ref, aw_ref,
    xo_ref, ro_ref, rr_ref, awr_ref, *, k,
):
    i = pl.program_id(0)
    alpha = alpha_ref[0, 0]
    rn = r_ref[...].astype(jnp.float32) - alpha * ap_ref[...].astype(
        jnp.float32
    )
    xo_ref[...] = (
        x_ref[...].astype(jnp.float32) + alpha * p_ref[...].astype(jnp.float32)
    ).astype(xo_ref.dtype)
    ro_ref[...] = rn.astype(ro_ref.dtype)

    @pl.when(i == 0)
    def _init():
        rr_ref[0, 0] = jnp.float32(0.0)
        for ki in range(k):
            awr_ref[ki, 0] = jnp.float32(0.0)

    rr_ref[0, 0] += jnp.sum(rn * rn)
    awv = aw_ref[...].astype(jnp.float32)  # (k, rows, lanes)
    for ki in range(k):
        awr_ref[ki, 0] += jnp.sum(awv[ki] * rn)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_cg_update_pallas(
    x: jnp.ndarray,
    r: jnp.ndarray,
    p: jnp.ndarray,
    ap: jnp.ndarray,
    alpha,
    aw: Optional[jnp.ndarray] = None,
    *,
    block: int = 4096,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """One-pass CG state update over flat vectors (f32 accumulation).

    Returns ``(x + α·p, r − α·ap, ‖r_new‖², AW @ r_new | None)``.

    Shapes are padded to the (rows·128) tile internally; padded tails are
    zero so both reductions are exact, and outputs are sliced back to n.
    The pads are identity when n is already tile-aligned (the usual case
    for model shapes) — on TPU, misaligned n pays a pad/slice per call,
    so prefer aligned problem sizes (or a smaller ``block``) there.
    """
    n = x.shape[0]
    rows = max(8, block // _LANES)
    n_pad = round_up(n, _LANES * rows)
    nrows = n_pad // _LANES
    grid = (nrows // rows,)

    x2, r2, p2, ap2 = (_pad_rows(v, n_pad) for v in (x, r, p, ap))
    alpha2 = jnp.asarray(alpha, jnp.float32).reshape(1, 1)

    vec_spec = block_spec((rows, _LANES), lambda i: (i, 0))
    smem = functools.partial(block_spec, memory_space=pltpu.SMEM)
    in_specs = [smem((1, 1), lambda i: (0, 0))] + [vec_spec] * 4
    out_specs = [
        vec_spec,
        vec_spec,
        smem((1, 1), lambda i: (0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((nrows, _LANES), x.dtype),
        jax.ShapeDtypeStruct((nrows, _LANES), r.dtype),
        jax.ShapeDtypeStruct((1, 1), jnp.float32),
    ]
    args = [alpha2, x2, r2, p2, ap2]

    if aw is not None:
        k = aw.shape[0]
        args.append(_pad_rows(aw, n_pad))
        in_specs.append(
            block_spec((k, rows, _LANES), lambda i: (0, i, 0))
        )
        out_specs.append(smem((k, 1), lambda i: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((k, 1), jnp.float32))
        kernel = functools.partial(_cg_update_aw_kernel, k=k)
    else:
        kernel = _cg_update_kernel

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_cg_update",
    )(*args)

    x_new = outs[0].reshape(n_pad)[:n]
    r_new = outs[1].reshape(n_pad)[:n]
    # Reductions accumulate in f32 on the TPU but are returned in the
    # accumulation dtype of the inputs, so solver loop carries keep a
    # consistent dtype across the pallas and chunked paths (x64 mode).
    rr = outs[2][0, 0].astype(_acc(r.dtype))
    awr = outs[3][:, 0].astype(_acc(r.dtype)) if aw is not None else None
    return x_new, r_new, rr, awr


def fused_cg_update_chunked(x, r, p, ap, alpha, aw=None):
    """Pure-jnp twin: same math, one fused XLA loop per output group."""
    acc = _acc(r.dtype)
    x_new = x + alpha * p
    r_new = r - alpha * ap
    ra = r_new.astype(acc)
    rr = jnp.sum(ra * ra)
    awr = _mm(aw.astype(acc), ra) if aw is not None else None
    return x_new, r_new, rr, awr


# ---------------------------------------------------------------------------
# fused_rz_reduce: rᵀz and (AW)ᵀz — the preconditioned iteration's reductions
# ---------------------------------------------------------------------------
#
# Preconditioned def-CG applies z = M⁻¹r *after* the residual update, so the
# recurrence scalar rᵀz and the deflation GEMV (AW)ᵀz cannot ride in
# fused_cg_update's pass (which only sees r).  This second fused pass reads
# (r, z, AW) once and emits both reductions — the preconditioned iteration
# costs exactly one extra sweep over n-sized data beyond the unpreconditioned
# one, not three.


def _rz_reduce_kernel(r_ref, z_ref, rz_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        rz_ref[0, 0] = jnp.float32(0.0)

    rz_ref[0, 0] += jnp.sum(
        r_ref[...].astype(jnp.float32) * z_ref[...].astype(jnp.float32)
    )


def _rz_reduce_aw_kernel(r_ref, z_ref, aw_ref, rz_ref, awz_ref, *, k):
    i = pl.program_id(0)
    zv = z_ref[...].astype(jnp.float32)

    @pl.when(i == 0)
    def _init():
        rz_ref[0, 0] = jnp.float32(0.0)
        for ki in range(k):
            awz_ref[ki, 0] = jnp.float32(0.0)

    rz_ref[0, 0] += jnp.sum(r_ref[...].astype(jnp.float32) * zv)
    awv = aw_ref[...].astype(jnp.float32)  # (k, rows, lanes)
    for ki in range(k):
        awz_ref[ki, 0] += jnp.sum(awv[ki] * zv)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_rz_reduce_pallas(
    r: jnp.ndarray,
    z: jnp.ndarray,
    aw: Optional[jnp.ndarray] = None,
    *,
    block: int = 4096,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """``(rᵀz, AW @ z | None)`` in one read of ``r, z, AW`` (f32 accum)."""
    n = r.shape[0]
    rows = max(8, block // _LANES)
    n_pad = round_up(n, _LANES * rows)
    nrows = n_pad // _LANES
    grid = (nrows // rows,)

    r2, z2 = _pad_rows(r, n_pad), _pad_rows(z, n_pad)
    vec_spec = block_spec((rows, _LANES), lambda i: (i, 0))
    smem = functools.partial(block_spec, memory_space=pltpu.SMEM)

    in_specs = [vec_spec, vec_spec]
    out_specs = [smem((1, 1), lambda i: (0, 0))]
    out_shape = [jax.ShapeDtypeStruct((1, 1), jnp.float32)]
    args = [r2, z2]
    if aw is not None:
        k = aw.shape[0]
        args.append(_pad_rows(aw, n_pad))
        in_specs.append(block_spec((k, rows, _LANES), lambda i: (0, i, 0)))
        out_specs.append(smem((k, 1), lambda i: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((k, 1), jnp.float32))
        kernel = functools.partial(_rz_reduce_aw_kernel, k=k)
    else:
        kernel = _rz_reduce_kernel

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_rz_reduce",
    )(*args)
    rz = outs[0][0, 0].astype(_acc(r.dtype))
    awz = outs[1][:, 0].astype(_acc(r.dtype)) if aw is not None else None
    return rz, awz


def fused_rz_reduce_chunked(r, z, aw=None):
    """Pure-jnp twin: one fused XLA reduction group in the acc dtype."""
    acc = _acc(r.dtype)
    za = z.astype(acc)
    rz = jnp.sum(r.astype(acc) * za)
    awz = _mm(aw.astype(acc), za) if aw is not None else None
    return rz, awz


# ---------------------------------------------------------------------------
# lsmr_update: hbar ← h − c0·hbar, x ← x + c1·hbar, h ← v − c2·h — one pass
# ---------------------------------------------------------------------------
#
# One LSMR iteration's non-matvec vector work is three coupled AXPY-style
# recurrences over (x, hbar, h, v).  Issued separately they are three HBM
# sweeps (six reads, three writes); fused, every operand is read once and
# the shared intermediate hbar_new never round-trips through HBM.  The
# rotation scalars are pre-reduced by the solver (they come from the 2×2
# Givens recurrences, O(1) work) and ride in SMEM.


def _lsmr_update_kernel(c_ref, x_ref, hbar_ref, h_ref, v_ref,
                        xo_ref, hbo_ref, ho_ref):
    c0, c1, c2 = c_ref[0, 0], c_ref[1, 0], c_ref[2, 0]
    hv = h_ref[...].astype(jnp.float32)
    hb = hv - c0 * hbar_ref[...].astype(jnp.float32)
    xo_ref[...] = (
        x_ref[...].astype(jnp.float32) + c1 * hb
    ).astype(xo_ref.dtype)
    hbo_ref[...] = hb.astype(hbo_ref.dtype)
    ho_ref[...] = (
        v_ref[...].astype(jnp.float32) - c2 * hv
    ).astype(ho_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def lsmr_update_pallas(
    x: jnp.ndarray,
    hbar: jnp.ndarray,
    h: jnp.ndarray,
    v: jnp.ndarray,
    c0,
    c1,
    c2,
    *,
    block: int = 4096,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-pass LSMR vector update (f32 accumulation on the VPU).

    Returns ``(x + c1·(h − c0·hbar), h − c0·hbar, v − c2·h)`` — the
    ``(x, hbar, h)`` state after one iteration, with the scalars packed
    into SMEM and every n-sized operand read exactly once.
    """
    n = x.shape[0]
    rows = max(8, block // _LANES)
    n_pad = round_up(n, _LANES * rows)
    nrows = n_pad // _LANES
    grid = (nrows // rows,)

    x2, hb2, h2, v2 = (_pad_rows(u, n_pad) for u in (x, hbar, h, v))
    c2_ = jnp.stack([
        jnp.asarray(c0, jnp.float32),
        jnp.asarray(c1, jnp.float32),
        jnp.asarray(c2, jnp.float32),
    ]).reshape(3, 1)

    vec_spec = block_spec((rows, _LANES), lambda i: (i, 0))
    smem = functools.partial(block_spec, memory_space=pltpu.SMEM)
    outs = pl.pallas_call(
        _lsmr_update_kernel,
        grid=grid,
        in_specs=[smem((3, 1), lambda i: (0, 0))] + [vec_spec] * 4,
        out_specs=[vec_spec] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((nrows, _LANES), x.dtype),
            jax.ShapeDtypeStruct((nrows, _LANES), hbar.dtype),
            jax.ShapeDtypeStruct((nrows, _LANES), h.dtype),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="lsmr_update",
    )(c2_, x2, hb2, h2, v2)
    return (
        outs[0].reshape(n_pad)[:n],
        outs[1].reshape(n_pad)[:n],
        outs[2].reshape(n_pad)[:n],
    )


def lsmr_update_chunked(x, hbar, h, v, c0, c1, c2):
    """Pure-jnp twin: same math, one fused XLA loop over the four vectors."""
    hbar_new = h - c0 * hbar
    x_new = x + c1 * hbar_new
    h_new = v - c2 * h
    return x_new, hbar_new, h_new


# ---------------------------------------------------------------------------
# fused_deflate_direction: p ← βp + r − Wμ, plus the (p, Ap) buffer write
# ---------------------------------------------------------------------------


def _deflate_buf_kernel(
    idx_ref, beta_ref, mu_ref, r_ref, p_ref, ap_ref, w_ref,
    pbi_ref, abi_ref, po_ref, pbo_ref, abo_ref, *, k,
):
    del idx_ref, pbi_ref, abi_ref  # routing only (index maps / aliasing)
    pv = p_ref[...].astype(jnp.float32)
    acc = r_ref[...].astype(jnp.float32) + beta_ref[0, 0] * pv
    for ki in range(k):
        acc -= mu_ref[ki, 0] * w_ref[ki].astype(jnp.float32)
    po_ref[...] = acc.astype(po_ref.dtype)
    pbo_ref[0] = p_ref[...].astype(pbo_ref.dtype)
    abo_ref[0] = ap_ref[...].astype(abo_ref.dtype)


def _deflate_kernel(beta_ref, mu_ref, r_ref, p_ref, w_ref, po_ref, *, k):
    pv = p_ref[...].astype(jnp.float32)
    acc = r_ref[...].astype(jnp.float32) + beta_ref[0, 0] * pv
    for ki in range(k):
        acc -= mu_ref[ki, 0] * w_ref[ki].astype(jnp.float32)
    po_ref[...] = acc.astype(po_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_deflate_direction_pallas(
    r: jnp.ndarray,
    p: jnp.ndarray,
    beta,
    w: jnp.ndarray,
    mu: jnp.ndarray,
    ap: Optional[jnp.ndarray] = None,
    idx=None,
    p_buf: Optional[jnp.ndarray] = None,
    ap_buf: Optional[jnp.ndarray] = None,
    *,
    block: int = 4096,
    interpret: bool = False,
):
    """Deflated direction update, optionally recording ``(p, ap)``.

    ``p_new = β·p + r − μᵀW``; when ``p_buf``/``ap_buf`` are given, the
    *incoming* ``p`` and ``ap`` are stored into buffer row ``idx`` in the
    same pass — callers guard the write by pointing ``idx`` at a spare
    row.  The buffers are aliased through the kernel (donated), so only
    the selected row moves; returns ``(p_new, p_buf, ap_buf)``.
    """
    n = r.shape[0]
    k = w.shape[0]
    rows = max(8, block // _LANES)
    n_pad = round_up(n, _LANES * rows)
    nrows = n_pad // _LANES
    grid = (nrows // rows,)

    r2, p2 = _pad_rows(r, n_pad), _pad_rows(p, n_pad)
    w2 = _pad_rows(w, n_pad)
    beta2 = jnp.asarray(beta, jnp.float32).reshape(1, 1)
    mu2 = jnp.asarray(mu, jnp.float32).reshape(k, 1)

    have_buf = p_buf is not None
    smem = functools.partial(block_spec, memory_space=pltpu.SMEM)

    if not have_buf:
        out = pl.pallas_call(
            functools.partial(_deflate_kernel, k=k),
            grid=grid,
            in_specs=[
                smem((1, 1), lambda i: (0, 0)),
                smem((k, 1), lambda i: (0, 0)),
                block_spec((rows, _LANES), lambda i: (i, 0)),
                block_spec((rows, _LANES), lambda i: (i, 0)),
                block_spec((k, rows, _LANES), lambda i: (0, i, 0)),
            ],
            out_specs=block_spec((rows, _LANES), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((nrows, _LANES), p.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            ),
            interpret=interpret,
            name="fused_deflate_direction",
        )(beta2, mu2, r2, p2, w2)
        return out.reshape(n_pad)[:n], None, None

    m = p_buf.shape[0]
    ap2 = _pad_rows(ap, n_pad)
    pb2, ab2 = _pad_rows(p_buf, n_pad), _pad_rows(ap_buf, n_pad)
    idx2 = jnp.asarray(idx, jnp.int32).reshape(1)

    vec = lambda: block_spec((rows, _LANES), lambda i, idx_ref: (i, 0))
    row = lambda: block_spec(
        (1, rows, _LANES), lambda i, idx_ref: (idx_ref[0], i, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            smem((1, 1), lambda i, idx_ref: (0, 0)),  # beta
            smem((k, 1), lambda i, idx_ref: (0, 0)),  # mu
            vec(),  # r
            vec(),  # p
            vec(),  # ap
            block_spec(
                (k, rows, _LANES), lambda i, idx_ref: (0, i, 0)
            ),  # w
            row(),  # p_buf (pass-through for aliasing)
            row(),  # ap_buf
        ],
        out_specs=[vec(), row(), row()],
    )
    # Alias the buffers in→out (inputs count the scalar-prefetch arg).
    outs = pl.pallas_call(
        functools.partial(_deflate_buf_kernel, k=k),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nrows, _LANES), p.dtype),
            jax.ShapeDtypeStruct((m, nrows, _LANES), p_buf.dtype),
            jax.ShapeDtypeStruct((m, nrows, _LANES), ap_buf.dtype),
        ],
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_deflate_direction",
    )(idx2, beta2, mu2, r2, p2, ap2, w2, pb2, ab2)
    p_new = outs[0].reshape(n_pad)[:n]
    p_buf_new = outs[1].reshape(m, n_pad)[:, :n]
    ap_buf_new = outs[2].reshape(m, n_pad)[:, :n]
    return p_new, p_buf_new, ap_buf_new


# ---------------------------------------------------------------------------
# self_gram: S Sᵀ for a stacked flat basis — the extraction's single GEMM
# ---------------------------------------------------------------------------
#
# Harmonic-Ritz extraction needs G = (AZ)(AZ)ᵀ and F = (AZ)Zᵀ.  Stacking
# S = [Z; AZ] (2m, n) and forming S Sᵀ yields both as quadrants in ONE
# tall-skinny GEMM — one read of the basis data instead of three separate
# gram passes (ZZᵀ for column norms, then G, then F).


def _self_gram_kernel(s_ref, o_ref, acc_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sb = s_ref[...].astype(jnp.float32)  # (m_pad, bn)
    acc_ref[...] += jax.lax.dot_general(
        sb, sb, (((1,), (1,)), ((), ())),
        precision=F32, preferred_element_type=jnp.float32,
    )

    @pl.when(j == pl.num_programs(0) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def self_gram_pallas(
    s: jnp.ndarray, *, block: int = 2048, interpret: bool = False
) -> jnp.ndarray:
    """``S Sᵀ`` for ``S`` of shape ``(m, n)``, blocked over ``n``.

    The grid walks n-blocks sequentially and accumulates the ``(m, m)``
    Gram tile in a VMEM scratch (f32); only the final step writes back.
    Zero-padding in both axes is exact (padded rows/cols contribute 0 and
    padded output rows are sliced off).
    """
    m, n = s.shape
    m_pad = round_up(max(m, 8), 8)
    bn = min(round_up(block, _LANES), round_up(n, _LANES))
    n_pad = round_up(n, bn)
    s_p = jnp.pad(s, ((0, m_pad - m), (0, n_pad - n)))

    out = pl.pallas_call(
        _self_gram_kernel,
        grid=(n_pad // bn,),
        in_specs=[block_spec((m_pad, bn), lambda j: (0, j))],
        out_specs=block_spec((m_pad, m_pad), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, m_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m_pad, m_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="self_gram",
    )(s_p)
    return out[:m, :m].astype(_acc(s.dtype))


def self_gram_chunked(s: jnp.ndarray, block: int = 8192) -> jnp.ndarray:
    """Pure-jnp twin: scan over n-blocks, accumulating in the acc dtype.

    A single GEMM when ``n ≤ block`` (the usual extraction size); the
    blocked scan bounds live memory for very long flat vectors.
    """
    acc = _acc(s.dtype)
    m, n = s.shape
    if n <= block:
        sa = s.astype(acc)
        return _mm(sa, sa.T)
    n_pad = round_up(n, block)
    sp = jnp.pad(s, ((0, 0), (0, n_pad - n))).astype(acc)
    blocks = sp.reshape(m, n_pad // block, block).transpose(1, 0, 2)

    def body(g, sb):
        return g + _mm(sb, sb.T), None

    g0 = jnp.zeros((m, m), acc)
    g, _ = jax.lax.scan(body, g0, blocks)
    return g


# ---------------------------------------------------------------------------
# recombine_blocks: [uᵀZ; uᵀAZ] from S = [Z; AZ] — the windowed refresh GEMM
# ---------------------------------------------------------------------------
#
# The paper's zero-extra-matvec refresh rebuilds BOTH the next recycled
# basis W' = uᵀZ and its operator products AW' = uᵀAZ from quantities the
# solve already stored.  Doing it as one kernel over the stacked S = [Z; AZ]
# (2m, n) reads the basis data once: each n-block loads the full (2m, bn)
# column slab, applies uᵀ to each half on the MXU, and writes the (2k, bn)
# output slab.  Output blocks are disjoint per grid step.


def _recombine_blocks_kernel(ut_ref, s_ref, o_ref, *, m_pad, k_pad):
    ut = ut_ref[...]  # (k_pad, m_pad) f32
    sb = s_ref[...].astype(jnp.float32)  # (2·m_pad, bn)
    top = jax.lax.dot_general(
        ut, sb[:m_pad], (((1,), (0,)), ((), ())),
        precision=F32, preferred_element_type=jnp.float32,
    )
    bot = jax.lax.dot_general(
        ut, sb[m_pad:], (((1,), (0,)), ((), ())),
        precision=F32, preferred_element_type=jnp.float32,
    )
    o_ref[:k_pad] = top.astype(o_ref.dtype)
    o_ref[k_pad:] = bot.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def recombine_blocks_pallas(
    s: jnp.ndarray,
    u: jnp.ndarray,
    *,
    block: int = 2048,
    interpret: bool = False,
) -> jnp.ndarray:
    """``[uᵀ·S_top; uᵀ·S_bot]`` for ``S`` of shape ``(2m, n)``, ``u`` of
    ``(m, k)`` — blocked over ``n``, f32 accumulation on the MXU.

    Both halves are padded to an 8-row tile independently so the static
    half split survives padding; zero pad rows/cols contribute exact
    zeros and are sliced off the output.
    """
    m2, n = s.shape
    m = m2 // 2
    assert 2 * m == m2, "recombine_blocks needs an even (2m, n) stack"
    k = u.shape[1]
    m_pad = round_up(max(m, 8), 8)
    k_pad = round_up(max(k, 8), 8)
    bn = min(round_up(block, _LANES), round_up(n, _LANES))
    n_pad = round_up(n, bn)

    s_p = jnp.concatenate(
        [
            jnp.pad(s[:m], ((0, m_pad - m), (0, n_pad - n))),
            jnp.pad(s[m:], ((0, m_pad - m), (0, n_pad - n))),
        ],
        axis=0,
    )
    ut_p = jnp.pad(
        u.astype(jnp.float32).T, ((0, k_pad - k), (0, m_pad - m))
    )

    out = pl.pallas_call(
        functools.partial(_recombine_blocks_kernel, m_pad=m_pad, k_pad=k_pad),
        grid=(n_pad // bn,),
        in_specs=[
            block_spec((k_pad, m_pad), lambda j: (0, 0)),
            block_spec((2 * m_pad, bn), lambda j: (0, j)),
        ],
        out_specs=block_spec((2 * k_pad, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((2 * k_pad, n_pad), s.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="recombine_blocks",
    )(ut_p, s_p)
    return jnp.concatenate(
        [out[:k, :n], out[k_pad : k_pad + k, :n]], axis=0
    )


def recombine_blocks_chunked(
    s: jnp.ndarray, u: jnp.ndarray, block: int = 8192
) -> jnp.ndarray:
    """Pure-jnp twin: one fused two-block GEMM when ``n ≤ block``, else a
    scan over n-blocks with the kernel's blocking (bounded live memory)."""
    m2, n = s.shape
    m = m2 // 2
    acc = _acc(s.dtype)
    ut = u.astype(acc).T  # (k, m)
    if n <= block:
        sa = s.astype(acc)
        out = jnp.concatenate([_mm(ut, sa[:m]), _mm(ut, sa[m:])], axis=0)
        return out.astype(s.dtype)
    n_pad = round_up(n, block)
    sp = jnp.pad(s, ((0, 0), (0, n_pad - n))).astype(acc)
    blocks = sp.reshape(m2, n_pad // block, block).transpose(1, 0, 2)

    def body(_, sb):
        return None, jnp.concatenate(
            [_mm(ut, sb[:m]), _mm(ut, sb[m:])], axis=0
        )

    _, outs = jax.lax.scan(body, None, blocks)
    k = u.shape[1]
    return (
        outs.transpose(1, 0, 2).reshape(2 * k, n_pad)[:, :n].astype(s.dtype)
    )


def fused_deflate_direction_chunked(
    r, p, beta, w=None, mu=None, ap=None, idx=None, p_buf=None, ap_buf=None
):
    """Pure-jnp twin.  The buffer update is a single masked
    ``dynamic_update_slice`` (no read-modify-write of the old row); inside
    a ``while_loop`` the buffers are donated, so only row ``idx`` moves."""
    p_new = beta * p + r
    if w is not None:
        p_new = p_new - (
            _mm(mu.astype(_acc(w.dtype)), w.astype(_acc(w.dtype)))
        ).astype(p.dtype)
    if p_buf is None:
        return p_new, None, None
    i = jnp.asarray(idx, jnp.int32)
    zero = jnp.int32(0)
    p_buf = jax.lax.dynamic_update_slice(
        p_buf, p[None].astype(p_buf.dtype), (i, zero)
    )
    ap_buf = jax.lax.dynamic_update_slice(
        ap_buf, ap[None].astype(ap_buf.dtype), (i, zero)
    )
    return p_new, p_buf, ap_buf
