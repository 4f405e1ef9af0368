"""Mamba2 SSD (state-space duality) chunked-scan Pallas kernel.

The SSD recurrence  ``h_t = exp(a·dt_t)·h_{t−1} + dt_t·B_t x_tᵀ``,
``y_t = C_t h_t``  is evaluated with the chunked dual form (arXiv
2405.21060): the sequence is split into chunks of size ``c``; within a
chunk the contribution is a masked quadratic form (three MXU matmuls),
between chunks only the (p × n) state is carried:

    cs_t   = Σ_{u≤t} a·dt_u                       (log-decay cumsum, ≤ 0)
    G      = C_chunk B_chunkᵀ                     (c × c,   MXU)
    M[t,s] = exp(cs_t − cs_s)·dt_s·[s ≤ t]        (VPU)
    Y      = (M ⊙ G) X  +  exp(cs)·(C H0ᵀ)        (two MXU matmuls)
    H1     = exp(cs_c)·H0 + Xᵀ·(exp(cs_c − cs)·dt ⊙ B)

All decay exponents are ≤ 0 (a < 0, dt ≥ 0) so every ``exp`` is in (0, 1]
— numerically safe in f32.

Grid: ``(batch, heads, n_chunks)`` with chunks innermost and *sequential*
("arbitrary" semantics) — the (p × n) state lives in VMEM scratch across
chunk steps.  batch/head grid dims are parallel.  This is the TPU-native
replacement for the paper-adjacent GPU pattern of one threadblock per
(batch, head) scanning serially: on TPU the systolic MXU does the chunk
quadratics while the sequential grid carries the recurrence.

The wrapper folds ``a`` into precomputed ``a·dt`` so the kernel body has no
per-head scalar indexing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import block_spec, round_up


def _ssd_kernel(x_ref, dt_ref, adt_ref, b_ref, c_ref, y_ref, h_ref):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)  # (c, p)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (c, 1)
    adt = adt_ref[0, 0].astype(jnp.float32)  # (c, 1)
    bmat = b_ref[0, 0].astype(jnp.float32)  # (c, n)
    cmat = c_ref[0, 0].astype(jnp.float32)  # (c, n)

    cs = jnp.cumsum(adt, axis=0)  # (c, 1) inclusive, ≤ 0 decreasing
    cs_total = cs[-1:, :]  # (1, 1)

    # Intra-chunk masked quadratic.
    g = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (c, c): G[t, s] = C_t·B_s
    delta = cs - cs.T  # (c, c): cs_t − cs_s
    t_idx = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    mask = s_idx <= t_idx
    m = jnp.where(mask, jnp.exp(jnp.where(mask, delta, 0.0)) * dt.T, 0.0)
    y = jax.lax.dot_general(
        m * g, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (c, p)

    # Inter-chunk: contribution of the carried state.
    h0 = h_ref[...]  # (p, n)
    y += jnp.exp(cs) * jax.lax.dot_general(
        cmat, h0, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (c, p)

    # State update for the next chunk.
    bw = bmat * (jnp.exp(cs_total - cs) * dt)  # (c, n)
    h_ref[...] = jnp.exp(cs_total) * h0 + jax.lax.dot_general(
        x, bw, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (p, n)

    y_ref[0, 0] = y.astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret")
)
def ssd_scan_pallas(
    x: jnp.ndarray,  # (b, l, h, p)
    dt: jnp.ndarray,  # (b, l, h)  (≥ 0, post-softplus)
    a: jnp.ndarray,  # (h,)        (< 0)
    bmat: jnp.ndarray,  # (b, l, g, n)
    cmat: jnp.ndarray,  # (b, l, g, n)
    d: jnp.ndarray | None = None,  # (h,) skip
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, l, h, p = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    hpg = h // g

    c = min(chunk, round_up(l, 8))
    l_p = round_up(l, c)

    # Head-major layouts; fold a into a·dt; expand B/C across head groups.
    xh = jnp.pad(x.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, l_p - l), (0, 0)))
    dth = jnp.pad(dt.transpose(0, 2, 1), ((0, 0), (0, 0), (0, l_p - l)))[..., None]
    adth = dth * a[None, :, None, None]
    bh = jnp.repeat(bmat.transpose(0, 2, 1, 3), hpg, axis=1)
    ch = jnp.repeat(cmat.transpose(0, 2, 1, 3), hpg, axis=1)
    bh = jnp.pad(bh, ((0, 0), (0, 0), (0, l_p - l), (0, 0)))
    ch = jnp.pad(ch, ((0, 0), (0, 0), (0, l_p - l), (0, 0)))

    grid = (b, h, l_p // c)
    y = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            block_spec((1, 1, c, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            block_spec((1, 1, c, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            block_spec((1, 1, c, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            block_spec((1, 1, c, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
            block_spec((1, 1, c, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=block_spec((1, 1, c, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, l_p, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_chunked_scan",
    )(xh, dth, adth, bh, ch)

    y = y[:, :, :l, :].transpose(0, 2, 1, 3)  # (b, l, h, p)
    if d is not None:
        y = y + x * d[None, None, :, None]
    return y

