"""Public jit'd wrappers for the Pallas kernels, with implementation dispatch.

Every op takes ``impl ∈ {"auto", "pallas", "interpret", "reference",
"chunked"}``:

* ``pallas``     — the TPU kernel (real hardware target);
* ``interpret``  — the same kernel body, interpreted on CPU (validation);
* ``reference``  — the pure-jnp oracle from ``ref.py`` (materializes);
* ``chunked``    — a memory-efficient pure-jnp implementation with the same
  blocking structure as the kernel, built from ``lax.scan``.  This is what
  the multi-pod dry-run compiles (identical collective profile under pjit,
  linear memory, compiles on every backend) and what CPU end-to-end runs
  use;
* ``auto``       — ``pallas`` on TPU, ``chunked`` elsewhere.

Mosaic has no 64-bit types, so ``pallas`` (chosen or resolved from
``auto``) raises a ``ValueError`` for float64 operands instead of routing
them anywhere else: the TPU path runs in float32.

Keeping the kernel and the scan implementation in one file per op — with a
single oracle — is the repo's kernel contract (see kernels/EXAMPLE.md).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.cg_fused import (
    fused_cg_update_chunked,
    fused_cg_update_pallas,
    fused_deflate_direction_chunked,
    fused_deflate_direction_pallas,
    fused_rz_reduce_chunked,
    fused_rz_reduce_pallas,
    lsmr_update_chunked,
    lsmr_update_pallas,
    recombine_blocks_chunked,
    recombine_blocks_pallas,
    self_gram_chunked,
    self_gram_pallas,
)
from repro.kernels.blocks import F32
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rbf_matvec import rbf_matvec_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

_NEG_INF = -1e30


def _resolve(impl: str, op: str, *operands) -> str:
    """Resolve ``auto`` and refuse 64-bit float operands on ``pallas``."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "chunked"
    if impl == "pallas":
        for a in operands:
            if (
                a is not None
                and jnp.issubdtype(a.dtype, jnp.floating)
                and jnp.dtype(a.dtype).itemsize == 8
            ):
                raise ValueError(
                    f"{op}: impl='pallas' got a {jnp.dtype(a.dtype).name} "
                    "operand; the TPU kernels take no 64-bit types — pass "
                    "float32 data, or impl='chunked'/'reference'"
                )
    return impl


# ---------------------------------------------------------------------------
# RBF Gram matvec
# ---------------------------------------------------------------------------


def rbf_matvec(
    x: jnp.ndarray,
    v: jnp.ndarray,
    theta: float,
    lengthscale: float,
    *,
    impl: str = "auto",
    block: int = 256,
) -> jnp.ndarray:
    """``K(X,X) @ v`` for the RBF kernel, no O(n²) memory (except reference).

    ``v`` may be ``(n,)`` or ``(n, r)`` (multi-RHS, e.g. refreshing ``A·W``
    for a k-vector recycled basis in one fused pass).
    """
    squeeze = v.ndim == 1
    v2 = v[:, None] if squeeze else v
    impl = _resolve(impl, "rbf_matvec", x, v)
    if impl in ("pallas", "interpret"):
        out = rbf_matvec_pallas(
            x / lengthscale,
            (theta**2) * v2,
            block_m=block,
            block_n=block,
            interpret=(impl == "interpret"),
        )
    elif impl == "reference":
        out = ref.rbf_matvec(x, v2, theta, lengthscale)
    elif impl == "chunked":
        xs = x / lengthscale
        out = _rbf_matvec_chunked(xs, xs, (theta**2) * v2, block)
    else:
        raise ValueError(f"unknown impl={impl!r}")
    return out[:, 0] if squeeze else out


def rbf_matvec_rect(
    x_rows: jnp.ndarray,
    x_cols: jnp.ndarray,
    v: jnp.ndarray,
    theta: float,
    lengthscale: float,
    *,
    impl: str = "auto",
    block: int = 256,
) -> jnp.ndarray:
    """Rectangular Gram matvec ``K(X_rows, X_cols) @ v``, no O(m·n) memory.

    The sharded-operator primitive (DESIGN.md §5): each shard of the
    ``"solve"`` mesh keeps its local ROW block of the data and contracts
    it against the full (all-gathered) column set — one call per shard,
    K never materialized.  ``x_rows`` is ``(m, d)``, ``x_cols`` ``(n, d)``,
    ``v`` ``(n,)`` or ``(n, r)``; output ``(m,)`` / ``(m, r)``.  The
    square :func:`rbf_matvec` is the same kernel with the rows as the
    columns.
    """
    squeeze = v.ndim == 1
    v2 = v[:, None] if squeeze else v
    impl = _resolve(impl, "rbf_matvec_rect", x_rows, x_cols, v)
    if impl in ("pallas", "interpret"):
        out = rbf_matvec_pallas(
            x_rows / lengthscale,
            (theta**2) * v2,
            x_cols / lengthscale,
            block_m=block,
            block_n=block,
            interpret=(impl == "interpret"),
        )
    elif impl == "reference":
        out = ref.rbf_matvec_rect(x_rows, x_cols, v2, theta, lengthscale)
    elif impl == "chunked":
        out = _rbf_matvec_chunked(
            x_rows / lengthscale, x_cols / lengthscale, (theta**2) * v2, block
        )
    else:
        raise ValueError(f"unknown impl={impl!r}")
    return out[:, 0] if squeeze else out


def _rbf_matvec_chunked(
    xr: jnp.ndarray, xc: jnp.ndarray, vs: jnp.ndarray, block: int
):
    """Row-blocked Gram matvec ``K(xr, xc) @ vs``: a scan over row blocks,
    all columns per step, O(block · n) score memory.  The same math as the
    Pallas kernel (pre-scaled inputs), so dtype/rounding behaviour matches
    closely; the square matvec passes the same data for rows and
    columns."""
    m, d = xr.shape
    nb = max(1, block)
    m_pad = ((m + nb - 1) // nb) * nb
    xp = jnp.pad(xr, ((0, m_pad - m), (0, 0)))
    sq_cols = jnp.sum(xc * xc, axis=1)

    def body(_, xi):
        sq_i = jnp.sum(xi * xi, axis=1, keepdims=True)
        cross = jnp.matmul(xi, xc.T, precision=F32)
        d2 = jnp.maximum(sq_i + sq_cols[None, :] - 2.0 * cross, 0.0)
        return None, jnp.matmul(jnp.exp(-0.5 * d2), vs, precision=F32)

    _, ys = jax.lax.scan(body, None, xp.reshape(-1, nb, d))
    return ys.reshape(m_pad, vs.shape[1])[:m]


# ---------------------------------------------------------------------------
# Fused CG iteration updates (the def-CG inner-loop hot path)
# ---------------------------------------------------------------------------


def fused_cg_update(
    x: jnp.ndarray,
    r: jnp.ndarray,
    p: jnp.ndarray,
    ap: jnp.ndarray,
    alpha,
    aw: Optional[jnp.ndarray] = None,
    *,
    impl: str = "auto",
    block: int = 4096,
):
    """``(x + α p, r − α ap, ‖r_new‖², AW @ r_new | None)`` in one pass.

    The CG state update fused with both per-iteration reductions — the
    ``rᵀr`` recurrence scalar and the deflation GEMV ``(AW)ᵀ r`` (``aw``
    is the flat ``(k, n)`` basis; pass ``None`` when not deflating).
    """
    impl = _resolve(impl, "fused_cg_update", x, r, p, ap, aw)
    if impl in ("pallas", "interpret"):
        return fused_cg_update_pallas(
            x, r, p, ap, alpha, aw,
            block=block, interpret=(impl == "interpret"),
        )
    if impl == "reference":
        return ref.fused_cg_update(x, r, p, ap, alpha, aw)
    if impl == "chunked":
        return fused_cg_update_chunked(x, r, p, ap, alpha, aw)
    raise ValueError(f"unknown impl={impl!r}")


def fused_rz_reduce(
    r: jnp.ndarray,
    z: jnp.ndarray,
    aw: Optional[jnp.ndarray] = None,
    *,
    impl: str = "auto",
    block: int = 4096,
):
    """``(rᵀz, AW @ z | None)`` in one pass over ``r, z, AW``.

    The preconditioned def-CG iteration's second fused sweep: the PCG
    recurrence scalar ``rᵀz`` (z = M⁻¹r is only available *after* the
    residual update, so it cannot ride in :func:`fused_cg_update`) plus
    the deflation GEMV taken in the preconditioned inner product.
    """
    impl = _resolve(impl, "fused_rz_reduce", r, z, aw)
    if impl in ("pallas", "interpret"):
        return fused_rz_reduce_pallas(
            r, z, aw, block=block, interpret=(impl == "interpret")
        )
    if impl == "reference":
        return ref.fused_rz_reduce(r, z, aw)
    if impl == "chunked":
        return fused_rz_reduce_chunked(r, z, aw)
    raise ValueError(f"unknown impl={impl!r}")


def fused_deflate_direction(
    r: jnp.ndarray,
    p: jnp.ndarray,
    beta,
    w: Optional[jnp.ndarray] = None,
    mu: Optional[jnp.ndarray] = None,
    ap: Optional[jnp.ndarray] = None,
    idx=None,
    p_buf: Optional[jnp.ndarray] = None,
    ap_buf: Optional[jnp.ndarray] = None,
    *,
    impl: str = "auto",
    block: int = 4096,
):
    """``p ← β p + r − μᵀ W`` fused with the guarded ring-buffer write.

    When ``p_buf``/``ap_buf`` are given the *incoming* ``(p, ap)`` is
    stored into row ``idx`` in the same pass (callers point ``idx`` at a
    spare row to suppress the write).  Returns ``(p_new, p_buf, ap_buf)``.

    The Pallas kernel serves the deflating combos; the plain-CG direction
    update (``w is None``) is two-operand elementwise work that XLA
    already fuses optimally, so it lowers to the chunked form everywhere.
    """
    impl = _resolve(
        impl, "fused_deflate_direction", r, p, w, ap, p_buf, ap_buf
    )
    if impl in ("pallas", "interpret") and w is not None:
        return fused_deflate_direction_pallas(
            r, p, beta, w, mu, ap, idx, p_buf, ap_buf,
            block=block, interpret=(impl == "interpret"),
        )
    if impl == "reference":
        return ref.fused_deflate_direction(
            r, p, beta, w, mu, ap, idx, p_buf, ap_buf
        )
    if impl in ("chunked", "pallas", "interpret"):
        return fused_deflate_direction_chunked(
            r, p, beta, w, mu, ap, idx, p_buf, ap_buf
        )
    raise ValueError(f"unknown impl={impl!r}")


def lsmr_update(
    x: jnp.ndarray,
    hbar: jnp.ndarray,
    h: jnp.ndarray,
    v: jnp.ndarray,
    c0,
    c1,
    c2,
    *,
    impl: str = "auto",
    block: int = 4096,
):
    """``(x + c1·(h − c0·hbar), h − c0·hbar, v − c2·h)`` in one pass.

    The LSMR iteration's three coupled vector recurrences (see
    ``ref.lsmr_update`` for the semantic definition) fused into a single
    sweep over ``x, hbar, h, v`` — the least-squares analogue of
    :func:`fused_cg_update`.  The rotation scalars ``c0, c1, c2`` are the
    pre-reduced Givens quantities (O(1) host-free scalars).
    """
    impl = _resolve(impl, "lsmr_update", x, hbar, h, v)
    if impl in ("pallas", "interpret"):
        return lsmr_update_pallas(
            x, hbar, h, v, c0, c1, c2,
            block=block, interpret=(impl == "interpret"),
        )
    if impl == "reference":
        return ref.lsmr_update(x, hbar, h, v, c0, c1, c2)
    if impl == "chunked":
        return lsmr_update_chunked(x, hbar, h, v, c0, c1, c2)
    raise ValueError(f"unknown impl={impl!r}")


def self_gram(
    s: jnp.ndarray,
    *,
    impl: str = "auto",
    block: int = 8192,
) -> jnp.ndarray:
    """``S Sᵀ`` for a stacked flat basis ``S`` of shape ``(m, n)``.

    The harmonic-Ritz extraction stacks ``S = [Z; AZ]`` and reads its
    ``G``/``F`` gram blocks out of the quadrants of this one tall-skinny
    GEMM (one pass over the basis data).  Accumulates in f32 on the TPU
    kernel and in the acc dtype (f64-preserving) elsewhere.
    """
    impl = _resolve(impl, "self_gram", s)
    if impl in ("pallas", "interpret"):
        return self_gram_pallas(
            s, block=min(block, 2048), interpret=(impl == "interpret")
        )
    if impl == "reference":
        return ref.self_gram(s)
    if impl == "chunked":
        return self_gram_chunked(s, block)
    raise ValueError(f"unknown impl={impl!r}")


def recombine_blocks(
    s: jnp.ndarray,
    u: jnp.ndarray,
    *,
    impl: str = "auto",
    block: int = 8192,
) -> jnp.ndarray:
    """``[uᵀ·S_top; uᵀ·S_bot]`` — the stacked two-block recombination GEMM.

    ``s`` stacks two row-bases ``S = [Z; AZ]`` of shape ``(2m, n)``;
    ``u`` is the ``(m, k)`` recombination matrix from the extraction
    eigenproblem.  The result ``(2k, n)`` holds the next recycled basis
    ``W' = uᵀZ`` and its operator products ``AW' = uᵀAZ``, rebuilt from
    already-stored quantities in ONE pass over the basis data — the
    paper's zero-extra-matvec refresh (``core/strategies.py``).
    """
    impl = _resolve(impl, "recombine_blocks", s)
    if impl in ("pallas", "interpret"):
        return recombine_blocks_pallas(
            s, u, block=min(block, 2048), interpret=(impl == "interpret")
        )
    if impl == "reference":
        return ref.recombine_blocks(s, u)
    if impl == "chunked":
        return recombine_blocks_chunked(s, u, block)
    raise ValueError(f"unknown impl={impl!r}")


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention(
    q: jnp.ndarray,  # (b, h, sq, dh)
    k: jnp.ndarray,  # (b, hkv, sk, dh)
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset: int = 0,
    impl: str = "auto",
    block_q: int = 512,
    block_k: int = 1024,
) -> jnp.ndarray:
    """GQA softmax attention; see ref.mha_attention for semantics."""
    impl = _resolve(impl, "attention", q, k, v)
    if impl in ("pallas", "interpret"):
        return flash_attention_pallas(
            q, k, v,
            causal=causal, scale=scale, q_offset=q_offset,
            block_q=min(block_q, 128), block_k=min(block_k, 128),
            interpret=(impl == "interpret"),
        )
    if impl == "reference":
        return ref.mha_attention(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset
        )
    if impl == "chunked":
        return _attention_chunked(
            q, k, v,
            causal=causal, scale=scale, q_offset=q_offset,
            block_q=block_q, block_k=block_k,
        )
    raise ValueError(f"unknown impl={impl!r}")


def _attention_chunked(
    q, k, v, *, causal, scale, q_offset, block_q, block_k
):
    """Double-scan online-softmax attention: O(bq·bk) score memory.

    Outer scan over query blocks, inner scan over KV blocks with the
    flash-attention (m, l, acc) carry — the pure-jnp mirror of the Pallas
    kernel, compilable on CPU/GPU/TPU and linear-memory at 32k/512k.
    """
    b, h, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    group = h // hkv
    scale = dh**-0.5 if scale is None else scale

    bq = min(block_q, sq)
    bk = min(block_k, sk)
    sq_p = ((sq + bq - 1) // bq) * bq
    sk_p = ((sk + bk - 1) // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    kb = kp.reshape(b, hkv, sk_p // bk, bk, dh)
    vb = vp.reshape(b, hkv, sk_p // bk, bk, dh)

    def q_block(carry, inputs):
        qi, iq = inputs  # (b, h, bq, dh), block index

        def kv_block(state, kv_in):
            m_prev, l_prev, acc = state
            kj, vj, jk = kv_in  # (b, hkv, bk, dh), idx
            kjh = jnp.repeat(kj, group, axis=1)
            vjh = jnp.repeat(vj, group, axis=1)
            s = (
                jnp.einsum("bhqd,bhkd->bhqk", qi, kjh).astype(jnp.float32)
                * scale
            )
            kpos = jk * bk + jnp.arange(bk)[None, :]
            qpos = q_offset + iq * bq + jnp.arange(bq)[:, None]
            mask = kpos < sk
            if causal:
                mask = mask & (kpos <= qpos)
            s = jnp.where(mask[None, None], s, _NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vj.dtype), vjh
            ).astype(jnp.float32)
            return (m_new, l_new, acc), None

        init = (
            jnp.full((b, h, bq, 1), _NEG_INF, jnp.float32),
            jnp.zeros((b, h, bq, 1), jnp.float32),
            jnp.zeros((b, h, bq, dh), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            kv_block,
            init,
            (
                kb.transpose(2, 0, 1, 3, 4),
                vb.transpose(2, 0, 1, 3, 4),
                jnp.arange(sk_p // bk),
            ),
        )
        l = jnp.where(l == 0.0, 1.0, l)
        return carry, (acc / l).astype(q.dtype)

    _, ys = jax.lax.scan(
        q_block,
        None,
        (
            qp.reshape(b, h, sq_p // bq, bq, dh).transpose(2, 0, 1, 3, 4),
            jnp.arange(sq_p // bq),
        ),
    )
    out = ys.transpose(1, 2, 0, 3, 4).reshape(b, h, sq_p, dh)
    return out[:, :, :sq]


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------


def ssd(
    x: jnp.ndarray,  # (b, l, h, p)
    dt: jnp.ndarray,  # (b, l, h)
    a: jnp.ndarray,  # (h,)
    bmat: jnp.ndarray,  # (b, l, g, n)
    cmat: jnp.ndarray,  # (b, l, g, n)
    d: Optional[jnp.ndarray] = None,
    *,
    impl: str = "auto",
    chunk: int = 128,
    initial_state: Optional[jnp.ndarray] = None,  # (b, h, p, n)
    return_state: bool = False,
):
    """SSD scan; optionally seeded with / returning the (b,h,p,n) state —
    the prefill path (chunked scan + final state handoff to decode)."""
    impl = _resolve(impl, "ssd", x, bmat, cmat)
    if impl in ("pallas", "interpret") and not return_state and initial_state is None:
        return ssd_scan_pallas(
            x, dt, a, bmat, cmat, d, chunk=chunk,
            interpret=(impl == "interpret"),
        )
    if impl == "reference" and not return_state and initial_state is None:
        return ref.ssd_reference(x, dt, a, bmat, cmat, d)
    if impl in ("chunked", "pallas", "interpret", "reference"):
        return _ssd_chunked(
            x, dt, a, bmat, cmat, d, chunk,
            initial_state=initial_state, return_state=return_state,
        )
    raise ValueError(f"unknown impl={impl!r}")


def _ssd_chunked(x, dt, a, bmat, cmat, d, chunk, *,
                 initial_state=None, return_state=False):
    """Pure-jnp chunked SSD — same blocking as the Pallas kernel, with the
    inter-chunk state carried by lax.scan.  O(l·c) score memory."""
    b, l, h, p = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    hpg = h // g

    c = min(chunk, l)
    l_p = ((l + c - 1) // c) * c
    xp = jnp.pad(x, ((0, 0), (0, l_p - l), (0, 0), (0, 0)))
    dtp = jnp.pad(dt, ((0, 0), (0, l_p - l), (0, 0)))
    bp = jnp.pad(bmat, ((0, 0), (0, l_p - l), (0, 0), (0, 0)))
    cp = jnp.pad(cmat, ((0, 0), (0, l_p - l), (0, 0), (0, 0)))

    nc = l_p // c
    # (nc, b, c, h, p) etc.
    xc = xp.reshape(b, nc, c, h, p).transpose(1, 0, 2, 3, 4)
    dtc = dtp.reshape(b, nc, c, h).transpose(1, 0, 2, 3)
    bc = bp.reshape(b, nc, c, g, n).transpose(1, 0, 2, 3, 4)
    cc = cp.reshape(b, nc, c, g, n).transpose(1, 0, 2, 3, 4)

    def chunk_step(hstate, inputs):
        xi, dti, bi, ci = inputs
        adt = dti * a[None, None, :]  # (b, c, h)
        cs = jnp.cumsum(adt, axis=1)  # (b, c, h)
        cs_tot = cs[:, -1:, :]
        bih = jnp.repeat(bi, hpg, axis=2)  # (b, c, h, n)
        cih = jnp.repeat(ci, hpg, axis=2)

        gmat = jnp.einsum("bthn,bshn->bhts", cih, bih)  # (b, h, c, c)
        delta = cs[:, :, None, :] - cs[:, None, :, :]  # (b, t, s, h)
        tri = jnp.tril(jnp.ones((c, c), bool))
        m = jnp.where(
            tri[None, :, :, None],
            jnp.exp(jnp.where(tri[None, :, :, None], delta, 0.0))
            * dti[:, None, :, :],
            0.0,
        ).transpose(0, 3, 1, 2)  # (b, h, t, s)
        y = jnp.einsum("bhts,bshp->bthp", m * gmat, xi)

        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "bthn,bhpn->bhtp", cih, hstate
        ).transpose(0, 2, 1, 3)

        bw = bih * (jnp.exp(cs_tot - cs) * dti)[..., None]  # (b, c, h, n)
        hnew = jnp.exp(cs_tot[:, 0, :])[:, :, None, None] * hstate + (
            jnp.einsum("bshp,bshn->bhpn", xi, bw)
        )
        return hnew, y

    h0 = (
        initial_state.astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((b, h, p, n), jnp.float32)
    )
    h_final, yc = jax.lax.scan(chunk_step, h0, (xc, dtc, bc, cc))
    y = yc.transpose(1, 0, 2, 3, 4).reshape(b, l_p, h, p)[:, :l]
    y = y.astype(x.dtype)
    if d is not None:
        y = y + x * d[None, None, :, None]
    if return_state:
        # NOTE: with l_p > l the padded tail has dt=0 ⇒ identity updates,
        # so h_final is exact for the true length.
        return y, h_final
    return y


def ssd_decode_step(
    hstate: jnp.ndarray,  # (b, h, p, n)
    x_t: jnp.ndarray,  # (b, h, p)
    dt_t: jnp.ndarray,  # (b, h)
    a: jnp.ndarray,  # (h,)
    b_t: jnp.ndarray,  # (b, g, n)
    c_t: jnp.ndarray,  # (b, g, n)
    d: Optional[jnp.ndarray] = None,
):
    """One SSD decode step: O(h·p·n), the SSM analogue of a KV-cache read.

    Returns ``(new_state, y_t)``.
    """
    h = x_t.shape[1]
    hpg = h // b_t.shape[1]
    decay = jnp.exp(a[None, :] * dt_t)  # (b, h)
    bth = jnp.repeat(b_t, hpg, axis=1)
    cth = jnp.repeat(c_t, hpg, axis=1)
    upd = jnp.einsum("bhp,bhn->bhpn", x_t * dt_t[..., None], bth)
    new = hstate * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", new, cth)
    if d is not None:
        y = y + x_t * d[None, :, None]
    return new, y.astype(x_t.dtype)
