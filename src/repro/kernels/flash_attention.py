"""Flash-attention (online-softmax) Pallas kernel with GQA + causal masking.

TPU adaptation of the memory-efficient attention algorithm: the (sq × sk)
score matrix is never materialized in HBM.  Grid is
``(batch·q_heads, sq/bq, sk/bk)`` with the KV dimension innermost
("arbitrary" semantics); running max ``m``, normalizer ``l`` and the
unnormalized accumulator live in VMEM scratch and persist across KV steps.

Causal handling: KV blocks strictly above the diagonal are skipped with
``pl.when`` (no flops, no VMEM traffic for the masked region beyond the
pipelined fetch), diagonal blocks are masked elementwise.  For decode
(sq == 1 with a long KV cache) the same kernel is used with ``q_offset =
cache_len − 1``.

Block sizes default to MXU/VPU-aligned (128); the wrapper in ops.py pads
sq/sk as needed (padding keys are masked out via −inf logits).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import block_spec, round_up

_NEG_INF = -1e30


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    causal: bool,
    q_offset: int,
    block_q: int,
    block_k: int,
    sk_valid: int,
):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = q_offset + iq * block_q
    k_start = jk * block_k

    # A KV block participates unless (causal and) it lies fully above the
    # diagonal of the *last* query row in this block.
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, dh)
        k = k_ref[0].astype(jnp.float32)  # (bk, dh)
        v = v_ref[0].astype(jnp.float32)  # (bk, dh)

        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (bq, bk)

        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < sk_valid  # padding keys
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=1, keepdims=True)

        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(jk == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "q_offset", "block_q", "block_k", "interpret"
    ),
)
def flash_attention_pallas(
    q: jnp.ndarray,  # (b, h, sq, dh)
    k: jnp.ndarray,  # (b, hkv, sk, dh)
    v: jnp.ndarray,  # (b, hkv, sk, dh)
    *,
    causal: bool = False,
    scale: float | None = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    group = h // hkv
    scale = dh**-0.5 if scale is None else scale

    bq = min(block_q, round_up(sq, 8))
    bk = min(block_k, round_up(sk, 8))
    sq_p = round_up(sq, bq)
    sk_p = round_up(sk, bk)
    dh_p = round_up(dh, 128)

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, dh_p - dh)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, dh_p - dh)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, dh_p - dh)))

    qp = qp.reshape(b * h, sq_p, dh_p)
    kp = kp.reshape(b * hkv, sk_p, dh_p)
    vp = vp.reshape(b * hkv, sk_p, dh_p)

    grid = (b * h, sq_p // bq, sk_p // bk)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        q_offset=q_offset,
        block_q=bq,
        block_k=bk,
        sk_valid=sk,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            block_spec((1, bq, dh_p), lambda bh, i, j: (bh, i, 0)),
            block_spec(
                (1, bk, dh_p), lambda bh, i, j, g=group: (bh // g, j, 0)
            ),
            block_spec(
                (1, bk, dh_p), lambda bh, i, j, g=group: (bh // g, j, 0)
            ),
        ],
        out_specs=block_spec((1, bq, dh_p), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, dh_p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh_p), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_gqa",
    )(qp, kp, vp)
    return out.reshape(b, h, sq_p, dh_p)[:, :, :sq, :dh]

