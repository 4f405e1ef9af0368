"""Per-kernel validation: Pallas (interpret=True) and chunked impls vs the
pure-jnp oracles in kernels/ref.py, swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis.trace_audit import iter_eqns
from repro.kernels import ops, ref
from repro.kernels.rbf_matvec import R_VPU

F32 = jnp.float32
BF16 = jnp.bfloat16


def _tol(dtype, scale=1.0):
    return dict(
        rtol=scale * (2e-2 if dtype == BF16 else 2e-4),
        atol=scale * (5e-2 if dtype == BF16 else 5e-4),
    )


# ---------------------------------------------------------------------------
# rbf_matvec
# ---------------------------------------------------------------------------


def _assert_matches_oracle(got, x, v, theta, ls):
    """``got`` against ``K(X, X) @ v`` in float64, relative to its largest
    entry: the kernels' f32 distance expansion is the thing under test."""
    want = np.asarray(ref.rbf_matvec(
        x.astype(jnp.float64), v.astype(jnp.float64), theta, ls))
    scale = max(1.0, np.abs(want).max())
    np.testing.assert_allclose(np.asarray(got) / scale, want / scale,
                               **_tol(F32))


class TestRBFMatvec:
    @pytest.mark.parametrize("impl", ["interpret", "chunked"])
    @pytest.mark.parametrize(
        "n,d,r",
        [(64, 3, 1), (200, 17, 4), (257, 784, 8), (8, 1, 1),
         (130, 7, R_VPU), (150, 5, R_VPU + 1)],
    )
    def test_matches_oracle(self, impl, n, d, r):
        rng = np.random.default_rng(n + d + r)
        x = jnp.asarray(rng.standard_normal((n, d)), F32)
        v = jnp.asarray(rng.standard_normal((n, r)), F32)
        got = ops.rbf_matvec(x, v, 1.3, 2.1, impl=impl, block=64)
        _assert_matches_oracle(got, x, v, 1.3, 2.1)

    @pytest.mark.parametrize("r", [1, 3, R_VPU])
    @pytest.mark.parametrize("nb", [1, 2, 3, 4, 5])
    def test_symmetric_band_matches_oracle(self, nb, r):
        """The square pass at r ≤ R_VPU visits each unordered pair of
        row blocks once: nb odd, nb even with its t = nb/2 column taken
        by half the rows, and n not a multiple of the block."""
        n = 32 * nb - 5
        rng = np.random.default_rng(10 * nb + r)
        x = jnp.asarray(rng.standard_normal((n, 3)), F32)
        v = jnp.asarray(rng.standard_normal((n, r)), F32)
        got = ops.rbf_matvec(x, v, 1.2, 1.9, impl="interpret", block=32)
        assert got.shape == (n, r)
        _assert_matches_oracle(got, x, v, 1.2, 1.9)

    def test_symmetric_band_applies_each_tile_both_ways(self):
        """``(K e_b)_a == (K e_a)_b`` bit for bit: one tile gives both,
        in the same block, across blocks, and across the t = nb/2 pairs
        (blocks 0 and 2, 1 and 3 of four)."""
        rng = np.random.default_rng(17)
        x = jnp.asarray(rng.standard_normal((123, 3)), F32)
        idx = np.array([3, 20, 40, 70, 100, 122])
        e = jnp.zeros((123, len(idx)), F32).at[idx, np.arange(len(idx))].set(1)
        y = np.asarray(ops.rbf_matvec(x, e, 1.0, 1.6, impl="interpret",
                                      block=32))
        block = y[idx]  # block[p, q] = K[idx[p], idx[q]]
        np.testing.assert_array_equal(block, block.T)
        assert np.all(block[~np.eye(len(idx), dtype=bool)] > 0)

    def test_single_vector_shape(self):
        x = jnp.ones((10, 2), F32)
        v = jnp.ones((10,), F32)
        y = ops.rbf_matvec(x, v, 1.0, 1.0, impl="chunked")
        assert y.shape == (10,)

    @settings(max_examples=12, deadline=None)
    @given(
        n=st.integers(4, 150),
        d=st.integers(1, 40),
        r=st.integers(1, 5),
        block=st.sampled_from([16, 32, 128]),
        seed=st.integers(0, 2**16),
    )
    def test_property_chunked_any_shape(self, n, d, r, block, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((n, d)), F32)
        v = jnp.asarray(rng.standard_normal((n, r)), F32)
        want = np.asarray(ref.rbf_matvec(x, v, 0.9, 1.4))
        got = np.asarray(ops.rbf_matvec(x, v, 0.9, 1.4, impl="chunked", block=block))
        scale = max(1.0, np.abs(want).max())
        np.testing.assert_allclose(got / scale, want / scale, **_tol(F32))

    def test_multirhs_equals_stacked_single(self):
        # multi-RHS fused pass (the A·W refresh path) == k single matvecs
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((40, 6)), F32)
        V = jnp.asarray(rng.standard_normal((40, 3)), F32)
        multi = ops.rbf_matvec(x, V, 1.1, 0.8, impl="interpret", block=32)
        singles = jnp.stack(
            [
                ops.rbf_matvec(x, V[:, i], 1.1, 0.8, impl="interpret", block=32)
                for i in range(3)
            ],
            axis=1,
        )
        np.testing.assert_allclose(multi, singles, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("r", [1, R_VPU, R_VPU + 1])
    def test_lane_chunks_match_oracle(self, r):
        # block 256: two 128-lane chunks per tile on the VPU body, two
        # column tiles, and n not a multiple of the block.
        rng = np.random.default_rng(r)
        x = jnp.asarray(rng.standard_normal((300, 9)), F32)
        v = jnp.asarray(rng.standard_normal((300, r)), F32)
        got = ops.rbf_matvec(x, v, 1.3, 2.1, impl="interpret", block=256)
        _assert_matches_oracle(got, x, v, 1.3, 2.1)

    @pytest.mark.parametrize("r", [1, R_VPU, R_VPU + 1])
    def test_vmapped_batch_matches_oracle(self, r):
        # The serve pool step's form: one kernel call over a batch axis,
        # at an odd (3) and an even (4) number of row blocks.
        rng = np.random.default_rng(30 + r)
        for n in (90, 120):
            xb = jnp.asarray(rng.standard_normal((3, n, 4)), F32)
            vb = jnp.asarray(rng.standard_normal((3, n, r)), F32)
            got = jax.vmap(
                lambda x, v: ops.rbf_matvec(x, v, 1.1, 1.7,
                                            impl="interpret", block=32)
            )(xb, vb)
            for x, v, y in zip(xb, vb, got):
                _assert_matches_oracle(y, x, v, 1.1, 1.7)

    @pytest.mark.parametrize("r,dots", [(1, 1), (R_VPU + 1, 2)])
    def test_kv_product_runs_on_the_vpu_for_narrow_v(self, r, dots):
        """At r ≤ R_VPU the kernel body holds one contraction, the
        distance GEMM at HIGHEST; wider V adds the MXU ``Kb @ V``."""
        x, v = jnp.ones((40, 3), F32), jnp.ones((40, r), F32)
        jaxpr = jax.make_jaxpr(
            lambda x, v: ops.rbf_matvec(x, v, 1.0, 1.0, impl="interpret",
                                        block=32)
        )(x, v)
        calls = [e for e in iter_eqns(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        assert "rbf_gram_matvec" in str(calls[0].params["name"])
        found = [e for e in iter_eqns(calls[0].params["jaxpr"])
                 if e.primitive.name == "dot_general"]
        assert len(found) == dots
        assert all(e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
                   for e in found)
        cross = found[0]
        assert [a.aval.shape for a in cross.invars] == [(32, 128), (32, 128)]


class TestRBFMatvecRect:
    """Rectangular Gram matvec ``K(X_rows, X_cols) @ v`` — the sharded
    operator's per-shard primitive (DESIGN.md §5)."""

    @pytest.mark.parametrize("impl", ["interpret", "chunked"])
    @pytest.mark.parametrize(
        "m,n,d,r",
        [(48, 96, 3, 1), (33, 200, 11, 4), (70, 150, 6, R_VPU),
         (40, 130, 5, R_VPU + 1)],
    )
    def test_matches_oracle(self, impl, m, n, d, r):
        rng = np.random.default_rng(m + n + d)
        xr = jnp.asarray(rng.standard_normal((m, d)), F32)
        xc = jnp.asarray(rng.standard_normal((n, d)), F32)
        v = jnp.asarray(rng.standard_normal((n, r)), F32)
        theta, ls = 1.3, 2.1
        want = np.asarray(
            ref.rbf_matvec_rect(
                xr.astype(jnp.float64),
                xc.astype(jnp.float64),
                v.astype(jnp.float64),
                theta,
                ls,
            )
        )
        got = np.asarray(
            ops.rbf_matvec_rect(xr, xc, v, theta, ls, impl=impl, block=32)
        )
        assert got.shape == (m, r)
        scale = max(1.0, np.abs(want).max())
        np.testing.assert_allclose(got / scale, want / scale, **_tol(F32))

    def test_square_case_equals_rbf_matvec(self):
        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.standard_normal((70, 4)), F32)
        v = jnp.asarray(rng.standard_normal((70,)), F32)
        sq = ops.rbf_matvec(x, v, 0.9, 1.4, impl="chunked", block=32)
        rect = ops.rbf_matvec_rect(x, x, v, 0.9, 1.4, impl="chunked", block=32)
        assert rect.shape == (70,)
        np.testing.assert_allclose(rect, sq, rtol=1e-5, atol=1e-5)

    def test_row_blocks_concatenate_to_full_matvec(self):
        # The sharding identity the mesh operator relies on: every shard
        # computes K(X_local, X_full) @ v and the concatenation of the
        # row-block outputs IS the full square matvec.
        rng = np.random.default_rng(11)
        n, d, shards = 96, 5, 4
        x = jnp.asarray(rng.standard_normal((n, d)), F32)
        v = jnp.asarray(rng.standard_normal((n,)), F32)
        full = ops.rbf_matvec(x, v, 1.1, 0.8, impl="chunked", block=16)
        blocks = [
            ops.rbf_matvec_rect(
                x[i * (n // shards):(i + 1) * (n // shards)],
                x, v, 1.1, 0.8, impl="chunked", block=16,
            )
            for i in range(shards)
        ]
        np.testing.assert_allclose(
            jnp.concatenate(blocks), full, rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


ATTN_CASES = [
    # b, h, hkv, sq, sk, dh, causal, q_offset
    (2, 4, 2, 64, 64, 32, False, 0),
    (1, 8, 2, 96, 96, 64, True, 0),
    (2, 4, 4, 1, 133, 64, True, 132),   # decode
    (1, 2, 1, 40, 200, 16, False, 0),   # cross-attention shape
    (1, 16, 2, 33, 33, 128, True, 0),   # ragged blocks
]


class TestAttention:
    @pytest.mark.parametrize("impl", ["interpret", "chunked"])
    @pytest.mark.parametrize("case", ATTN_CASES)
    @pytest.mark.parametrize("dtype", [F32, BF16])
    def test_matches_oracle(self, impl, case, dtype):
        b, h, hkv, sq, sk, dh, causal, q_offset = case
        rng = np.random.default_rng(abs(hash(case)) % 2**31)
        q = jnp.asarray(rng.standard_normal((b, h, sq, dh)), dtype)
        k = jnp.asarray(rng.standard_normal((b, hkv, sk, dh)), dtype)
        v = jnp.asarray(rng.standard_normal((b, hkv, sk, dh)), dtype)
        want = np.asarray(
            ref.mha_attention(
                q.astype(F32), k.astype(F32), v.astype(F32),
                causal=causal, q_offset=q_offset,
            )
        )
        got = np.asarray(
            ops.attention(
                q, k, v, causal=causal, q_offset=q_offset,
                impl=impl, block_q=32, block_k=32,
            )
        ).astype(np.float32)
        np.testing.assert_allclose(got, want, **_tol(dtype))

    @settings(max_examples=10, deadline=None)
    @given(
        sq=st.integers(1, 70),
        sk=st.integers(1, 70),
        dh=st.sampled_from([8, 16, 32]),
        causal=st.booleans(),
        seed=st.integers(0, 2**16),
    )
    def test_property_chunked(self, sq, sk, dh, causal, seed):
        if causal and sq > sk:
            sq = sk  # causal requires q positions within the cache
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((1, 2, sq, dh)), F32)
        k = jnp.asarray(rng.standard_normal((1, 2, sk, dh)), F32)
        v = jnp.asarray(rng.standard_normal((1, 2, sk, dh)), F32)
        off = sk - sq if causal else 0
        want = np.asarray(
            ref.mha_attention(q, k, v, causal=causal, q_offset=off)
        )
        got = np.asarray(
            ops.attention(
                q, k, v, causal=causal, q_offset=off,
                impl="chunked", block_q=16, block_k=16,
            )
        )
        np.testing.assert_allclose(got, want, **_tol(F32))


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


SSD_CASES = [
    # b, l, h, p, g, n, chunk
    (1, 64, 2, 16, 1, 16, 32),
    (2, 100, 4, 8, 2, 24, 32),
    (1, 37, 2, 4, 2, 8, 16),     # ragged chunk
    (2, 128, 8, 32, 1, 64, 64),
]


class TestSSD:
    @pytest.mark.parametrize("impl", ["interpret", "chunked"])
    @pytest.mark.parametrize("case", SSD_CASES)
    def test_matches_sequential_oracle(self, impl, case):
        b, l, h, p, g, n, chunk = case
        rng = np.random.default_rng(abs(hash(case)) % 2**31)
        x = jnp.asarray(rng.standard_normal((b, l, h, p)), F32)
        dt = jnp.asarray(rng.uniform(0.01, 0.4, (b, l, h)), F32)
        a = jnp.asarray(-rng.uniform(0.3, 2.0, (h,)), F32)
        B = jnp.asarray(rng.standard_normal((b, l, g, n)), F32)
        C = jnp.asarray(rng.standard_normal((b, l, g, n)), F32)
        D = jnp.asarray(rng.standard_normal((h,)), F32)
        want = np.asarray(ref.ssd_reference(x, dt, a, B, C, D))
        got = np.asarray(
            ops.ssd(x, dt, a, B, C, D, impl=impl, chunk=chunk)
        )
        scale = max(1.0, np.abs(want).max())
        np.testing.assert_allclose(got / scale, want / scale, **_tol(F32, 2.0))

    def test_decode_step_matches_scan(self):
        """Feeding tokens one-by-one through ssd_decode_step must equal the
        full-sequence scan — the serve-path invariant."""
        b, l, h, p, g, n = 2, 20, 2, 8, 1, 8
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((b, l, h, p)), F32)
        dt = jnp.asarray(rng.uniform(0.05, 0.3, (b, l, h)), F32)
        a = jnp.asarray(-rng.uniform(0.5, 1.5, (h,)), F32)
        B = jnp.asarray(rng.standard_normal((b, l, g, n)), F32)
        C = jnp.asarray(rng.standard_normal((b, l, g, n)), F32)
        full = ref.ssd_reference(x, dt, a, B, C)
        state = jnp.zeros((b, h, p, n), F32)
        outs = []
        for t in range(l):
            state, y = ops.ssd_decode_step(
                state, x[:, t], dt[:, t], a, B[:, t], C[:, t]
            )
            outs.append(y)
        got = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(full), rtol=1e-4, atol=1e-4
        )

    @settings(max_examples=8, deadline=None)
    @given(
        l=st.integers(2, 80),
        chunk=st.sampled_from([8, 16, 64]),
        seed=st.integers(0, 2**16),
    )
    def test_property_chunk_invariance(self, l, chunk, seed):
        """Output must be independent of the chunk size (pure blocking)."""
        rng = np.random.default_rng(seed)
        b, h, p, g, n = 1, 2, 4, 1, 8
        x = jnp.asarray(rng.standard_normal((b, l, h, p)), F32)
        dt = jnp.asarray(rng.uniform(0.01, 0.4, (b, l, h)), F32)
        a = jnp.asarray(-rng.uniform(0.3, 2.0, (h,)), F32)
        B = jnp.asarray(rng.standard_normal((b, l, g, n)), F32)
        C = jnp.asarray(rng.standard_normal((b, l, g, n)), F32)
        y1 = ops.ssd(x, dt, a, B, C, impl="chunked", chunk=chunk)
        y2 = ops.ssd(x, dt, a, B, C, impl="chunked", chunk=2 * chunk)
        np.testing.assert_allclose(
            np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4
        )
