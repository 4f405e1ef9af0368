"""Regression tests for the rank-revealing harmonic-Ritz extraction and
the prefill/forward consistency invariant (EXPERIMENTS §Paper-validation
numerics finding + §Perf cell C)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import SolveSpec, cg, defcg, from_matrix, harmonic_ritz, solve
from repro.core import pytree as pt


class TestRitzNumerics:
    @settings(max_examples=10, deadline=None)
    @given(
        n=st.integers(64, 200),
        k=st.integers(2, 8),
        span=st.floats(2.0, 5.0),
        seed=st.integers(0, 2**16),
    )
    def test_theta_positive_and_outliers_found(self, n, k, span, seed):
        """Extraction from a long recording window must return strictly
        positive Ritz values approximating the top eigenvalues — the
        mixed-column-scale rounding regression (see core/recycle.py)."""
        rng = np.random.default_rng(seed)
        q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        eigs = np.concatenate(
            [np.linspace(1.0, 10.0, n - k), np.logspace(3, 3 + span, k)]
        )
        A = jnp.asarray((q * eigs) @ q.T)
        b = jnp.asarray(rng.standard_normal(n))

        res = defcg(from_matrix(A), b, tol=1e-10, maxiter=20 * n, ell=3 * k)
        m = int(res.recycle.stored)
        Z = pt.basis_slice(res.recycle.P, m)
        AZ = pt.basis_slice(res.recycle.AP, m)
        W, AW, theta = harmonic_ritz(Z, AZ, k)
        th = np.sort(np.asarray(theta))[::-1]
        assert (th > 0).all()
        # top Ritz value ≈ top eigenvalue
        np.testing.assert_allclose(th[0], eigs[-1], rtol=0.05)

    def test_recycled_solve_meets_kappa_eff_bound(self):
        """After the numerics fix the *recycled* (Ritz-W) solve obeys the
        κ_eff iteration bound, not just the exact-eigenvector one."""
        rng = np.random.default_rng(3)
        n, k = 256, 8
        q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        eigs = np.concatenate(
            [np.linspace(1.0, 10.0, n - k), np.logspace(3, 5, k)]
        )
        A = jnp.asarray((q * eigs) @ q.T)
        spec = SolveSpec(k=k, ell=3 * k, tol=1e-5, maxiter=10000)
        first = solve(from_matrix(A), jnp.asarray(rng.standard_normal(n)), spec)
        b2 = jnp.asarray(rng.standard_normal(n))
        rec = solve(from_matrix(A), b2, spec, first.state)
        fresh = cg(from_matrix(A), b2, tol=1e-5, maxiter=10000)
        bound = 1.5 * 0.5 * np.sqrt(10.0) * np.log(2.0 / 1e-5)
        assert int(rec.info.iterations) <= bound
        assert int(rec.info.iterations) < 0.5 * int(fresh.info.iterations)
        np.testing.assert_allclose(
            np.asarray(A @ rec.x), np.asarray(b2),
            atol=1e-4 * float(jnp.linalg.norm(b2)),
        )


class TestPrefillConsistency:
    @pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mamba2-1.3b"])
    def test_prefill_then_decode_matches_forward(self, arch):
        """prefill(prompt) + decode(next) must equal the full forward on
        [prompt; next] — the §Perf cell-C fix must stay semantics-exact."""
        from repro import models
        from repro.configs import get_smoke_config
        from repro.models.layers import lm_head_weights

        cfg = get_smoke_config(arch)
        b, s = 2, 24
        params = models.init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size
        )

        hidden, _ = models.forward_hidden(params, {"tokens": tokens}, cfg)
        full_logits = hidden @ lm_head_weights(params["embed"], cfg)

        state = models.init_decode_state(cfg, b, max_len=s)
        state, pre_logits = models.prefill(
            params, {"tokens": tokens[:, : s - 1]}, state, cfg
        )
        # prefill's last-position logits == forward logits at position s-2
        np.testing.assert_allclose(
            np.asarray(pre_logits[:, 0], np.float32),
            np.asarray(full_logits[:, s - 2], np.float32),
            rtol=2e-2, atol=2e-2,
        )
        dec_logits, state = models.decode_step(
            params, tokens[:, s - 1 :], state, cfg
        )
        np.testing.assert_allclose(
            np.asarray(dec_logits[:, 0], np.float32),
            np.asarray(full_logits[:, s - 1], np.float32),
            rtol=2e-2, atol=2e-2,
        )
