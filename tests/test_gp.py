"""GP-classification substrate tests — the paper's workload in miniature.

Validates the three Table-1 columns agree (Cholesky is exact; CG/def-CG
track it to solver tolerance), that def-CG recycling reduces iterations
across the Newton sequence (the paper's headline claim), and that the
inducing-point baseline shows the cost/precision gap of Fig. 4.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SolveSpec
from repro.data import make_infinite_digits
from repro.gp import RBFKernel, laplace_gpc, subset_gpc


N = 220
KERNEL = RBFKernel(theta=3.0, lengthscale=3.0)


@pytest.fixture(scope="module")
def digits():
    x, y = make_infinite_digits(N, seed=7)
    return jnp.asarray(x, jnp.float64), jnp.asarray(y, jnp.float64)


@pytest.fixture(scope="module")
def solutions(digits):
    x, y = digits
    chol = laplace_gpc(x, y, KERNEL, spec=None, newton_tol=1e-2)
    cg_r = laplace_gpc(
        x, y, KERNEL, spec=SolveSpec(method="cg", tol=1e-6), newton_tol=1e-2
    )
    def_r = laplace_gpc(
        x, y, KERNEL,
        spec=SolveSpec(method="defcg", k=8, ell=12, tol=1e-6),
        newton_tol=1e-2,
    )
    return chol, cg_r, def_r


class TestLaplaceGPC:
    def test_spec_is_required(self, digits):
        """No default solver: a fit names its SolveSpec, or None for the
        dense Cholesky solve."""
        x, y = digits
        with pytest.raises(TypeError, match="spec"):
            laplace_gpc(x, y, KERNEL, newton_tol=1e-2)

    def test_newton_monotone(self, solutions):
        chol, _, _ = solutions
        psi = chol.trace.psi
        assert all(b >= a - 1e-6 for a, b in zip(psi, psi[1:]))

    def test_iterative_matches_cholesky(self, solutions):
        chol, cg_r, def_r = solutions
        # Table-1 agreement: same final log p(y|f) to solver tolerance.
        assert abs(cg_r.logp - chol.logp) / abs(chol.logp) < 1e-4
        assert abs(def_r.logp - chol.logp) / abs(chol.logp) < 1e-4
        np.testing.assert_allclose(
            np.asarray(def_r.f), np.asarray(chol.f), rtol=0, atol=5e-3
        )

    def test_defcg_saves_iterations(self, solutions):
        # Paper Fig 2: after the first system, def-CG uses fewer CG
        # iterations than plain CG.
        _, cg_r, def_r = solutions
        cg_total = sum(cg_r.trace.solver_iterations[1:])
        def_total = sum(def_r.trace.solver_iterations[1:])
        assert def_total < cg_total

    def test_training_accuracy(self, digits, solutions):
        x, y = digits
        chol, _, _ = solutions
        acc = float(jnp.mean((jnp.sign(chol.f) == y)))
        assert acc > 0.95

    def test_classes_separate(self, digits, solutions):
        x, y = digits
        chol, _, _ = solutions
        mean_pos = float(jnp.mean(chol.f[y > 0]))
        mean_neg = float(jnp.mean(chol.f[y < 0]))
        assert mean_pos > 0 > mean_neg


class TestInducingBaseline:
    def test_subset_worse_than_full(self, digits, solutions):
        # Fig 4: a small subset is fast but leaves a persistent logp gap.
        x, y = digits
        chol, _, _ = solutions
        import jax

        sub = subset_gpc(x, y, KERNEL, m=N // 8, key=jax.random.PRNGKey(0))
        rel_err = abs(sub.logp_full - chol.logp) / abs(chol.logp)
        assert rel_err > 1e-4  # finite, uncorrected approximation error
        # and bigger subsets should shrink the gap
        sub2 = subset_gpc(x, y, KERNEL, m=N // 2, key=jax.random.PRNGKey(0))
        rel_err2 = abs(sub2.logp_full - chol.logp) / abs(chol.logp)
        assert rel_err2 < rel_err
