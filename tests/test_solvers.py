"""Unit + property tests for repro.core solvers (CG / def-CG / recycling).

These encode the paper's mathematical claims as executable checks:
  * def-CG keeps residuals orthogonal to the deflation space (Eq. 5);
  * deflating the top-k eigenvectors yields the κ_eff = λ_n/λ_{k+1}
    convergence improvement (§2.1) — checked as an iteration-count drop;
  * harmonic Ritz values approximate extremal eigenvalues (§2.3);
  * recycling across a drifting sequence of systems reduces iterations
    (the paper's central empirical claim, Table 1 / Fig 2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    RecycleState,
    SolveSpec,
    cg,
    cholesky_solve,
    defcg,
    from_matrix,
    harmonic_ritz,
    materialize,
    random_orthonormal_basis,
    randomized_nystrom,
    nystrom_preconditioner,
    solve,
)
from repro.core import pytree as pt
from tests.conftest import make_spd


def _solve_setup(n=64, cond=1e4, seed=0):
    rng = np.random.default_rng(seed)
    A, eigs, q = make_spd(n, cond, rng)
    b = rng.standard_normal(n)
    return jnp.asarray(A), jnp.asarray(b), eigs, q


class TestCG:
    def test_converges_to_direct_solution(self):
        A, b, _, _ = _solve_setup()
        res = cg(from_matrix(A), b, tol=1e-12, maxiter=500)
        x_direct = jnp.linalg.solve(A, b)
        np.testing.assert_allclose(res.x, x_direct, rtol=1e-8, atol=1e-8)
        assert bool(res.info.converged)

    def test_exact_in_n_iterations(self):
        # Krylov finite-termination: CG reaches machine precision in ≤ n its.
        A, b, _, _ = _solve_setup(n=24, cond=1e2)
        res = cg(from_matrix(A), b, tol=1e-13, maxiter=200)
        assert int(res.info.iterations) <= 40  # n + numerics slack

    def test_clustered_spectrum_converges_fast(self):
        # k distinct eigenvalues → ≤ k iterations (exact arithmetic).
        rng = np.random.default_rng(1)
        q, _ = np.linalg.qr(rng.standard_normal((50, 50)))
        eigs = np.repeat([1.0, 10.0, 100.0], [20, 20, 10])
        A = jnp.asarray((q * eigs) @ q.T)
        b = jnp.asarray(rng.standard_normal(50))
        res = cg(from_matrix(A), b, tol=1e-10, maxiter=100)
        assert int(res.info.iterations) <= 6

    def test_pytree_vectors(self):
        # CG over a dict-structured unknown (the LM/GGN use case).
        rng = np.random.default_rng(2)
        A, _, _ = make_spd(12, 50.0, rng)
        A = jnp.asarray(A)

        def matvec(tree):
            flat = jnp.concatenate([tree["a"].ravel(), tree["b"].ravel()])
            out = A @ flat
            return {"a": out[:8].reshape(2, 4), "b": out[8:]}

        b_tree = {
            "a": jnp.asarray(rng.standard_normal((2, 4))),
            "b": jnp.asarray(rng.standard_normal(4)),
        }
        res = cg(matvec, b_tree, tol=1e-12, maxiter=100)
        flat_x = jnp.concatenate([res.x["a"].ravel(), res.x["b"].ravel()])
        flat_b = jnp.concatenate([b_tree["a"].ravel(), b_tree["b"].ravel()])
        np.testing.assert_allclose(A @ flat_x, flat_b, rtol=1e-8, atol=1e-8)

    def test_jacobi_pcg_reduces_iterations(self):
        # Badly row-scaled SPD: Jacobi preconditioning must win.
        rng = np.random.default_rng(3)
        n = 80
        A0, _, _ = make_spd(n, 10.0, rng)
        s = np.logspace(0, 3, n)
        A = jnp.asarray(A0 * np.outer(s, s))
        b = jnp.asarray(rng.standard_normal(n))
        plain = cg(from_matrix(A), b, tol=1e-10, maxiter=2000)
        from repro.core import jacobi

        pre = cg(
            from_matrix(A), b, tol=1e-10, maxiter=2000, M=jacobi(jnp.diag(A))
        )
        assert int(pre.info.iterations) < int(plain.info.iterations)
        x_direct = jnp.linalg.solve(A, b)
        np.testing.assert_allclose(pre.x, x_direct, rtol=1e-6, atol=1e-6)


class TestDefCG:
    def test_matches_cg_without_deflation(self):
        A, b, _, _ = _solve_setup()
        r1 = cg(from_matrix(A), b, tol=1e-10, maxiter=500)
        r2 = defcg(from_matrix(A), b, tol=1e-10, maxiter=500, ell=0)
        np.testing.assert_allclose(r1.x, r2.x, rtol=1e-9, atol=1e-10)
        assert int(r1.info.iterations) == int(r2.info.iterations)

    def test_residual_orthogonal_to_W(self):
        # Eq. (5): every def-CG residual ⟂ span{W}.  Check the final one.
        A, b, eigs, q = _solve_setup(n=48, cond=1e5)
        W = pt.basis_from_vectors([jnp.asarray(q[:, -i]) for i in (1, 2, 3)])
        res = defcg(from_matrix(A), b, W=W, tol=1e-8, maxiter=200, ell=0)
        r = b - A @ res.x
        np.testing.assert_allclose(
            np.asarray(pt.basis_dot(W, r)), 0.0, atol=1e-6 * float(pt.tree_norm(b))
        )

    def test_exact_topk_deflation_hits_kappa_eff(self):
        # §2.1: deflating the top-k eigenvectors → κ_eff = λ_{n-k}/λ_1.
        # CG iteration count scales ~ sqrt(κ); expect a clear drop.
        n, k = 96, 8
        rng = np.random.default_rng(7)
        q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        eigs = np.concatenate([np.linspace(1.0, 10.0, n - k), np.logspace(3, 5, k)])
        A = jnp.asarray((q * eigs) @ q.T)
        b = jnp.asarray(rng.standard_normal(n))

        plain = cg(from_matrix(A), b, tol=1e-10, maxiter=3000)
        W = pt.basis_from_vectors([jnp.asarray(q[:, n - k + i]) for i in range(k)])
        defl = defcg(from_matrix(A), b, W=W, tol=1e-10, maxiter=3000)

        x_direct = jnp.linalg.solve(A, b)
        np.testing.assert_allclose(defl.x, x_direct, rtol=1e-5, atol=1e-6)
        # κ drops 1e5/1 → 10; iterations should drop by at least 2x.
        assert int(defl.info.iterations) * 2 < int(plain.info.iterations)

    def test_warm_start_projection(self):
        # Line 3 of Alg 1: x0 correction zeroes Wᵀr0 (checked indirectly:
        # solving the same system twice with recycling is near-free).
        A, b, _, _ = _solve_setup(n=64, cond=1e4)
        spec = SolveSpec(k=8, ell=16, tol=1e-10, maxiter=1000)
        first = solve(from_matrix(A), b, spec)
        second = solve(from_matrix(A), b, spec, first.state, x0=first.x)
        assert int(second.info.iterations) <= 2

    def test_seeded_basis_without_aw_is_refreshed(self):
        """An a-priori basis seeded as a RecycleState with zero AW: the
        exact refresh computes its A-products before the first solve,
        deflates with them, and charges the k matvecs."""
        A, b, eigs, q = _solve_setup(n=96, cond=1e5, seed=17)
        k = 8
        W = jnp.asarray(q[:, -k:].T)  # top-k eigenvectors as (k, n) rows
        seeded = RecycleState(
            W=W, AW=jnp.zeros_like(W), theta=jnp.zeros(k),
            systems_solved=jnp.int32(0), drift=jnp.zeros(()),
        )
        spec = SolveSpec(k=k, ell=12, tol=1e-8, maxiter=3000)
        res = solve(from_matrix(A), b, spec, seeded)
        assert bool(res.info.converged)
        # the carried AW is A·W of the extracted basis, not the zero seed
        np.testing.assert_allclose(
            np.asarray(res.state.AW), np.asarray(res.state.W @ A),
            rtol=0, atol=1e-8 * float(np.abs(eigs).max()),
        )
        # exact top-k deflation: clearly beats fresh CG, and the k AW
        # matvecs are charged
        fresh = cg(from_matrix(A), b, tol=1e-8, maxiter=3000)
        assert int(res.info.iterations) < int(fresh.info.iterations)
        assert int(res.info.matvecs) == int(res.info.iterations) + 1 + k

    def test_zero_iteration_solve_keeps_basis_state(self):
        """Regression: a 0-iteration solve (exact x0) records nothing and
        must leave a cold state cold — an all-zero basis, not a phantom
        one that gets charged k refresh matvecs on every later system."""
        A, b, _, _ = _solve_setup(n=48, cond=1e2)
        x_exact = jnp.linalg.solve(A, b)
        spec = SolveSpec(k=4, ell=8, tol=1e-6, maxiter=500)
        res = solve(from_matrix(A), b, spec, x0=x_exact)
        assert int(res.info.iterations) == 0
        assert not np.any(np.asarray(res.state.W))
        # the next solve runs as a plain first system: no refresh charge
        res2 = solve(from_matrix(A), b, spec, res.state)
        plain = defcg(from_matrix(A), b, ell=8, tol=1e-6, maxiter=500)
        assert int(res2.info.matvecs) == int(plain.info.matvecs)
        assert np.any(np.asarray(res2.state.W))  # recycling bootstrapped

    def test_recycling_drifting_sequence(self):
        # The paper's setting: a slowly drifting SPD sequence — recycling
        # must reduce iterations vs fresh CG on the later systems.
        n, k, ell = 96, 8, 12
        rng = np.random.default_rng(11)
        q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        eigs = np.concatenate(
            [np.linspace(1.0, 5.0, n - k), np.logspace(3.0, 4.5, k)]
        )
        base = (q * eigs) @ q.T
        spec = SolveSpec(k=k, ell=ell, tol=1e-8, maxiter=5000)
        state = None
        cg_iters, defcg_iters = [], []
        x_prev = None
        for i in range(5):
            pert = rng.standard_normal((n, n)) * 0.01
            Ai = jnp.asarray(base + pert @ pert.T)  # SPD drift
            bi = jnp.asarray(rng.standard_normal(n))
            cg_iters.append(int(cg(from_matrix(Ai), bi, tol=1e-8, maxiter=5000).info.iterations))
            res = solve(from_matrix(Ai), bi, spec, state, x0=x_prev)
            state, x_prev = res.state, res.x
            defcg_iters.append(int(res.info.iterations))
            np.testing.assert_allclose(
                Ai @ res.x, bi, rtol=0, atol=1e-7 * np.linalg.norm(bi)
            )
        # After the first system, recycling should clearly win (paper ~25%).
        assert sum(defcg_iters[1:]) < 0.85 * sum(cg_iters[1:])

    def test_breakdown_flag_on_indefinite(self):
        A = jnp.diag(jnp.array([1.0, -1.0, 2.0]))
        b = jnp.array([1.0, 1.0, 1.0])
        res = cg(from_matrix(A), b, tol=1e-12, maxiter=50)
        assert bool(res.info.breakdown) or not bool(res.info.converged)

    def test_fallback_matvec_accounting(self):
        """Regression: when a poisoned basis sends the solve down the
        recovery ladder, the reported matvecs must be the TRUE total —
        every refresh and every attempt — not just the last attempt."""
        A, b, _, _ = _solve_setup(n=64, cond=1e4)
        k, ell, maxiter = 4, 8, 6  # maxiter too small to converge
        W = random_orthonormal_basis(jax.random.PRNGKey(0), b, k)
        seeded = RecycleState(
            W=W, AW=jnp.zeros_like(W), theta=jnp.zeros(k),
            systems_solved=jnp.int32(0), drift=jnp.zeros(()),
        )
        spec = SolveSpec(k=k, ell=ell, tol=1e-10, maxiter=maxiter)
        res = solve(from_matrix(A), b, spec, seeded)
        assert not bool(res.info.converged)  # every attempt hits maxiter
        # rung 1 (refresh AW, redo) and rung 2 (drop the basis) ran; rung
        # 3 (shifted plain CG) needs a breakdown
        assert int(res.report.rung) == 2
        assert np.any(np.asarray(res.state.W))  # rung 2 re-bootstrapped

        # Reference costs of the attempts, run in isolation.
        AW = pt.basis_map_vectors(from_matrix(A), W)
        failed = defcg(
            from_matrix(A), b, W=W, AW=AW, ell=ell,
            tol=1e-10, maxiter=maxiter, waw_jitter=spec.waw_jitter,
        )
        fallback = defcg(
            from_matrix(A), b, ell=ell, tol=1e-10, maxiter=maxiter
        )
        expected = (
            2 * (k + int(failed.info.matvecs))  # first attempt and rung 1
            + int(fallback.info.matvecs)  # rung 2, basis dropped
        )
        assert int(res.info.matvecs) == expected


class TestHarmonicRitz:
    def test_ritz_values_approximate_extremal_eigs(self):
        n, ell, k = 128, 24, 4
        A, b, eigs, _ = _solve_setup(n=n, cond=1e4, seed=13)
        res = defcg(from_matrix(A), b, tol=1e-12, maxiter=500, ell=ell)
        m = int(res.recycle.stored)
        Z = pt.basis_slice(res.recycle.P, m)
        AZ = pt.basis_slice(res.recycle.AP, m)
        _, _, theta = harmonic_ritz(Z, AZ, k, select="largest")
        # Largest harmonic Ritz value should approach λ_max within a few %.
        assert np.max(np.asarray(theta)) > 0.5 * eigs[-1]

    def test_extracted_basis_deflates(self):
        # End-to-end: Ritz basis from run 1 must speed up run 2 (same A).
        A, b, _, _ = _solve_setup(n=96, cond=1e5, seed=17)
        first = defcg(from_matrix(A), b, tol=1e-8, maxiter=3000, ell=16)
        m = int(first.recycle.stored)
        Z = pt.basis_slice(first.recycle.P, m)
        AZ = pt.basis_slice(first.recycle.AP, m)
        W, AW, _ = harmonic_ritz(Z, AZ, 8)
        rng = np.random.default_rng(23)
        b2 = jnp.asarray(rng.standard_normal(96))
        fresh = cg(from_matrix(A), b2, tol=1e-8, maxiter=3000)
        defl = defcg(from_matrix(A), b2, W=W, AW=AW, tol=1e-8, maxiter=3000)
        assert int(defl.info.iterations) < int(fresh.info.iterations)
        np.testing.assert_allclose(
            A @ defl.x, b2, rtol=0, atol=1e-6 * np.linalg.norm(b2)
        )


class TestNystrom:
    def test_sketch_finds_top_eigenspace(self):
        A, _, eigs, q = _solve_setup(n=64, cond=1e4, seed=29)
        U, lam = randomized_nystrom(
            from_matrix(A), jnp.zeros(64), rank=6, key=jax.random.PRNGKey(0)
        )
        np.testing.assert_allclose(lam[0], eigs[-1], rtol=0.05)

    def test_nystrom_pcg(self):
        A, b, eigs, _ = _solve_setup(n=96, cond=1e5, seed=31)
        U, lam = randomized_nystrom(
            from_matrix(A), jnp.zeros(96), rank=10, key=jax.random.PRNGKey(1)
        )
        M = nystrom_preconditioner(U, lam, sigma=1.0)
        plain = cg(from_matrix(A), b, tol=1e-8, maxiter=3000)
        pre = cg(from_matrix(A), b, tol=1e-8, maxiter=3000, M=M)
        assert int(pre.info.iterations) < int(plain.info.iterations)
        np.testing.assert_allclose(
            A @ pre.x, b, rtol=0, atol=1e-6 * np.linalg.norm(b)
        )


class TestProperties:
    @settings(max_examples=20, deadline=None)
    @given(
        n=st.integers(8, 48),
        cond=st.floats(1e1, 1e6),
        seed=st.integers(0, 2**16),
    )
    def test_cg_solves_any_spd(self, n, cond, seed):
        rng = np.random.default_rng(seed)
        A, _, _ = make_spd(n, cond, rng)
        b = rng.standard_normal(n)
        res = cg(from_matrix(jnp.asarray(A)), jnp.asarray(b), tol=1e-10, maxiter=20 * n)
        np.testing.assert_allclose(
            A @ np.asarray(res.x), b, atol=1e-7 * max(1.0, np.linalg.norm(b))
        )

    @settings(max_examples=15, deadline=None)
    @given(
        n=st.integers(12, 40),
        k=st.integers(1, 6),
        seed=st.integers(0, 2**16),
    )
    def test_defcg_invariants(self, n, k, seed):
        """def-CG with a random-orthonormal W still solves the system and
        keeps Wᵀr ≈ 0 — deflation is *correct* for any full-rank W."""
        rng = np.random.default_rng(seed)
        A, _, _ = make_spd(n, 1e4, rng)
        b = rng.standard_normal(n)
        W = random_orthonormal_basis(
            jax.random.PRNGKey(seed % 97), jnp.zeros(n), k
        )
        res = defcg(
            from_matrix(jnp.asarray(A)), jnp.asarray(b), W=W, tol=1e-10, maxiter=20 * n
        )
        x = np.asarray(res.x)
        np.testing.assert_allclose(
            A @ x, b, atol=1e-6 * max(1.0, np.linalg.norm(b))
        )
        r = jnp.asarray(b - A @ x)
        np.testing.assert_allclose(
            np.asarray(pt.basis_dot(W, r)), 0.0, atol=1e-6 * max(1.0, np.linalg.norm(b))
        )

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16))
    def test_materialize_ggn_is_symmetric(self, seed):
        """GGN operator must be symmetric PSD (+damping) — def-CG's precondition."""
        from repro.core import GGNOperator

        rng = np.random.default_rng(seed)
        Wm = jnp.asarray(rng.standard_normal((5, 3)))
        x = jnp.asarray(rng.standard_normal((7, 3)))

        def model(params):
            return x @ (params["w"].T @ Wm.T @ Wm @ params["w"])  # nonlinear in params

        def loss_hvp(outputs, t):
            return 2.0 * t  # squared loss Hessian = 2I

        params = {"w": jnp.asarray(rng.standard_normal((3, 3)))}
        op = GGNOperator(model, loss_hvp, params, damping=jnp.float64(0.1))
        dense = materialize(op, params)
        np.testing.assert_allclose(dense, dense.T, atol=1e-8)
        eigs = np.linalg.eigvalsh(np.asarray(dense))
        assert eigs.min() >= 0.0999  # PSD + damping
