"""Front-door API tests (ISSUE 3 tentpole): SolveSpec + RecycleState,
preconditioned def-CG, and the batched multi-tenant entry point.

Five layers of checks:

  1. preconditioned def-CG parity: ``defcg(…, M)`` (Jacobi and Nyström)
     must reproduce an explicitly split-preconditioned reference solve —
     plain def-CG on ``E A E`` with the transformed basis ``E⁻¹W``,
     ``E = M^{-1/2}`` — to 1e-10 (trajectory parity at a fixed iteration
     count below convergence, where rounding noise cannot accumulate);
  2. the ``solve`` front door: state carry, refresh accounting, and
     round-tripping ``RecycleState`` through the checkpoint layer;
  3. ``solve_batch``: B vmapped tenants bit-match B sequential ``solve``
     calls (per-tenant masks freeze finished lanes), and the whole batch
     traces to one XLA computation with no host syncs;
  4. validation of a seeded ``RecycleState`` by ``solve`` (host-side
     error instead of a mid-solve XLA shape failure);
  5. the paper-level claim: Nyström-preconditioned def-CG (invariant-K
     sketch + per-system Woodbury) beats unpreconditioned def-CG in
     matvecs on the GP Laplace Newton sequence.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_pytree, save_pytree
from repro.core import (
    DEFAULT_WAW_JITTER,
    RecycleState,
    SolveSpec,
    cg,
    defcg,
    from_matrix,
    jacobi,
    nystrom_preconditioner,
    randomized_nystrom,
    solve,
    solve_batch,
    solve_jit,
    solve_sequence,
)
from repro.core import pytree as pt
from tests.conftest import make_spd


def _spd_problem(n=64, cond=1e3, seed=1, row_scale=0.8):
    rng = np.random.default_rng(seed)
    A0, _, _ = make_spd(n, cond, rng)
    s = np.logspace(0, row_scale, n)
    A = jnp.asarray(A0 * np.outer(s, s))
    b = jnp.asarray(rng.standard_normal(n))
    return A, b, rng


class TestSolveSpec:
    def test_waw_jitter_single_default(self):
        """Satellite: ONE waw_jitter default, carried by the spec and
        shared by defcg and the sequence engine."""
        import inspect

        from repro.core import recycle as recycle_mod

        assert SolveSpec().waw_jitter == DEFAULT_WAW_JITTER == 1e-12
        assert (
            inspect.signature(defcg).parameters["waw_jitter"].default
            == DEFAULT_WAW_JITTER
        )
        assert (
            inspect.signature(recycle_mod.solve_sequence)
            .parameters["waw_jitter"]
            .default
            == DEFAULT_WAW_JITTER
        )

    def test_validation(self):
        with pytest.raises(ValueError, match="method"):
            SolveSpec(method="gmres")
        with pytest.raises(ValueError, match="refresh_aw"):
            SolveSpec(refresh_aw="sometimes")
        with pytest.raises(ValueError, match="precond"):
            SolveSpec(precond="ilu")
        with pytest.raises(ValueError, match="k >= 1"):
            SolveSpec(k=0)

    def test_hashable_static_jit_arg(self):
        """Two equal specs must be one jit cache entry."""
        assert SolveSpec(k=4) == SolveSpec(k=4)
        assert hash(SolveSpec(k=4)) == hash(SolveSpec(k=4))
        assert SolveSpec(k=4) != SolveSpec(k=5)


class TestPreconditionedDefCGParity:
    """defcg(M) ≡ split-preconditioned plain def-CG, at 1e-10."""

    def _parity_case(self, M_dense_inv, M_apply, n=64, k=4, iters=15):
        A, b, rng = _spd_problem(n=n)
        # E = M^{-1/2} (symmetric): def-PCG on (A, b, M) must equal plain
        # def-CG on (EAE, Eb) with basis W̃ = E⁻¹W, mapped back by E.
        lam, q = np.linalg.eigh(np.asarray(M_dense_inv))
        E = (q * np.sqrt(lam)) @ q.T
        At = jnp.asarray(E @ np.asarray(A) @ E)
        bt = jnp.asarray(E @ np.asarray(b))
        W = jnp.asarray(np.linalg.qr(rng.standard_normal((n, k)))[0].T)
        Wt = jnp.asarray(np.asarray(W) @ np.linalg.inv(E))

        # Fixed iteration count below convergence: exact trajectory parity
        # (post-convergence steps wander in rounding noise by design).
        ref = defcg(
            from_matrix(At), bt, W=Wt, tol=0.0, maxiter=iters, waw_jitter=0.0
        )
        got = defcg(
            from_matrix(A), b, W=W, tol=0.0, maxiter=iters, waw_jitter=0.0,
            M=M_apply,
        )
        assert int(ref.info.iterations) == int(got.info.iterations) == iters
        x_ref = jnp.asarray(E @ np.asarray(ref.x))
        np.testing.assert_allclose(
            np.asarray(got.x), np.asarray(x_ref), rtol=1e-10, atol=1e-10
        )
        # and run to convergence: the preconditioned solve hits the TRUE
        # residual tolerance of the untransformed system
        conv = defcg(from_matrix(A), b, W=W, tol=1e-10, maxiter=5000, M=M_apply)
        assert bool(conv.info.converged)
        np.testing.assert_allclose(
            np.asarray(A @ conv.x), np.asarray(b),
            atol=1e-8 * float(jnp.linalg.norm(b)),
        )

    def test_jacobi_parity(self):
        A, _, _ = _spd_problem()
        d = jnp.diag(A)
        self._parity_case(np.diag(1.0 / np.asarray(d)), jacobi(d))

    def test_nystrom_parity(self):
        A, _, _ = _spd_problem()
        n = A.shape[0]
        U, lam = randomized_nystrom(
            from_matrix(A), jnp.zeros(n), rank=10, key=jax.random.PRNGKey(0)
        )
        M = nystrom_preconditioner(U, lam, sigma=1.0)
        M_dense = np.stack(
            [np.asarray(M(jnp.eye(n, dtype=A.dtype)[i])) for i in range(n)]
        ).T
        self._parity_case(M_dense, M)

    def test_pcg_defcg_no_basis_matches_cg(self):
        """defcg(M) without a basis is exactly preconditioned CG."""
        A, b, _ = _spd_problem()
        M = jacobi(jnp.diag(A))
        r_cg = cg(from_matrix(A), b, tol=1e-10, maxiter=2000, M=M)
        r_def = defcg(from_matrix(A), b, tol=1e-10, maxiter=2000, ell=0, M=M)
        assert int(r_cg.info.iterations) == int(r_def.info.iterations)
        np.testing.assert_allclose(
            np.asarray(r_cg.x), np.asarray(r_def.x), rtol=1e-9, atol=1e-10
        )


def _drifting_mats(n=96, k=8, num=4, seed=11, drift=0.01):
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    eigs = np.concatenate(
        [np.linspace(1.0, 5.0, n - k), np.logspace(3.0, 4.5, k)]
    )
    base = (q * eigs) @ q.T
    mats, bs = [], []
    for _ in range(num):
        pert = rng.standard_normal((n, n)) * drift
        mats.append(base + pert @ pert.T)
        bs.append(rng.standard_normal(n))
    return jnp.asarray(np.stack(mats)), jnp.asarray(np.stack(bs))


class TestSolveFrontDoor:
    SPEC = SolveSpec(k=8, ell=12, tol=1e-8, maxiter=5000)

    def test_state_carry_cuts_iterations(self):
        mats, bs = _drifting_mats()
        state = None
        iters = []
        for i in range(mats.shape[0]):
            res = solve_jit(from_matrix(mats[i]), bs[i], self.SPEC, state)
            state = res.state
            iters.append(int(res.info.iterations))
            np.testing.assert_allclose(
                np.asarray(mats[i] @ res.x), np.asarray(bs[i]),
                atol=1e-6 * float(jnp.linalg.norm(bs[i])),
            )
        assert int(state.systems_solved) == mats.shape[0]
        assert all(it < 0.6 * iters[0] for it in iters[1:])

    def test_matches_sequence_engine(self):
        """solve() iterated == solve_sequence(): same engine, same counts."""
        mats, bs = _drifting_mats(num=3, seed=5)
        seq = solve_sequence(
            mats, bs, self.SPEC, make_operator=from_matrix
        )
        state = None
        for i in range(3):
            res = solve(from_matrix(mats[i]), bs[i], self.SPEC, state)
            state = res.state
            assert int(res.info.iterations) == int(seq.info.iterations[i])
            assert int(res.info.matvecs) == int(seq.info.matvecs[i])
        np.testing.assert_allclose(
            np.asarray(state.W), np.asarray(seq.state.W), rtol=1e-9, atol=1e-9
        )
        assert int(seq.state.systems_solved) == 3

    def test_refresh_accounting(self):
        """matvecs = iterations + 1 (r₀) + k (refresh) after bootstrap."""
        mats, bs = _drifting_mats(num=2, seed=9)
        r1 = solve(from_matrix(mats[0]), bs[0], self.SPEC)
        assert int(r1.info.matvecs) == int(r1.info.iterations) + 1  # cold
        r2 = solve(from_matrix(mats[1]), bs[1], self.SPEC, r1.state)
        assert int(r2.info.matvecs) == int(r2.info.iterations) + 1 + 8

    def test_state_spec_mismatch_rejected(self):
        mats, bs = _drifting_mats(num=1)
        bad = RecycleState.zeros(4, bs.shape[1], bs.dtype)  # k=4 vs spec k=8
        with pytest.raises(ValueError, match="state and spec must agree"):
            solve(from_matrix(mats[0]), bs[0], self.SPEC, bad)

    def test_precond_strategy_requires_m(self):
        mats, bs = _drifting_mats(num=1)
        spec = dataclasses.replace(self.SPEC, precond="nystrom")
        with pytest.raises(ValueError, match="make_preconditioner"):
            solve(from_matrix(mats[0]), bs[0], spec)

    def test_sequence_precond_strategy_requires_factory(self):
        """A declared preconditioner strategy must not silently run
        unpreconditioned through the sequence front door."""
        mats, bs = _drifting_mats(num=2)
        spec = dataclasses.replace(self.SPEC, precond="jacobi")
        with pytest.raises(ValueError, match="factory"):
            solve_sequence(mats, bs, spec, make_operator=from_matrix)

    def test_atol_respected_by_sequence_paths(self):
        """SolveSpec.atol reaches the sequence engine (it was only honored
        by the single-system path)."""
        mats, bs = _drifting_mats(num=2)
        loose = SolveSpec(k=4, ell=8, tol=0.0, atol=1e-2, maxiter=3000)
        seq = solve_sequence(mats, bs, loose, make_operator=from_matrix)
        assert np.asarray(seq.info.converged).all()
        # tol=0, atol=0 would run every system to maxiter
        assert (np.asarray(seq.info.iterations) < 3000).all()

    def test_sequence_ell_zero_carries_state(self):
        """ell=0 (no recording) is a valid spec — the sequence runs,
        solves correctly, and carries the incoming basis/theta through."""
        mats, bs = _drifting_mats(num=2)
        spec = SolveSpec(k=4, ell=0, tol=1e-8, maxiter=5000)
        seq = solve_sequence(mats, bs, spec, make_operator=from_matrix)
        assert np.asarray(seq.info.converged).all()
        assert seq.state.theta.shape == (4,)
        assert int(seq.state.systems_solved) == 2

    def test_legacy_w0_signature_removed(self):
        """The PR-3-era solve_sequence(systems, b, W0, AW0, k=…) shim is
        gone: positional arrays in the spec slot raise, keywords raise,
        and the supported replacement — state0=RecycleState — works."""
        mats, bs = _drifting_mats(num=3)
        first = solve_sequence(mats[:1], bs[:1], self.SPEC,
                               make_operator=from_matrix)
        with pytest.raises(TypeError, match="removed"):
            solve_sequence(mats[1:], bs[1:], first.state.W, first.state.AW,
                           make_operator=from_matrix)
        with pytest.raises(TypeError):
            solve_sequence(mats[1:], bs[1:], self.SPEC,
                           W0=first.state.W, AW0=first.state.AW,
                           make_operator=from_matrix)
        seq = solve_sequence(mats[1:], bs[1:], self.SPEC, first.state,
                             make_operator=from_matrix)
        assert np.asarray(seq.info.converged).all()

    def test_recycle_state_checkpoint_roundtrip(self, tmp_path):
        """RecycleState must survive checkpoint/manager.py unchanged —
        restoring a checkpoint resumes the recycling sequence."""
        mats, bs = _drifting_mats(num=1)
        res = solve(from_matrix(mats[0]), bs[0], self.SPEC)
        train_state = {"params": jnp.ones(3), "recycle": res.state}
        path = save_pytree(train_state, str(tmp_path), step=1)
        out = restore_pytree(train_state, path)
        assert isinstance(out["recycle"], RecycleState)
        for a, b in zip(
            jax.tree_util.tree_leaves(train_state["recycle"]),
            jax.tree_util.tree_leaves(out["recycle"]),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # ... and the restored state keeps working as a warm start
        res2 = solve(from_matrix(mats[0]), bs[0], self.SPEC, out["recycle"])
        assert int(res2.info.iterations) < int(res.info.iterations)


class TestSolveBatch:
    SPEC = SolveSpec(k=6, ell=10, tol=1e-8, maxiter=3000)

    def test_vmap_parity_with_sequential_solves(self):
        """B batched tenants must match B sequential solve() calls —
        identical iterates (masked lanes freeze), counts and states."""
        B = 5
        rng = np.random.default_rng(17)
        mats, bs = [], []
        for i in range(B):
            A0, _, _ = make_spd(48, 10.0 ** (2 + i % 3), rng)
            mats.append(A0)
            bs.append(rng.standard_normal(48))
        mats = jnp.asarray(np.stack(mats))
        bs = jnp.asarray(np.stack(bs))

        batch = solve_batch(mats, bs, self.SPEC, make_operator=from_matrix)
        assert np.asarray(batch.info.converged).all()
        for i in range(B):
            single = solve(from_matrix(mats[i]), bs[i], self.SPEC)
            assert int(batch.info.iterations[i]) == int(
                single.info.iterations
            ), i
            assert int(batch.info.matvecs[i]) == int(single.info.matvecs), i
            np.testing.assert_allclose(
                np.asarray(batch.x[i]), np.asarray(single.x),
                rtol=1e-12, atol=1e-12,
            )
            np.testing.assert_allclose(
                np.asarray(batch.state.W[i]), np.asarray(single.state.W),
                rtol=1e-9, atol=1e-9,
            )

    def test_batched_states_feed_back(self):
        """A second batched round consumes the first round's states."""
        B, n = 3, 64
        rng = np.random.default_rng(23)
        q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        eigs = np.concatenate(
            [np.linspace(1.0, 5.0, n - 6), np.logspace(3.0, 4.5, 6)]
        )
        A0 = (q * eigs) @ q.T
        mats = jnp.asarray(
            np.stack([A0 + 0.01 * np.eye(n) * i for i in range(B)])
        )
        bs = jnp.asarray(rng.standard_normal((B, n)))
        first = solve_batch(mats, bs, self.SPEC, make_operator=from_matrix)
        bs2 = jnp.asarray(rng.standard_normal((B, n)))
        second = solve_batch(
            mats, bs2, self.SPEC, first.state, make_operator=from_matrix
        )
        assert np.asarray(second.info.converged).all()
        assert (
            np.asarray(second.info.iterations)
            < 0.7 * np.asarray(first.info.iterations)
        ).all()
        np.testing.assert_array_equal(
            np.asarray(second.state.systems_solved), 2
        )

    def test_batched_sequences(self):
        """sequence=True: B tenants × N systems each, one computation."""
        B, N, n = 3, 3, 64
        rng = np.random.default_rng(29)
        A0, _, _ = make_spd(n, 1e4, rng)
        mats = np.empty((B, N, n, n))
        bs = np.empty((B, N, n))
        for t in range(B):
            for i in range(N):
                pert = rng.standard_normal((n, n)) * 0.01
                mats[t, i] = A0 * (1.0 + 0.1 * t) + pert @ pert.T
                bs[t, i] = rng.standard_normal(n)
        mats, bs = jnp.asarray(mats), jnp.asarray(bs)
        batch = solve_batch(
            mats, bs, self.SPEC, make_operator=from_matrix, sequence=True
        )
        assert batch.x.shape == (B, N, n)
        for t in range(B):
            seq = solve_sequence(
                mats[t], bs[t], self.SPEC, make_operator=from_matrix
            )
            # Batched eigh (the extraction's reduction) rounds differently
            # from the single-problem LAPACK path, and across a sequence
            # the extracted basis feeds the NEXT solve — so cross-system
            # counts may drift by ±1 iteration.  Solutions still meet the
            # same residual tolerance.
            np.testing.assert_allclose(
                np.asarray(batch.info.iterations[t]),
                np.asarray(seq.info.iterations),
                atol=2,
            )
            for i in range(N):
                np.testing.assert_allclose(
                    np.asarray(mats[t, i] @ batch.x[t, i]),
                    np.asarray(bs[t, i]),
                    atol=1e-6 * float(jnp.linalg.norm(bs[t, i])),
                )

    def test_cg_batch_passes_state_through(self):
        """method='cg' neither consumes nor updates recycle state — a
        supplied batched state must come back untouched, not be dropped."""
        mats, bs = _drifting_mats(num=2)
        prev = solve_batch(mats, bs, self.SPEC, make_operator=from_matrix)
        cg_spec = SolveSpec(method="cg", tol=1e-8, maxiter=3000)
        out = solve_batch(
            mats, bs, cg_spec, prev.state, make_operator=from_matrix
        )
        assert out.state is prev.state
        assert np.asarray(out.info.converged).all()

    def test_per_tenant_convergence_mask(self):
        """A hard tenant must not corrupt an easy tenant's answer."""
        n = 48
        rng = np.random.default_rng(31)
        easy, _, _ = make_spd(n, 10.0, rng)
        hard, _, _ = make_spd(n, 1e6, rng)
        mats = jnp.asarray(np.stack([easy, hard]))
        bs = jnp.asarray(rng.standard_normal((2, n)))
        spec = SolveSpec(k=4, ell=8, tol=1e-12, maxiter=40)  # hard one fails
        batch = solve_batch(mats, bs, spec, make_operator=from_matrix)
        conv = np.asarray(batch.info.converged)
        assert conv[0] and not conv[1]
        single = solve(from_matrix(mats[0]), bs[0], spec)
        np.testing.assert_allclose(
            np.asarray(batch.x[0]), np.asarray(single.x),
            rtol=1e-12, atol=1e-12,
        )


def _seeded(W, AW=None):
    """A RecycleState seeded a priori with the rows ``W`` (zero AW unless
    given): the front door's replacement for a manager's ``seed``."""
    k = W.shape[0]
    return RecycleState(
        W=W, AW=jnp.zeros_like(W) if AW is None else AW,
        theta=jnp.zeros(k), systems_solved=jnp.int32(0),
        drift=jnp.zeros(()),
    )


class TestSeedValidation:
    SPEC = SolveSpec(k=4, ell=8)

    def test_seed_too_many_vectors_rejected(self):
        rng = np.random.default_rng(0)
        A = from_matrix(jnp.asarray(make_spd(32, 1e2, rng)[0]))
        b = jnp.asarray(rng.standard_normal(32))
        W = jnp.asarray(rng.standard_normal((6, 32)))
        with pytest.raises(ValueError, match=r"needs \(4, 32\)"):
            solve(A, b, self.SPEC, _seeded(W))

    def test_seed_mismatched_aw_rejected(self):
        rng = np.random.default_rng(0)
        A = from_matrix(jnp.asarray(make_spd(32, 1e2, rng)[0]))
        b = jnp.asarray(rng.standard_normal(32))
        W = jnp.asarray(rng.standard_normal((4, 32)))
        with pytest.raises(ValueError, match="shaped like state.W"):
            solve(A, b, self.SPEC,
                  _seeded(W, jnp.asarray(rng.standard_normal((4, 16)))))
        with pytest.raises(ValueError, match="shaped like state.W"):
            solve(A, b, self.SPEC,
                  _seeded(W, {"a": jnp.asarray(rng.standard_normal((4, 32)))}))

    def test_valid_seed_still_works(self):
        rng = np.random.default_rng(3)
        A, _, q = make_spd(64, 1e4, rng)
        A = jnp.asarray(A)
        W = jnp.asarray(q[:, -4:].T)
        spec = SolveSpec(k=4, ell=8, tol=1e-8, maxiter=3000)
        res = solve(from_matrix(A), jnp.asarray(rng.standard_normal(64)),
                    spec, _seeded(W))
        assert bool(res.info.converged)
        # the zero seed AW was refreshed: the carried AW is A·W
        np.testing.assert_allclose(
            np.asarray(res.state.AW), np.asarray(res.state.W @ A),
            rtol=0, atol=1e-8 * float(jnp.abs(A).max()),
        )


class TestLaplaceNystromPrecondition:
    @pytest.fixture(scope="class")
    def gp_runs(self):
        """The GP Laplace Newton sequence, plain vs Nyström def-CG."""
        from repro.data import make_infinite_digits
        from repro.gp import RBFKernel, laplace_gpc

        x, y = make_infinite_digits(260, seed=7)
        x = jnp.asarray(x, jnp.float64)
        y = jnp.asarray(y, jnp.float64)
        kernel = RBFKernel(theta=30.0, lengthscale=32.0)
        base = SolveSpec(k=8, ell=12, tol=1e-10, maxiter=4000)
        nys = dataclasses.replace(base, precond="nystrom", precond_rank=40)
        plain = laplace_gpc(x, y, kernel, spec=base, newton_tol=1e-4)
        pre = laplace_gpc(
            x, y, kernel, spec=nys,
            precond_key=jax.random.PRNGKey(0), newton_tol=1e-4,
        )
        return plain, pre

    def test_nystrom_defcg_needs_measurably_fewer_matvecs(self, gp_runs):
        """Acceptance criterion: Nyström-preconditioned def-CG beats
        unpreconditioned def-CG on the GP Laplace sequence — per-system
        solver iterations AND total matvecs (sketch cost INCLUDED; the
        invariant-K sketch amortizes across the Newton sequence)."""
        plain, pre = gp_runs
        it_plain = plain.trace.solver_iterations
        it_pre = pre.trace.solver_iterations
        assert len(it_plain) == len(it_pre)
        assert all(p < q for p, q in zip(it_pre, it_plain))
        assert sum(it_pre) < 0.6 * sum(it_plain)
        # total operator applications, one-off sketch charged to system 1
        assert sum(pre.trace.solver_matvecs) < 0.95 * sum(
            plain.trace.solver_matvecs
        )

    def test_same_mode_found(self, gp_runs):
        plain, pre = gp_runs
        assert abs(pre.logp - plain.logp) / abs(plain.logp) < 1e-6
        np.testing.assert_allclose(
            np.asarray(pre.f), np.asarray(plain.f), atol=5e-4
        )
