"""Device-resident sequence engine tests (ISSUE 2 tentpole).

Four layers of checks:

  1. flat/pytree parity: ``harmonic_ritz_flat`` must reproduce the pytree
     ``harmonic_ritz`` (the semantic oracle) at 1e-10, including with a
     traced validity mask standing in for the oracle's static slice;
  2. ``solve_sequence``: a drifting-operator sequence run as ONE jitted
     scan must show falling def-CG iteration counts (paper Fig. 2
     qualitative check), correct solutions, and honest matvec accounting;
  3. host-sync freedom: the whole N-system sequence must trace (no
     ``int()``/``.item()`` on traced state in the per-system path);
  4. multi-RHS refresh: ``apply_to_basis`` must equal the k-matvec sweep
     for every concrete operator (one fused pass ≡ k applications).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    GGNOperator,
    KernelSystemOperator,
    apply_to_basis,
    defcg,
    from_matrix,
    harmonic_ritz,
    harmonic_ritz_flat,
    SolveSpec,
    solve_sequence,
)
from repro.core import pytree as pt
from tests.conftest import make_spd


def _recorded_basis(n=120, k=6, ell=14, seed=0):
    """Run one recording def-CG solve; return its (P, AP, stored)."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    eigs = np.concatenate(
        [np.linspace(1.0, 5.0, n - k), np.logspace(3, 4.5, k)]
    )
    A = jnp.asarray((q * eigs) @ q.T)
    b = jnp.asarray(rng.standard_normal(n))
    res = defcg(from_matrix(A), b, tol=1e-12, maxiter=20 * n, ell=ell)
    return res.recycle, A, b


class TestFlatPytreeParity:
    def test_full_window_parity(self):
        """Flat extraction == pytree oracle at 1e-10 on a full window."""
        rec, _, _ = _recorded_basis()
        k, ell = 6, 14
        m = int(rec.stored)
        assert m == ell  # sanity: the window filled
        Wp, AWp, thp = harmonic_ritz(rec.P, rec.AP, k)
        Wf, AWf, thf = harmonic_ritz_flat(rec.P, rec.AP, k)
        np.testing.assert_allclose(
            np.asarray(thf), np.asarray(thp), rtol=1e-10
        )
        # Ritz vectors match up to per-column sign (eigh convention).
        Wp_flat = pt.ravel_basis(Wp)
        signs = jnp.sign(jnp.sum(Wp_flat * Wf, axis=1))
        np.testing.assert_allclose(
            np.asarray(Wf * signs[:, None]), np.asarray(Wp_flat),
            rtol=1e-8, atol=1e-10,
        )
        np.testing.assert_allclose(
            np.asarray(AWf * signs[:, None]), np.asarray(pt.ravel_basis(AWp)),
            rtol=1e-8, atol=1e-8,
        )

    def test_masked_window_matches_static_slice(self):
        """A traced validity mask must equal the oracle's static slice —
        the host-sync-free replacement for ``int(stored)`` + ``[:m]``."""
        rec, _, _ = _recorded_basis(n=90, k=4, ell=20, seed=3)
        stored = 11  # pretend the solve stopped mid-window
        P_sl = pt.basis_slice(rec.P, stored)
        AP_sl = pt.basis_slice(rec.AP, stored)
        Wp, _, thp = harmonic_ritz(P_sl, AP_sl, 4)
        _, _, thf = harmonic_ritz_flat(
            rec.P, rec.AP, 4, valid=jnp.arange(20) < jnp.int32(stored)
        )
        np.testing.assert_allclose(
            np.asarray(thf), np.asarray(thp), rtol=1e-10
        )

    def test_extracted_flat_basis_deflates(self):
        """End-to-end: the flat-extracted basis speeds up a second solve."""
        rec, A, _ = _recorded_basis(seed=5)
        W, AW, _ = harmonic_ritz_flat(rec.P, rec.AP, 6)
        rng = np.random.default_rng(99)
        b2 = jnp.asarray(rng.standard_normal(A.shape[0]))
        fresh = defcg(from_matrix(A), b2, tol=1e-8, maxiter=3000, ell=0)
        defl = defcg(from_matrix(A), b2, W=W, AW=AW, tol=1e-8, maxiter=3000)
        assert int(defl.info.iterations) < int(fresh.info.iterations)
        np.testing.assert_allclose(
            np.asarray(A @ defl.x), np.asarray(b2),
            atol=1e-6 * float(jnp.linalg.norm(b2)),
        )


def _drifting_sequence(n=96, k=8, num=5, seed=11, drift=0.01):
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    eigs = np.concatenate(
        [np.linspace(1.0, 5.0, n - k), np.logspace(3.0, 4.5, k)]
    )
    base = (q * eigs) @ q.T
    mats, bs = [], []
    for _ in range(num):
        pert = rng.standard_normal((n, n)) * drift
        mats.append(base + pert @ pert.T)  # SPD drift
        bs.append(rng.standard_normal(n))
    return jnp.asarray(np.stack(mats)), jnp.asarray(np.stack(bs))


SPEC = SolveSpec(k=8, ell=12, tol=1e-8, maxiter=5000)


class TestSolveSequence:
    def test_drifting_sequence_iterations_fall(self):
        """Paper Fig. 2: recycling must cut iterations after system 1."""
        mats, bs = _drifting_sequence()
        seq = solve_sequence(mats, bs, SPEC, make_operator=from_matrix)
        iters = np.asarray(seq.info.iterations)
        cg_iters = [
            int(
                defcg(
                    from_matrix(mats[i]), bs[i], tol=1e-8, maxiter=5000, ell=0
                ).info.iterations
            )
            for i in range(mats.shape[0])
        ]
        # every recycled system after the first clearly beats fresh CG
        assert all(iters[i] < 0.6 * cg_iters[i] for i in range(1, len(iters)))
        assert np.sum(iters[1:]) < 0.85 * np.sum(cg_iters[1:])
        for i in range(mats.shape[0]):
            np.testing.assert_allclose(
                np.asarray(mats[i] @ seq.x[i]), np.asarray(bs[i]),
                atol=1e-6 * float(jnp.linalg.norm(bs[i])),
            )

    def test_matvec_accounting_includes_refresh(self):
        """exact refresh ⇒ matvecs = iterations + 1 (r₀) + k (refresh) —
        except the cold bootstrap system, whose all-zero basis needs (and
        is charged) no refresh."""
        mats, bs = _drifting_sequence(num=3)
        seq = solve_sequence(mats, bs, SPEC, make_operator=from_matrix)
        np.testing.assert_array_equal(
            np.asarray(seq.info.matvecs),
            np.asarray(seq.info.iterations) + 1 + np.array([0, 8, 8]),
        )

    def test_stale_seeding_requires_aw(self):
        """W0 without AW0 in stale mode would deflate against AW = 0 and
        report a silently wrong 'converged' solution — must be rejected."""
        mats, bs = _drifting_sequence(num=2)
        from repro.core import recycle as recycle_mod

        W0 = jnp.asarray(np.random.default_rng(0).standard_normal((4, 96)))
        with pytest.raises(ValueError, match="stale"):
            recycle_mod.solve_sequence(
                mats, bs, W0, None, k=4, ell=8, make_operator=from_matrix,
                refresh_aw="stale",
            )

    def test_stale_mode_solves_correctly(self):
        """Stale AW (zero refresh matvecs) over an UNCHANGED operator —
        the multiple-RHS setting, where the stale products are exact:
        solutions meet tolerance, recycling cuts iterations, and
        matvecs = iterations + 2 (r₀ shortcut + one true-matvec rederive).

        (Under operator drift, stale deflation can destabilize the
        conjugacy recurrence outright — the recovery ladder and the
        WindowedRecombine drift guard cover that.)"""
        mats, bs = _drifting_sequence(num=4, seed=29, drift=0.0)
        seq = solve_sequence(
            mats, bs, dataclasses.replace(SPEC, refresh_aw="stale"),
            make_operator=from_matrix,
        )
        for i in range(mats.shape[0]):
            np.testing.assert_allclose(
                np.asarray(mats[i] @ seq.x[i]), np.asarray(bs[i]),
                atol=1e-6 * float(jnp.linalg.norm(bs[i])),
            )
        np.testing.assert_array_equal(
            np.asarray(seq.info.matvecs),
            np.asarray(seq.info.iterations) + 2,
        )
        iters = np.asarray(seq.info.iterations)
        assert iters[-1] < iters[0]

    def test_traces_without_host_sync(self):
        """The whole N-system sequence must be traceable: any int()/.item()
        on traced per-system state would raise a ConcretizationTypeError
        here.  This is the acceptance criterion made executable — extended
        to the batched multi-tenant front door, which must likewise lower
        to ONE XLA computation (single jaxpr, no host round-trips)."""
        from repro.core import recycle as recycle_mod

        mats, bs = _drifting_sequence(num=3)

        def run(mats, bs):
            seq = recycle_mod.solve_sequence(
                mats, bs, k=4, ell=8, make_operator=from_matrix,
                tol=1e-6, maxiter=200,
            )
            return seq.info.iterations, seq.W

        jaxpr = jax.make_jaxpr(run)(mats, bs)
        assert jaxpr is not None

        from repro.core import SolveSpec, solve_batch

        spec = SolveSpec(k=4, ell=8, tol=1e-6, maxiter=200)

        def run_batch(mats, bs):
            out = solve_batch(mats, bs, spec, make_operator=from_matrix)
            return out.x, out.info.converged, out.state.W

        # B tenants (reusing the drifting mats as independent systems)
        jaxpr_b = jax.make_jaxpr(run_batch)(mats, bs)
        assert jaxpr_b is not None

        def run_batch_seq(mats, bs):
            out = solve_batch(
                mats[None], bs[None], spec,
                make_operator=from_matrix, sequence=True,
            )
            return out.info.iterations, out.state.W

        jaxpr_bs = jax.make_jaxpr(run_batch_seq)(mats, bs)
        assert jaxpr_bs is not None

    def test_warm_start_carry(self):
        """carry_x: re-solving the same system is near-free."""
        n = 64
        rng = np.random.default_rng(7)
        A, _, _ = make_spd(n, 1e4, rng)
        b = rng.standard_normal(n)
        mats = jnp.asarray(np.stack([A] * 3))
        bs = jnp.asarray(np.stack([b] * 3))
        seq = solve_sequence(
            mats, bs, SolveSpec(k=6, ell=12, tol=1e-8, maxiter=2000),
            make_operator=from_matrix, carry_x=True,
        )
        iters = np.asarray(seq.info.iterations)
        assert iters[1] <= 2 and iters[2] <= 2

    def test_seeding_from_previous_result(self):
        """The returned state seeds a follow-up call (sequence resume)."""
        mats, bs = _drifting_sequence(num=4, seed=41)
        first = solve_sequence(
            mats[:2], bs[:2], SPEC, make_operator=from_matrix
        )
        resumed = solve_sequence(
            mats[2:], bs[2:], SPEC, first.state, make_operator=from_matrix
        )
        cold = solve_sequence(mats[2:], bs[2:], SPEC, make_operator=from_matrix)
        # the seeded run's FIRST system already benefits from recycling
        assert int(resumed.info.iterations[0]) < int(cold.info.iterations[0])


class TestMultiRHSRefresh:
    def test_dense_operator_matmat(self):
        rng = np.random.default_rng(0)
        A, _, _ = make_spd(48, 1e3, rng)
        op = from_matrix(jnp.asarray(A))
        W = jnp.asarray(rng.standard_normal((5, 48)))
        np.testing.assert_allclose(
            np.asarray(apply_to_basis(op, W)),
            np.asarray(pt.basis_map_vectors(op, W)),
            rtol=1e-12,
        )

    def test_kernel_system_operator_multi_rhs(self):
        from repro.kernels import ref as kref

        rng = np.random.default_rng(1)
        n, d, m = 80, 4, 6
        xs = jnp.asarray(rng.standard_normal((n, d)))
        kmat = kref.rbf_gram(xs, 1.5, 1.2)
        sqrt_h = jnp.asarray(rng.uniform(0.05, 0.5, n))
        op = KernelSystemOperator(lambda v: kmat @ v, sqrt_h)
        W = jnp.asarray(rng.standard_normal((m, n)))
        np.testing.assert_allclose(
            np.asarray(apply_to_basis(op, W)),
            np.asarray(pt.basis_map_vectors(op, W)),
            rtol=1e-10,
        )

    def test_ggn_operator_linearize_once(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((16, 3)))

        def model(params):
            return jnp.tanh(x @ params["w"]) @ params["v"]

        params = {
            "w": jnp.asarray(rng.standard_normal((3, 4))) * 0.3,
            "v": jnp.asarray(rng.standard_normal((4, 2))) * 0.3,
        }
        op = GGNOperator(
            model, lambda out, t: 2.0 * t, params, damping=jnp.float64(0.1)
        )
        W = pt.basis_from_vectors(
            [pt.tree_random_like(jax.random.PRNGKey(i), params) for i in range(3)]
        )
        got = apply_to_basis(op, W)
        want = pt.basis_map_vectors(op.matvec, W)
        for g, w in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
        ):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-10, atol=1e-12
            )


class TestRitzClampRegression:
    def test_fewer_positive_than_k_is_masked(self):
        """Rank-deficient window with k > surviving positive Ritz count:
        the trailing slots must be exact zeros — not 1e300 'Ritz values'
        normalized out of near-null vectors (the +inf argsort bug)."""
        rng = np.random.default_rng(4)
        n = 64
        A, _, _ = make_spd(n, 1e3, rng)
        A = jnp.asarray(A)
        z1 = jnp.asarray(rng.standard_normal(n))
        z2 = jnp.asarray(rng.standard_normal(n))
        # duplicated columns → rank-2 basis, ask for k=4
        Z = jnp.stack([z1, z2, z1, z2])
        AZ = Z @ A
        for extract in (harmonic_ritz, harmonic_ritz_flat):
            W, AW, theta = extract(Z, AZ, 4)
            th = np.asarray(theta)
            assert np.all(np.isfinite(th))
            assert np.all(th < 1e10), th  # no 1e300 garbage
            assert np.sum(th > 0) == 2
            np.testing.assert_array_equal(th[2:], 0.0)
            Wf = pt.ravel_basis(W)
            np.testing.assert_array_equal(np.asarray(Wf)[2:], 0.0)

    def test_clamped_basis_still_deflates_safely(self):
        """def-CG with a clamped (zero-padded) basis: the zero columns are
        an exact deflation no-op under the jitter floor — the solve must
        converge to the true solution."""
        rng = np.random.default_rng(8)
        n = 64
        A, _, _ = make_spd(n, 1e3, rng)
        A = jnp.asarray(A)
        z1 = jnp.asarray(rng.standard_normal(n))
        z2 = jnp.asarray(rng.standard_normal(n))
        Z = jnp.stack([z1, z2, z1, z2])
        W, AW, _ = harmonic_ritz_flat(Z, Z @ A, 4)
        b = jnp.asarray(rng.standard_normal(n))
        # no explicit waw_jitter: zero columns must be regularized away
        # unconditionally (any jitter setting, including the 0.0 default)
        for jitter in (0.0, 1e-12):
            res = defcg(
                from_matrix(A), b, W=W, AW=AW,
                tol=1e-10, maxiter=2000, waw_jitter=jitter,
            )
            assert bool(res.info.converged)
            np.testing.assert_allclose(
                np.asarray(A @ res.x), np.asarray(b),
                atol=1e-7 * float(jnp.linalg.norm(b)),
            )
