"""Trajectory pins for the def-CG front door and the engine harness.

The single-system and sequence front doors (``solve`` /
``solve_sequence``) and the loop harness under them (``core/engine.py``)
are pinned against golden data captured on a fig2-style GP Newton trace:

  * plain CG on the first Newton system — final iterate, iteration count,
    matvec count, status, residual norm;
  * the def-CG sequence front door over the drifting trace — per-system
    solutions, residual norms, iteration/matvec counts, statuses, Ritz
    values, recovery rungs, and the final recycled basis;
  * a recovery-ladder case (indefinite operator, ladder armed) — the
    rung taken, terminal status, honest matvec total and the adopted
    iterate.

Regenerate the golden file ONLY when a deliberate numeric change is
intended (document it in the change):

    PYTHONPATH=src python tests/test_trajectory_pin.py

Every integer field (iterations, matvecs, status, rung) must match
EXACTLY: a lost or gained iteration, a different stop or a different rung
is a change of algorithm, not of rounding.  The floats are compared to
bounds derived from each scenario's own ``tol`` (1e-10 CG, 1e-9
sequence, 1e-8 ladder), because a compiler may reassociate the loop
body's sums and move the last bits:

  * ``x`` of the CG and sequence solves: ``‖x − x_golden‖ ≤ 2·tol·‖b‖``.
    Both runs stop with ``‖r‖ ≤ tol·‖b‖``, and ``x − x_golden =
    A⁻¹(r_golden − r)``; the operator is ``I + H½KH½``, whose eigenvalues
    are ≥ 1, so ``‖A⁻¹‖ ≤ 1``.
  * the sequence's final ``W`` and ``AW`` (rows aligned in sign: a Ritz
    vector's sign is arbitrary and def-CG is blind to it) and its Ritz
    values θ: relative error ≤ tol (Frobenius norms).  They are built
    from the recorded directions of solves that are only defined to tol.
  * the ladder's adopted ``x``: relative error ≤ tol.  It is an iterate
    of the pinned attempt after the pinned iteration count, not a
    converged solution, so no residual bound applies; tol is the
    resolution the scenario asks of it.
  * residual norms: each at or below its stopping threshold
    ``max(tol·‖b‖, atol)`` (``atol`` = 0 here), and within a factor
    ``RESIDUAL_FACTOR`` = 2 of the golden.  Reaching tol took the CG
    solve 26 iterations and each sequence solve 17–22, so one iteration
    cuts the residual about 2.4–3.4×: a factor 2 is less than one
    iteration's worth, and far above the ~1% that rounding moves it.

Under JAX 0.9.0 the largest drifts from the golden are 1.2e-11 in ``x``,
2.8e-15 in ``W``, 2.2e-14 in ``AW``, 8.5e-14 in θ (absolute) and 1.2%
in a residual norm.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "trajectories_fig2.npz")

_N = 96  # GP trace size — small enough for CI, big enough to iterate
_K, _ELL = 4, 8
_NUM_SYSTEMS = 4
CG_TOL, SEQ_TOL, LADDER_TOL = 1e-10, 1e-9, 1e-8
RESIDUAL_FACTOR = 2.0


def _fig2_newton_trace():
    """A miniature fig2 GP-classification Newton trace.

    ``A_t = I + H_t^{1/2} K H_t^{1/2}`` over a fixed RBF Gram matrix with
    the Newton-drifting diagonal ``H_t`` of a logistic likelihood — the
    paper's sequence of related SPD systems, deterministic by seed.
    """
    from repro.gp import RBFKernel

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((_N, 4)))
    kmat = RBFKernel(theta=2.0, lengthscale=1.5).gram(x)

    ops, bs = [], []
    f = jnp.asarray(rng.standard_normal(_N) * 0.3)
    y = jnp.asarray(np.sign(rng.standard_normal(_N)))
    for t in range(_NUM_SYSTEMS):
        pi = jax.nn.sigmoid(f)
        sqrt_h = jnp.sqrt(pi * (1.0 - pi))
        ops.append(sqrt_h)
        bs.append(sqrt_h * (y - pi) + 0.1 * f)
        f = f + 0.35 * jnp.asarray(rng.standard_normal(_N))
    return kmat, jnp.stack(ops), jnp.stack(bs)


def _indefinite_problem():
    rng = np.random.default_rng(3)
    q, _ = np.linalg.qr(rng.standard_normal((48, 48)))
    eigs = np.concatenate([np.linspace(0.5, 4.0, 44), [-1.0, -0.2, 2.0, 9.0]])
    mat = jnp.asarray((q * eigs) @ q.T)
    b = jnp.asarray(rng.standard_normal(48))
    return mat, b


def _run_all():
    """Execute the pinned scenarios; returns a dict of numpy arrays."""
    from repro.core import (
        KernelSystemOperator,
        SolveSpec,
        cg,
        from_matrix,
        solve,
        solve_sequence,
    )

    kmat, sqrt_hs, bs = _fig2_newton_trace()
    out = {"b_norm": np.linalg.norm(np.asarray(bs), axis=1)}

    # -- plain CG on the first Newton system -----------------------------
    op0 = KernelSystemOperator(lambda v: kmat @ v, sqrt_hs[0])
    res = cg(op0, bs[0], tol=CG_TOL, maxiter=600)
    out["cg_x"] = np.asarray(res.x)
    out["cg_iterations"] = np.asarray(res.info.iterations)
    out["cg_matvecs"] = np.asarray(res.info.matvecs)
    out["cg_status"] = np.asarray(res.info.status)
    out["cg_residual_norm"] = np.asarray(res.info.residual_norm)

    # -- def-CG sequence over the drifting Newton trace ------------------
    spec = SolveSpec(method="defcg", k=_K, ell=_ELL, tol=SEQ_TOL, maxiter=600)
    seq = solve_sequence(
        sqrt_hs,
        bs,
        spec,
        make_operator=lambda sh: KernelSystemOperator(
            lambda v: kmat @ v, sh
        ),
    )
    out["seq_x"] = np.asarray(seq.x)
    out["seq_iterations"] = np.asarray(seq.info.iterations)
    out["seq_matvecs"] = np.asarray(seq.info.matvecs)
    out["seq_status"] = np.asarray(seq.info.status)
    out["seq_residual_norm"] = np.asarray(seq.info.residual_norm)
    out["seq_theta"] = np.asarray(seq.theta)
    out["seq_rung"] = np.asarray(seq.report.rung)
    out["seq_final_W"] = np.asarray(seq.state.W)
    out["seq_final_AW"] = np.asarray(seq.state.AW)

    # -- recovery-ladder behavior on an indefinite operator --------------
    mat, b = _indefinite_problem()
    bad_spec = SolveSpec(method="defcg", k=3, ell=6, tol=LADDER_TOL, maxiter=300,
                         recovery_rungs=3, recovery_shift=1e-6)
    # A warm basis forces the deflated path; the indefinite spectrum
    # breaks it, so the ladder must climb — pin the rung it lands on.
    warm = solve(from_matrix(jnp.asarray(np.eye(48) * 2.0)), b, bad_spec)
    res_bad = solve(from_matrix(mat), b, bad_spec, warm.state)
    out["ladder_status"] = np.asarray(res_bad.info.status)
    out["ladder_rung"] = np.asarray(res_bad.report.rung)
    out["ladder_matvecs"] = np.asarray(res_bad.info.matvecs)
    out["ladder_x"] = np.asarray(res_bad.x)
    return out


@pytest.fixture(scope="module")
def golden():
    if not os.path.exists(GOLDEN):
        pytest.skip("golden trajectory file missing — regenerate with "
                    "`python tests/test_trajectory_pin.py`")
    with np.load(GOLDEN) as z:
        return dict(z)


@pytest.fixture(scope="module")
def current():
    return _run_all()


def _assert_exact(current, golden, keys):
    for key in keys:
        np.testing.assert_array_equal(current[key], golden[key], err_msg=key)


def _assert_residuals(current, golden, key, tol, b_norm):
    got, want = current[key], golden[key]
    assert np.all(got <= tol * b_norm), (key, got, tol * b_norm)
    ratio = got / want
    assert np.all((ratio <= RESIDUAL_FACTOR) & (ratio >= 1 / RESIDUAL_FACTOR)), (
        key, ratio)


def _rel(got, want):
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def test_cg_trajectory_pinned(golden, current):
    _assert_exact(current, golden, ("cg_iterations", "cg_matvecs", "cg_status"))
    b_norm = current["b_norm"][0]
    assert np.linalg.norm(current["cg_x"] - golden["cg_x"]) <= 2 * CG_TOL * b_norm
    _assert_residuals(current, golden, "cg_residual_norm", CG_TOL, b_norm)


def test_defcg_sequence_pinned(golden, current):
    _assert_exact(current, golden, ("seq_iterations", "seq_matvecs",
                                    "seq_status", "seq_rung"))
    b_norm = current["b_norm"]
    x_err = np.linalg.norm(current["seq_x"] - golden["seq_x"], axis=1)
    assert np.all(x_err <= 2 * SEQ_TOL * b_norm), (x_err, b_norm)
    _assert_residuals(current, golden, "seq_residual_norm", SEQ_TOL, b_norm)
    # Align each recycled vector's sign with the golden's.
    sign = np.where(np.sum(current["seq_final_W"] * golden["seq_final_W"],
                           axis=1, keepdims=True) < 0, -1.0, 1.0)
    for key, got in (("seq_final_W", sign * current["seq_final_W"]),
                     ("seq_final_AW", sign * current["seq_final_AW"]),
                     ("seq_theta", current["seq_theta"])):
        assert _rel(got, golden[key]) <= SEQ_TOL, key


def test_recovery_ladder_pinned(golden, current):
    _assert_exact(current, golden, ("ladder_status", "ladder_rung",
                                    "ladder_matvecs"))
    assert _rel(current["ladder_x"], golden["ladder_x"]) <= LADDER_TOL


if __name__ == "__main__":
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    np.savez_compressed(GOLDEN, **_run_all())
    print(f"wrote {GOLDEN}")
