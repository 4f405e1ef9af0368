"""Sharded Krylov engine: parity, collective counts, scaling.

The conftest forces ``xla_force_host_platform_device_count=8``, so the
"solve" mesh here is 8 real (host) devices — shard_map runs genuinely
SPMD and the compiled HLO carries the real collectives.  Three gates:

1. PARITY — sharded cg/defcg/lsmr match the unsharded engine's iterates
   (x to 1e-10, identical iteration/matvec counts, matching RecycleState
   up to per-row sign) at mesh sizes 1, 4 and 8, and the recycled
   warm-start win survives sharding.
2. COMMUNICATION — the def-CG/CG while body contains EXACTLY ONE
   all-reduce per iteration (LSMR its inherent two), asserted from
   compiled HLO via repro.launch.hlo_stats.while_body_collectives.
3. SCALE — the sharded RBF operator solves an n = 1e5 GP system without
   materializing the n×n Gram matrix (slow tier).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sharded
from repro.core.api import SolveSpec, solve, solve_jit
from repro.core.operators import DenseMatrixOperator, RBFKernelSystemOperator
from repro.core.recycle import RecycleState
from repro.launch import hlo_stats
from repro.launch.mesh import (
    make_solve_mesh,
    solve_state_shardings,
    solve_vector_sharding,
)

from conftest import make_spd


def _system(n=64, cond=50.0, seed=0):
    rng = np.random.default_rng(seed)
    a_np, _, _ = make_spd(n, cond=cond, rng=rng)
    A = DenseMatrixOperator(mat=jnp.asarray(a_np))
    b = jnp.asarray(rng.standard_normal(n))
    return A, b, rng


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------


class TestSolveMesh:
    def test_eight_forced_host_devices(self):
        assert jax.device_count() == 8

    def test_default_takes_all_devices(self):
        mesh = make_solve_mesh()
        assert mesh.axis_names == ("solve",)
        assert mesh.shape["solve"] == 8

    def test_explicit_count(self):
        for n in (1, 4, 8):
            assert make_solve_mesh(n).shape["solve"] == n

    def test_out_of_range_count_raises(self):
        with pytest.raises(ValueError, match="out of range"):
            make_solve_mesh(9)
        with pytest.raises(ValueError, match="out of range"):
            make_solve_mesh(0)

    def test_state_shardings_match_spec_rules(self):
        mesh = make_solve_mesh(8)
        sh = solve_state_shardings(mesh)
        assert sh.W.spec == sharded.basis_spec()
        assert sh.AW.spec == sharded.basis_spec()
        assert sh.theta.spec == jax.sharding.PartitionSpec()
        assert solve_vector_sharding(mesh).spec == sharded.vector_spec()

    def test_shard_recycle_state_places_leaves(self):
        mesh = make_solve_mesh(8)
        st = sharded.shard_recycle_state(
            RecycleState.zeros(4, 64, jnp.float64), mesh
        )
        assert st.W.sharding.spec == sharded.basis_spec()
        assert st.theta.sharding.spec == jax.sharding.PartitionSpec()


# ---------------------------------------------------------------------------
# parity with the unsharded engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_devices", [1, 4, 8])
@pytest.mark.parametrize("method", ["cg", "defcg", "lsmr"])
def test_sharded_matches_unsharded(method, n_devices):
    """x matches to 1e-10 at every mesh size.  CG/def-CG iteration and
    matvec counts may differ by AT MOST one: the sharded stopping test
    rides the one-step ``‖r₊‖²`` recurrence (the price of one all-reduce
    per iteration), which can cross the threshold one step before/after
    the unsharded fresh reduction when the crossing is within rounding.
    LSMR's coupled Golub–Kahan recurrences accumulate association
    differences over the run, so its counts get a small slack — the
    iterates themselves still pin at 1e-10."""
    A, b, _ = _system()
    spec = SolveSpec(method=method, k=4, ell=6, tol=1e-12, maxiter=300)
    st = RecycleState.zeros(4, 64, jnp.float64)
    ref = solve(A, b, spec, st)
    got = solve(A, b, spec, st, mesh=make_solve_mesh(n_devices))

    slack = 5 if method == "lsmr" else 1
    np.testing.assert_allclose(got.x, ref.x, rtol=0, atol=1e-10)
    assert abs(int(got.info.iterations) - int(ref.info.iterations)) <= slack
    assert abs(int(got.info.matvecs) - int(ref.info.matvecs)) <= 2 * slack
    assert bool(got.info.converged) and bool(ref.info.converged)
    assert int(got.info.status) == int(ref.info.status)


def test_sharded_defcg_state_matches_up_to_row_sign():
    A, b, _ = _system()
    spec = SolveSpec(method="defcg", k=4, ell=6, tol=1e-10, maxiter=200)
    st = RecycleState.zeros(4, 64, jnp.float64)
    ref = solve(A, b, spec, st)
    got = solve(A, b, spec, st, mesh=make_solve_mesh(8))

    # Harmonic-Ritz vectors are sign-ambiguous per row; align then compare.
    w_r, w_g = np.asarray(ref.state.W), np.asarray(got.state.W)
    signs = np.sign(np.sum(w_r * w_g, axis=1))
    np.testing.assert_allclose(w_g * signs[:, None], w_r, atol=1e-10)
    np.testing.assert_allclose(
        np.asarray(got.state.AW) * signs[:, None],
        np.asarray(ref.state.AW),
        atol=1e-10,
    )
    np.testing.assert_allclose(got.state.theta, ref.state.theta, atol=1e-10)
    assert int(got.state.systems_solved) == int(ref.state.systems_solved) == 1


def test_recycling_win_survives_sharding():
    """The paper's claim under SPMD: a recycled second solve beats the
    cold first one by the same margin as the unsharded engine."""
    A, b, rng = _system()
    b2 = jnp.asarray(rng.standard_normal(64))
    spec = SolveSpec(method="defcg", k=4, ell=8, tol=1e-8, maxiter=200)
    st0 = RecycleState.zeros(4, 64, jnp.float64)
    mesh = make_solve_mesh(8)

    ref1 = solve(A, b, spec, st0)
    ref2 = solve(A, b2, spec, ref1.state)
    got1 = solve(A, b, spec, st0, mesh=mesh)
    got2 = solve(A, b2, spec, got1.state, mesh=mesh)

    assert int(ref2.info.iterations) < int(ref1.info.iterations)
    assert int(got2.info.iterations) < int(got1.info.iterations)
    assert abs(int(got1.info.iterations) - int(ref1.info.iterations)) <= 1
    assert abs(int(got2.info.iterations) - int(ref2.info.iterations)) <= 1
    np.testing.assert_allclose(got2.x, ref2.x, rtol=0, atol=1e-10)


def test_state_reshards_across_mesh_sizes():
    """A state produced on one mesh is a legal warm start on another:
    _prepare re-commits every traced input onto the target mesh, so a
    mesh-8 state feeds a mesh-1 (or unsharded) solve instead of dying
    on a cross-device jit error — and the answers agree."""
    A, b, rng = _system()
    b2 = jnp.asarray(rng.standard_normal(64))
    spec = SolveSpec(method="defcg", k=4, ell=8, tol=1e-8, maxiter=200)
    st0 = RecycleState.zeros(4, 64, jnp.float64)

    got1 = solve(A, b, spec, st0, mesh=make_solve_mesh(8))
    r_m1 = solve(A, b2, spec, got1.state, mesh=make_solve_mesh(1))
    r_m8 = solve(A, b2, spec, got1.state, mesh=make_solve_mesh(8))
    r_un = solve(A, b2, spec, got1.state)
    np.testing.assert_allclose(r_m1.x, r_m8.x, rtol=0, atol=1e-10)
    np.testing.assert_allclose(r_un.x, r_m8.x, rtol=0, atol=1e-10)
    assert abs(int(r_m1.info.iterations) - int(r_m8.info.iterations)) <= 1


def test_sharded_lsmr_damped_parity():
    A, b, _ = _system()
    spec = SolveSpec(
        method="lsmr", tol=1e-10, maxiter=300, lsq_shift=1e-2
    )
    ref = solve(A, b, spec)
    got = solve(A, b, spec, mesh=make_solve_mesh(8))
    # The two runs sum their reductions in different orders, and LSMR's
    # coupled recurrences carry the difference to the stopping step, so
    # x agrees to what the stopping rule leaves, not bitwise.  Both are
    # stationary points of ‖Ax − b‖² + δ‖x‖² to within their normal
    # residuals g = Aᵀ(b − Ax) − δx, and the normal matrix AᵀA + δI has
    # no eigenvalue below σ_min(A)² + δ, so ‖x_got − x_ref‖ ≤ (‖g_got‖ +
    # ‖g_ref‖) / (σ_min² + δ).  The 1e-12 covers the float64 rounding of
    # evaluating g itself (‖A‖² ‖x‖ · n · eps).
    a = np.asarray(A.mat)
    bb = np.asarray(b)
    damp = spec.lsq_shift

    def normal_residual(x):
        x = np.asarray(x)
        return np.linalg.norm(a.T @ (bb - a @ x) - damp * x)

    floor = np.linalg.svd(a, compute_uv=False)[-1] ** 2 + damp
    bound = (normal_residual(got.x) + normal_residual(ref.x)) / floor
    assert np.linalg.norm(np.asarray(got.x) - np.asarray(ref.x)) <= bound + 1e-12
    assert abs(int(got.info.iterations) - int(ref.info.iterations)) <= 5
    assert bool(got.info.converged) and bool(ref.info.converged)


def test_sharded_x0_and_trace_parity():
    """Warm start threads through, and the recorded residual trace
    follows the unsharded trajectory (a tol=1e-8 stop leaves x at the
    ~1e-8 convergence level, so the x pin here is commensurate; the
    tight 1e-10 trajectory pin lives in test_sharded_matches_unsharded
    at tol=1e-12)."""
    A, b, rng = _system()
    x0 = jnp.asarray(rng.standard_normal(64))
    spec = SolveSpec(method="defcg", k=4, ell=6, tol=1e-8, maxiter=200)
    st = RecycleState.zeros(4, 64, jnp.float64)
    ref = solve(A, b, spec, st, x0=x0, record_residuals=True)
    got = solve(
        A, b, spec, st, x0=x0, record_residuals=True,
        mesh=make_solve_mesh(8),
    )
    np.testing.assert_allclose(got.x, ref.x, rtol=0, atol=1e-6)
    # Early trace entries are bitwise-close; deep into the solve the
    # association-level beta differences amplify through the conjugacy
    # recurrences (both runs still converge to the same x), so pin the
    # prefix and the endpoint rather than the full tail.
    j = min(int(ref.info.iterations), int(got.info.iterations))
    prefix = min(j, 25)
    np.testing.assert_allclose(
        got.info.residual_norms[:prefix],
        ref.info.residual_norms[:prefix],
        rtol=1e-6,
    )
    assert bool(got.info.converged) and bool(ref.info.converged)


def test_solve_jit_with_static_mesh():
    """``mesh`` is a static argname of solve_jit — jitting the front
    door with a mesh reproduces the eager sharded solve exactly."""
    A, b, _ = _system()
    mesh = make_solve_mesh(8)
    spec = SolveSpec(method="cg", tol=1e-8, maxiter=200)
    eager = solve(A, b, spec, mesh=mesh)
    jitted = solve_jit(A, b, spec, mesh=mesh)
    np.testing.assert_allclose(jitted.x, eager.x, rtol=0, atol=1e-12)
    assert int(jitted.info.iterations) == int(eager.info.iterations)


def test_rbf_operator_sharded_parity():
    rng = np.random.default_rng(1)
    n = 256
    X = jnp.asarray(rng.standard_normal((n, 3)))
    sqrt_h = jnp.asarray(0.5 + rng.random(n))
    A = RBFKernelSystemOperator(
        x=X, sqrt_h=sqrt_h, theta=1.3, lengthscale=1.1,
        impl="chunked", block=64,
    )
    b = jnp.asarray(rng.standard_normal(n))
    spec = SolveSpec(method="defcg", k=4, ell=6, tol=1e-9, maxiter=400)
    st = RecycleState.zeros(4, n, jnp.float64)
    ref = solve(A, b, spec, st)
    got = solve(A, b, spec, st, mesh=make_solve_mesh(8))
    # The sharded solve sums each reduction per shard and then across
    # shards, and its stopping test rides a one-step recurrence: x agrees
    # to what the stopping rule leaves, not bitwise.  A = I + H½KH½ has no
    # eigenvalue below 1, so two solutions differ by at most the sum of
    # their true residuals; the 1e-12 covers the float64 rounding of
    # evaluating those residuals (‖A‖ ‖x‖ · n · eps).  Each true residual
    # is the solve's own stopping level: in float64 the recurrence that
    # stopped it is within rounding of the true one, so 1% room.
    true = [float(jnp.linalg.norm(b - A.matvec(r.x))) for r in (got, ref)]
    assert max(true) <= 1.01 * spec.tol * float(jnp.linalg.norm(b))
    assert float(jnp.linalg.norm(got.x - ref.x)) <= sum(true) + 1e-12
    assert abs(int(got.info.iterations) - int(ref.info.iterations)) <= 1
    assert abs(int(got.info.matvecs) - int(ref.info.matvecs)) <= 1


# ---------------------------------------------------------------------------
# front-door contract
# ---------------------------------------------------------------------------


class TestFrontDoor:
    def test_unsupported_method_raises(self):
        A, b, _ = _system()
        with pytest.raises(NotImplementedError, match="no sharded path"):
            solve(
                A, b, SolveSpec(method="deflsmr"), mesh=make_solve_mesh(8)
            )

    def test_preconditioner_rejected(self):
        A, b, _ = _system()
        with pytest.raises(ValueError, match="no preconditioner"):
            solve(A, b, SolveSpec(method="cg"), M=lambda r: r,
                  mesh=make_solve_mesh(8))

    def test_batch_axis_rejected(self):
        A, b, _ = _system()
        with pytest.raises(ValueError, match="do not compose"):
            solve(A, b, SolveSpec(method="cg"), batch_axis="tenant",
                  mesh=make_solve_mesh(8))

    def test_indivisible_n_raises(self):
        A, b, _ = _system(n=60)  # 60 % 8 != 0
        with pytest.raises(ValueError, match="not divisible"):
            solve(A, b, SolveSpec(method="cg"), mesh=make_solve_mesh(8))

    def test_wrong_mesh_axis_raises(self):
        A, b, _ = _system()
        bad = jax.make_mesh((8,), ("data",))
        with pytest.raises(ValueError, match="'solve' axis"):
            solve(A, b, SolveSpec(method="cg"), mesh=bad)

    def test_unsupported_operator_raises(self):
        b = jnp.ones(64)
        with pytest.raises(TypeError, match="shards the operator"):
            sharded.solve_sharded(
                lambda v: v, b, SolveSpec(method="cg"),
                mesh=make_solve_mesh(8),
            )

    def test_no_mesh_is_the_unsharded_path(self):
        A, b, _ = _system()
        res = solve(A, b, SolveSpec(method="cg", tol=1e-8))
        assert bool(res.info.converged)


# ---------------------------------------------------------------------------
# communication: collective counts pinned from compiled HLO
# ---------------------------------------------------------------------------


def _while_body_allreduce_counts(method, **spec_kw):
    A, b, _ = _system()
    st = RecycleState.zeros(4, 64, jnp.float64)
    spec = SolveSpec(method=method, k=4, ell=6, maxiter=200, **spec_kw)
    low = sharded.lower_sharded(A, b, spec, st, mesh=make_solve_mesh(8))
    hlo = low.compile().as_text()
    per_body = hlo_stats.while_body_collectives(hlo)
    assert per_body, "no while loop found in compiled sharded solve"
    return per_body


def test_defcg_one_allreduce_per_iteration():
    """THE tentpole contract: every def-CG iteration — recording scan
    phase and while phase both lower to HLO while loops — performs
    exactly ONE all-reduce (the merged psum) and one all-gather (the
    matvec input)."""
    for name, counts in _while_body_allreduce_counts("defcg").items():
        assert counts.get("all-reduce", 0) == 1, (name, counts)
        assert counts.get("all-gather", 0) == 1, (name, counts)
        assert counts.get("reduce-scatter", 0) == 0, (name, counts)


def test_cg_one_allreduce_per_iteration():
    for name, counts in _while_body_allreduce_counts("cg").items():
        assert counts.get("all-reduce", 0) == 1, (name, counts)


def test_lsmr_two_allreduces_per_iteration():
    """LSMR's β/α normalizations are serially dependent — two is its
    floor, and the sharded body must not exceed it."""
    for name, counts in _while_body_allreduce_counts("lsmr").items():
        assert counts.get("all-reduce", 0) == 2, (name, counts)


# ---------------------------------------------------------------------------
# hlo_stats counting helpers (unit level, synthetic HLO)
# ---------------------------------------------------------------------------

_SYNTH_ASYNC = """\
HloModule synth

%body.1 (p.0: (f32[2])) -> (f32[2]) {
  %p.0 = (f32[2]) parameter(0)
  %g.0 = f32[2] get-tuple-element((f32[2]) %p.0), index=0
  %ars = (f32[2], f32[2]) all-reduce-start(f32[2] %g.0), to_apply=%add
  %ard = f32[2] all-reduce-done((f32[2], f32[2]) %ars)
  ROOT %t.0 = (f32[2]) tuple(f32[2] %ard)
}

%cond.1 (p.1: (f32[2])) -> pred[] {
  %p.1 = (f32[2]) parameter(0)
  ROOT %c.0 = pred[] constant(true)
}

ENTRY %main (a.0: f32[2]) -> (f32[2]) {
  %a.0 = f32[2] parameter(0)
  %t.1 = (f32[2]) tuple(f32[2] %a.0)
  ROOT %w.0 = (f32[2]) while((f32[2]) %t.1), condition=%cond.1, body=%body.1
}
"""


class TestHloStatsCounting:
    def test_async_pair_counts_once(self):
        census = hlo_stats.count_collectives(_SYNTH_ASYNC)
        assert census["all-reduce"] == 1

    def test_async_pair_counts_once_in_while_body(self):
        per_body = hlo_stats.while_body_collectives(_SYNTH_ASYNC)
        assert per_body == {"body.1": {"all-reduce": 1}}

    def test_sync_form_counts(self):
        hlo = _SYNTH_ASYNC.replace(
            "%ars = (f32[2], f32[2]) all-reduce-start(f32[2] %g.0), "
            "to_apply=%add",
            "%ars2 = f32[2] all-reduce(f32[2] %g.0), to_apply=%add",
        ).replace(
            "%ard = f32[2] all-reduce-done((f32[2], f32[2]) %ars)",
            "%ard = f32[2] all-gather(f32[2] %ars2), dimensions={0}",
        )
        census = hlo_stats.count_collectives(hlo)
        assert census["all-reduce"] == 1
        assert census["all-gather"] == 1

    def test_nested_while_not_charged_to_outer_body(self):
        hlo = """\
HloModule nested

%inner_body (q.0: (f32[2])) -> (f32[2]) {
  %q.0 = (f32[2]) parameter(0)
  %gi = f32[2] get-tuple-element((f32[2]) %q.0), index=0
  %ari = f32[2] all-reduce(f32[2] %gi), to_apply=%add
  ROOT %ti = (f32[2]) tuple(f32[2] %ari)
}

%inner_cond (q.1: (f32[2])) -> pred[] {
  %q.1 = (f32[2]) parameter(0)
  ROOT %ci = pred[] constant(true)
}

%outer_body (p.0: (f32[2])) -> (f32[2]) {
  %p.0 = (f32[2]) parameter(0)
  %g.0 = f32[2] get-tuple-element((f32[2]) %p.0), index=0
  %ag = f32[4] all-gather(f32[2] %g.0), dimensions={0}
  %sl = f32[2] slice(f32[4] %ag), slice={[0:2]}
  %tn = (f32[2]) tuple(f32[2] %sl)
  %wi = (f32[2]) while((f32[2]) %tn), condition=%inner_cond, body=%inner_body
  %gw = f32[2] get-tuple-element((f32[2]) %wi), index=0
  ROOT %t.0 = (f32[2]) tuple(f32[2] %gw)
}

%outer_cond (p.1: (f32[2])) -> pred[] {
  %p.1 = (f32[2]) parameter(0)
  ROOT %c.0 = pred[] constant(true)
}

ENTRY %main (a.0: f32[2]) -> (f32[2]) {
  %a.0 = f32[2] parameter(0)
  %t.1 = (f32[2]) tuple(f32[2] %a.0)
  ROOT %w.0 = (f32[2]) while((f32[2]) %t.1), condition=%outer_cond, body=%outer_body
}
"""
        per_body = hlo_stats.while_body_collectives(hlo)
        assert per_body["outer_body"] == {"all-gather": 1}
        assert per_body["inner_body"] == {"all-reduce": 1}

    def test_conditional_branches_are_worst_case(self):
        hlo = """\
HloModule branchy

%yes (y.0: f32[2]) -> f32[2] {
  %y.0 = f32[2] parameter(0)
  ROOT %ay = f32[2] all-reduce(f32[2] %y.0), to_apply=%add
}

%no (n.0: f32[2]) -> f32[2] {
  %n.0 = f32[2] parameter(0)
  ROOT %an = f32[2] all-reduce(f32[2] %n.0), to_apply=%add
}

%body.1 (p.0: (pred[], f32[2])) -> (pred[], f32[2]) {
  %p.0 = (pred[], f32[2]) parameter(0)
  %pr = pred[] get-tuple-element((pred[], f32[2]) %p.0), index=0
  %g.0 = f32[2] get-tuple-element((pred[], f32[2]) %p.0), index=1
  %cd = f32[2] conditional(pred[] %pr, f32[2] %g.0, f32[2] %g.0), true_computation=%yes, false_computation=%no
  ROOT %t.0 = (pred[], f32[2]) tuple(pred[] %pr, f32[2] %cd)
}

%cond.1 (p.1: (pred[], f32[2])) -> pred[] {
  %p.1 = (pred[], f32[2]) parameter(0)
  ROOT %c.0 = pred[] constant(true)
}

ENTRY %main (a.0: pred[], b.0: f32[2]) -> (pred[], f32[2]) {
  %a.0 = pred[] parameter(0)
  %b.0 = f32[2] parameter(1)
  %t.1 = (pred[], f32[2]) tuple(pred[] %a.0, f32[2] %b.0)
  ROOT %w.0 = (pred[], f32[2]) while((pred[], f32[2]) %t.1), condition=%cond.1, body=%body.1
}
"""
        per_body = hlo_stats.while_body_collectives(hlo)
        # Both branches are counted — an upper bound per iteration.
        assert per_body["body.1"] == {"all-reduce": 2}

    def test_count_collectives_on_real_lowering(self):
        A, b, _ = _system()
        low = sharded.lower_sharded(
            A, b, SolveSpec(method="cg", maxiter=50),
            mesh=make_solve_mesh(8),
        )
        census = hlo_stats.count_collectives(low.compile().as_text())
        assert census["all-reduce"] >= 1
        assert census["all-gather"] >= 1


# ---------------------------------------------------------------------------
# scale: n = 1e5 GP solve without materializing K (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_rbf_gp_solve_n_1e5_never_materializes_gram():
    """An n = 1e5 RBF GP system (75 GB dense Gram in f64 — far beyond
    materializing) solves through the sharded operator: row-blocks of X
    local per shard, K-tiles formed and consumed on the fly.  maxiter is
    tiny (each matvec is ~n² work on this 1-core CPU box) — the gate is
    completion + CONSISTENCY: the recurrence-tracked ‖r₁‖ must match the
    true ‖b − A x₁‖ recomputed with one more chunked matvec.  (A strict
    per-step decrease is NOT a valid gate: plain-CG residual 2-norms are
    non-monotone, and on this near-singular Gram the first step
    overshoots ‖r‖ by ~50× in exact arithmetic.)"""
    rng = np.random.default_rng(7)
    n = 100_000
    X = jnp.asarray(rng.standard_normal((n, 2)), dtype=jnp.float32)
    sqrt_h = jnp.asarray(0.5 + rng.random(n), dtype=jnp.float32)
    A = RBFKernelSystemOperator(
        x=X, sqrt_h=sqrt_h, theta=1.0, lengthscale=2.0,
        impl="chunked", block=512,
    )
    b = jnp.asarray(rng.standard_normal(n), dtype=jnp.float32)
    spec = SolveSpec(method="defcg", k=4, ell=0, tol=1e-8, maxiter=1)
    res = solve(
        A, b, spec, RecycleState.zeros(4, n, jnp.float32),
        record_residuals=True, mesh=make_solve_mesh(8),
    )
    assert np.all(np.isfinite(np.asarray(res.x)))
    assert float(jnp.linalg.norm(res.x)) > 0.0
    trace = np.asarray(res.info.residual_norms)
    assert np.isfinite(trace[0]) and np.isfinite(trace[1])
    np.testing.assert_allclose(
        trace[0], np.linalg.norm(np.asarray(b)), rtol=1e-4
    )
    true_r = float(jnp.linalg.norm(b - A.matvec(res.x)))
    np.testing.assert_allclose(trace[1], true_r, rtol=5e-2)
