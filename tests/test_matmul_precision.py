"""Every contraction in a solve asks for full f32 precision.

On a TPU an f32 ``dot_general`` without ``Precision.HIGHEST`` runs as one
bf16 pass.  That is enough to break deflation: μ = (WᵀAW)⁻¹(AW)ᵀr then
misses the solve's tolerance and a recycled def-CG runs to ``maxiter``.
The CPU computes every f32 product exactly whatever the flag says, so the
tests read the precision off the traced jaxpr instead: each front door's
solve is traced with library operators, and every ``dot_general`` in it,
including those inside Pallas kernel bodies and shard_map bodies, must
ask for ``HIGHEST``.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.trace_audit import reduced_precision_dots
from repro.core import SolveSpec, from_matrix
from repro.core.api import make_preconditioner, solve, solve_pool_step
from repro.core.operators import RBFKernelSystemOperator
from repro.kernels import ops as kops
from repro.launch.mesh import make_solve_mesh

N, D, K, ELL = 64, 8, 3, 4


@pytest.fixture(params=["chunked", "interpret"])
def impl(request, monkeypatch):
    """Steer ``impl="auto"`` to the plain-XLA twins or the Pallas bodies."""
    real = kops._resolve
    monkeypatch.setattr(
        kops, "_resolve",
        lambda i, op, *a: real(request.param if i == "auto" else i, op, *a),
    )
    return request.param


def _spd():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (N, N), jnp.float32) / jnp.sqrt(N)
    a = jnp.matmul(q, q.T, precision="highest") + jnp.eye(N, dtype=q.dtype)
    b = jax.random.normal(jax.random.fold_in(key, 1), (N,), jnp.float32)
    return a, b


def _rbf():
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (N, D), jnp.float32)
    sqrt_h = jnp.full((N,), 0.5, jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (N,), jnp.float32)
    return RBFKernelSystemOperator(x, sqrt_h, 1.0, 2.0, 32, "auto"), b


def _assert_full_precision(fn, *args):
    bad = reduced_precision_dots(jax.make_jaxpr(fn)(*args))
    assert not bad, f"{len(bad)} contraction(s) below HIGHEST: {bad[:4]}"


SPD_SPECS = {
    "cg": SolveSpec(method="cg", tol=1e-6, maxiter=80),
    "defcg": SolveSpec(method="defcg", k=K, ell=ELL, tol=1e-6, maxiter=80),
    "defcg_stale": SolveSpec(
        method="defcg", k=K, ell=ELL, tol=1e-6, maxiter=80,
        refresh_aw="stale",
    ),
}


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
@pytest.mark.parametrize("name", sorted(SPD_SPECS))
def test_spd_solve_is_full_precision(impl, name, warm):
    a, b = _spd()
    spec = SPD_SPECS[name]
    state = solve(from_matrix(a), b, spec).state if warm else None
    _assert_full_precision(
        lambda a, b, st: solve(from_matrix(a), b, spec, st).x, a, b, state
    )


def test_rbf_newton_solve_is_full_precision(impl):
    """The paper's path: def-CG on ``I + H½KH½`` with a carried basis."""
    op, b = _rbf()
    spec = SPD_SPECS["defcg"]
    state = solve(op, b, spec).state
    _assert_full_precision(
        lambda op, b, st: solve(op, b, spec, st).x, op, b, state
    )


def test_nystrom_preconditioned_solve_is_full_precision(impl):
    a, b = _spd()
    spec = SolveSpec(method="defcg", k=K, ell=ELL, tol=1e-6, maxiter=80,
                     precond="nystrom", precond_rank=4)
    state = solve(from_matrix(a), b, SPD_SPECS["defcg"]).state

    def run(a, b, st):
        m = make_preconditioner(from_matrix(a), spec, b,
                                key=jax.random.PRNGKey(3))
        return solve(from_matrix(a), b, spec, st, M=m).x

    _assert_full_precision(run, a, b, state)


@pytest.mark.parametrize("method", ["lsmr", "deflsmr"])
def test_least_squares_solve_is_full_precision(impl, method):
    key = jax.random.PRNGKey(4)
    a = jax.random.normal(key, (N + 16, N), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (N + 16,), jnp.float32)
    spec = SolveSpec(method=method, k=K, ell=ELL, tol=1e-6, maxiter=80,
                     lsq_shift=0.1)
    state = solve(from_matrix(a), b, spec).state
    _assert_full_precision(
        lambda a, b, st: solve(from_matrix(a), b, spec, st).x, a, b, state
    )


def test_pool_step_is_full_precision(impl):
    a, b = _spd()
    spec = SPD_SPECS["defcg"]
    state = solve(from_matrix(a), b, spec).state
    stack = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda l: jnp.stack([l, l]), t
    )
    _assert_full_precision(
        lambda a, b, st: solve_pool_step(
            a, b, spec, st, jnp.array([True, False]),
            make_operator=from_matrix,
        ).x,
        stack(a), stack(b), stack(state),
    )


@pytest.mark.parametrize("method", ["cg", "defcg", "lsmr"])
def test_sharded_solve_is_full_precision(impl, method):
    mesh = make_solve_mesh(2)
    spec = SolveSpec(method=method, k=K, ell=ELL, tol=1e-6, maxiter=80)
    if method == "lsmr":
        a, b = _spd()
        op = from_matrix(a)
    else:
        op, b = _rbf()
    state = solve(op, b, spec, mesh=mesh).state
    _assert_full_precision(
        lambda op, b, st: solve(op, b, spec, st, mesh=mesh).x, op, b, state
    )
