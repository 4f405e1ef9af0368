"""Laplace-approximation GP classification — the paper's experiment (§3).

Newton's method on the latent posterior Ψ(f) = log p(y|f) − ½ fᵀK⁻¹f,
with the Kuss–Rasmussen numerically-stable restructuring: each Newton
iteration solves the SPD system (paper Eq. 9–10)

    A⁽ⁱ⁾ = I + H½ K H½,       b⁽ⁱ⁾ = H½ K (H f + ∇ log p(y|f)),

whose eigenvalues lie in [1, n·max(K)/4].  The solver is pluggable —
``cholesky`` (exact, the paper's cubic baseline), ``cg``, or ``defcg``
with a :class:`repro.core.RecycleManager` carrying the deflation basis
across Newton iterations (the paper's contribution).  Since the operator
changes every Newton step (H½ moves with f), the manager recomputes
``A⁽ⁱ⁾W`` each iteration — via ``basis_matvec`` of
``RBFKernelSystemOperator`` (or of ``KernelSystemOperator`` over a dense
K) this is ONE fused multi-RHS Gram pass (each K-tile formed once for all k
recycled vectors), not k sequential matvecs; both the matrix-free kernel
matvec and the dense ``K @ V`` path batch natively.

The logistic likelihood p(y_i|f_i) = σ(y_i f_i) with y ∈ {−1, +1}.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from repro.core import (
    KernelSystemOperator,
    RecycleManager,
    SolveSpec,
    cholesky_solve,
    jacobi,
    kernel_nystrom_preconditioner,
    randomized_nystrom,
)
from repro.core import pytree as pt
from repro.core.api import solve_jit
from repro.core.operators import RBFKernelSystemOperator
from repro.core.solvers import cg_jit
from repro.gp.kernels import RBFKernel


def log_sigmoid(z):
    return -jnp.logaddexp(0.0, -z)


def logistic_quantities(f: jnp.ndarray, y: jnp.ndarray):
    """Returns (log p(y|f), ∇ log p, H diag) for the logistic likelihood."""
    pi = jax.nn.sigmoid(f)
    logp = jnp.sum(log_sigmoid(y * f))
    grad = (y + 1.0) / 2.0 - pi
    hdiag = pi * (1.0 - pi)  # = −∇∇ log p (positive)
    return logp, grad, hdiag


def newton_system(f: jnp.ndarray, y: jnp.ndarray, k_mv: Callable):
    """The Newton system at latent ``f`` (paper Eq. 9–10).

    Returns ``(sqrt_h, b, bg)``: the operator is ``A = I + H½ K H½``, the
    right-hand side ``b = H½ K bg`` with ``bg = H f + ∇ log p(y|f)``, and
    ``k_mv`` applies ``K``.
    """
    _, grad, hdiag = logistic_quantities(f, y)
    sqrt_h = jnp.sqrt(hdiag)
    bg = hdiag * f + grad
    return sqrt_h, sqrt_h * k_mv(bg), bg


def newton_step(k_mv: Callable, sqrt_h, bg, sol):
    """``(a, f)`` from the solution ``sol`` of :func:`newton_system`:
    ``a = bg − H½ sol`` and the next latent ``f = K a``."""
    a_vec = bg - sqrt_h * sol
    return a_vec, k_mv(a_vec)


@dataclasses.dataclass
class NewtonTrace:
    """Per-Newton-iteration record (mirrors the columns of paper Table 1)."""

    logp: List[float] = dataclasses.field(default_factory=list)
    psi: List[float] = dataclasses.field(default_factory=list)
    solver_iterations: List[int] = dataclasses.field(default_factory=list)
    solver_matvecs: List[int] = dataclasses.field(default_factory=list)
    solver_converged: List[bool] = dataclasses.field(default_factory=list)
    # Recovery-ladder rung each solve ended on (0 = clean; spec path only).
    solver_rungs: List[int] = dataclasses.field(default_factory=list)
    cumulative_time: List[float] = dataclasses.field(default_factory=list)
    residual_traces: List = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class LaplaceResult:
    f: jnp.ndarray
    psi: float
    logp: float
    trace: NewtonTrace
    converged: bool


def laplace_gpc(
    x: jnp.ndarray,
    y: jnp.ndarray,
    kernel: RBFKernel,
    *,
    solver: str = "defcg",
    solver_tol: float = 1e-5,
    solver_maxiter: int = 2000,
    recycle: Optional[RecycleManager] = None,
    spec: Optional[SolveSpec] = None,
    precond_key=None,
    newton_tol: float = 1.0,
    max_newton: int = 30,
    impl: str = "auto",
    block: int = 256,
    record_residuals: bool = False,
    k_dense: Optional[jnp.ndarray] = None,
    dense_matvec: bool = False,
) -> LaplaceResult:
    """Find the Laplace mode f̂ of GP classification by Newton's method.

    Args:
      solver: "cholesky" | "cg" | "defcg" (ignored when ``spec`` given).
      recycle: RecycleManager for solver="defcg" (created if None).
      spec: a :class:`repro.core.SolveSpec` — the front-door path: every
        Newton system is solved by ``repro.core.solve`` with a
        :class:`RecycleState` carried across iterations (one jitted
        computation per solve, no host-driven manager) and the spec's
        preconditioner strategy applied.  ``precond="jacobi"`` builds
        ``diag(A) = 1 + h·k(x,x)`` per iteration; ``precond="nystrom"``
        sketches the INVARIANT kernel ``K ≈ UΛUᵀ`` once
        (``precond_rank + 8`` kernel matvecs, charged to the first
        system's matvec count) and rebinds it to each system's drifting
        ``H½`` by a rank-r Woodbury solve
        (:func:`repro.core.kernel_nystrom_preconditioner`) — zero
        operator matvecs per system, exact under drift.  The spec's
        ``strategy`` rides along: ``WindowedRecombine`` runs the Newton
        sequence at the paper's zero-refresh-matvec accounting (the drift
        guard pays k matvecs only on the early, fast-moving Newton
        steps), and ``MGeometryHarmonic`` + a preconditioner extracts in
        the effective ``M⁻¹A`` geometry.
      precond_key: PRNG key for ``spec.precond="nystrom"``.
      newton_tol: stop when ΔΨ < newton_tol (paper used ΔΨ < 1).
      k_dense: pre-materialized K.  Required by the Cholesky path (built
        here if absent).  If ``dense_matvec=True`` the iterative solvers
        also use it (2n² flops/matvec — the paper's own setup, where K is
        formed once per hyperparameter setting); otherwise they use the
        fused matrix-free Gram matvec (O(n·d) memory, the TPU-scale path).
      dense_matvec: see above.

    The returned trace contains per-iteration log p(y|f), Ψ, solver
    iteration/matvec counts and cumulative wall time spent in the linear
    solver — everything paper Table 1 / Figs 2–3 report.
    """
    n = x.shape[0]
    f = jnp.zeros(n, x.dtype)
    if spec is not None:
        if spec.precond == "custom":
            raise ValueError(
                "laplace_gpc builds the preconditioner itself and has no M "
                "parameter — use spec.precond='jacobi'/'nystrom'/'none', or "
                "drive repro.core.solve directly for a custom M"
            )
        solver = "spec"
    if (solver == "cholesky" or dense_matvec) and k_dense is None:
        k_dense = kernel.gram(x)
    if dense_matvec:
        k_mv = lambda v: k_dense @ v  # noqa: E731 — stable closure for jit
    else:
        k_mv = kernel.matvec_fn(x, impl=impl, block=block)
    if solver == "defcg" and recycle is None:
        recycle = RecycleManager(k=8, ell=12, tol=solver_tol, maxiter=solver_maxiter)
    solve_state = None  # RecycleState carried across Newton systems
    k_sketch = None  # once-per-call Nyström sketch (U, lam) of K
    sketch_matvecs = 0

    trace = NewtonTrace()
    psi_prev = -jnp.inf
    x_prev = None
    solve_time = 0.0
    converged = False

    for it in range(max_newton):
        sqrt_h, b, bg = newton_system(f, y, k_mv)

        t0 = time.perf_counter()
        if solver == "cholesky":
            amat = (
                jnp.eye(n, dtype=x.dtype)
                + sqrt_h[:, None] * k_dense * sqrt_h[None, :]
            )
            xsol = cholesky_solve(amat, b)
            info = None
        else:
            if dense_matvec:
                a_op = KernelSystemOperator(k_mv, sqrt_h)
            else:
                # x rides as a pytree leaf: a closure would bake the (n, d)
                # data into every compiled solve as a constant.
                a_op = RBFKernelSystemOperator(
                    x, sqrt_h, kernel.theta, kernel.lengthscale, block, impl
                )
            if solver == "spec":
                M = None
                if spec.precond == "jacobi":
                    # diag(A) = 1 + h_i k(x_i, x_i) — exact, host-free.
                    diag_k = (
                        jnp.diag(k_dense)
                        if dense_matvec
                        else jnp.full(n, kernel.theta**2, x.dtype)
                    )
                    M = jacobi(1.0 + sqrt_h * sqrt_h * diag_k)
                elif spec.precond == "nystrom":
                    if k_sketch is None:
                        key = (
                            precond_key
                            if precond_key is not None
                            else jax.random.PRNGKey(0)
                        )
                        k_sketch = randomized_nystrom(
                            k_mv,
                            jnp.zeros(n, x.dtype),
                            rank=spec.precond_rank,
                            key=key,
                        )
                        sketch_matvecs = spec.precond_rank + 8
                    M = kernel_nystrom_preconditioner(
                        k_sketch[0], k_sketch[1], sqrt_h
                    )
                res = solve_jit(
                    a_op, b, spec, solve_state, x0=x_prev, M=M,
                    record_residuals=record_residuals,
                )
                solve_state = res.state
            elif solver == "cg":
                res = cg_jit(
                    a_op, b, x_prev,
                    tol=solver_tol, maxiter=solver_maxiter,
                    record_residuals=record_residuals,
                )
            elif solver == "defcg":
                res = recycle.solve(
                    a_op, b, x_prev,
                    tol=solver_tol, maxiter=solver_maxiter,
                    record_residuals=record_residuals,
                )
            else:
                raise ValueError(f"unknown solver={solver!r}")
            xsol, info = res.x, res.info
            rung = int(res.report.rung) if solver == "spec" else 0
        jax.block_until_ready(xsol)
        solve_time += time.perf_counter() - t0

        a_vec, f = newton_step(k_mv, sqrt_h, bg, xsol)
        x_prev = xsol

        logp_new, _, _ = logistic_quantities(f, y)
        psi = logp_new - 0.5 * pt.vdot(a_vec, f)

        trace.logp.append(float(logp_new))
        trace.psi.append(float(psi))
        trace.cumulative_time.append(solve_time)
        if info is not None:
            trace.solver_iterations.append(int(info.iterations))
            trace.solver_converged.append(bool(info.converged))
            trace.solver_rungs.append(rung)
            # The one-off Nyström sketch cost is charged to the system
            # that built it — honest a-priori-subspace accounting.
            trace.solver_matvecs.append(int(info.matvecs) + sketch_matvecs)
            sketch_matvecs = 0
            if record_residuals and info.residual_norms is not None:
                trace.residual_traces.append(
                    jnp.asarray(info.residual_norms)
                )
        else:
            trace.solver_iterations.append(n)  # direct solve ≙ full rank
            trace.solver_converged.append(True)
            trace.solver_rungs.append(0)
            trace.solver_matvecs.append(0)

        if jnp.abs(psi - psi_prev) < newton_tol:
            converged = True
            break
        psi_prev = psi

    logp_final, _, _ = logistic_quantities(f, y)
    return LaplaceResult(
        f=f, psi=float(psi), logp=float(logp_final),
        trace=trace, converged=converged,
    )


def predict_latent(
    x_train: jnp.ndarray,
    y_train: jnp.ndarray,
    f_hat: jnp.ndarray,
    x_test: jnp.ndarray,
    kernel: RBFKernel,
) -> jnp.ndarray:
    """Posterior-mean latent at test points: k(X*, X) ∇log p(y|f̂)."""
    _, grad, _ = logistic_quantities(f_hat, y_train)
    return kernel.cross(x_test, x_train) @ grad
