"""Laplace-approximation GP classification — the paper's experiment (§3).

Newton's method on the latent posterior Ψ(f) = log p(y|f) − ½ fᵀK⁻¹f,
with the Kuss–Rasmussen numerically-stable restructuring: each Newton
iteration solves the SPD system (paper Eq. 9–10)

    A⁽ⁱ⁾ = I + H½ K H½,       b⁽ⁱ⁾ = H½ K (H f + ∇ log p(y|f)),

whose eigenvalues lie in [1, n·max(K)/4].  Each system is solved either
exactly by a dense Cholesky factorization (``spec=None``, the paper's
cubic baseline) or through the front door ``repro.core.solve`` per a
:class:`repro.core.SolveSpec` — ``method="defcg"`` carries the recycled
deflation basis across Newton iterations in a ``RecycleState`` (the
paper's contribution).  Since the operator changes every Newton step (H½
moves with f), an exact refresh recomputes ``A⁽ⁱ⁾W`` each iteration — via
``basis_matvec`` of ``RBFKernelSystemOperator`` (or of
``KernelSystemOperator`` over a dense K) this is ONE fused multi-RHS Gram
pass (each K-tile formed once for all k recycled vectors), not k
sequential matvecs.

The logistic likelihood p(y_i|f_i) = σ(y_i f_i) with y ∈ {−1, +1}.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import (
    KernelSystemOperator,
    SolveSpec,
    cholesky_solve,
    jacobi,
    kernel_nystrom_preconditioner,
    randomized_nystrom,
)
from repro.core import pytree as pt
from repro.core import sharded
from repro.core.api import solve_jit
from repro.core.operators import RBFKernelSystemOperator
from repro.gp.kernels import DenseMatvec, RBFKernel
from repro.runtime import spans

# Every blocking read of a fit waits in a span of this name.
WAIT = "laplace.wait"
_fit_ids = itertools.count(1)


def log_sigmoid(z):
    return -jnp.logaddexp(0.0, -z)


def logistic_quantities(f: jnp.ndarray, y: jnp.ndarray):
    """Returns (log p(y|f), ∇ log p, H diag) for the logistic likelihood."""
    pi = jax.nn.sigmoid(f)
    logp = jnp.sum(log_sigmoid(y * f))
    grad = (y + 1.0) / 2.0 - pi
    hdiag = pi * (1.0 - pi)  # = −∇∇ log p (positive)
    return logp, grad, hdiag


@jax.jit
def newton_system(f: jnp.ndarray, y: jnp.ndarray, k_mv: Callable):
    """The Newton system at latent ``f`` (paper Eq. 9–10).

    Returns ``(sqrt_h, b, bg)``: the operator is ``A = I + H½ K H½``, the
    right-hand side ``b = H½ K bg`` with ``bg = H f + ∇ log p(y|f)``, and
    ``k_mv`` applies ``K``.  One compiled program; ``k_mv`` is a pytree
    callable (:class:`repro.gp.kernels.GramMatvec` or ``DenseMatvec``)
    whose data are arguments, not constants.
    """
    _, grad, hdiag = logistic_quantities(f, y)
    sqrt_h = jnp.sqrt(hdiag)
    bg = hdiag * f + grad
    return sqrt_h, sqrt_h * k_mv(bg), bg


@jax.jit
def newton_step(k_mv: Callable, sqrt_h, bg, sol):
    """``(a, f)`` from the solution ``sol`` of :func:`newton_system`:
    ``a = bg − H½ sol`` and the next latent ``f = K a``.  One compiled
    program, like :func:`newton_system`."""
    a_vec = bg - sqrt_h * sol
    return a_vec, k_mv(a_vec)


@jax.jit
def _readout(f, y, a_vec, psi_prev, newton_tol, counts):
    """A Newton system's scalars in one array, for one device read.

    Returns ``(row, psi)``: ``row`` is ``[log p(y|f), Ψ, |Ψ − Ψ_prev| <
    newton_tol, *counts]`` in ``f``'s dtype, ``psi`` the next system's
    ``psi_prev`` (left on the device).
    """
    logp, _, _ = logistic_quantities(f, y)
    psi = logp - 0.5 * pt.vdot(a_vec, f)
    done = jnp.abs(psi - psi_prev) < newton_tol
    row = [logp, psi, done] + [jnp.asarray(c) for c in counts]
    return jnp.stack([v.astype(f.dtype) for v in row]), psi


def place(mesh, x: jnp.ndarray, y: jnp.ndarray):
    """``(x, y, moved_bytes)``: the data's rows split over ``mesh``'s
    ``"solve"`` axis, as :func:`laplace_gpc` runs them there.  Arrays
    already placed so are returned as they are; ``moved_bytes`` counts
    what had to move."""
    rows = NamedSharding(mesh, P(sharded.SOLVE_AXIS, None))
    vec = NamedSharding(mesh, sharded.vector_spec())
    out, moved = [], 0
    for a, want in ((x, rows), (y, vec)):
        if not (isinstance(a, jax.Array)
                and a.sharding.is_equivalent_to(want, a.ndim)):
            moved += a.nbytes
            a = jax.device_put(a, want)
        out.append(a)
    return out[0], out[1], moved


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
    spec: Optional[SolveSpec],
    precond_key=None,
    newton_tol: float = 1.0,
    max_newton: int = 30,
    impl: str = "auto",
    block: int = 256,
    record_residuals: bool = False,
    k_dense: Optional[jnp.ndarray] = None,
    dense_matvec: bool = False,
    mesh=None,
) -> LaplaceResult:
    """Find the Laplace mode f̂ of GP classification by Newton's method.

    Args:
      spec: required.  ``None`` solves every Newton system exactly by a
        dense Cholesky factorization of ``A`` (the paper's cubic
        baseline).  A :class:`repro.core.SolveSpec` solves each by
        ``repro.core.solve`` with a :class:`RecycleState` carried across
        iterations (one jitted computation per solve) and the spec's
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
      k_dense: pre-materialized K.  Used by the Cholesky path (built
        here if absent).  If ``dense_matvec=True`` the iterative solvers
        also use it (2n² flops/matvec — the paper's own setup, where K is
        formed once per hyperparameter setting); otherwise they use the
        fused matrix-free Gram matvec (O(n·d) memory, the TPU-scale path).
      dense_matvec: see above.
      mesh: a 1-D ``"solve"`` mesh (:func:`repro.launch.mesh.make_solve_mesh`)
        to split the fit over: ``x``, ``y`` and ``f`` are row-sharded on
        it (:func:`place`; data already placed there is not moved), each
        system is solved by the sharded def-CG (``solve(..., mesh=)``)
        with its :class:`RecycleState` and warm start carried sharded from
        system to system, and the driver's two Gram passes a system run
        split over the chips (:class:`repro.gp.kernels.GramMatvec`).
        Needs ``spec`` with ``precond="none"`` and the fused kernel (no
        ``dense_matvec``); the sharded engine has no recovery ladder, so
        every system reports rung 0.  ``None`` (the default) runs on one
        device.

    The returned trace contains per-iteration log p(y|f), Ψ, solver
    iteration/matvec counts and cumulative wall time spent in the linear
    solver (the ``laplace.solve`` spans) — everything paper Table 1 /
    Figs 2–3 report.

    Spans (:mod:`repro.runtime.spans`): one ``laplace.fit`` (attrs
    ``fit``, a process-wide id, ``systems``, ``syncs`` and ``shards``, 1
    or the mesh's size) holds, on a mesh, one ``laplace.place`` (attr
    ``moved_bytes``, 0 when the data was placed already) and one
    ``laplace.system`` per Newton system, which holds
    ``laplace.newton_system``, ``laplace.solve`` and
    ``laplace.newton_step``, each one compiled program.  Every device
    read is one ``laplace.wait`` span: 2 per system (the solution, inside
    ``laplace.solve``, and one readout of log p, Ψ, the ΔΨ test and the
    solve's iterations, converged, matvecs and rung) and none per fit (the
    returned Ψ and log p are the last readout's), but one inside
    ``laplace.place`` when data had to move.
    """
    n = x.shape[0]
    f = jnp.zeros(n, x.dtype)
    if spec is not None and spec.precond == "custom":
        raise ValueError(
            "laplace_gpc builds the preconditioner itself and has no M "
            "parameter — use spec.precond='jacobi'/'nystrom'/'none', or "
            "drive repro.core.solve directly for a custom M"
        )
    if mesh is not None and (spec is None or dense_matvec):
        raise ValueError(
            "laplace_gpc(mesh=) solves through the sharded front door over "
            "the fused Gram kernel: pass a SolveSpec and leave dense_matvec "
            "off"
        )
    if (spec is None or dense_matvec) and k_dense is None:
        k_dense = kernel.gram(x)
    if dense_matvec:
        k_mv = DenseMatvec(k_dense)
    elif mesh is None:
        k_mv = kernel.matvec_fn(x, impl=impl, block=block)
    solve_state = None  # RecycleState carried across Newton systems
    k_sketch = None  # once-per-call Nyström sketch (U, lam) of K
    sketch_matvecs = 0

    def solve(sqrt_h, b, x_prev):
        """One Newton system's solve: ``(x, info, rung)``."""
        nonlocal solve_state, k_sketch, sketch_matvecs
        if spec is None:
            amat = (
                jnp.eye(n, dtype=x.dtype)
                + sqrt_h[:, None] * k_dense * sqrt_h[None, :]
            )
            return cholesky_solve(amat, b), None, 0
        if dense_matvec:
            a_op = KernelSystemOperator(k_mv, sqrt_h)
        else:
            # x rides as a pytree leaf: a closure would bake the (n, d)
            # data into every compiled solve as a constant.
            a_op = RBFKernelSystemOperator(
                x, sqrt_h, kernel.theta, kernel.lengthscale, block, impl
            )
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
            record_residuals=record_residuals, mesh=mesh,
        )
        solve_state = res.state
        return res.x, res.info, res.report.rung

    trace = NewtonTrace()
    psi_prev = np.asarray(-np.inf, x.dtype)
    x_prev = None
    solve_time = 0.0
    converged = False

    shards = 1 if mesh is None else mesh.shape[sharded.SOLVE_AXIS]
    with spans.span(
        "laplace.fit", fit=next(_fit_ids), systems=0, shards=shards
    ) as fit:
        if mesh is not None:
            with spans.span("laplace.place") as placed:
                x, y, moved = place(mesh, x, y)
                placed.attrs["moved_bytes"] = moved
                if moved:
                    spans.block((x, y), WAIT)
                f = jnp.zeros(n, x.dtype, device=NamedSharding(
                    mesh, sharded.vector_spec()
                ))
            k_mv = kernel.matvec_fn(x, impl=impl, block=block, mesh=mesh)
        for it in range(max_newton):
            with spans.span("laplace.system", syncs=0):
                with spans.span("laplace.newton_system"):
                    sqrt_h, b, bg = newton_system(f, y, k_mv)
                with spans.span("laplace.solve") as solved:
                    xsol, info, rung = solve(sqrt_h, b, x_prev)
                    spans.block(xsol, WAIT)
                solve_time += solved.seconds
                with spans.span("laplace.newton_step"):
                    a_vec, f = newton_step(k_mv, sqrt_h, bg, xsol)
                x_prev = xsol

                counts = () if info is None else (
                    info.iterations, info.converged, info.matvecs, rung
                )
                row, psi_prev = _readout(
                    f, y, a_vec, psi_prev, newton_tol, counts
                )
                row = spans.fetch(row, WAIT)
                trace.logp.append(float(row[0]))
                trace.psi.append(float(row[1]))
                trace.cumulative_time.append(solve_time)
                fit.attrs["systems"] += 1
                if info is not None:
                    iterations, conv, matvecs, rung = row[3:]
                    trace.solver_iterations.append(int(iterations))
                    trace.solver_converged.append(bool(conv))
                    trace.solver_rungs.append(int(rung))
                    # The one-off Nyström sketch cost is charged to the
                    # system that built it — honest a-priori-subspace
                    # accounting.
                    trace.solver_matvecs.append(int(matvecs) + sketch_matvecs)
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

                if row[2]:
                    converged = True
                    break

    # Log p at the final f is the last readout's.
    return LaplaceResult(
        f=f, psi=trace.psi[-1], logp=trace.logp[-1], trace=trace,
        converged=converged,
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
