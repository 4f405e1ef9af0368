"""Recycled LSMR: regularized least-squares on the method-agnostic engine.

This opens the repo's second method axis (DESIGN.md §12): where CG /
def-CG solve SPD systems ``A x = b``, LSMR (Fong & Saunders 2011) solves
the regularized least-squares problem

    min_x ‖A x − b‖² + λ‖x‖²,        A: (m, n) rectangular,

via Golub–Kahan bidiagonalization of the *augmented* operator

    Â = [A; √λ·I],   b̂ = [b; 0],

which is mathematically LSQR/LSMR on the damped problem but — unlike the
textbook ``damp`` recurrences — stays exact under a **warm start**: the
initial residual ``r̂₀ = [b − A x₀; −√λ x₀]`` is carried as an explicit
``(u_m, u_n)`` block pair, so a recycled sequence converges to the TRUE
ridge solution, not the proximal one.  ``λ = 0`` statically drops the
bottom block (no dead state rides the loop).

The iteration is seated on :mod:`repro.core.engine` exactly like def-CG:
LSMR supplies only its ``step``/``state`` contract; the harness owns
tolerance logic, the sticky ``fail`` code, the stagnation detector, the
recording scan + while-loop split and the vmap-aware matvec gate.  The
three vector recurrences of an iteration (``hbar``/``x``/``h``) lower to
ONE fused pass (:func:`repro.kernels.ops.lsmr_update`).

Recycling (the paper's §2.3 transplanted to least-squares) happens in
the **normal-equations geometry**: LSMR is MINRES on
``N dx = Âᵀ r̂₀`` with ``N = AᵀA + λI`` (SPD), so a deflation basis
``W`` with products ``NW = N·W`` plays exactly the role ``(W, AW)``
plays for def-CG:

* warm start   ``x₀' = x_prev + W (WᵀNW)⁻¹ Wᵀ s₀``, ``s₀ = Âᵀ r̂(x_prev)``,
  which zeroes the W-component of the normal residual;
* per-iteration right-projection ``Q v = v − W (WᵀNW)⁻¹ (NW)ᵀ v`` — the
  bidiagonalization runs on ``Â·Q`` (adjoint ``Qᵀ·Âᵀ``), keeping the
  Krylov space N-orthogonal to ``W`` at the cost of two k×n GEMVs per
  operator application and ZERO extra A/Aᵀ products;
* window recording: the recurrence already holds ``g_j = B̂ᵀu_j``, so
  ``N̂ v_j = α_j g_j + β_{j+1} g_{j+1}`` is free — the ``(v_j, N̂v_j)``
  rows feed the SAME masked harmonic-Ritz extraction
  (:func:`repro.core.strategies.extract_next_basis_core`) def-CG uses,
  with ``(Z, AZ) = ([W; V], [NW; N̂V])``.  (For a deflated solve the
  recorded products are of the *deflated* normal operator — approximate
  in the same sense as the repo's stale-``AW`` mode; the per-system
  ``refresh_aw="exact"`` pass re-derives true ``NW`` products.)

Matvec accounting counts ``A`` and ``Aᵀ`` applications each as 1 (the
λ-block and all projections are free): init costs 1 Aᵀ (+1 A with a
warm start), every iteration exactly 2.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_factor, cho_solve

from repro.core import engine
from repro.core import operators as ops_mod
from repro.core import pytree as pt
from repro.core.solvers import (
    DEFAULT_WAW_JITTER,
    CGResult,
    RecycleData,
    SolveInfo,
    SolveStatus,
)
from repro.core.strategies import extract_next_basis_core
from repro.kernels import ops as kops

Pytree = Any


def _sym_ortho(a, b):
    """Stable Givens pair ``(c, s, r)`` with ``r = √(a² + b²)``.

    The degenerate ``r = 0`` case returns ``(0, 0, 0)`` — it only arises
    at exact termination (``α = β = 0``), which the step latches as
    converged, so the zeros never propagate.
    """
    r = jnp.sqrt(a * a + b * b)
    safe = jnp.where(r == 0.0, 1.0, r)
    return a / safe, b / safe, r


def _domain_template(A, b: Pytree):
    """The x-space pytree structure of ``A``, discovered at zero cost.

    Rectangular operators map x-space to b-space, so ``b`` alone does not
    determine the solution structure; one ``eval_shape`` of the adjoint
    (no FLOPs, no device work) does.
    """
    probe = jax.eval_shape(ops_mod.adjoint_matvec(A), b)
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), probe
    )


def _factor_wnw(w_flat, nw_flat, k: int, jitter: float):
    """Cholesky of ``WᵀNW`` — same regularization policy as def-CG's
    ``WᵀAW`` factor: relative diagonal jitter, plus UNconditional
    regularization of exactly-zero columns (clamped extraction slots /
    cold states deflate as exact no-ops; see ``solvers._factor_waw``)."""
    wnw = pt.matmul(w_flat, nw_flat.T)
    wnw = 0.5 * (wnw + wnw.T)
    dj = jnp.diag(wnw)
    tr = jnp.sum(dj)
    if jitter:
        scale = jnp.where(tr > 0, tr / k, 1.0)
        wnw = wnw + jitter * scale * jnp.eye(k, dtype=wnw.dtype)
    wnw = wnw + jnp.diag(
        jnp.where(dj == 0.0, jnp.maximum(tr / k, 1.0), 0.0)
    )
    return cho_factor(wnw)


def lsmr(
    A,
    b: Pytree,
    x0: Optional[Pytree] = None,
    W: Optional[jnp.ndarray] = None,
    NW: Optional[jnp.ndarray] = None,
    *,
    damp: float = 0.0,
    ell: int = 0,
    tol: float = 1e-6,
    atol: float = 0.0,
    maxiter: int = 1000,
    min_iters: int = 0,
    record_residuals: bool = False,
    waw_jitter: float = DEFAULT_WAW_JITTER,
    flat_recycle: bool = False,
    batch_axis: Optional[str] = None,
    stagnation_window: int = 0,
) -> CGResult:
    """(Deflated) LSMR for ``min ‖Ax − b‖² + damp·‖x‖²``.

    Args:
      A: rectangular operator.  Its adjoint resolves through
         :func:`repro.core.operators.adjoint_matvec` — an ``rmatvec``
         (:class:`LinearOperator`, :class:`DenseMatrixOperator`,
         :class:`GaussNewtonOperator`) when present, else the operator's
         own matvec (this repo's symmetric-by-contract default).
      b: right-hand side (range-space pytree; its structure may differ
         from the solution's — the domain structure is discovered from
         the adjoint).
      x0: warm start.  Handled EXACTLY (explicit augmented residual
         blocks), so warm-started ridge solves converge to the same
         minimizer as cold ones.
      W, NW: optional flat ``(k, n)`` deflation basis and its
         normal-operator products ``(AᵀA + damp·I)·W`` — the deflated
         method (``SolveSpec.method="deflsmr"``).  Zero rows deflate as
         exact no-ops, so a cold state is valid.
      damp: the ridge shift λ ≥ 0 (static; selects the augmented-block
         code path at trace time).
      ell: number of leading ``(v, N̂v)`` pairs to record for the
         harmonic-Ritz extraction — zero extra matvecs, same contract as
         def-CG's ``(P, AP)`` window.
      tol, atol: convergence is declared on the normal residual
         ``‖Âᵀr̂‖ ≤ max(tol·‖Âᵀr̂₀‖, atol)`` — the quantity LSMR
         monotonically decreases, reported as ``info.residual_norm``.
      min_iters, record_residuals, waw_jitter, flat_recycle, batch_axis,
      stagnation_window: as in :func:`repro.core.solvers.defcg`.

    Returns ``CGResult``; ``recycle.P``/``recycle.AP`` hold the
    ``(v, N̂v)`` window (``alpha``/``beta`` are None — LSMR's extraction
    needs no recurrence coefficients).
    """
    if damp < 0.0:
        raise ValueError(f"damp must be >= 0, got {damp}")
    has_shift = damp > 0.0
    sqrt_damp = float(damp) ** 0.5  # repro-lint: disable=host-sync-in-trace — damp is a static Python scalar (lsmr_jit static argname)

    b_flat, unravel_b = pt.ravel_vector(b)
    if x0 is not None:
        x_flat, unravel_x = pt.ravel_vector(x0)
    else:
        x_flat, unravel_x = pt.ravel_vector(_domain_template(A, b))

    A_flat = engine.flat_operator(A, unravel_x)
    At_flat = engine.flat_operator(
        ops_mod.adjoint_matvec(A), unravel_b
    )

    deflating = W is not None
    if deflating:
        k = W.shape[0]
        nw_flat = NW if NW is not None else jnp.zeros_like(W)
        wnw_cho = _factor_wnw(W, nw_flat, k, waw_jitter)
        winv = cho_solve(wnw_cho, jnp.eye(k, dtype=W.dtype))

        def q_apply(vv):
            # Right projection: N-orthogonalize against W.
            return vv - pt.matmul(pt.matmul(winv, pt.matmul(nw_flat, vv)), W)

        def qt_apply(gg):
            # Its transpose, applied to adjoint products.
            return gg - pt.matmul(pt.matmul(winv, pt.matmul(W, gg)), nw_flat)
    else:
        q_apply = qt_apply = lambda z: z  # noqa: E731

    # -- initial augmented residual r̂₀ = [b − A x₀; −√λ x₀] --------------
    init_mv = jnp.int32(1)  # the Âᵀu₁ below
    if x0 is not None:
        r_m = b_flat - A_flat(x_flat)
        init_mv = init_mv + 1
    else:
        r_m = b_flat
    u_n0 = -sqrt_damp * x_flat if has_shift else None

    beta_sq = pt.vdot(r_m, r_m)
    if has_shift:
        beta_sq = beta_sq + pt.vdot(u_n0, u_n0)
    beta1 = jnp.sqrt(beta_sq)
    safe_b = jnp.where(beta1 == 0.0, 1.0, beta1)
    u_m0 = r_m / safe_b
    u_n0 = (u_n0 / safe_b) if has_shift else None

    g0 = At_flat(u_m0)
    if has_shift:
        g0 = g0 + sqrt_damp * u_n0
    g0 = qt_apply(g0)
    alpha1 = jnp.sqrt(pt.vdot(g0, g0))
    safe_a = jnp.where(alpha1 == 0.0, 1.0, alpha1)
    v0 = g0 / safe_a

    normar0 = alpha1 * beta1
    threshold = jnp.maximum(tol * normar0, atol)
    diverged_at = 1e8 * normar0
    trace0 = engine.trace_init(normar0, maxiter, record_residuals)
    fail0 = engine.initial_fail(normar0)
    stag0 = engine.stagnation_init(normar0, stagnation_window)
    one = jnp.ones((), b_flat.dtype)

    def active_fn(state):
        j, zetabar, fail = state[0], state[7], state[16]
        keep_going = (jnp.abs(zetabar) > threshold) | (j < min_iters)
        return (j < maxiter) & keep_going & (fail == 0)

    def step(state, active, gate_matvec):
        """One LSMR iteration; ``active=False`` freezes the state.

        Same freezing policy as def-CG's step: only the two operator
        applications hide behind the harness's ``cond`` gate — the cheap
        vector passes run as masked no-ops.
        """
        (j, x, u_m, u_n, v, g, alpha, zetabar, alphabar, rho, rhobar,
         cbar, sbar, h, hbar, trace, fail, stag) = state
        v_in = v

        # -- bidiagonalization: β u⁺ = Â(Qv) − α u ----------------------
        qv = q_apply(v)
        if gate_matvec:
            av = engine.gated_matvec(
                A_flat, qv, active, batch_axis, out_like=u_m
            )
        else:
            av = A_flat(qv)
        u_m_new = av - alpha * u_m
        beta_sq_ = pt.vdot(u_m_new, u_m_new)
        if has_shift:
            u_n_new = sqrt_damp * qv - alpha * u_n
            beta_sq_ = beta_sq_ + pt.vdot(u_n_new, u_n_new)
        beta_new = jnp.sqrt(beta_sq_)
        sb = jnp.where(beta_new == 0.0, 1.0, beta_new)
        u_m_new = u_m_new / sb
        if has_shift:
            u_n_new = u_n_new / sb

        # -- α v⁺ = Qᵀ(Âᵀu⁺) − β v --------------------------------------
        if gate_matvec:
            atu = engine.gated_matvec(
                At_flat, u_m_new, active, batch_axis, out_like=v
            )
        else:
            atu = At_flat(u_m_new)
        g_new = atu + sqrt_damp * u_n_new if has_shift else atu
        g_new = qt_apply(g_new)
        # The window row, free from recurrence quantities:
        #   N̂ v_j = B̂ᵀB̂ v_j = α_j·B̂ᵀu_j + β_{j+1}·B̂ᵀu_{j+1}.
        nv = alpha * g + beta_new * g_new
        w_vec = g_new - beta_new * v
        alpha_new = jnp.sqrt(pt.vdot(w_vec, w_vec))
        sa = jnp.where(alpha_new == 0.0, 1.0, alpha_new)
        v_new = w_vec / sa

        # -- the two Givens rotations (Fong & Saunders 2011, §2.2; the
        # λ-rotation is statically absent — λ lives in Â itself) --------
        rho_old, rhobar_old = rho, rhobar
        c, s, rho_new = _sym_ortho(alphabar, beta_new)
        thetanew = s * alpha_new
        alphabar_new = c * alpha_new
        thetabar = sbar * rho_new
        cbar_new, sbar_new, rhobar_new = _sym_ortho(
            cbar * rho_new, thetanew
        )
        zeta = cbar_new * zetabar
        zetabar_new = -sbar_new * zetabar

        # -- fused vector triple: hbar/x/h in one pass ------------------
        sr = jnp.where(rho_new == 0.0, 1.0, rho_new)
        srb = jnp.where(rhobar_new == 0.0, 1.0, rhobar_new)
        c0 = thetabar * rho_new / (rho_old * rhobar_old)
        c1 = zeta / (sr * srb)
        c2 = thetanew / sr
        x_new, hbar_new, h_new = kops.lsmr_update(
            x, hbar, h, v_new, c0, c1, c2
        )

        # Exact termination: a zero β or α means Âᵀr̂ has been driven to
        # (numerical) zero — latch the convergence quantity there.
        exact = (beta_new == 0.0) | (alpha_new == 0.0)
        zetabar_new = jnp.where(exact, 0.0, zetabar_new)
        normar_new = jnp.abs(zetabar_new)

        fail = jnp.where(
            (fail == 0) & active & (~jnp.isfinite(normar_new)),
            SolveStatus.BREAKDOWN_NONFINITE,
            fail,
        ).astype(jnp.int32)
        fail = jnp.where(
            (fail == 0) & active & (normar_new > diverged_at),
            SolveStatus.STAGNATED,
            fail,
        ).astype(jnp.int32)
        if stag is not None:
            stag, fail = engine.stagnation_update(
                stag, normar_new, fail, active, stagnation_window
            )
        if trace is not None:
            old = trace[j + 1]
            trace = trace.at[j + 1].set(
                jnp.where(active, normar_new, old)
            )

        sel = lambda new, cur: jnp.where(active, new, cur)  # noqa: E731
        state_new = (
            j + active.astype(j.dtype),
            sel(x_new, x),
            sel(u_m_new, u_m),
            sel(u_n_new, u_n) if has_shift else None,
            sel(v_new, v),
            sel(g_new, g),
            sel(alpha_new, alpha),
            sel(zetabar_new, zetabar),
            sel(alphabar_new, alphabar),
            sel(rho_new, rho),
            sel(rhobar_new, rhobar),
            sel(cbar_new, cbar),
            sel(sbar_new, sbar),
            sel(h_new, h),
            sel(hbar_new, hbar),
            trace,
            fail,
            stag,
        )
        return state_new, (v_in, nv)

    state = (
        jnp.int32(0), x_flat, u_m0, u_n0, v0, g0, alpha1,
        normar0, alpha1, one, one, one, jnp.zeros((), b_flat.dtype),
        v0, jnp.zeros_like(v0), trace0, fail0, stag0,
    )
    state, rows = engine.run_recording_loop(
        step, active_fn, state, ell=ell
    )
    j, x = state[0], state[1]
    zetabar, trace, fail = state[7], state[15], state[16]
    normar = jnp.abs(zetabar)

    if deflating:
        # The Krylov correction lives in the Q-subspace: one exit-time
        # projection of the accumulated update (two k×n GEMVs, once).
        x = x_flat + q_apply(x - x_flat)

    converged = normar <= threshold
    info = SolveInfo(
        iterations=j,
        converged=converged,
        residual_norm=normar,
        matvecs=init_mv + 2 * j,
        residual_norms=trace,
        breakdown=fail > 0,
        status=engine.exit_status(converged, fail),
    )
    recycle = None
    if ell > 0:
        v_rows, nv_rows = rows
        if flat_recycle:
            recycle = RecycleData(
                P=v_rows, AP=nv_rows, stored=jnp.minimum(j, ell),
            )
        else:
            recycle = RecycleData(
                P=pt.unravel_basis(v_rows, unravel_x),
                AP=pt.unravel_basis(nv_rows, unravel_x),
                stored=jnp.minimum(j, ell),
            )
    return CGResult(x=unravel_x(x), info=info, recycle=recycle)


lsmr_jit = jax.jit(
    lsmr,
    static_argnames=(
        "damp",
        "ell",
        "tol",
        "atol",
        "maxiter",
        "min_iters",
        "record_residuals",
        "waw_jitter",
        "flat_recycle",
        "batch_axis",
        "stagnation_window",
    ),
)


# ---------------------------------------------------------------------------
# Recycled least-squares sequences
# ---------------------------------------------------------------------------


def _normal_basis_flat(A, unravel_x, w_flat, damp: float):
    """``(AᵀA + damp·I) @ W`` for a flat ``(k, n)`` basis — one multi-RHS
    forward pass and one adjoint pass (2k accounted matvecs)."""
    basis = pt.unravel_basis(w_flat, unravel_x)
    aw = ops_mod.apply_to_basis(A, basis)
    nw = pt.ravel_basis(
        ops_mod.apply_to_basis(ops_mod.adjoint_matvec(A), aw)
    )
    if damp > 0.0:
        nw = nw + damp * w_flat
    return nw


def _one_recycled_lsmr(
    A,
    b: Pytree,
    x0: Optional[Pytree],
    w: jnp.ndarray,
    nw_carry: jnp.ndarray,
    unravel_x,
    *,
    k: int,
    ell: int,
    damp: float,
    tol: float,
    atol: float,
    maxiter: int,
    select: str,
    waw_jitter: float,
    refresh_aw: str,
    record_residuals: bool = False,
    batch_axis: Optional[str] = None,
    stagnation_window: int = 0,
):
    """ONE system of the recycled LSMR step, on flat state.

    The least-squares mirror of ``recycle._one_recycled_solve`` and the
    single source of per-system semantics shared by the front-door
    :func:`repro.core.solve` and :func:`solve_sequence_lsmr`'s scan body:

    1. per-system basis refresh: ``refresh_aw="exact"`` re-derives
       ``NW = (AᵀA + λI)W`` under THIS system's operator (2k accounted
       matvecs); ``"stale"`` reuses the carried products (zero matvecs,
       approximate deflation — the paper's cheap mode);
    2. deflated warm start ``x₀' = x_prev + W (WᵀNW)⁻¹ Wᵀ s₀`` with
       ``s₀ = Âᵀr̂(x_prev)`` (2 matvecs; exact no-op on a cold basis);
    3. the deflated solve (:func:`lsmr` with the N-orthogonal
       projection);
    4. extraction: the recorded ``(v, N̂v)`` window and the carried
       ``(W, NW)`` stack through the SAME masked harmonic-Ritz core
       def-CG uses — zero extra matvecs.

    A broken or non-finite outcome retires the basis (zeroed carry, the
    sequence re-bootstraps cold) and falls the solution back to the
    finite warm start — same terminal policy as the def-CG ladder's
    last resort, without the ladder (LSMR has no SPD breakdown modes;
    nonfinite input is the realistic failure here).

    Returns ``(x, info, w_next, nw_next, theta, rung)`` with ``theta``
    None when ``ell == 0`` and ``rung`` always 0 (kept for carry-shape
    parity with the def-CG path).
    """
    b_flat, _ = pt.ravel_vector(b)
    A_flat = engine.flat_operator(A, unravel_x)
    At_flat = engine.flat_operator(
        ops_mod.adjoint_matvec(A), pt.ravel_vector(b)[1]
    )

    refresh_charge = jnp.int32(0)
    if refresh_aw == "exact":
        nw_used = _normal_basis_flat(A, unravel_x, w, damp)
        refresh_charge = refresh_charge + 2 * k
    else:
        nw_used = nw_carry

    # Deflated warm start in x-space (s₀ = Aᵀ(b − A x_prev) − λ x_prev).
    x_prev = (
        jnp.zeros((w.shape[1],), b_flat.dtype)
        if x0 is None
        else pt.ravel(x0)
    )
    r_m = b_flat - A_flat(x_prev)
    s0 = At_flat(r_m)
    if damp > 0.0:
        s0 = s0 - damp * x_prev
    wnw_cho = _factor_wnw(w, nw_used, k, waw_jitter)
    cvec = cho_solve(wnw_cho, pt.matmul(w, s0))
    x0p = x_prev + pt.matmul(cvec, w)
    guess_charge = jnp.int32(2)

    result = lsmr(
        A,
        b,
        unravel_x(x0p),
        W=w,
        NW=nw_used,
        damp=damp,
        ell=ell,
        tol=tol,
        atol=atol,
        maxiter=maxiter,
        record_residuals=record_residuals,
        waw_jitter=waw_jitter,
        flat_recycle=True,
        batch_axis=batch_axis,
        stagnation_window=stagnation_window,
    )
    info = result.info
    info = info._replace(
        matvecs=info.matvecs + refresh_charge + guess_charge
    )

    if ell > 0:
        w2, nw2, theta, _ = extract_next_basis_core(
            w, nw_used, result.recycle.P, result.recycle.AP,
            result.recycle.stored, k, select=select,
        )
    else:
        w2, nw2, theta = w, nw_used, None

    # Terminal retirement: never hand a poisoned basis (or non-finite
    # coordinates) to the next system.
    x_flat = pt.ravel(result.x)
    x_safe = jnp.where(jnp.isfinite(x_prev), x_prev, 0.0)
    x_flat = jnp.where(jnp.all(jnp.isfinite(x_flat)), x_flat, x_safe)
    retire = (
        info.breakdown
        | ~jnp.all(jnp.isfinite(w2))
        | ~jnp.all(jnp.isfinite(nw2))
    )
    w2 = jnp.where(retire, 0.0, w2)
    nw2 = jnp.where(retire, 0.0, nw2)
    if theta is not None:
        theta = jnp.where(retire, 0.0, theta)
    return (
        unravel_x(x_flat), info, w2, nw2, theta, jnp.int32(0),
    )


def solve_sequence_lsmr(
    systems: Any,
    b_seq: Pytree,
    W0: Optional[jnp.ndarray] = None,
    NW0: Optional[jnp.ndarray] = None,
    *,
    k: int,
    ell: int,
    damp: float = 0.0,
    make_operator: Optional[Callable[[Any], Any]] = None,
    tol: float = 1e-6,
    atol: float = 0.0,
    maxiter: int = 1000,
    select: str = "largest",
    waw_jitter: float = DEFAULT_WAW_JITTER,
    refresh_aw: str = "exact",
    carry_x: bool = False,
    batch_axis: Optional[str] = None,
    stagnation_window: int = 0,
    x_prev0: Optional[jnp.ndarray] = None,
):
    """Recycled LSMR across a sequence of least-squares problems.

    The least-squares twin of :func:`repro.core.recycle.solve_sequence`:
    one ``lax.scan`` carrying the flat ``(W, NW)`` recycled basis (and
    optionally the warm-start solution) across systems — zero host syncs,
    the whole sequence jits as one XLA computation.  Returns the same
    :class:`repro.core.recycle.SequenceResult` shape, with the ``AW``
    slot holding the normal-operator products ``NW``.
    """
    from repro.core.recycle import SequenceResult

    if refresh_aw not in ("exact", "stale"):
        raise ValueError(f"unknown refresh_aw={refresh_aw!r}")
    make_op = make_operator if make_operator is not None else (lambda s: s)

    b0 = jax.tree_util.tree_map(lambda l: l[0], b_seq)
    A0 = make_op(jax.tree_util.tree_map(lambda l: l[0], systems))
    x_tmpl = _domain_template(A0, b0)
    x0_flat, unravel_x = pt.ravel_vector(x_tmpl)
    n = x0_flat.shape[0]
    dtype = x0_flat.dtype

    w_init = jnp.zeros((k, n), dtype) if W0 is None else W0.astype(dtype)
    nw_init = (
        jnp.zeros((k, n), dtype)
        if (NW0 is None or W0 is None)
        else NW0.astype(dtype)
    )
    x_init = (
        jnp.zeros((n,), dtype) if x_prev0 is None else x_prev0.astype(dtype)
    )

    def body(carry, xs):
        w, nw, x_prev = carry
        sys_i, b = xs
        A = make_op(sys_i)
        x0 = unravel_x(x_prev) if carry_x else None
        x_out, info, w2, nw2, theta, rung = _one_recycled_lsmr(
            A,
            b,
            x0,
            w,
            nw,
            unravel_x=unravel_x,
            k=k,
            ell=ell,
            damp=damp,
            tol=tol,
            atol=atol,
            maxiter=maxiter,
            select=select,
            waw_jitter=waw_jitter,
            refresh_aw=refresh_aw,
            batch_axis=batch_axis,
            stagnation_window=stagnation_window,
        )
        return (w2, nw2, pt.ravel(x_out)), (x_out, info, theta, rung)

    (w_fin, nw_fin, _), (xs_out, infos, thetas, rungs) = jax.lax.scan(
        body, (w_init, nw_init, x_init), (systems, b_seq)
    )
    return SequenceResult(
        x=xs_out, info=infos, theta=thetas, W=w_fin, AW=nw_fin,
        drift=jnp.zeros((), dtype), rung=rungs,
    )


solve_sequence_lsmr_jit = jax.jit(
    solve_sequence_lsmr,
    static_argnames=(
        "k",
        "ell",
        "damp",
        "make_operator",
        "tol",
        "atol",
        "maxiter",
        "select",
        "waw_jitter",
        "refresh_aw",
        "carry_x",
        "batch_axis",
        "stagnation_window",
    ),
)
