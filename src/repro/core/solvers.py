"""Iterative SPD solvers: CG, preconditioned CG, and deflated CG.

This file implements the paper's Algorithm 1 (Saad et al.'s deflated
conjugate gradient) as a jit-able, pytree-native, shardable solver:

* vectors are arbitrary pytrees (``repro.core.pytree``) at the API; the
  *inner loop* runs on a contiguous flat ``(n,)`` vector — each solve packs
  its pytree once (``pt.ravel_vector``), iterates on flat state, and
  unpacks once at exit (the flat-engine fast path, DESIGN.md §8);
* ``A`` is any matrix-free operator (``repro.core.operators``);
* the main iteration is driven by the method-agnostic harness
  (:mod:`repro.core.engine`): CG and def-CG supply only their per-method
  ``step``/``state`` contract, while the harness owns tolerance
  resolution, breakdown classification, stagnation tracking, the
  recording scan + while-loop split, and the vmap-aware matvec gate —
  the whole solve lowers to a single XLA computation that pjit can shard
  across a pod;
* the non-matvec vector work of an iteration lowers to two fused passes
  (``repro.kernels.ops.fused_cg_update`` / ``fused_deflate_direction``:
  Pallas kernels on TPU, fused-jnp elsewhere) instead of ~8 separate HBM
  sweeps — in the memory-bound regime the paper targets this, not the
  matvec, is the bottleneck;
* the first ``ell`` search directions and their ``A``-products are recorded
  into fixed-size ring buffers, which is all the harmonic-Ritz recycling
  step (``repro.core.recycle``) needs — zero extra matvecs, exactly the
  "readily available quantities" trick of the paper (§2.3, adapted: we
  store ``P``/``AP`` directly and form ``F``/``G`` by two tall-skinny GEMMs,
  which is MXU-friendly; see DESIGN.md §8).

Deflation (the lines that differ from textbook CG, cf. paper Alg. 1
lines 3 & 11):

    x0  = x_{-1} + W (WᵀAW)⁻¹ Wᵀ r_{-1}          # Wᵀ r0 = 0
    p0  = r0 − W μ0,        WᵀAW μ0 = WᵀA r0
    p_j = β p_{j-1} + r_j − W μ_j,  WᵀAW μ_j = WᵀA r_j

``WᵀA r`` is evaluated as ``(AW)ᵀ r`` (A symmetric) and fused into the
residual-update pass, so the per-iteration deflation overhead is one k×k
triangular solve plus the ``W μ`` combine inside the direction pass —
O(nk) flops and *no* additional collectives beyond the two GEMV psums.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_factor, cho_solve

from repro.core import engine
from repro.core import operators as ops_mod
from repro.core import pytree as pt
from repro.core.engine import (  # noqa: F401  (re-exported API surface)
    SolveInfo,
    SolveStatus,
)
from repro.kernels import ops as kops

Pytree = Any

# The ONE waw_jitter default, carried by ``repro.core.api.SolveSpec`` and
# referenced (never re-written as a literal) by every solve path.  Keep it
# SMALL: jitter ≳1e-8 reinjects un-deflated W-components each iteration and
# makes def-CG diverge with a well-converged Ritz basis (measured; see the
# ``waw_jitter`` arg of :func:`defcg`).
DEFAULT_WAW_JITTER = 1e-12

# The ONE noise floor for drift-guard thresholds, in units of the working
# dtype's eps: drift measurements (residual differences, gram asymmetry)
# carry rounding-level terms even for an exactly unchanged operator
# (~1e-16 in f64, ~1e-7 in f32), and a threshold below this floor would
# buy k-matvec refreshes on pure noise.  Shared by defcg's in-solve
# guard and every strategy-layer comparison (``repro.core.strategies``).
DRIFT_NOISE_FLOOR_EPS = 500.0

# Backwards-compatible aliases: the loop scaffolding moved to
# repro.core.engine (the method-agnostic harness); these names stay
# importable from here because recycle/api/serve grew up against them.
_STAGNATION_RTOL = engine.STAGNATION_RTOL
_classify_breakdown = engine.classify_breakdown
_exit_status = engine.exit_status
_tolerances = engine.tolerances
_flat_operator = engine.flat_operator


class RecycleData(NamedTuple):
    """Stored Krylov quantities — the solver→strategy window handoff.

    This is the contract between the def-CG scan phase and the
    :mod:`repro.core.strategies` layer: everything a recycle strategy may
    consume at the end-of-solve transition is recorded here, all of it
    "readily available" (paper §2.3) — zero extra matvecs.
    """

    P: Pytree  # basis of ell search directions
    AP: Pytree  # their A-products
    stored: jax.Array  # int32: valid columns (may be < ell on early converge)
    # CG recurrence coefficients of the recorded iterations: ``alpha[j]``
    # is the step size taken along ``P[j]``; ``beta[j]`` the direction
    # coefficient computed at the END of iteration j (it builds p_{j+1}).
    # Rows past ``stored`` are zero.  None when ``ell == 0``.
    alpha: Optional[jax.Array] = None  # (ell,)
    beta: Optional[jax.Array] = None  # (ell,)
    # The (k, n) basis products the solve ACTUALLY deflated with — set
    # only under ``stale_guard`` (flat recycle), where the in-solve guard
    # may have replaced the caller's stale AW with a fresh ``A·W``: the
    # extraction must recombine what was used, not what was passed.
    aw_used: Optional[jax.Array] = None


class CGResult(NamedTuple):
    x: Pytree
    info: SolveInfo
    recycle: Optional[RecycleData] = None


# ---------------------------------------------------------------------------
# Conjugate gradients (the paper's CG baseline)
# ---------------------------------------------------------------------------


def cg(
    A,
    b: Pytree,
    x0: Optional[Pytree] = None,
    *,
    tol: float = 1e-5,
    atol: float = 0.0,
    maxiter: int = 1000,
    M: Optional[Callable[[Pytree], Pytree]] = None,
    record_residuals: bool = False,
    stagnation_window: int = 0,
) -> CGResult:
    """(Preconditioned) conjugate gradients for SPD ``A``.

    ``M`` is an (SPD) preconditioner apply ``r ↦ M⁻¹ r``; ``None`` gives
    plain CG, matching the paper's baseline.

    The loop carries ``rᵀz`` through its state (computed once per
    iteration, not twice), and without a preconditioner the recurrence
    scalar is the ``‖r‖²`` reduction the fused update pass already emits —
    plain CG costs exactly one reduction per iteration beyond ``pᵀAp``.

    Per-iteration breakdown detection rides those same reductions: a
    non-finite or non-positive ``pᵀAp`` and a runaway ``‖r‖`` stop the
    loop with a typed cause in ``info.status`` (:class:`SolveStatus`).
    ``stagnation_window > 0`` additionally declares STAGNATED when the
    best residual fails to improve by 1% over that many consecutive
    iterations (0 — the default — adds no state and no checks).
    """
    b_flat, unravel = pt.ravel_vector(b)
    x_flat = jnp.zeros_like(b_flat) if x0 is None else pt.ravel(x0)
    A_flat = engine.flat_operator(A, unravel)
    precond = engine.flat_operator(M, unravel) if M is not None else None

    r0 = b_flat - A_flat(x_flat)
    z0 = precond(r0) if precond is not None else r0
    p0 = z0
    rz0 = pt.tree_dot(r0, z0)
    rnorm0 = pt.tree_norm(r0)
    threshold, _ = engine.tolerances(b_flat, tol, atol)

    trace0 = engine.trace_init(rnorm0, maxiter, record_residuals)
    diverged_at = 1e8 * jnp.maximum(rnorm0, pt.tree_norm(b_flat))

    def active_fn(state):
        j, _, _, _, _, _, rnorm, _, fail, _ = state
        return (j < maxiter) & (rnorm > threshold) & (fail == 0)

    def step(state, active, gate_matvec):
        # CG never records a window (ell == 0): the harness only runs
        # this in the while phase, so ``active``/``gate_matvec`` carry no
        # information and the body stays the unmasked textbook iteration.
        del active, gate_matvec
        j, x, r, z, p, rz, rnorm, trace, fail, stag = state
        ap = A_flat(p)
        d = pt.tree_dot(p, ap)
        bad, code = engine.classify_breakdown(d, rnorm, diverged_at)
        fail = jnp.where(fail > 0, fail, code)
        # Sanitize a poisoned A·p before it reaches the update pass:
        # alpha is zeroed on breakdown, but 0·NaN would still poison x/r.
        ap = jnp.where(bad, 0.0, ap)
        alpha = jnp.where(bad, 0.0, rz / jnp.where(bad, 1.0, d))
        x, r, rr, _ = kops.fused_cg_update(x, r, p, ap, alpha)
        if precond is not None:
            z = precond(r)
            rz_new = pt.tree_dot(r, z)
        else:
            z = r
            rz_new = rr
        beta = rz_new / jnp.where(rz == 0.0, 1.0, rz)
        p, _, _ = kops.fused_deflate_direction(z, p, beta)
        rnorm = jnp.sqrt(rr)
        fail = jnp.where(
            (fail == 0) & (~jnp.isfinite(rnorm)),
            SolveStatus.BREAKDOWN_NONFINITE,
            fail,
        ).astype(jnp.int32)
        if stag is not None:
            stag, fail = engine.stagnation_update(
                stag, rnorm, fail, jnp.bool_(True), stagnation_window
            )
        if trace is not None:
            trace = trace.at[j + 1].set(rnorm)
        return (j + 1, x, r, z, p, rz_new, rnorm, trace, fail, stag), ()

    fail0 = engine.initial_fail(rnorm0)
    stag0 = engine.stagnation_init(rnorm0, stagnation_window)
    state = (
        jnp.int32(0), x_flat, r0, z0, p0, rz0, rnorm0, trace0, fail0, stag0,
    )
    state, _ = engine.run_recording_loop(step, active_fn, state, ell=0)
    j, x, _, _, _, _, rnorm, trace, fail, _ = state
    converged = rnorm <= threshold
    info = SolveInfo(
        iterations=j,
        converged=converged,
        residual_norm=rnorm,
        matvecs=j + 1,
        residual_norms=trace,
        breakdown=fail > 0,
        status=engine.exit_status(converged, fail),
    )
    return CGResult(x=unravel(x), info=info)


# ---------------------------------------------------------------------------
# Deflated conjugate gradients — paper Algorithm 1
# ---------------------------------------------------------------------------


def deflated_initial_guess(x_prev, r_prev, W, AW, waw_cho):
    """Line 3 of Alg. 1: ``x0 = x_{-1} + W (WᵀAW)⁻¹ Wᵀ r_{-1}``.

    Returns ``(x0, r0)`` with ``r0`` updated via ``AW`` (no extra matvec):
    ``r0 = r_{-1} − AW c``.
    """
    c = cho_solve(waw_cho, pt.basis_dot(W, r_prev))
    x0 = pt.tree_add(x_prev, pt.basis_combine(W, c))
    r0 = pt.tree_sub(r_prev, pt.basis_combine(AW, c))
    return x0, r0


def redeflate(x, r, wr, awr, rs, W, AW, waw_inv, awaw, keep):
    """Line 3 of Alg. 1 again, inside the loop: ``x += W c``, ``r −= AW c``
    with ``c = (WᵀAW)⁻¹ Wᵀr``, flat ``(k, n)`` bases.

    ``wr`` is ``Wᵀr`` and ``awr``, ``rs`` the caller's ``(AW)ᵀr`` and
    ``‖r‖²``; they come back updated to the new ``r`` through the k×k
    ``awaw = (AW)ᵀAW`` (no further pass over ``r``).  Nothing is applied
    where ``keep`` is False (a frozen or broken step), nor while
    ``‖Wᵀr‖ ≤ √eps·‖r‖``: that small a part moves α by its square and the
    stopping test not at all, and leaving it keeps the iterates those of
    plain def-CG.  Returns ``(x, r, rs, awr, c)``.  Only for an exact
    ``AW``: with stale products ``AW c`` is not ``A W c``, and the
    recurrence would leave the true residual.

    In exact arithmetic def-CG keeps ``Wᵀr = 0`` and ``c`` is zero.  In
    float32 each step's rounding leaves a part of ``r`` along ``W`` that
    no later direction removes, since every direction is A-orthogonal to
    ``W``.  It grows with the basis' Ritz values, so with n; once the
    rest of ``r`` falls below it, ``α = ‖r‖²/pᵀAp`` overshoots (``rᵀp =
    ‖r‖² − (Wᵀr)ᵀμ``) and the residual grows again.  On a v5e the GP
    Newton systems' warm solves diverged so from n = 2^16 on (PERF.md);
    taking ``c`` out every step keeps them converging.
    """
    keep = keep & (pt.vdot(wr, wr) > jnp.finfo(r.dtype).eps * rs)
    c = jnp.where(keep, pt.matmul(waw_inv, wr.astype(waw_inv.dtype)), 0.0)
    mc = pt.matmul(awaw, c)
    c_r = c.astype(r.dtype)
    x = x + pt.matmul(c_r, W)
    r = r - pt.matmul(c_r, AW)
    rs = jnp.maximum(rs - 2.0 * pt.vdot(c, awr) + pt.vdot(c, mc), 0.0)
    return x, r, rs.astype(awr.dtype), awr - mc.astype(awr.dtype), c


def defcg(
    A,
    b: Pytree,
    x0: Optional[Pytree] = None,
    W: Optional[Pytree] = None,
    AW: Optional[Pytree] = None,
    *,
    ell: int = 0,
    tol: float = 1e-5,
    atol: float = 0.0,
    maxiter: int = 1000,
    min_iters: int = 0,
    record_residuals: bool = False,
    waw_jitter: float = DEFAULT_WAW_JITTER,
    exact_aw: bool = True,
    flat_recycle: bool = False,
    M: Optional[Callable[[Pytree], Pytree]] = None,
    batch_axis: Optional[str] = None,
    stale_guard: Optional[float] = None,
    stagnation_window: int = 0,
) -> CGResult:
    """Deflated CG — ``def-CG(k, ell)`` of the paper (k = basis size of W).

    Args:
      A: SPD operator (callable on pytrees).
      b: right-hand side.
      x0: previous solution / warm start (``x_{-1}`` in Alg. 1).
      W: deflation basis (stacked pytree of k vectors) or None → plain CG
         that *still records* the first ``ell`` directions, which is how the
         first system of a sequence bootstraps recycling (paper Fig. 1).
      AW: ``A @ W``; computed here (k matvecs) when not supplied.
      ell: number of leading (p, Ap) pairs to record for Ritz extraction.
      min_iters: force at least this many iterations (useful to guarantee
         ``ell`` stored columns inside fully-jitted outer loops).
      waw_jitter: relative diagonal jitter for the k×k Cholesky.  Keep
         this SMALL (the :data:`DEFAULT_WAW_JITTER` = 1e-12 shared with
         every other solve path): the jitter perturbs μ = (WᵀAW)⁻¹(AW)ᵀr,
         and the un-deflated W-component it reinjects each iteration
         compounds — with a well-converged Ritz basis and a wide θ spread,
         jitter ≳1e-8 makes def-CG diverge outright (measured).
         Exactly-zero basis columns (clamped extraction slots) are
         regularized away unconditionally regardless of this setting.
      M: optional SPD preconditioner apply ``r ↦ M⁻¹ r``.  Deflation and
         preconditioning compose (the Soodhalter et al. projection
         framework): the iteration is the split-preconditioned def-CG —
         it carries the PCG recurrence scalar ``rᵀz`` (z = M⁻¹r) through
         loop state and deflates in the preconditioned inner product
         (``μ = (WᵀAW)⁻¹ (AW)ᵀ z``), which is exactly plain def-CG on
         ``M^{-1/2} A M^{-1/2}`` with the transformed basis ``M^{1/2}W``
         mapped back (tested to 1e-10 against that reference).  Costs one
         extra fused pass (``kernels.ops.fused_rz_reduce``) plus the M
         apply per iteration; convergence is still tested on the TRUE
         residual ‖r‖.
      exact_aw: declare that ``AW`` is exactly ``A @ W``.  When False (a
         *stale* basis recycled across a drifted operator — the paper's
         cheap mode), the initial residual is recomputed with one true
         matvec instead of the ``r0 = r − AW c`` shortcut, keeping CG's
         convergence target exact while the deflation is approximate.
      stale_guard: in-solve drift guard for the stale mode (requires
         ``exact_aw=False``; ignored otherwise).  The stale setup already
         computes both the shortcut residual ``r_s = r − AW·c`` and the
         true ``r_t = b − A·x₀`` — their difference is exactly
         ``(A·W − AW)·c``, a FREE measurement of how stale the products
         are along the deflated direction, available BEFORE the first
         iteration.  When ``‖r_t − r_s‖ / ‖r_init‖`` exceeds this
         threshold, the setup refreshes ``AW = A·W`` (k matvecs, counted
         in ``info.matvecs``) and redoes the deflated guess under a
         ``lax.cond`` — stale deflation that would destabilize the
         conjugacy recurrence is caught on the system it would break, at
         zero cost when it would not.  (Under ``vmap`` the cond lowers to
         a select, so a batched solve pays the refresh GEMM
         unconditionally — same caveat as the cold-bootstrap refresh.)
      flat_recycle: return the recorded ``(P, AP)`` as raw flat
         ``(ell, n)`` arrays instead of unraveling them to the vector's
         pytree structure — the device-resident sequence engine consumes
         them flat, so the round-trip would be pure waste.
      batch_axis: name of a ``vmap`` axis this solve is lifted over
         (``solve_batch`` passes its tenant axis).  Used for the
         all-tenants-converged early exit: the recording scan runs a
         fixed ``ell`` steps, and under ``vmap`` its per-step
         ``lax.cond`` matvec gate lowers to a ``select`` (both branches
         execute) — so without this, every tenant pays ``ell`` matvecs
         even after the whole batch converged.  With the axis name the
         gate becomes a cross-tenant ``any(active)`` reduction, which is
         unbatched, so the ``cond`` survives ``vmap`` and the operator is
         skipped once EVERY lane is frozen.  ``None`` (default) keeps the
         per-lane gate.
      stagnation_window: > 0 enables the stalled-residual detector: the
         solve is stopped with STAGNATED status when the best ‖r‖ seen
         fails to improve by 1% over this many consecutive iterations.
         The default 0 carries no extra loop state and adds no checks —
         the clean path is bit-identical to a detector-free solve.

    Internals: the whole solve — setup (Wᵀ A W factorization, deflated
    initial guess) and iteration — runs on the flat engine: the vector
    packs to a contiguous ``(n,)`` array and the deflation basis to a 2-D
    ``(k, n)`` array, so ``(AW)ᵀ r`` fuses into the residual-update pass
    and ``W μ`` into the direction pass.  The iteration itself is driven
    by :func:`repro.core.engine.run_recording_loop` — def-CG supplies
    only its ``step``/``active_fn`` pair, the harness owns the
    fixed-length masked recording scan (whose stacked outputs *are* the
    ``(P, AP, α, β)`` record) and the buffer-free ``while_loop`` for the
    remaining iterations.  Steps after convergence inside the scan
    window are frozen — the matvec is skipped via the harness's gated
    ``lax.cond``, the cheap vector passes run as masked no-ops, zero
    rows are recorded — so the two-phase split is semantically identical
    to one guarded loop.

    Returns ``CGResult`` whose ``recycle`` field feeds
    :func:`repro.core.recycle.harmonic_ritz`.
    """
    b_flat, unravel = pt.ravel_vector(b)
    threshold, _ = engine.tolerances(b_flat, tol, atol)
    matvecs = jnp.int32(0)
    guard_fired = jnp.bool_(False)

    A_flat = engine.flat_operator(A, unravel)
    precond = engine.flat_operator(M, unravel) if M is not None else None
    x_flat = (
        jnp.zeros_like(b_flat) if x0 is None else pt.ravel(x0)
    )

    deflating = W is not None
    w_flat = aw_flat = waw_inv = None
    if deflating:
        # Setup runs in flat space as well (not just the loop), so the
        # whole solve is structure-blind: any pytree layout of the same
        # coordinates produces bit-identical iterates.
        k = pt.basis_size(W)
        w_flat = pt.ravel_basis(W)

        @jax.named_scope("recycle.refresh_aw")
        def _apply_basis(w_f):
            # One fused multi-RHS operator application (each K-tile /
            # linearization formed once for all k vectors), not k
            # sequential matvecs — same primitive as the refresh paths.
            basis = pt.unravel_basis(w_f, unravel)
            return pt.ravel_basis(ops_mod.apply_to_basis(A, basis))

        if AW is None:
            aw_flat = _apply_basis(w_flat)
            matvecs = matvecs + k
        else:
            aw_flat = pt.ravel_basis(AW)

        def _factor_waw(aw_f):
            waw = pt.gram(w_flat, aw_f)
            waw = 0.5 * (waw + waw.T)
            dj = jnp.diag(waw)
            tr = jnp.sum(dj)
            if waw_jitter:
                scale = jnp.where(tr > 0, tr / k, 1.0)
                waw = waw + waw_jitter * scale * jnp.eye(k, dtype=waw.dtype)
            # Exactly-zero columns (clamped extraction slots — see
            # recycle.harmonic_ritz_flat) are regularized UNconditionally:
            # Wᵀr = 0 there, so any positive diagonal entry yields the
            # same deflation result (c_i = μ_i = 0) while keeping the
            # Cholesky finite.  A no-op when no column is zero, whatever
            # waw_jitter is.
            waw = waw + jnp.diag(
                jnp.where(dj == 0.0, jnp.maximum(tr / k, 1.0), 0.0)
            )
            return cho_factor(waw)

        def _post_guess(aw_f, waw_cho, z_f):
            # Deflation in the preconditioned inner product: μ from (AW)ᵀz.
            mu0 = cho_solve(waw_cho, pt.basis_dot(aw_f, z_f))
            p0 = z_f - pt.basis_combine(w_flat, mu0)
            # In-loop μ solves become one k×k GEMV: (WᵀAW)⁻¹ is formed
            # once from the (jittered, equilibrated) Cholesky —
            # numerically benign at these sizes, and it keeps LAPACK
            # dispatches out of the loop.
            winv = cho_solve(waw_cho, jnp.eye(k, dtype=aw_f.dtype))
            return p0, winv

        waw_cho = _factor_waw(aw_flat)
        x_in = x_flat
        r_init = b_flat - A_flat(x_in)
        matvecs = matvecs + 1
        x_flat, r_flat = deflated_initial_guess(
            x_in, r_init, w_flat, aw_flat, waw_cho
        )
        if not exact_aw:
            r_short = r_flat
            r_flat = b_flat - A_flat(x_flat)
            matvecs = matvecs + 1
            if stale_guard is not None:
                # In-solve drift guard: ‖r_true − r_short‖ = ‖(A·W − AW)c‖
                # measures the staleness of AW along the deflated
                # component — both residuals are already paid for.  Above
                # the threshold, refresh AW = A·W and redo the deflated
                # guess BEFORE iterating (a stale μ-recurrence diverges,
                # it does not merely slow down).
                drift_obs = pt.tree_norm(r_flat - r_short) / jnp.maximum(
                    pt.tree_norm(r_init), jnp.finfo(r_init.dtype).tiny
                )
                # Floor the threshold above the WORKING dtype's rounding
                # noise (the two residuals differ by ~eps-level terms
                # even with an exact AW): without this, f32 solves would
                # re-trigger k-matvec refreshes on pure noise.
                guard_eff = jnp.maximum(
                    jnp.asarray(stale_guard, drift_obs.dtype),
                    DRIFT_NOISE_FLOOR_EPS * jnp.finfo(r_init.dtype).eps,
                )
                refresh = drift_obs > guard_eff

                def _refresh_setup(_):
                    aw_n = _apply_basis(w_flat)
                    cho_n = _factor_waw(aw_n)
                    x_n, r_n = deflated_initial_guess(
                        x_in, r_init, w_flat, aw_n, cho_n
                    )
                    z_n = precond(r_n) if precond is not None else r_n
                    p_n, winv_n = _post_guess(aw_n, cho_n, z_n)
                    return aw_n, x_n, r_n, z_n, p_n, winv_n

                def _keep_setup(_):
                    z_s = precond(r_flat) if precond is not None else r_flat
                    p_s, winv_s = _post_guess(aw_flat, waw_cho, z_s)
                    return aw_flat, x_flat, r_flat, z_s, p_s, winv_s

                aw_flat, x_flat, r_flat, z_flat, p_flat, waw_inv = (
                    # repro-lint: disable=cond-batched-pred — documented
                    # caveat (see docstring): under vmap this lowers to a
                    # select and a batched solve pays the refresh GEMM.
                    jax.lax.cond(refresh, _refresh_setup, _keep_setup, None)
                )
                matvecs = matvecs + k * refresh.astype(matvecs.dtype)
                guard_fired = refresh

        if waw_inv is None:  # exact or unguarded-stale setup
            z_flat = precond(r_flat) if precond is not None else r_flat
            p_flat, waw_inv = _post_guess(aw_flat, waw_cho, z_flat)
        awaw = pt.gram(aw_flat, aw_flat)
    else:
        r_flat = b_flat - A_flat(x_flat)
        matvecs = matvecs + 1
        z_flat = precond(r_flat) if precond is not None else r_flat
        p_flat = z_flat

    rnorm0 = pt.tree_norm(r_flat)
    # The carried recurrence scalar: rᵀz (== ‖r‖² without a preconditioner).
    rs0 = pt.tree_dot(r_flat, z_flat)

    trace0 = engine.trace_init(rnorm0, maxiter, record_residuals)
    diverged_at = 1e8 * jnp.maximum(rnorm0, pt.tree_norm(b_flat))

    def active_fn(state):
        j, rnorm, fail = state[0], state[5], state[7]
        keep_going = (rnorm > threshold) | (j < min_iters)
        return (j < maxiter) & keep_going & (fail == 0)

    def step(state, active, gate_matvec):
        """One def-CG iteration; ``active=False`` freezes the state.

        The recording scan runs a fixed step count, so steps after
        convergence are frozen: the matvec is gated behind the harness's
        ``cond`` (skipping the expensive operator outright), while the
        cheap fused vector passes are masked via ``alpha = 0`` and a
        frozen ``p`` — wrapping the *whole* body in a ``cond`` measured
        slower on active steps (branch-boundary state copies) than
        letting the no-op passes run.
        """
        j, x, r, p, rs, rnorm, trace, fail, stag = state
        p_in = p
        if gate_matvec:
            ap = engine.gated_matvec(A_flat, p, active, batch_axis)
        else:
            ap = A_flat(p)
        d = pt.tree_dot(p, ap)
        bad, code = engine.classify_breakdown(d, rnorm, diverged_at)
        fail = jnp.where((fail == 0) & active, code, fail)
        # Sanitize a poisoned A·p before the fused passes touch it: alpha
        # is zeroed on breakdown, but 0·NaN = NaN would still poison x, r,
        # and (through μ) the next direction — a broken step must leave
        # the last HEALTHY iterate in state for the recovery ladder.
        ap = jnp.where(bad, 0.0, ap)
        alpha = jnp.where(bad | (~active), 0.0, rs / jnp.where(bad, 1.0, d))

        mu = None
        if precond is None:
            # Unpreconditioned: rᵀr IS the recurrence scalar, and the
            # deflation GEMV rides in the update pass.
            if deflating:
                x, r, rs_new, awr = kops.fused_cg_update(
                    x, r, p, ap, alpha, aw_flat
                )
                if exact_aw:
                    x, r, rs_new, awr, _ = redeflate(
                        x, r, pt.matmul(w_flat, r), awr, rs_new, w_flat,
                        aw_flat, waw_inv, awaw, active & (~bad),
                    )
                mu = pt.matmul(waw_inv, awr.astype(waw_inv.dtype))
            else:
                x, r, rs_new, _ = kops.fused_cg_update(x, r, p, ap, alpha)
            rr = rs_new
            zvec = r
        else:
            # Split-preconditioned: z = M⁻¹r only exists after the update,
            # so rᵀz and (AW)ᵀz go in a second fused pass; convergence is
            # still tested on the true residual ‖r‖ from the update pass.
            x, r, rr, _ = kops.fused_cg_update(x, r, p, ap, alpha)
            zvec = precond(r)
            rs_new, awz = kops.fused_rz_reduce(
                r, zvec, aw_flat if deflating else None
            )
            if deflating:
                mu = pt.matmul(waw_inv, awz.astype(waw_inv.dtype))
        beta = rs_new / jnp.where(rs == 0.0, 1.0, rs)

        p_new, _, _ = kops.fused_deflate_direction(zvec, p, beta, w_flat, mu)
        # Freeze p on breakdown too (not just inactivity): a poisoned
        # basis/preconditioner can make p_new non-finite through μ even
        # with a sanitized A·p.
        p = jnp.where(active & (~bad), p_new, p)

        rnorm_new = jnp.sqrt(rr)
        fail = jnp.where(
            (fail == 0) & active & (~jnp.isfinite(rnorm_new)),
            SolveStatus.BREAKDOWN_NONFINITE,
            fail,
        ).astype(jnp.int32)
        rnorm = jnp.where(active, rnorm_new, rnorm)
        if stag is not None:
            stag, fail = engine.stagnation_update(
                stag, rnorm_new, fail, active, stagnation_window
            )
        if trace is not None:
            # Frozen steps rewrite slot j+1 with its old value, keeping
            # the NaN tail of the trace untouched.
            old = trace[j + 1]
            trace = trace.at[j + 1].set(jnp.where(active, rnorm, old))
        j = j + active.astype(j.dtype)
        return (j, x, r, p, rs_new, rnorm, trace, fail, stag), (
            p_in, ap, alpha, beta,
        )

    fail0 = engine.initial_fail(rnorm0)
    stag0 = engine.stagnation_init(rnorm0, stagnation_window)
    state = (
        jnp.int32(0), x_flat, r_flat, p_flat, rs0, rnorm0, trace0,
        fail0, stag0,
    )

    state, rows = engine.run_recording_loop(
        step, active_fn, state, ell=ell
    )
    p_rows = ap_rows = a_rows = b_rows = None
    if rows is not None:
        p_rows, ap_rows, a_rows, b_rows = rows
    j, x, _, _, _, rnorm, trace, fail, _ = state

    converged = rnorm <= threshold
    info = SolveInfo(
        iterations=j,
        converged=converged,
        residual_norm=rnorm,
        matvecs=matvecs + j,
        residual_norms=trace,
        breakdown=fail > 0,
        status=engine.exit_status(converged, fail),
        guard_fired=guard_fired,
    )
    recycle = None
    if ell > 0:
        if flat_recycle:
            recycle = RecycleData(
                P=p_rows, AP=ap_rows, stored=jnp.minimum(j, ell),
                alpha=a_rows, beta=b_rows,
                aw_used=(
                    aw_flat
                    if (deflating and not exact_aw and stale_guard is not None)
                    else None
                ),
            )
        else:
            recycle = RecycleData(
                P=pt.unravel_basis(p_rows, unravel),
                AP=pt.unravel_basis(ap_rows, unravel),
                stored=jnp.minimum(j, ell),
                alpha=a_rows, beta=b_rows,
            )
    return CGResult(x=unravel(x), info=info, recycle=recycle)


# ---------------------------------------------------------------------------
# Dense baseline (paper Table 1's Cholesky column)
# ---------------------------------------------------------------------------


def cholesky_solve(mat: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Exact SPD solve via Cholesky — the paper's cubic-cost baseline."""
    return cho_solve(cho_factor(mat), b)
