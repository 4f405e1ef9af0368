"""SPMD sharding of the flat Krylov engine (DESIGN.md §5).

This module makes the flat engine *n-parallel*: every length-n vector of
an iteration (``x, r, p, z``), the ``(k, n)`` recycled-basis leaves of
:class:`repro.core.recycle.RecycleState`, and the recorded ``(ell, n)``
window rows are sharded along the coordinate dimension over a 1-D
``"solve"`` mesh axis, and the def-CG / CG / LSMR loop harnesses run
under :func:`jax.shard_map` with the fused kernel
ops (:mod:`repro.kernels.ops`) applied per-shard.

The communication contract is ONE collective per def-CG iteration: all
scalar reductions of a step — ``pᵀAp``, ``rᵀAp``, ``ApᵀAp``, the
deflation GEMVs ``(AW)ᵀAp`` / ``(AW)ᵀr``, and a FRESH ``‖r‖²`` of the
incoming residual — are packed into a single
:func:`repro.core.engine.psum_merged` all-reduce.  The post-update
quantities then follow from one-step recurrences

    ‖r₊‖² = ‖r‖² − 2α·rᵀAp + α²·ApᵀAp,
    (AW)ᵀr₊ = (AW)ᵀr − α·(AW)ᵀAp,

used ONLY for β, μ and the stopping test; α is always formed from the
freshly-reduced ``‖r‖²`` of the actual residual vector, so recurrence
rounding does NOT accumulate across iterations (a fully-carried ``‖r‖²``
decouples from the true residual near convergence and diverges — the
one-step form differs from the unsharded fresh reductions only in
floating-point association; parity is ~1e-13 relative in f64, pinned at
1e-10 by the test suite).  LSMR inherently
needs two all-reduces per iteration (``β = ‖u₊‖`` must normalize ``u``
before ``Âᵀu`` can be formed).  The per-while-body collective counts are
pinned from compiled HLO by
:func:`repro.launch.hlo_stats.while_body_collectives`.

Operator side: a matvec under the mesh costs one ``all_gather`` of the
direction vector plus the one merged all-reduce.  Two operator kinds are
sharded natively:

* :class:`repro.core.operators.DenseMatrixOperator` — the matrix is
  row-sharded ``P("solve", None)``; each shard contracts its row block
  against the gathered vector.
* :class:`repro.core.operators.RBFKernelSystemOperator` — the data
  ``X`` is row-sharded; the full ``X`` is all-gathered ONCE per solve
  (hoisted out of the while loop as a constant) and each shard forms its
  local K-tile rows on the fly via
  :func:`repro.kernels.ops.rbf_matvec_rect` — ``K`` is never
  materialized, which is what lets n = 10⁵–10⁶ GP solves run at all.

Differences from the unsharded front door (documented, tested):

* No recovery ladder (``spec.recovery_rungs`` is ignored): a broken
  solve retires the basis (zeroed carry) and falls the solution back to
  the finite warm start — the same terminal policy as the recycled-LSMR
  path.  Clean solves are identical either way (the ladder runs zero
  iterations on them).
* ``method="deflsmr"``, preconditioners, and ``batch_axis`` are not
  supported (NotImplementedError / ValueError at the front door).
* Only the :class:`HarmonicRitz` strategy (the default) is accepted.

Everything else — tolerances, breakdown classification, stagnation,
matvec accounting, the recording-scan/while-loop split, the extraction —
reuses the engine and strategy cores verbatim, with
``psum_axis="solve"`` threaded where an n-reduction hides.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_factor, cho_solve
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import engine
from repro.core import operators as ops_mod
from repro.core import pytree as pt
from repro.core.engine import SolveInfo, SolveStatus
from repro.core.recycle import RecycleState
from repro.core.strategies import HarmonicRitz, extract_next_basis_core
from repro.kernels import ops as kops

Pytree = Any

# The 1-D mesh axis every length-n dimension shards over (see
# repro.launch.mesh.make_solve_mesh).
SOLVE_AXIS = "solve"

_SHARDED_METHODS = ("cg", "defcg", "lsmr")


# ---------------------------------------------------------------------------
# Sharding rules — the PartitionSpec vocabulary of the solve state
# ---------------------------------------------------------------------------


def vector_spec() -> P:
    """Length-n solve vectors (x, r, p, b): sharded along n."""
    return P(SOLVE_AXIS)


def basis_spec() -> P:
    """``(k, n)`` basis stacks (W, AW) and ``(ell, n)`` window rows:
    replicated over rows, sharded along the n columns."""
    return P(None, SOLVE_AXIS)


def recycle_state_specs() -> RecycleState:
    """A :class:`RecycleState`-shaped pytree of PartitionSpecs — the
    sharding rule for carrying recycle state on the solve mesh (W/AW
    column-sharded, the k-sized/scalar leaves replicated)."""
    return RecycleState(
        W=basis_spec(),
        AW=basis_spec(),
        theta=P(),
        systems_solved=P(),
        drift=P(),
    )


def shard_recycle_state(state: RecycleState, mesh: Mesh) -> RecycleState:
    """Place a ``RecycleState`` on ``mesh`` per :func:`recycle_state_specs`.

    Explicit per-leaf placement — PartitionSpec subclasses tuple, so a
    tree_map pairing leaves with specs would descend into the specs.
    """
    s = recycle_state_specs()

    def put(leaf, spec):
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return RecycleState(
        W=put(state.W, s.W),
        AW=put(state.AW, s.AW),
        theta=put(state.theta, s.theta),
        systems_solved=put(state.systems_solved, s.systems_solved),
        drift=put(state.drift, s.drift),
    )


def _commit(mesh: Mesh, x, spec: P):
    """Place one traced input on ``mesh`` under ``spec`` before the
    jitted shard_map call.  A no-op for well-placed arrays; for arrays
    committed to different devices (a ``RecycleState`` carried from a
    solve on another mesh size, say) it is the reshard that makes them
    legal inputs instead of a cross-device jit error."""
    return jax.device_put(x, NamedSharding(mesh, spec))


def _commit_tree(mesh: Mesh, tree, spec_tree):
    """:func:`_commit` over an operator-leaves pytree paired with its
    spec pytree.  Flatten-up-to keeps each PartitionSpec whole at the
    leaf positions (a naive two-tree map could descend into the specs —
    PartitionSpec subclasses tuple)."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    specs = treedef.flatten_up_to(spec_tree)
    return jax.tree_util.tree_unflatten(
        treedef, [_commit(mesh, x, s) for x, s in zip(flat, specs)]
    )


# ---------------------------------------------------------------------------
# Operator planning — which leaves shard, and how the shard applies them
# ---------------------------------------------------------------------------


def _plan_operator(A, *, need_adjoint: bool):
    """Host-side classification of an operator for the solve mesh.

    Returns ``(kind, aux, leaves, leaf_specs)``: ``leaves`` are the
    traced arrays handed through ``shard_map`` under ``leaf_specs``;
    ``kind``/``aux`` are static and select the per-shard apply built by
    :func:`_make_applies`.
    """
    if isinstance(A, ops_mod.RBFKernelSystemOperator):
        aux = (float(A.theta), float(A.lengthscale), int(A.block), A.impl)
        return ("rbf", aux, (A.x, A.sqrt_h), (P(SOLVE_AXIS, None), P(SOLVE_AXIS)))
    mat = getattr(A, "mat", None)
    if mat is not None:
        leaves = (mat,)
        specs = (P(SOLVE_AXIS, None),)
        if need_adjoint:
            # LSMR contracts with Aᵀ too: ship the transpose as its own
            # row-sharded leaf so the adjoint matvec is also a local
            # row-block GEMV (transposing the sharded leaf in-loop would
            # re-lay the matrix out every iteration).
            leaves = (mat, jnp.swapaxes(mat, -2, -1))
            specs = (P(SOLVE_AXIS, None), P(SOLVE_AXIS, None))
        return ("dense", (), leaves, specs)
    raise TypeError(
        "solve(..., mesh=...) shards the operator's data leaves along n; "
        "that needs a DenseMatrixOperator (row-sharded matrix) or an "
        f"RBFKernelSystemOperator (row-sharded data) — got {type(A).__name__}. "
        "Unsharded callers: drop the mesh argument."
    )


def _make_applies(kind: str, aux, leaves):
    """Build the per-shard ``(apply, rapply, basis_apply)`` closures.

    Runs INSIDE the shard_map body: ``leaves`` are local shards.  Each
    matvec all-gathers its input vector once; the RBF operator
    additionally all-gathers the full data ``X`` at closure-build time —
    a loop constant XLA hoists, so it happens once per solve, not per
    iteration.
    """
    ax = SOLVE_AXIS
    if kind == "dense":
        mat_loc = leaves[0]

        def apply(v_loc):
            v_full = jax.lax.all_gather(v_loc, ax, tiled=True)
            return pt.matmul(mat_loc, v_full)

        if len(leaves) > 1:
            mat_t_loc = leaves[1]

            def rapply(u_loc):
                u_full = jax.lax.all_gather(u_loc, ax, tiled=True)
                return pt.matmul(mat_t_loc, u_full)

        else:
            rapply = apply

        def basis_apply(w_loc):  # (k, n_loc) -> (k, n_loc)
            w_full = jax.lax.all_gather(w_loc, ax, axis=1, tiled=True)
            return pt.matmul(w_full, mat_loc.T)

        return apply, rapply, basis_apply

    if kind == "rbf":
        theta, lengthscale, block, impl = aux
        x_loc, sh_loc = leaves
        # Gathered ONCE per solve (closure constant, hoisted out of the
        # while loop) — each shard then owns the rectangular tile
        # (local rows × all columns) of K implicitly.
        x_full = jax.lax.all_gather(x_loc, ax, tiled=True)

        def apply(v_loc):
            u_full = jax.lax.all_gather(sh_loc * v_loc, ax, tiled=True)
            kv_loc = kops.rbf_matvec_rect(
                x_loc, x_full, u_full, theta, lengthscale,
                impl=impl, block=block,
            )
            return v_loc + sh_loc * kv_loc

        def basis_apply(w_loc):  # (k, n_loc): one fused multi-RHS pass
            u_full = jax.lax.all_gather(
                w_loc * sh_loc[None, :], ax, axis=1, tiled=True
            )
            kv_loc = kops.rbf_matvec_rect(
                x_loc, x_full, u_full.T, theta, lengthscale,
                impl=impl, block=block,
            )
            return w_loc + sh_loc[None, :] * kv_loc.T

        return apply, apply, basis_apply

    raise ValueError(f"unknown operator kind {kind!r}")


# ---------------------------------------------------------------------------
# Sharded method bodies — per-shard views, merged-psum reductions
# ---------------------------------------------------------------------------


def _sharded_cg_body(
    kind, aux, *, tol, atol, maxiter, stagnation_window, record_residuals
):
    """Plain CG on per-shard state: one merged all-reduce per iteration
    (``[pᵀAp, rᵀAp, ApᵀAp, ‖r‖²]``).  α comes from the FRESH ``‖r‖²``
    of the incoming residual; only β and the stopping test ride the
    one-step ``‖r₊‖²`` recurrence, so rounding never accumulates."""
    ax = SOLVE_AXIS

    def body(leaves, b_loc, x0_loc):
        apply, _, _ = _make_applies(kind, aux, leaves)
        r0 = b_loc - apply(x0_loc)
        bsq, rs0 = engine.psum_merged(
            [pt.vdot(b_loc, b_loc), pt.vdot(r0, r0)], ax
        )
        bnorm = jnp.sqrt(bsq)
        threshold = jnp.maximum(tol * bnorm, atol)
        rnorm0 = jnp.sqrt(rs0)
        p0 = r0
        trace0 = engine.trace_init(rnorm0, maxiter, record_residuals)
        diverged_at = 1e8 * jnp.maximum(rnorm0, bnorm)

        def active_fn(state):
            j, _, _, _, rnorm, _, fail, _ = state
            return (j < maxiter) & (rnorm > threshold) & (fail == 0)

        def step(state, active, gate_matvec):
            del active, gate_matvec  # ell == 0: while-phase only
            j, x, r, p, rnorm, trace, fail, stag = state
            ap = apply(p)
            d, rap, apap, rs = engine.psum_merged(
                [
                    pt.vdot(p, ap), pt.vdot(r, ap),
                    pt.vdot(ap, ap), pt.vdot(r, r),
                ],
                ax,
            )
            bad, code = engine.classify_breakdown(d, rnorm, diverged_at)
            fail = jnp.where(fail > 0, fail, code)
            ap = jnp.where(bad, 0.0, ap)
            rap = jnp.where(bad, 0.0, rap)
            apap = jnp.where(bad, 0.0, apap)
            alpha = jnp.where(bad, 0.0, rs / jnp.where(bad, 1.0, d))
            x, r, _, _ = kops.fused_cg_update(x, r, p, ap, alpha)
            # One-step ‖r₊‖² recurrence off the fresh ‖r‖² (clamped: at
            # convergence the cancellation can go eps-negative).
            rs_new = jnp.maximum(
                rs - 2.0 * alpha * rap + alpha * alpha * apap, 0.0
            )
            beta = rs_new / jnp.where(rs == 0.0, 1.0, rs)
            p, _, _ = kops.fused_deflate_direction(r, p, beta)
            rnorm = jnp.sqrt(rs_new)
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
            return (j + 1, x, r, p, rnorm, trace, fail, stag), ()

        fail0 = engine.initial_fail(rnorm0)
        stag0 = engine.stagnation_init(rnorm0, stagnation_window)
        state = (
            jnp.int32(0), x0_loc, r0, p0, rnorm0, trace0, fail0, stag0,
        )
        state, _ = engine.run_recording_loop(step, active_fn, state, ell=0)
        j, x, _, _, rnorm, trace, fail, _ = state
        converged = rnorm <= threshold
        out = {
            "x": x,
            "iterations": j,
            "converged": converged,
            "residual_norm": rnorm,
            "matvecs": j + 1,
            "breakdown": fail > 0,
            "status": engine.exit_status(converged, fail),
        }
        if record_residuals:
            out["trace"] = trace
        return out

    return body


def _sharded_defcg_body(
    kind,
    aux,
    *,
    k,
    ell,
    tol,
    atol,
    maxiter,
    select,
    waw_jitter,
    refresh_aw,
    stagnation_window,
    record_residuals,
):
    """Deflated CG + harmonic-Ritz extraction on per-shard state.

    The iteration's ONE all-reduce merges ``[pᵀAp, rᵀAp, ApᵀAp,
    (AW)ᵀAp, ‖r‖², (AW)ᵀr]`` — fresh reductions of the incoming
    residual plus the Ap products; the post-update ``‖r₊‖²`` /
    ``(AW)ᵀr₊`` that β and μ need come from one-step recurrences off
    those fresh values, so μ and β need no second collective and
    recurrence rounding never accumulates.
    """
    ax = SOLVE_AXIS

    def body(leaves, b_loc, x0_loc, w_loc, aw_carry_loc):
        apply, _, basis_apply = _make_applies(kind, aux, leaves)
        dtype = b_loc.dtype
        matvecs = jnp.int32(0)

        # -- strategy.prepare (HarmonicRitz): exact refresh or stale -----
        if refresh_aw == "stale":
            aw_used = aw_carry_loc
        else:
            has_w = (
                jax.lax.psum(jnp.sum((w_loc != 0).astype(jnp.int32)), ax) > 0
            )
            aw_used = jax.lax.cond(
                has_w, basis_apply, lambda ww: jnp.zeros_like(ww), w_loc
            )
            matvecs = matvecs + k * has_w.astype(jnp.int32)

        # -- setup: WᵀAW factor + deflated initial guess -----------------
        r_init = b_loc - apply(x0_loc)
        matvecs = matvecs + 1
        waw, bsq, wr = engine.psum_merged(
            [pt.matmul(w_loc, aw_used.T), pt.vdot(b_loc, b_loc),
             pt.matmul(w_loc, r_init)],
            ax,
        )
        bnorm = jnp.sqrt(bsq)
        threshold = jnp.maximum(tol * bnorm, atol)

        # Same regularization policy as solvers._factor_waw.
        waw = 0.5 * (waw + waw.T)
        dj = jnp.diag(waw)
        tr = jnp.sum(dj)
        if waw_jitter:
            scale = jnp.where(tr > 0, tr / k, 1.0)
            waw = waw + waw_jitter * scale * jnp.eye(k, dtype=waw.dtype)
        waw = waw + jnp.diag(
            jnp.where(dj == 0.0, jnp.maximum(tr / k, 1.0), 0.0)
        )
        waw_cho = cho_factor(waw)

        c = cho_solve(waw_cho, wr)
        x = x0_loc + pt.matmul(c, w_loc)
        r = r_init - pt.matmul(c, aw_used)
        rs0, awr0 = engine.psum_merged([pt.vdot(r, r), pt.matmul(aw_used, r)], ax)
        mu0 = cho_solve(waw_cho, awr0)
        p0 = r - pt.matmul(mu0, w_loc)
        winv = cho_solve(waw_cho, jnp.eye(k, dtype=aw_used.dtype))
        rnorm0 = jnp.sqrt(rs0)

        trace0 = engine.trace_init(rnorm0, maxiter, record_residuals)
        diverged_at = 1e8 * jnp.maximum(rnorm0, bnorm)

        def active_fn(state):
            j, rnorm, fail = state[0], state[4], state[6]
            return (j < maxiter) & (rnorm > threshold) & (fail == 0)

        def step(state, active, gate_matvec):
            j, x, r, p, rnorm, trace, fail, stag = state
            p_in = p
            if gate_matvec:
                ap = engine.gated_matvec(apply, p, active, None)
            else:
                ap = apply(p)
            rap_l, awap_l = kops.fused_rz_reduce(r, ap, aw_used)
            rs_l, awr_l = kops.fused_rz_reduce(r, r, aw_used)
            d, rap, apap, awap, rs, awr = engine.psum_merged(
                [pt.vdot(p, ap), rap_l, pt.vdot(ap, ap), awap_l,
                 rs_l, awr_l],
                ax,
            )
            bad, code = engine.classify_breakdown(d, rnorm, diverged_at)
            fail = jnp.where((fail == 0) & active, code, fail)
            # Sanitize the poisoned reductions too: alpha is zeroed on
            # breakdown, but 0·NaN would still poison the recurrences.
            ap = jnp.where(bad, 0.0, ap)
            rap = jnp.where(bad, 0.0, rap)
            apap = jnp.where(bad, 0.0, apap)
            awap = jnp.where(bad, 0.0, awap)
            alpha = jnp.where(
                bad | (~active), 0.0, rs / jnp.where(bad, 1.0, d)
            )
            x, r, _, _ = kops.fused_cg_update(x, r, p, ap, alpha)
            rs_new = jnp.maximum(
                rs - 2.0 * alpha * rap + alpha * alpha * apap, 0.0
            )
            awr_new = awr - alpha * awap
            mu = pt.matmul(winv, awr_new.astype(winv.dtype))
            beta = rs_new / jnp.where(rs == 0.0, 1.0, rs)
            p_new, _, _ = kops.fused_deflate_direction(
                r, p, beta, w_loc, mu
            )
            p = jnp.where(active & (~bad), p_new, p)
            rnorm_new = jnp.sqrt(rs_new)
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
                old = trace[j + 1]
                trace = trace.at[j + 1].set(jnp.where(active, rnorm, old))
            j = j + active.astype(j.dtype)
            return (j, x, r, p, rnorm, trace, fail, stag), (
                p_in, ap, alpha, beta,
            )

        fail0 = engine.initial_fail(rnorm0)
        stag0 = engine.stagnation_init(rnorm0, stagnation_window)
        state = (
            jnp.int32(0), x, r, p0, rnorm0, trace0, fail0, stag0,
        )
        state, rows = engine.run_recording_loop(
            step, active_fn, state, ell=ell
        )
        j, x = state[0], state[1]
        rnorm, trace, fail = state[4], state[5], state[6]
        converged = rnorm <= threshold
        breakdown = fail > 0

        # -- strategy.transition: sharded harmonic-Ritz extraction -------
        theta = None
        if ell > 0:
            p_rows, ap_rows, _, _ = rows
            w2, aw2, theta, _ = extract_next_basis_core(
                w_loc, aw_used, p_rows, ap_rows, jnp.minimum(j, ell), k,
                select=select, psum_axis=ax,
            )
        else:
            w2, aw2 = w_loc, aw_used

        # -- terminal retirement (the ladder-less safety floor; mirrors
        # lsmr._one_recycled_lsmr): never hand poisoned coordinates or a
        # poisoned basis to the caller / next system.  One merged
        # all-reduce covers both finiteness checks.
        nonfinite_x = jnp.sum((~jnp.isfinite(x)).astype(jnp.int32))
        nonfinite_basis = jnp.sum(
            (~jnp.isfinite(w2)).astype(jnp.int32)
        ) + jnp.sum((~jnp.isfinite(aw2)).astype(jnp.int32))
        nonfinite_x, nonfinite_basis = engine.psum_merged(
            [nonfinite_x, nonfinite_basis], ax
        )
        x_safe = jnp.where(jnp.isfinite(x0_loc), x0_loc, jnp.zeros((), dtype))
        x = jnp.where(nonfinite_x == 0, x, x_safe)
        retire = breakdown | (nonfinite_basis > 0)
        w2 = jnp.where(retire, 0.0, w2)
        aw2 = jnp.where(retire, 0.0, aw2)
        if theta is not None:
            theta = jnp.where(retire, 0.0, theta)

        out = {
            "x": x,
            "iterations": j,
            "converged": converged,
            "residual_norm": rnorm,
            "matvecs": matvecs + j,
            "breakdown": breakdown,
            "status": engine.exit_status(converged, fail),
            "w": w2,
            "aw": aw2,
        }
        if record_residuals:
            out["trace"] = trace
        if ell > 0:
            out["theta"] = theta
        return out

    return body


def _sharded_lsmr_body(
    kind,
    aux,
    *,
    damp,
    tol,
    atol,
    maxiter,
    stagnation_window,
    record_residuals,
    has_x0,
):
    """Plain LSMR on per-shard state — 2 all-reduces per iteration (the
    Golub–Kahan β and α normalizations are serially dependent: ``u₊``
    must be normalized before ``Âᵀu₊`` exists)."""
    ax = SOLVE_AXIS
    has_shift = damp > 0.0
    sqrt_damp = float(damp) ** 0.5

    def body(leaves, b_loc, x0_loc):
        apply, rapply, _ = _make_applies(kind, aux, leaves)

        init_mv = jnp.int32(1)
        if has_x0:
            r_m = b_loc - apply(x0_loc)
            init_mv = init_mv + 1
        else:
            r_m = b_loc
        u_n0 = -sqrt_damp * x0_loc if has_shift else None

        bsum = pt.vdot(r_m, r_m)
        if has_shift:
            bsum = bsum + pt.vdot(u_n0, u_n0)
        (beta_sq,) = engine.psum_merged([bsum], ax)
        beta1 = jnp.sqrt(beta_sq)
        safe_b = jnp.where(beta1 == 0.0, 1.0, beta1)
        u_m0 = r_m / safe_b
        u_n0 = (u_n0 / safe_b) if has_shift else None

        g0 = rapply(u_m0)
        if has_shift:
            g0 = g0 + sqrt_damp * u_n0
        (asum,) = engine.psum_merged([pt.vdot(g0, g0)], ax)
        alpha1 = jnp.sqrt(asum)
        safe_a = jnp.where(alpha1 == 0.0, 1.0, alpha1)
        v0 = g0 / safe_a

        normar0 = alpha1 * beta1
        threshold = jnp.maximum(tol * normar0, atol)
        diverged_at = 1e8 * normar0
        trace0 = engine.trace_init(normar0, maxiter, record_residuals)
        fail0 = engine.initial_fail(normar0)
        stag0 = engine.stagnation_init(normar0, stagnation_window)
        one = jnp.ones((), b_loc.dtype)

        def active_fn(state):
            j, zetabar, fail = state[0], state[7], state[16]
            return (
                (j < maxiter) & (jnp.abs(zetabar) > threshold) & (fail == 0)
            )

        def step(state, active, gate_matvec):
            del active, gate_matvec  # ell == 0: while-phase only
            (j, x, u_m, u_n, v, g, alpha, zetabar, alphabar, rho, rhobar,
             cbar, sbar, h, hbar, trace, fail, stag) = state

            av = apply(v)
            u_m_new = av - alpha * u_m
            bs = pt.vdot(u_m_new, u_m_new)
            if has_shift:
                u_n_new = sqrt_damp * v - alpha * u_n
                bs = bs + pt.vdot(u_n_new, u_n_new)
            (beta_sq_,) = engine.psum_merged([bs], ax)
            beta_new = jnp.sqrt(beta_sq_)
            sb = jnp.where(beta_new == 0.0, 1.0, beta_new)
            u_m_new = u_m_new / sb
            if has_shift:
                u_n_new = u_n_new / sb

            atu = rapply(u_m_new)
            g_new = atu + sqrt_damp * u_n_new if has_shift else atu
            w_vec = g_new - beta_new * v
            (as_,) = engine.psum_merged([pt.vdot(w_vec, w_vec)], ax)
            alpha_new = jnp.sqrt(as_)
            sa = jnp.where(alpha_new == 0.0, 1.0, alpha_new)
            v_new = w_vec / sa

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

            sr = jnp.where(rho_new == 0.0, 1.0, rho_new)
            srb = jnp.where(rhobar_new == 0.0, 1.0, rhobar_new)
            c0 = thetabar * rho_new / (rho_old * rhobar_old)
            c1 = zeta / (sr * srb)
            c2 = thetanew / sr
            x_new, hbar_new, h_new = kops.lsmr_update(
                x, hbar, h, v_new, c0, c1, c2
            )

            exact = (beta_new == 0.0) | (alpha_new == 0.0)
            zetabar_new = jnp.where(exact, 0.0, zetabar_new)
            normar_new = jnp.abs(zetabar_new)

            fail = jnp.where(
                (fail == 0) & (~jnp.isfinite(normar_new)),
                SolveStatus.BREAKDOWN_NONFINITE,
                fail,
            ).astype(jnp.int32)
            fail = jnp.where(
                (fail == 0) & (normar_new > diverged_at),
                SolveStatus.STAGNATED,
                fail,
            ).astype(jnp.int32)
            if stag is not None:
                stag, fail = engine.stagnation_update(
                    stag, normar_new, fail, jnp.bool_(True),
                    stagnation_window,
                )
            if trace is not None:
                trace = trace.at[j + 1].set(normar_new)

            state_new = (
                j + 1, x_new, u_m_new,
                u_n_new if has_shift else None,
                v_new, g_new, alpha_new, zetabar_new, alphabar_new,
                rho_new, rhobar_new, cbar_new, sbar_new, h_new, hbar_new,
                trace, fail, stag,
            )
            return state_new, ()

        state = (
            jnp.int32(0), x0_loc, u_m0, u_n0, v0, g0, alpha1,
            normar0, alpha1, one, one, one, jnp.zeros((), b_loc.dtype),
            v0, jnp.zeros_like(v0), trace0, fail0, stag0,
        )
        state, _ = engine.run_recording_loop(step, active_fn, state, ell=0)
        j, x = state[0], state[1]
        zetabar, trace, fail = state[7], state[15], state[16]
        normar = jnp.abs(zetabar)
        converged = normar <= threshold
        out = {
            "x": x,
            "iterations": j,
            "converged": converged,
            "residual_norm": normar,
            "matvecs": init_mv + 2 * j,
            "breakdown": fail > 0,
            "status": engine.exit_status(converged, fail),
        }
        if record_residuals:
            out["trace"] = trace
        return out

    return body


def _sym_ortho(a, b):
    """Stable Givens pair — duplicated from repro.core.lsmr to keep this
    module importable without the (heavier) lsmr module at trace time."""
    r = jnp.sqrt(a * a + b * b)
    safe = jnp.where(r == 0.0, 1.0, r)
    return a / safe, b / safe, r


# ---------------------------------------------------------------------------
# Builder — shard_map + jit, cached per (mesh, operator kind, spec)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=128)
def _build(mesh: Mesh, method: str, kind: str, aux, leaf_specs, statics):
    """Compile-cached sharded solver: ``shard_map`` over ``mesh`` of the
    method body, jitted.  Everything static rides the cache key; the
    returned callable takes only traced arrays."""
    st = dict(statics)
    if method == "cg":
        body = _sharded_cg_body(
            kind, aux,
            tol=st["tol"], atol=st["atol"], maxiter=st["maxiter"],
            stagnation_window=st["stagnation_window"],
            record_residuals=st["record_residuals"],
        )
        in_specs = (leaf_specs, P(SOLVE_AXIS), P(SOLVE_AXIS))
    elif method == "defcg":
        body = _sharded_defcg_body(
            kind, aux,
            k=st["k"], ell=st["ell"], tol=st["tol"], atol=st["atol"],
            maxiter=st["maxiter"], select=st["select"],
            waw_jitter=st["waw_jitter"], refresh_aw=st["refresh_aw"],
            stagnation_window=st["stagnation_window"],
            record_residuals=st["record_residuals"],
        )
        in_specs = (
            leaf_specs, P(SOLVE_AXIS), P(SOLVE_AXIS),
            basis_spec(), basis_spec(),
        )
    elif method == "lsmr":
        body = _sharded_lsmr_body(
            kind, aux,
            damp=st["damp"], tol=st["tol"], atol=st["atol"],
            maxiter=st["maxiter"],
            stagnation_window=st["stagnation_window"],
            record_residuals=st["record_residuals"],
            has_x0=st["has_x0"],
        )
        in_specs = (leaf_specs, P(SOLVE_AXIS), P(SOLVE_AXIS))
    else:
        raise ValueError(f"unknown sharded method {method!r}")

    out_specs = {
        "x": vector_spec(),
        "iterations": P(),
        "converged": P(),
        "residual_norm": P(),
        "matvecs": P(),
        "breakdown": P(),
        "status": P(),
    }
    if st["record_residuals"]:
        out_specs["trace"] = P()
    if method == "defcg":
        out_specs["w"] = basis_spec()
        out_specs["aw"] = basis_spec()
        if st["ell"] > 0:
            out_specs["theta"] = P()

    sharded = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(sharded)


def _divisible(name: str, size: int, n_shards: int) -> None:
    if size % n_shards != 0:
        raise ValueError(
            f"{name} has length {size}, not divisible by the solve mesh's "
            f"{n_shards} shards — pad the problem or resize the mesh "
            "(repro.launch.mesh.make_solve_mesh(n_devices=...))"
        )


def _prepare(A, b, spec, state, *, mesh, x0, record_residuals):
    """Shared host-side setup of :func:`solve_sharded` /
    :func:`lower_sharded`: validation, operator planning, argument
    flattening.  Returns ``(fn, args, assemble)``."""
    from repro.core import api as api_mod

    spec = api_mod.SolveSpec() if spec is None else spec
    if not isinstance(mesh, Mesh) or SOLVE_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh must be a jax Mesh with a {SOLVE_AXIS!r} axis — build "
            "one with repro.launch.mesh.make_solve_mesh()"
        )
    if spec.method not in _SHARDED_METHODS:
        raise NotImplementedError(
            f"method={spec.method!r} has no sharded path yet (supported: "
            f"{_SHARDED_METHODS}); drop the mesh argument"
        )
    if spec.precond != "none":
        raise ValueError(
            "the sharded engine has no preconditioner path — use "
            "precond='none' or drop the mesh argument"
        )
    if spec.method == "defcg" and type(spec.strategy) is not HarmonicRitz:
        raise ValueError(
            "the sharded def-CG path extracts through the default "
            f"HarmonicRitz strategy only, got {type(spec.strategy).__name__}"
        )

    n_shards = mesh.shape[SOLVE_AXIS]
    need_adjoint = spec.method == "lsmr"
    kind, aux, leaves, leaf_specs = _plan_operator(
        A, need_adjoint=need_adjoint
    )

    b_flat, _ = pt.ravel_vector(b)
    m = b_flat.shape[0]
    _divisible("b", m, n_shards)

    if spec.method == "lsmr":
        if kind == "dense":
            n = leaves[0].shape[1]
        else:
            n = m  # symmetric-by-contract operators: domain == range
        _divisible("x", n, n_shards)
        has_x0 = x0 is not None
        x0_flat = (
            pt.ravel(x0) if has_x0 else jnp.zeros((n,), b_flat.dtype)
        )
        statics = (
            ("damp", float(spec.lsq_shift)),
            ("tol", float(spec.tol)),
            ("atol", float(spec.atol)),
            ("maxiter", int(spec.maxiter)),
            ("stagnation_window", int(spec.stagnation_window)),
            ("record_residuals", bool(record_residuals)),
            ("has_x0", has_x0),
        )
        fn = _build(mesh, "lsmr", kind, aux, leaf_specs, statics)
        args = (
            _commit_tree(mesh, leaves, leaf_specs),
            _commit(mesh, b_flat, vector_spec()),
            _commit(mesh, x0_flat, vector_spec()),
        )

        def assemble(out):
            info = _info_from(out, record_residuals)
            return api_mod.SolveResult(
                x=out["x"], info=info, state=state,
                report=api_mod._make_report(info, 0),
            )

        return fn, args, assemble

    n = m
    x0_flat = jnp.zeros_like(b_flat) if x0 is None else pt.ravel(x0)

    if spec.method == "cg":
        statics = (
            ("tol", float(spec.tol)),
            ("atol", float(spec.atol)),
            ("maxiter", int(spec.maxiter)),
            ("stagnation_window", int(spec.stagnation_window)),
            ("record_residuals", bool(record_residuals)),
        )
        fn = _build(mesh, "cg", kind, aux, leaf_specs, statics)
        args = (
            _commit_tree(mesh, leaves, leaf_specs),
            _commit(mesh, b_flat, vector_spec()),
            _commit(mesh, x0_flat, vector_spec()),
        )

        def assemble(out):
            info = _info_from(out, record_residuals)
            return api_mod.SolveResult(
                x=out["x"], info=info, state=state,
                report=api_mod._make_report(info, 0),
            )

        return fn, args, assemble

    # -- defcg ----------------------------------------------------------
    state_in = (
        RecycleState.zeros(spec.k, n, b_flat.dtype)
        if state is None
        else state
    )
    if state_in.W.ndim != 2 or state_in.W.shape != (spec.k, n):
        raise ValueError(
            f"state.W has shape {state_in.W.shape}; spec(k={spec.k}) over "
            f"this system needs ({spec.k}, {n}) — state and spec must agree"
        )
    statics = (
        ("k", int(spec.k)),
        ("ell", int(spec.ell)),
        ("tol", float(spec.tol)),
        ("atol", float(spec.atol)),
        ("maxiter", int(spec.maxiter)),
        ("select", spec.select),
        ("waw_jitter", float(spec.waw_jitter)),
        ("refresh_aw", spec.refresh_aw),
        ("stagnation_window", int(spec.stagnation_window)),
        ("record_residuals", bool(record_residuals)),
    )
    fn = _build(mesh, "defcg", kind, aux, leaf_specs, statics)
    args = (
        _commit_tree(mesh, leaves, leaf_specs),
        _commit(mesh, b_flat, vector_spec()),
        _commit(mesh, x0_flat, vector_spec()),
        _commit(mesh, state_in.W, basis_spec()),
        _commit(mesh, state_in.AW, basis_spec()),
    )

    def assemble(out):
        info = _info_from(out, record_residuals)
        new_state = RecycleState(
            W=out["w"],
            AW=out["aw"],
            theta=out["theta"] if spec.ell > 0 else state_in.theta,
            systems_solved=state_in.systems_solved + 1,
            drift=(
                jnp.zeros((), state_in.drift.dtype)
                if spec.ell > 0
                else state_in.drift
            ),
        )
        return api_mod.SolveResult(
            x=out["x"], info=info, state=new_state,
            report=api_mod._make_report(info, 0),
        )

    return fn, args, assemble


def _info_from(out, record_residuals: bool) -> SolveInfo:
    return SolveInfo(
        iterations=out["iterations"],
        converged=out["converged"],
        residual_norm=out["residual_norm"],
        matvecs=out["matvecs"],
        residual_norms=out.get("trace") if record_residuals else None,
        breakdown=out["breakdown"],
        status=out["status"],
    )


def solve_sharded(
    A,
    b: Pytree,
    spec=None,
    state: Optional[RecycleState] = None,
    *,
    mesh: Mesh,
    x0: Optional[Pytree] = None,
    record_residuals: bool = False,
):
    """One solve on the ``"solve"`` mesh — the sharded twin of
    :func:`repro.core.api.solve` (which forwards here when called with
    ``mesh=``).  Same ``SolveResult`` contract; see the module docstring
    for the (small, documented) semantic differences.
    """
    fn, args, assemble = _prepare(
        A, b, spec, state, mesh=mesh, x0=x0,
        record_residuals=record_residuals,
    )
    return assemble(fn(*args))


def lower_sharded(
    A,
    b: Pytree,
    spec=None,
    state: Optional[RecycleState] = None,
    *,
    mesh: Mesh,
    x0: Optional[Pytree] = None,
    record_residuals: bool = False,
):
    """The sharded solve's :class:`jax.stages.Lowered` — for the HLO
    collective-counting gates (``lowered.compile().as_text()`` feeds
    :func:`repro.launch.hlo_stats.while_body_collectives`)."""
    fn, args, _ = _prepare(
        A, b, spec, state, mesh=mesh, x0=x0,
        record_residuals=record_residuals,
    )
    return fn.lower(*args)
