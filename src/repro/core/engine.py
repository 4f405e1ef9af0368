"""Method-agnostic Krylov iteration harness (DESIGN.md §12).

Every iterative method in this repo — CG, def-CG, and now LSMR — shares
the same loop *scaffolding*: tolerance resolution, typed breakdown
classification with a sticky ``fail`` code, optional stalled-residual
detection, an optional residual-norm trace, honest matvec accounting,
the vmap-aware matvec gate, and the two-phase iteration shape (a
fixed-length masked recording ``lax.scan`` whose stacked outputs are the
recycling window, followed by a buffer-free ``lax.while_loop``).  Before
this module existed all of it lived inside ``core/solvers.py`` and any
second method would have had to copy-paste ~800 lines of it.

The contract a method implements:

* **state** — a flat tuple of traced values, opaque to the harness.
* ``active_fn(state) -> bool`` — whether the next step should run (the
  harness uses it as the while-loop condition AND to freeze scan steps
  after convergence).
* ``step(state, active, gate_matvec) -> (state, emit)`` — one iteration.
  ``active=False`` must freeze the state (masked no-op); ``gate_matvec``
  tells the step it is running inside the fixed-length recording scan,
  where the operator application should hide behind
  :func:`gated_matvec` so converged solves stop paying for it.  ``emit``
  is the per-step recycling record (rows of the window); the harness
  zero-masks it on frozen steps.

:func:`run_recording_loop` drives the two phases;
the classification/status/stagnation helpers are shared verbatim by the
method step functions.  Everything here is shape-static, jit-compatible
and vmap-safe — the harness adds no host syncs of its own.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import pytree as pt

Pytree = Any

# Stagnation test: a new best residual must beat the previous best by at
# least this factor to count as progress.  CG on a hard-but-healthy system
# keeps shaving the residual (1% over `stagnation_window` iterations is a
# very low bar); a solve that is looping on a poisoned recurrence does not.
STAGNATION_RTOL = 0.99


class SolveStatus:
    """Enumerated terminal status of an iterative solve.

    Plain int32 codes (not a Python enum) so they live inside jitted loop
    state and ``jnp.where`` selections.  ``0``/``1`` are the healthy exits;
    anything ``>= BREAKDOWN_NONFINITE`` means the iteration was cut short
    by a detected numerical failure and the recovery ladder
    (``repro.core.recycle``) may have re-solved.
    """

    CONVERGED = 0  # ‖r‖ ≤ max(tol·‖b‖, atol)
    MAXITER = 1  # iteration budget exhausted, no breakdown detected
    BREAKDOWN_NONFINITE = 2  # NaN/Inf in pᵀAp or ‖r‖ (poisoned matvec/basis)
    BREAKDOWN_INDEFINITE = 3  # pᵀAp ≤ 0: operator not SPD along p
    STAGNATED = 4  # residual stalled for `stagnation_window` iters, or diverged

    _NAMES = {
        0: "CONVERGED",
        1: "MAXITER",
        2: "BREAKDOWN_NONFINITE",
        3: "BREAKDOWN_INDEFINITE",
        4: "STAGNATED",
    }

    @classmethod
    def describe(cls, code) -> str:
        """Host-side pretty-printer for a (concrete) status code."""
        return cls._NAMES.get(int(code), f"UNKNOWN({int(code)})")


def classify_breakdown(d, rnorm, diverged_at):
    """Fold breakdown detection into the pᵀAp reduction already computed.

    Returns ``(bad, code)``: ``bad`` flags this iteration as broken and
    ``code`` is the int32 :class:`SolveStatus` cause (0 when healthy).
    Explosive residual growth (past the ``diverged_at`` ceiling) is
    classed as STAGNATED — "stopped converging" covers both stalling and
    running away; the non-finite/indefinite codes are reserved for
    detections at the reduction itself.
    """
    nonfinite = ~jnp.isfinite(d)
    indefinite = (~nonfinite) & (d <= 0.0)
    diverging = rnorm > diverged_at
    bad = nonfinite | indefinite | diverging
    code = jnp.where(
        nonfinite,
        SolveStatus.BREAKDOWN_NONFINITE,
        jnp.where(
            indefinite,
            SolveStatus.BREAKDOWN_INDEFINITE,
            SolveStatus.STAGNATED,
        ),
    )
    return bad, jnp.where(bad, code, 0).astype(jnp.int32)


def exit_status(converged, fail):
    return jnp.where(
        converged,
        SolveStatus.CONVERGED,
        jnp.where(fail > 0, fail, SolveStatus.MAXITER),
    ).astype(jnp.int32)


class SolveInfo(NamedTuple):
    """Diagnostics of an iterative solve (all traced values)."""

    iterations: jax.Array  # int32: iterations executed
    converged: jax.Array  # bool
    residual_norm: jax.Array  # final ‖r‖ (method's convergence quantity)
    matvecs: jax.Array  # total operator applications (A and Aᵀ both count)
    residual_norms: Optional[jax.Array] = None  # (maxiter+1,) trace or None
    breakdown: jax.Array | bool = False  # any in-loop breakdown detected
    status: jax.Array | int = 0  # int32 SolveStatus code of the terminal exit
    guard_fired: jax.Array | bool = False  # in-solve stale_guard refreshed AW


def tolerances(b, tol, atol):
    bnorm = pt.tree_norm(b)
    return jnp.maximum(tol * bnorm, atol), bnorm


def flat_operator(op, unravel):
    """Lift a pytree matvec/preconditioner to flat ``(n,)`` vectors."""

    def mv(v_flat):
        return pt.ravel(op(unravel(v_flat)))

    return mv


def initial_fail(rnorm0):
    """Sticky-fail seed: a non-finite initial residual (poisoned x0 /
    operator / basis) never enters the loop — flag it so the exit status
    reads BREAKDOWN_NONFINITE rather than a 0-iteration MAXITER."""
    return jnp.where(
        jnp.isfinite(rnorm0), 0, SolveStatus.BREAKDOWN_NONFINITE
    ).astype(jnp.int32)


def trace_init(rnorm0, maxiter: int, record: bool):
    """NaN-tailed residual trace, slot 0 pre-filled; ``None`` when off."""
    if not record:
        return None
    trace0 = jnp.full((maxiter + 1,), jnp.nan, dtype=rnorm0.dtype)
    return trace0.at[0].set(rnorm0)


def stagnation_init(rnorm0, window: int):
    """Stall-detector state ``(best, stall)`` — ``None`` when disarmed,
    so the clean path carries no extra loop state."""
    return (rnorm0, jnp.int32(0)) if window > 0 else None


def stagnation_update(stag, rnorm_new, fail, active, window: int):
    """One stall-detector step.  Returns ``(stag, fail)`` with STAGNATED
    latched into the sticky ``fail`` when the best residual has not
    improved by 1% for ``window`` consecutive active iterations."""
    best, stall = stag
    improved = rnorm_new < STAGNATION_RTOL * best
    stall_new = jnp.where(improved, 0, stall + 1).astype(jnp.int32)
    fail = jnp.where(
        (fail == 0) & active & (stall_new >= window),
        SolveStatus.STAGNATED,
        fail,
    ).astype(jnp.int32)
    stag = (
        jnp.where(active, jnp.minimum(best, rnorm_new), best),
        jnp.where(active, stall_new, stall),
    )
    return stag, fail


def psum_merged(parts, axis_name: str):
    """Batch several small reductions into ONE ``psum`` collective.

    ``parts`` is a sequence of per-shard partial reductions (scalars or
    1-D arrays, e.g. ``[pᵀap, rᵀap, apᵀap, AW@ap]``); they are packed
    into one flat vector, reduced with a single ``lax.psum`` over
    ``axis_name``, and unpacked to the original shapes.  This is the
    sharded engine's one-all-reduce-per-iteration contract (DESIGN.md
    §5): every scalar reduction of an iteration must ride this ONE
    collective — the HLO collective-counting pass
    (:func:`repro.launch.hlo_stats.while_body_collectives`) pins it.
    The collective carries the named scope ``sharded.psum``.
    """
    flats = [jnp.ravel(jnp.asarray(p)) for p in parts]
    packed = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
    with jax.named_scope("sharded.psum"):
        red = jax.lax.psum(packed, axis_name)
    out, off = [], 0
    for p, f in zip(parts, flats):
        out.append(jnp.reshape(red[off : off + f.shape[0]], jnp.shape(p)))
        off += f.shape[0]
    return out


def gated_matvec(
    apply, v, active, batch_axis: Optional[str], out_like=None
):
    """The recording scan's matvec gate: skip the operator outright once
    the solve has converged.

    Under ``vmap`` a per-lane ``lax.cond`` lowers to a ``select`` (both
    branches execute for every lane), so when ``batch_axis`` names the
    tenant axis the gate reduces ``active`` across it — the cross-tenant
    ``any(active)`` is unbatched, the ``cond`` survives batching, and the
    operator is skipped once EVERY lane is frozen.

    ``out_like`` shapes the skipped branch's zeros for RECTANGULAR
    operators (LSMR's ``A``/``Aᵀ`` map between different spaces); the
    default ``None`` keeps the square contract — zeros shaped like the
    input.
    """
    if batch_axis is None:
        run_mv = active
    else:
        run_mv = jax.lax.psum(active.astype(jnp.int32), batch_axis) > 0
    if out_like is None:
        return jax.lax.cond(run_mv, apply, jnp.zeros_like, v)
    return jax.lax.cond(
        run_mv, apply, lambda _: jnp.zeros_like(out_like), v
    )


def run_recording_loop(
    step: Callable,
    active_fn: Callable,
    state: Tuple,
    *,
    ell: int = 0,
):
    """Drive a method's iteration: recording scan, then plain while-loop.

    Phase 1 (``ell > 0``): exactly ``ell`` ``lax.scan`` steps whose
    stacked ``emit`` outputs are the recycling window — each row is
    written once by the scan, so no ``(ell, n)`` buffer rides through
    loop state (XLA copies loop-carried buffers on masked dynamic row
    writes; scan outputs it writes in place).  Steps after convergence
    are frozen: ``active_fn`` gates the step, the step's matvec hides
    behind :func:`gated_matvec`, and the emitted rows are zero-masked —
    the two-phase split is semantically identical to one guarded loop.

    Phase 2: a buffer-free ``lax.while_loop`` for the remaining
    iterations (``active=True``, matvec ungated).

    Returns ``(final_state, rows)`` where ``rows`` is the stacked emit
    pytree (``None`` when ``ell == 0``).
    """
    rows = None
    if ell > 0:

        def scan_body(state, _):
            active = active_fn(state)
            with jax.named_scope("engine.iteration"):
                state, emit = step(state, active, True)
            emit = jax.tree_util.tree_map(
                lambda e: jnp.where(active, e, jnp.zeros_like(e)), emit
            )
            return state, emit

        state, rows = jax.lax.scan(scan_body, state, None, length=ell)

    def cond(state):
        return active_fn(state)

    def body(state):
        with jax.named_scope("engine.iteration"):
            return step(state, jnp.bool_(True), False)[0]

    state = jax.lax.while_loop(cond, body, state)
    return state, rows
