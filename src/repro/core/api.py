"""One front door for every solve: ``SolveSpec`` + ``RecycleState``.

The paper's pitch is interpolating between a-priori low-rank approximations
(preconditioners) and exact solves (deflation/recycling) — Soodhalter et
al.'s recycling survey treats the two as one composable projection
framework.  Before this module, only plain ``cg`` accepted a
preconditioner, and five entry points each re-declared overlapping kwargs
with drifting defaults.  This module makes the combination declarative:

* :class:`SolveSpec` — a frozen, hashable description of *how* to solve
  (method axis ``cg``/``defcg``/``lsmr``/``deflsmr``, deflation sizes,
  tolerances, preconditioner strategy, least-squares shift).  It is
  the single source of truth for solver configuration: every default
  (``waw_jitter`` included) lives here or in the constant it re-exports,
  and the spec passes through ``jit`` as a static argument.
* :class:`RecycleState` (re-exported from :mod:`repro.core.recycle`) — the
  *what is carried between solves*: flat ``(k, n)`` recycled basis, its
  A-products, Ritz values, and a solve counter.  A registered pytree, so
  it checkpoints, shards, and vmaps over a leading tenant axis.

Front doors (everything else is a compatibility shim over these):

* :func:`solve` — one system.  ``solve(A, b, spec, state) -> SolveResult``
  runs (preconditioned) CG or def-CG, refreshes ``AW`` per the spec, and
  returns the next ``RecycleState``.  Fully traceable: no host syncs, so
  it jits (``solve_jit``), vmaps, and pjit-shards.
* :func:`solve_sequence` — N related systems as ONE ``lax.scan`` (the
  device-resident sequence engine), spec-driven and preconditionable;
  ``method="deflsmr"`` runs the same scan over regularized least-squares
  systems with normal-equations recycling geometry.
* :func:`solve_batch` — B independent tenants (systems or sequences)
  under one ``vmap``: one compiled program serves every tenant, each with
  its own ``RecycleState`` and convergence flag (``info.converged`` is
  the per-tenant mask).  This is the serving shape for many users'
  GP/Laplace problems at once.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import lsmr as lsmr_mod
from repro.core import preconditioners as precond_mod
from repro.core import pytree as pt
from repro.core import recycle as recycle_mod
from repro.core import solvers as solvers_mod
from repro.core.recycle import RecycleState, SequenceResult
from repro.core.solvers import DEFAULT_WAW_JITTER, SolveInfo
from repro.core.strategies import (
    HarmonicRitz,
    MGeometryHarmonic,
    RecycleStrategy,
    WindowedRecombine,
)

Pytree = Any

_METHODS = ("cg", "defcg", "lsmr", "deflsmr")
# The least-squares half of the method axis: plain and recycled LSMR on
# min ‖Ax − b‖² + lsq_shift·‖x‖² (rectangular A; see repro.core.lsmr).
_LSQ_METHODS = ("lsmr", "deflsmr")
_SELECTS = ("largest", "smallest")
_REFRESH_MODES = ("exact", "stale")
_PRECONDS = ("none", "jacobi", "nystrom", "custom")

# The vmap axis name solve_batch lifts tenants over; the def-CG recording
# scan reduces `active` across it so the whole batch stops paying matvecs
# the moment the LAST tenant converges (see solvers.defcg `batch_axis`).
_TENANT_AXIS = "repro_tenants"


@dataclasses.dataclass(frozen=True)
class SolveSpec:
    """Declarative solver configuration — the single source of truth.

    Frozen and hashable, so it rides through ``jit`` as ONE static
    argument instead of a dozen drifting kwargs.  Field semantics:

    Attributes:
      method: the solver axis (DESIGN.md §12).  ``"cg"`` (no deflation;
        ``k``/``ell`` ignored) or ``"defcg"`` (deflated CG with
        harmonic-Ritz recycling) for SPD systems; ``"lsmr"`` (plain) or
        ``"deflsmr"`` (recycled, deflated in the normal-equations
        geometry) for regularized least-squares ``min ‖Ax − b‖² +
        lsq_shift·‖x‖²`` with rectangular ``A`` — see
        :mod:`repro.core.lsmr`.  The least-squares methods converge on
        the normal residual ``‖Âᵀr̂‖``, take no preconditioner, use the
        default :class:`HarmonicRitz` extraction only, and ignore the
        recovery ladder (LSMR has no SPD breakdown modes; a non-finite
        solve retires the basis and re-bootstraps instead).
      k: recycled subspace size (rows of ``RecycleState.W``).
      ell: leading ``(p, Ap)`` pairs recorded per solve for extraction.
      tol, atol, maxiter: convergence controls — stop when
        ``‖r‖ ≤ max(tol·‖b‖, atol)``.
      select: which end of the spectrum the extraction keeps
        (``"largest"`` deflates the top — right for ``A = I + H½KH½``).
      waw_jitter: relative diagonal jitter for the k×k ``WᵀAW`` Cholesky.
        The one shared default is
        :data:`repro.core.solvers.DEFAULT_WAW_JITTER`; keep it small
        (≳1e-8 measurably destabilizes def-CG — see ``solvers.defcg``).
      refresh_aw: ``"exact"`` — recompute ``AW`` per system (k matvecs,
        one fused multi-RHS pass); ``"stale"`` — reuse extraction
        products (zero matvecs, the paper's cheap mode; exact only for an
        unchanged operator).  Consumed by the :class:`HarmonicRitz`
        strategy only; the other strategies own their refresh policy, so
        combining them with ``"stale"`` is rejected as contradictory.
      strategy: the :class:`repro.core.strategies.RecycleStrategy` owning
        the end-of-solve transition ``(window, state) → state`` and the
        per-system refresh policy: :class:`HarmonicRitz` (incumbent),
        :class:`WindowedRecombine` (zero-matvec windowed refresh with a
        drift guard — the paper's O(n²(ℓ+1)k) accounting), or
        :class:`MGeometryHarmonic` (extraction in the preconditioner's
        geometry; requires ``precond != "none"``).
      precond: preconditioner strategy — ``"none"``, ``"jacobi"``
        (diagonal), ``"nystrom"`` (randomized eigensketch), or
        ``"custom"`` (caller passes any SPD apply as ``M``).  Strategies
        other than ``"none"`` need operator data; build the apply with
        :func:`make_preconditioner` and pass it as ``M``.
      precond_rank: sketch rank for ``"nystrom"``.
      precond_sigma: bulk shift σ for the Nyström formula.
      recovery_rungs: how far the escalating recovery ladder may climb
        when a def-CG attempt ends broken (``SolveStatus`` ≥ 2) or
        unconverged with a carried basis: 1 = refresh ``AW = A·W`` and
        redo, 2 = + drop the basis (cold re-solve + re-seed), 3 = + plain
        CG against ``A + σI`` with the preconditioner disabled (last
        resort for a numerically indefinite operator).  0 disarms
        recovery entirely.  Every executed attempt's matvecs are charged
        to ``info.matvecs``; the rung taken is reported in
        ``result.report.rung``.  The ladder is one ``lax.while_loop``
        that runs zero iterations on a clean solve — clean-path iterates
        and matvec totals are untouched.
      recovery_shift: the σ of the rung-3 shift, relative to nothing —
        an absolute diagonal offset (the escalated-jitter analog at the
        operator level).  Keep it far below the operator's smallest
        eigenvalue of interest; it biases the rung-3 solution by
        ``O(σ‖x‖)``.
      stagnation_window: > 0 arms the stalled-residual detector: a solve
        whose best ‖r‖ fails to improve by 1% over this many consecutive
        iterations stops with STAGNATED status (and, with recovery
        armed, climbs the ladder) instead of burning the rest of
        ``maxiter``.  0 (default) adds no loop state and no checks.
      lsq_shift: the ridge λ ≥ 0 of the least-squares methods (static —
        it selects the augmented-block code path at trace time; 0 solves
        ordinary least squares).  Rejected for the SPD methods, whose
        operators carry their own shift.
    """

    method: str = "defcg"
    k: int = 8
    ell: int = 12
    tol: float = 1e-5
    atol: float = 0.0
    maxiter: int = 1000
    select: str = "largest"
    waw_jitter: float = DEFAULT_WAW_JITTER
    refresh_aw: str = "exact"
    precond: str = "none"
    precond_rank: int = 16
    precond_sigma: float = 1.0
    strategy: RecycleStrategy = HarmonicRitz()
    recovery_rungs: int = 3
    recovery_shift: float = 1e-6
    stagnation_window: int = 0
    lsq_shift: float = 0.0

    def __post_init__(self):
        if self.method not in _METHODS:
            raise ValueError(f"method must be one of {_METHODS}, got {self.method!r}")
        if self.select not in _SELECTS:
            raise ValueError(f"select must be one of {_SELECTS}, got {self.select!r}")
        if self.refresh_aw not in _REFRESH_MODES:
            raise ValueError(
                f"refresh_aw must be one of {_REFRESH_MODES}, got {self.refresh_aw!r}"
            )
        if self.precond not in _PRECONDS:
            raise ValueError(
                f"precond must be one of {_PRECONDS}, got {self.precond!r}"
            )
        if self.method in ("defcg", "deflsmr") and self.k < 1:
            raise ValueError(f"{self.method} needs k >= 1, got k={self.k}")
        if self.lsq_shift < 0:
            raise ValueError(
                f"lsq_shift must be >= 0, got {self.lsq_shift}"
            )
        if self.lsq_shift != 0.0 and self.method not in _LSQ_METHODS:
            raise ValueError(
                f"lsq_shift is the ridge λ of the least-squares methods "
                f"{_LSQ_METHODS}; method={self.method!r} ignores it — SPD "
                "operators carry their own shift"
            )
        if self.method in _LSQ_METHODS:
            if self.precond != "none":
                raise ValueError(
                    f"method={self.method!r} has no preconditioner path — "
                    "LSMR's geometry is fixed by the augmented operator; "
                    "use precond='none'"
                )
            if type(self.strategy) is not HarmonicRitz:
                raise ValueError(
                    f"method={self.method!r} extracts through the shared "
                    "harmonic-Ritz core only — custom strategies are "
                    "def-CG policies"
                )
        if self.ell < 0 or self.maxiter < 1 or self.precond_rank < 1:
            raise ValueError("ell >= 0, maxiter >= 1, precond_rank >= 1 required")
        if self.tol < 0 or self.atol < 0 or self.waw_jitter < 0:
            raise ValueError("tol, atol and waw_jitter must be non-negative")
        if not 0 <= self.recovery_rungs <= recycle_mod.MAX_RECOVERY_RUNGS:
            raise ValueError(
                f"recovery_rungs must be in [0, "
                f"{recycle_mod.MAX_RECOVERY_RUNGS}], got {self.recovery_rungs}"
            )
        if self.recovery_shift < 0 or self.stagnation_window < 0:
            raise ValueError(
                "recovery_shift and stagnation_window must be non-negative"
            )
        if not isinstance(self.strategy, RecycleStrategy):
            raise ValueError(
                "strategy must be a repro.core.strategies.RecycleStrategy "
                f"instance, got {self.strategy!r}"
            )
        if (
            self.refresh_aw == "stale"
            and not isinstance(self.strategy, HarmonicRitz)
        ):
            raise ValueError(
                f"refresh_aw='stale' conflicts with strategy="
                f"{type(self.strategy).__name__}: non-default strategies "
                "own their refresh policy (WindowedRecombine IS the "
                "guarded stale mode)"
            )
        if self.strategy.needs_preconditioner and self.precond == "none":
            raise ValueError(
                f"strategy={type(self.strategy).__name__} extracts in the "
                "preconditioner's geometry — it needs precond != 'none'"
            )
        if (
            isinstance(self.strategy, WindowedRecombine)
            and self.method == "defcg"
            and self.ell == 0
        ):
            # Without a recording window there is no transition: the
            # carried AW can never be re-derived from stored quantities
            # and the drift carry never updates, so every solve would
            # re-pay the in-solve refresh it exists to avoid.
            raise ValueError(
                "strategy=WindowedRecombine needs ell > 0 — its refresh "
                "recombines the recorded window"
            )


class SolveReport(NamedTuple):
    """Failure-handling diagnostics of a solve — one per front door.

    A small pytree of traced values (per-system / per-tenant stacked on
    the sequence and batch doors):

    Attributes:
      status: int32 :class:`repro.core.solvers.SolveStatus` code of the
        ADOPTED attempt (CONVERGED / MAXITER / BREAKDOWN_NONFINITE /
        BREAKDOWN_INDEFINITE / STAGNATED).
      rung: int32 highest recovery-ladder rung executed (0 = clean solve,
        ladder never fired; see ``SolveSpec.recovery_rungs``).
      guard_firings: int32 count of in-solve stale-guard ``AW`` refreshes.
      matvecs: honest total operator applications, including every failed
        ladder attempt and every guard/ladder refresh.
    """

    status: jax.Array
    rung: jax.Array
    guard_firings: jax.Array
    matvecs: jax.Array


def _make_report(info: SolveInfo, rung) -> SolveReport:
    return SolveReport(
        status=jnp.asarray(info.status, jnp.int32),
        rung=jnp.asarray(rung, jnp.int32),
        guard_firings=jnp.asarray(info.guard_fired, jnp.int32),
        matvecs=jnp.asarray(info.matvecs, jnp.int32),
    )


class SolveResult(NamedTuple):
    """What :func:`solve` returns: solution, diagnostics, next state."""

    x: Pytree
    info: SolveInfo
    state: Optional[RecycleState]
    report: Optional[SolveReport] = None


class SequenceSolveResult(NamedTuple):
    """Per-system stacked outputs of :func:`solve_sequence` + final state."""

    x: Pytree  # (num_systems, …) solutions
    info: SolveInfo  # stacked diagnostics
    theta: jnp.ndarray  # (num_systems, k) Ritz-value trace
    state: RecycleState  # final state, ready to seed the next call
    report: Optional[SolveReport] = None  # per-system failure diagnostics


class BatchSolveResult(NamedTuple):
    """Per-tenant stacked outputs of :func:`solve_batch` (leading axis B).

    ``info.converged`` is the per-tenant convergence mask;
    ``report.status`` is the per-tenant (or ``(B, N)`` per-system)
    failure status — a broken tenant is retired into its slot of this
    report instead of poisoning the batch.
    """

    x: Pytree
    info: SolveInfo
    state: Optional[RecycleState]
    report: Optional[SolveReport] = None


def make_preconditioner(
    A,
    spec: SolveSpec,
    template: Pytree,
    *,
    diag: Optional[Pytree] = None,
    key=None,
):
    """Build the ``M`` apply for ``spec.precond`` (None for ``"none"``).

    ``"jacobi"`` needs ``diag`` (the operator diagonal as a vector
    pytree); ``"nystrom"`` needs ``key`` and spends
    ``spec.precond_rank + 8`` matvecs on the sketch — an a-priori cost
    that amortizes across every solve that reuses the returned apply.
    The result is a registered pytree node, so the jitted front doors
    treat it as traced data (rebuilding it per system reuses one
    compiled solve).
    """
    if spec.precond == "none":
        return None
    if spec.precond == "jacobi":
        if diag is None:
            raise ValueError("precond='jacobi' needs diag=<operator diagonal>")
        return precond_mod.jacobi(diag)
    if spec.precond == "nystrom":
        if key is None:
            raise ValueError("precond='nystrom' needs key=<PRNG key>")
        U, lam = precond_mod.randomized_nystrom(
            A, template, rank=spec.precond_rank, key=key
        )
        return precond_mod.nystrom_preconditioner(U, lam, spec.precond_sigma)
    raise ValueError(
        "precond='custom' supplies its own apply — pass it as M instead"
    )


def _check_m(spec: SolveSpec, M) -> None:
    if spec.precond not in ("none",) and M is None:
        raise ValueError(
            f"spec.precond={spec.precond!r} but no M was passed — build one "
            "with repro.core.make_preconditioner(A, spec, template, ...)"
        )


def _check_state(state: RecycleState, k: int, n: int, over: str) -> None:
    """Reject a carried state whose basis does not fit ``spec.k`` and the
    system's size, or whose ``AW`` is not one array shaped like ``W`` —
    here, with the shapes named, not as an XLA shape error mid-solve."""
    if state.W.ndim != 2 or state.W.shape != (k, n):
        raise ValueError(
            f"state.W has shape {state.W.shape}; spec(k={k}) over {over} "
            f"needs ({k}, {n}) — state and spec must agree"
        )
    if getattr(state.AW, "shape", None) != state.W.shape:
        raise ValueError(
            "state.AW must be one array shaped like state.W "
            f"{state.W.shape}, got "
            f"{jax.tree_util.tree_map(jnp.shape, state.AW)}"
        )


# ---------------------------------------------------------------------------
# solve — one system
# ---------------------------------------------------------------------------


def solve(
    A,
    b: Pytree,
    spec: Optional[SolveSpec] = None,
    state: Optional[RecycleState] = None,
    *,
    x0: Optional[Pytree] = None,
    M=None,
    record_residuals: bool = False,
    batch_axis: Optional[str] = None,
    mesh=None,
) -> SolveResult:
    """Solve one SPD system ``A x = b`` per ``spec``, carrying ``state``.

    The single-system front door: (preconditioned) CG or def-CG on the
    flat engine.  For ``method="defcg"`` the returned ``state`` holds the
    harmonic-Ritz basis extracted from this solve — feed it back in for
    the next related system.  ``state=None`` bootstraps cold (an all-zero
    basis deflates as an exact no-op, so the first solve is plain CG plus
    recording).  Fully traceable — no host syncs — so this function jits
    (:data:`solve_jit`), vmaps (:func:`solve_batch`), and shards.

    ``M`` is the preconditioner apply for ``spec.precond`` (see
    :func:`make_preconditioner`); deflation composes with it through the
    split-preconditioned iteration of :func:`repro.core.solvers.defcg`.

    ``method="cg"`` and ``method="lsmr"`` neither consume nor update
    recycle state: a supplied ``state`` passes through UNTOUCHED (not
    validated, counter not bumped) so a mixed pipeline can thread one
    state through both.  The least-squares methods accept rectangular
    ``A`` (adjoint via ``rmatvec``; ``b`` lives in the range space, the
    solution in the domain) and solve ``min ‖Ax − b‖² +
    spec.lsq_shift·‖x‖²`` — ``info.residual_norm`` is then the normal
    residual ``‖Âᵀr̂‖``, the quantity LSMR converges on.

    Accounting: ``info.matvecs`` includes whatever refresh the spec's
    strategy spent (k operator applications for an exact refresh with a
    carried basis; zero on cold bootstraps, un-triggered guards, and
    stale mode), matching :func:`solve_sequence`.

    ``batch_axis`` names the ``vmap`` axis when this solve is lifted
    over tenants (``solve_batch`` sets it) — it arms the recording
    scan's cross-tenant matvec gate; leave ``None`` otherwise.

    ``mesh`` opts into the SPMD engine: pass a 1-D ``"solve"`` mesh
    (:func:`repro.launch.mesh.make_solve_mesh`) and the solve runs
    n-sharded across its devices through
    :func:`repro.core.sharded.solve_sharded` — one all-reduce per
    def-CG/CG iteration, operator data row-sharded.  ``mesh=None`` (the
    default) is the unchanged single-device path; the two differ only in
    the (documented) sharded-path restrictions — no preconditioner, no
    recovery ladder, ``cg``/``defcg``/``lsmr`` only.
    """
    spec = SolveSpec() if spec is None else spec
    if mesh is not None:
        if M is not None:
            raise ValueError(
                "the sharded engine has no preconditioner path — M must "
                "be None when mesh= is given"
            )
        if batch_axis is not None:
            raise ValueError(
                "mesh= and batch_axis= do not compose — shard one solve "
                "or vmap many, not both"
            )
        from repro.core import sharded as sharded_mod

        return sharded_mod.solve_sharded(
            A, b, spec, state, mesh=mesh, x0=x0,
            record_residuals=record_residuals,
        )
    _check_m(spec, M)

    if spec.method in _LSQ_METHODS:
        if M is not None:
            raise ValueError(
                f"method={spec.method!r} takes no preconditioner apply"
            )
        if spec.method == "lsmr":
            res = lsmr_mod.lsmr(
                A,
                b,
                x0,
                damp=spec.lsq_shift,
                tol=spec.tol,
                atol=spec.atol,
                maxiter=spec.maxiter,
                record_residuals=record_residuals,
                batch_axis=batch_axis,
                stagnation_window=spec.stagnation_window,
            )
            return SolveResult(
                x=res.x,
                info=res.info,
                state=state,
                report=_make_report(res.info, 0),
            )
        # deflsmr: the recycled basis lives in the DOMAIN space, whose
        # dimension a rectangular system's b cannot reveal — probe the
        # adjoint (zero cost) instead.
        x_tmpl = x0 if x0 is not None else lsmr_mod._domain_template(A, b)
        x_flat_t, unravel_x = pt.ravel_vector(x_tmpl)
        n = x_flat_t.shape[0]
        if state is None:
            state = RecycleState.zeros(spec.k, n, x_flat_t.dtype)
        _check_state(state, spec.k, n, "this system's domain")
        x, info, w2, nw2, theta, rung = lsmr_mod._one_recycled_lsmr(
            A,
            b,
            x0,
            state.W,
            state.AW,
            unravel_x,
            k=spec.k,
            ell=spec.ell,
            damp=spec.lsq_shift,
            tol=spec.tol,
            atol=spec.atol,
            maxiter=spec.maxiter,
            select=spec.select,
            waw_jitter=spec.waw_jitter,
            refresh_aw=spec.refresh_aw,
            record_residuals=record_residuals,
            batch_axis=batch_axis,
            stagnation_window=spec.stagnation_window,
        )
        new_state = RecycleState(
            W=w2,
            AW=nw2,  # the AW slot carries NW = (AᵀA + λI)W for deflsmr
            theta=state.theta if theta is None else theta,
            systems_solved=state.systems_solved + 1,
            drift=state.drift,
        )
        return SolveResult(
            x=x, info=info, state=new_state,
            report=_make_report(info, rung),
        )

    if spec.method == "cg":
        res = solvers_mod.cg(
            A,
            b,
            x0,
            tol=spec.tol,
            atol=spec.atol,
            maxiter=spec.maxiter,
            M=M,
            record_residuals=record_residuals,
            stagnation_window=spec.stagnation_window,
        )
        return SolveResult(
            x=res.x,
            info=res.info,
            state=state,
            report=_make_report(res.info, 0),
        )

    b_flat, unravel = pt.ravel_vector(b)
    n = b_flat.shape[0]
    if state is None:
        state = RecycleState.zeros(spec.k, n, b_flat.dtype)
    _check_state(state, spec.k, n, "this system")

    # Per-system semantics (refresh policy, accounting, strategy
    # transition, recovery ladder) are shared with solve_sequence's scan
    # body — ONE implementation, no drift.
    x, info, w2, aw2, theta, drift2, rung = recycle_mod._one_recycled_solve(
        A,
        b,
        x0,
        state.W,
        state.AW,
        state.drift,
        unravel,
        k=spec.k,
        ell=spec.ell,
        tol=spec.tol,
        atol=spec.atol,
        maxiter=spec.maxiter,
        select=spec.select,
        waw_jitter=spec.waw_jitter,
        refresh_aw=spec.refresh_aw,
        strategy=spec.strategy,
        M=M,
        record_residuals=record_residuals,
        batch_axis=batch_axis,
        recovery_rungs=spec.recovery_rungs,
        recovery_shift=spec.recovery_shift,
        stagnation_window=spec.stagnation_window,
    )
    new_state = RecycleState(
        W=w2,
        AW=aw2,
        # ell == 0 records nothing — carry the previous Ritz values.
        theta=state.theta if theta is None else theta,
        systems_solved=state.systems_solved + 1,
        drift=drift2.astype(state.drift.dtype),
    )
    return SolveResult(
        x=x, info=info, state=new_state, report=_make_report(info, rung)
    )


solve_jit = jax.jit(
    solve, static_argnames=("spec", "record_residuals", "batch_axis", "mesh")
)


# ---------------------------------------------------------------------------
# solve_sequence — N related systems, one lax.scan
# ---------------------------------------------------------------------------


def _solve_sequence_spec(
    systems: Any,
    b_seq: Pytree,
    spec: SolveSpec,
    state0: Optional[RecycleState],
    *,
    make_operator: Optional[Callable[[Any], Any]] = None,
    make_preconditioner: Optional[Callable[[Any], Any]] = None,
    carry_x: bool = False,
    divergence_fallback: bool = True,
    batch_axis: Optional[str] = None,
    x_prev0: Optional[jnp.ndarray] = None,
) -> SequenceSolveResult:
    if spec.method not in ("defcg", "deflsmr"):
        raise ValueError(
            "solve_sequence recycles a deflation basis — it needs "
            f"spec.method='defcg' or 'deflsmr', got {spec.method!r} (for "
            "plain CG/LSMR over independent systems use solve_batch)"
        )
    if spec.precond != "none" and make_preconditioner is None:
        raise ValueError(
            f"spec.precond={spec.precond!r} but no make_preconditioner was "
            "passed — the sequence path builds M per system, so supply a "
            "factory mapping each operator to its preconditioner apply"
        )
    if spec.method == "deflsmr":
        seq = lsmr_mod.solve_sequence_lsmr(
            systems,
            b_seq,
            state0.W if state0 is not None else None,
            state0.AW if state0 is not None else None,
            k=spec.k,
            ell=spec.ell,
            damp=spec.lsq_shift,
            make_operator=make_operator,
            tol=spec.tol,
            atol=spec.atol,
            maxiter=spec.maxiter,
            select=spec.select,
            waw_jitter=spec.waw_jitter,
            refresh_aw=spec.refresh_aw,
            carry_x=carry_x,
            batch_axis=batch_axis,
            stagnation_window=spec.stagnation_window,
            x_prev0=x_prev0,
        )
        return _finish_sequence(seq, spec, state0, b_seq)
    seq = recycle_mod.solve_sequence(
        systems,
        b_seq,
        state0.W if state0 is not None else None,
        state0.AW if state0 is not None else None,
        k=spec.k,
        ell=spec.ell,
        make_operator=make_operator,
        make_preconditioner=make_preconditioner,
        tol=spec.tol,
        atol=spec.atol,
        maxiter=spec.maxiter,
        select=spec.select,
        waw_jitter=spec.waw_jitter,
        refresh_aw=spec.refresh_aw,
        carry_x=carry_x,
        strategy=spec.strategy,
        drift0=state0.drift if state0 is not None else None,
        batch_axis=batch_axis,
        # divergence_fallback=False hard-disables recovery (the legacy
        # switch); otherwise the spec's ladder depth governs.
        recovery_rungs=(spec.recovery_rungs if divergence_fallback else 0),
        recovery_shift=spec.recovery_shift,
        stagnation_window=spec.stagnation_window,
        x_prev0=x_prev0,
    )
    return _finish_sequence(seq, spec, state0, b_seq)


def _finish_sequence(
    seq: SequenceResult,
    spec: SolveSpec,
    state0: Optional[RecycleState],
    b_seq: Pytree,
) -> SequenceSolveResult:
    """Fold an engine ``SequenceResult`` into the front door's return
    shape — shared by the def-CG and deflsmr sequence paths (for the
    latter, the ``AW`` slot carries the normal-operator products)."""
    num_systems = jax.tree_util.tree_leaves(b_seq)[0].shape[0]
    solved0 = (
        state0.systems_solved if state0 is not None else jnp.int32(0)
    )
    if seq.theta is not None:
        theta = seq.theta[-1]
    elif state0 is not None:
        # ell == 0 records nothing — carry the previous Ritz values.
        theta = state0.theta
    else:
        theta = jnp.zeros((spec.k,), seq.W.dtype)
    state = RecycleState(
        W=seq.W,
        AW=seq.AW,
        theta=theta,
        systems_solved=solved0 + num_systems,
        drift=seq.drift,
    )
    return SequenceSolveResult(
        x=seq.x,
        info=seq.info,
        theta=seq.theta,
        state=state,
        report=_make_report(seq.info, seq.rung),
    )


# The chunked driver's per-chunk engine call, jitted ONCE at module
# scope.  Calling the engine eagerly per chunk rebuilds the scan body
# closure every time, and jax's eager scan cache is keyed on the
# function object — so every chunk recompiled its scan (and a resumed
# run recompiled them all again).  Through this single jit the driver
# compiles at most two programs per run shape: the full-chunk program
# and one trailing partial chunk — the budget the trace audit
# (`repro.analysis.trace_audit`) pins.  All callables must be
# cache-stable (module-level factories, not per-call lambdas) to hit it.
_solve_sequence_spec_jit = jax.jit(
    _solve_sequence_spec,
    static_argnames=(
        "spec",
        "make_operator",
        "make_preconditioner",
        "carry_x",
        "divergence_fallback",
        "batch_axis",
    ),
)


def _solve_sequence_chunked(
    systems: Any,
    b_seq: Pytree,
    spec: SolveSpec,
    state0: Optional[RecycleState],
    *,
    make_operator: Optional[Callable[[Any], Any]],
    make_preconditioner: Optional[Callable[[Any], Any]],
    carry_x: bool,
    divergence_fallback: bool,
    checkpoint,
    checkpoint_every: int,
    resume: bool,
) -> SequenceSolveResult:
    """Crash-resumable sequence driver: chunked scans + checkpoints.

    Splits the N-system sequence into ``checkpoint_every``-sized chunks,
    runs each chunk as one engine scan (at most TWO compilations: the
    full-chunk program plus one trailing partial chunk), and saves the
    full resume image — accumulated per-system outputs, the carried
    :class:`RecycleState`, the warm-start carry, and ``next_index`` —
    after every chunk via ``checkpoint.save(..., blocking=True)``.

    With ``resume=True`` the newest restorable checkpoint is loaded and
    the loop continues from its ``next_index``.  Chunk boundaries are
    deterministic and the image is stored in full precision, so a
    killed-and-resumed run reproduces the uninterrupted run's iterates
    exactly.
    """
    num_systems = jax.tree_util.tree_leaves(b_seq)[0].shape[0]
    b0 = jax.tree_util.tree_map(lambda l: l[0], b_seq)
    if spec.method == "deflsmr":
        # Rectangular systems: the carried basis and solution live in
        # the DOMAIN space — probe the first operator's adjoint.
        make_op = (
            make_operator if make_operator is not None else (lambda s: s)
        )
        A0 = make_op(jax.tree_util.tree_map(lambda l: l[0], systems))
        x0_flat, unravel = pt.ravel_vector(
            lsmr_mod._domain_template(A0, b0)
        )
        n = x0_flat.shape[0]
        dtype = x0_flat.dtype
    else:
        b0_flat, unravel = pt.ravel_vector(b0)
        n = b0_flat.shape[0]
        dtype = b0_flat.dtype
    if state0 is None:
        state0 = RecycleState.zeros(spec.k, n, dtype)

    # The resume image: everything needed to continue mid-sequence.
    acc = {
        "x": jnp.zeros((num_systems, n), dtype),
        "theta": jnp.zeros((num_systems, spec.k), dtype),
        "iterations": jnp.zeros((num_systems,), jnp.int32),
        "converged": jnp.zeros((num_systems,), bool),
        "residual_norm": jnp.zeros((num_systems,), dtype),
        "matvecs": jnp.zeros((num_systems,), jnp.int32),
        "breakdown": jnp.zeros((num_systems,), bool),
        "status": jnp.zeros((num_systems,), jnp.int32),
        "guard_fired": jnp.zeros((num_systems,), bool),
        "rung": jnp.zeros((num_systems,), jnp.int32),
        "state": state0,
        "x_carry": jnp.zeros((n,), dtype),
    }
    start = 0
    if resume:
        restored = checkpoint.restore_latest(acc)
        if restored is not None:
            _, acc, extra = restored
            # repro-lint: disable=host-sync-in-trace — host resume path:
            # `extra` is the checkpoint's plain-dict metadata, never traced.
            start = int(extra["next_index"])

    ravel_each = jax.vmap(pt.ravel)
    while start < num_systems:
        stop = min(start + checkpoint_every, num_systems)
        sl = slice(start, stop)
        res = _solve_sequence_spec_jit(
            jax.tree_util.tree_map(lambda l: l[sl], systems),
            jax.tree_util.tree_map(lambda l: l[sl], b_seq),
            spec,
            acc["state"],
            make_operator=make_operator,
            make_preconditioner=make_preconditioner,
            carry_x=carry_x,
            divergence_fallback=divergence_fallback,
            x_prev0=acc["x_carry"] if carry_x else None,
        )
        x_flat = ravel_each(res.x)
        acc = dict(
            acc,
            x=acc["x"].at[sl].set(x_flat),
            iterations=acc["iterations"].at[sl].set(res.info.iterations),
            converged=acc["converged"].at[sl].set(res.info.converged),
            residual_norm=acc["residual_norm"]
            .at[sl]
            .set(res.info.residual_norm.astype(dtype)),
            matvecs=acc["matvecs"].at[sl].set(res.info.matvecs),
            breakdown=acc["breakdown"]
            .at[sl]
            .set(jnp.asarray(res.info.breakdown, bool)),
            status=acc["status"].at[sl].set(jnp.asarray(res.info.status)),
            guard_fired=acc["guard_fired"]
            .at[sl]
            .set(jnp.asarray(res.info.guard_fired, bool)),
            rung=acc["rung"].at[sl].set(res.report.rung),
            state=res.state,
            x_carry=x_flat[-1],
        )
        if res.theta is not None:
            acc["theta"] = acc["theta"].at[sl].set(res.theta)
        checkpoint.save(
            acc, step=stop, extra={"next_index": stop}, blocking=True
        )
        start = stop

    info = SolveInfo(
        iterations=acc["iterations"],
        converged=acc["converged"],
        residual_norm=acc["residual_norm"],
        matvecs=acc["matvecs"],
        breakdown=acc["breakdown"],
        status=acc["status"],
        guard_fired=acc["guard_fired"],
    )
    return SequenceSolveResult(
        x=jax.vmap(unravel)(acc["x"]),
        info=info,
        theta=acc["theta"] if spec.ell > 0 else None,
        state=acc["state"],
        report=_make_report(info, acc["rung"]),
    )


def solve_sequence(
    systems: Any,
    b_seq: Pytree,
    spec: Optional[SolveSpec] = None,
    state0: Optional[RecycleState] = None,
    *,
    make_operator: Optional[Callable[[Any], Any]] = None,
    make_preconditioner: Optional[Callable[[Any], Any]] = None,
    carry_x: bool = False,
    divergence_fallback: bool = True,
    checkpoint=None,
    checkpoint_every: int = 0,
    resume: bool = False,
):
    """Solve a sequence of related systems on-device, spec-driven.

    ``solve_sequence(systems, b_seq, spec, state0)`` is the front door:
    one ``lax.scan`` carries the :class:`RecycleState` across systems
    (zero host syncs; see :func:`repro.core.recycle.solve_sequence` for
    the engine internals), returns a :class:`SequenceSolveResult` whose
    ``state`` seeds the next call.  ``make_preconditioner`` maps each
    per-system operator to its ``M`` apply, so the whole scan runs
    Nyström/Jacobi-preconditioned def-CG.

    Crash resumability: pass ``checkpoint`` (a
    :class:`repro.checkpoint.CheckpointManager`) and ``checkpoint_every``
    (systems per chunk) to run the sequence as deterministic chunked
    scans, saving the full resume image after each chunk.  With
    ``resume=True`` the run continues from the newest restorable
    checkpoint; a killed-and-resumed run reproduces the uninterrupted
    run's iterates exactly.

    ``spec.method`` selects the engine: ``"defcg"`` (SPD systems) or
    ``"deflsmr"`` (regularized least-squares, normal-equations
    recycling geometry).  The PR-3-era positional ``(W0, AW0, k=…,
    ell=…)`` signature has been removed — seed the basis through
    ``state0=RecycleState(W=…, AW=…, …)`` instead.
    """
    if spec is not None and not isinstance(spec, SolveSpec):
        raise TypeError(
            "solve_sequence(systems, b, W0, AW0, k=..., ell=...) was "
            "removed; pass solve_sequence(systems, b, SolveSpec(k=..., "
            "ell=...), state0=RecycleState(W=..., AW=..., ...))"
        )
    if checkpoint is not None:
        if checkpoint_every < 1:
            raise ValueError(
                "checkpoint= needs checkpoint_every >= 1 (systems per "
                f"chunk), got {checkpoint_every}"
            )
        return _solve_sequence_chunked(
            systems,
            b_seq,
            SolveSpec() if spec is None else spec,
            state0,
            make_operator=make_operator,
            make_preconditioner=make_preconditioner,
            carry_x=carry_x,
            divergence_fallback=divergence_fallback,
            checkpoint=checkpoint,
            checkpoint_every=checkpoint_every,
            resume=resume,
        )
    if resume or checkpoint_every:
        raise ValueError(
            "resume=/checkpoint_every= need checkpoint=<CheckpointManager>"
        )
    return _solve_sequence_spec(
        systems,
        b_seq,
        SolveSpec() if spec is None else spec,
        state0,
        make_operator=make_operator,
        make_preconditioner=make_preconditioner,
        carry_x=carry_x,
        divergence_fallback=divergence_fallback,
    )


# ---------------------------------------------------------------------------
# solve_batch — B independent tenants, one vmap, one XLA computation
# ---------------------------------------------------------------------------


def solve_batch(
    systems: Any,
    b_batch: Pytree,
    spec: Optional[SolveSpec] = None,
    state: Optional[RecycleState] = None,
    *,
    make_operator: Optional[Callable[[Any], Any]] = None,
    make_preconditioner: Optional[Callable[[Any], Any]] = None,
    sequence: bool = False,
    carry_x: bool = False,
) -> BatchSolveResult:
    """Solve B independent systems (or sequences) in ONE compiled program.

    The multi-tenant serving shape: ``vmap`` lifts the flat def-CG engine
    over a leading tenant axis, so B users' GP/Laplace solves share one
    XLA computation — per-tenant ``RecycleState`` (leading axis B),
    per-tenant convergence masks (``info.converged``), no host syncs.
    Under ``vmap`` the while-loop runs until the *slowest* tenant
    converges; finished tenants' carries are masked frozen, so every
    tenant's answer matches its sequential :func:`solve` bit-for-bit.

    Args:
      systems: per-tenant operator data with a leading B axis on every
        traced leaf — a stacked operator pytree (e.g. one
        ``KernelSystemOperator`` whose ``sqrt_h`` is ``(B, n)``: B tenants
        sharing one kernel) consumed directly, or raw data mapped through
        ``make_operator``.  With ``sequence=True`` each leaf carries
        ``(B, N, …)``: B tenants × N systems each.
      b_batch: stacked right-hand sides, leading axis B (``(B, N, …)``
        with ``sequence=True``).
      state: batched :class:`RecycleState` (leading axis B on every
        leaf), e.g. a previous call's output.  ``None`` bootstraps every
        tenant cold.
      make_preconditioner: per-tenant operator → ``M`` apply factory
        (stable callable), as in :func:`solve_sequence`.
      sequence: treat each tenant as a *sequence* of N related systems
        (vmapped :func:`solve_sequence`) instead of a single system.
      carry_x: warm-start within each tenant's sequence
        (``sequence=True`` only).

    Returns a :class:`BatchSolveResult`; with ``sequence=True`` its
    ``x``/``info`` carry axes ``(B, N, …)`` and ``state`` is the B final
    per-tenant states.
    """
    spec = SolveSpec() if spec is None else spec
    make_op = make_operator if make_operator is not None else (lambda s: s)

    if sequence:
        if spec.method not in ("defcg", "deflsmr"):
            raise ValueError(
                "sequence=True requires spec.method='defcg' or 'deflsmr'"
            )

        def one_seq(sys_i, b_i, st_i):
            res = _solve_sequence_spec(
                sys_i,
                b_i,
                spec,
                st_i,
                make_operator=make_operator,
                make_preconditioner=make_preconditioner,
                carry_x=carry_x,
                batch_axis=_TENANT_AXIS,
            )
            return res.x, res.info, res.state, res.report

        if state is None:
            state = _batched_zero_state(
                b_batch, spec, axes=2,
                systems=systems, make_operator=make_operator,
            )
        x, info, state_out, report = jax.vmap(
            one_seq, axis_name=_TENANT_AXIS
        )(systems, b_batch, state)
        return BatchSolveResult(x=x, info=info, state=state_out, report=report)

    if spec.method in ("cg", "lsmr"):

        def one_cg(sys_i, b_i):
            A = make_op(sys_i)
            M = (
                make_preconditioner(A)
                if make_preconditioner is not None
                else None
            )
            res = solve(A, b_i, spec, None, M=M)
            return res.x, res.info, res.report

        # Plain CG/LSMR neither consume nor update recycle state — a
        # caller-supplied batched state passes through untouched (same
        # contract as solve()).
        x, info, report = jax.vmap(one_cg)(systems, b_batch)
        return BatchSolveResult(x=x, info=info, state=state, report=report)

    def one(sys_i, b_i, st_i):
        A = make_op(sys_i)
        M = (
            make_preconditioner(A)
            if make_preconditioner is not None
            else None
        )
        # batch_axis: the recording scan's matvec gate reduces `active`
        # across the tenant axis, so the batch stops paying operator
        # applications the moment its LAST tenant converges.
        res = solve(A, b_i, spec, st_i, M=M, batch_axis=_TENANT_AXIS)
        return res.x, res.info, res.state, res.report

    if state is None:
        state = _batched_zero_state(
            b_batch, spec, axes=1,
            systems=systems, make_operator=make_operator,
        )
    x, info, state_out, report = jax.vmap(one, axis_name=_TENANT_AXIS)(
        systems, b_batch, state
    )
    return BatchSolveResult(x=x, info=info, state=state_out, report=report)


def _batched_zero_state(
    b_batch: Pytree,
    spec: SolveSpec,
    axes: int,
    *,
    systems: Any = None,
    make_operator: Optional[Callable[[Any], Any]] = None,
) -> RecycleState:
    """Cold per-tenant states: leading B axis over RecycleState.zeros.

    For the least-squares methods the basis dimension is the DOMAIN
    size, which ``b`` (range space) cannot reveal — one tenant's
    operator adjoint is probed (``eval_shape``, zero cost) instead.
    """
    leaves = jax.tree_util.tree_leaves(b_batch)
    B = leaves[0].shape[0]
    b0 = jax.tree_util.tree_map(lambda l: l[(0,) * axes], b_batch)
    if spec.method in _LSQ_METHODS:
        make_op = (
            make_operator if make_operator is not None else (lambda s: s)
        )
        A0 = make_op(
            jax.tree_util.tree_map(lambda l: l[(0,) * axes], systems)
        )
        b0_flat, _ = pt.ravel_vector(
            lsmr_mod._domain_template(A0, b0)
        )
    else:
        b0_flat, _ = pt.ravel_vector(b0)
    n = b0_flat.shape[0]
    dtype = b0_flat.dtype
    return RecycleState(
        W=jnp.zeros((B, spec.k, n), dtype),
        AW=jnp.zeros((B, spec.k, n), dtype),
        theta=jnp.zeros((B, spec.k), dtype),
        systems_solved=jnp.zeros((B,), jnp.int32),
        drift=jnp.zeros((B,), dtype),
    )


solve_batch_jit = jax.jit(
    solve_batch,
    static_argnames=(
        "spec",
        "make_operator",
        "make_preconditioner",
        "sequence",
        "carry_x",
    ),
)


# ---------------------------------------------------------------------------
# solve_pool_step — one slot-masked serving step over a fixed slot pool
# ---------------------------------------------------------------------------


def _slot_bcast(active: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a ``(B,)`` slot mask against a ``(B, …)`` leaf."""
    return active.reshape(active.shape + (1,) * (leaf.ndim - 1))


def solve_pool_step(
    systems: Any,
    b_batch: Pytree,
    spec: Optional[SolveSpec],
    state: RecycleState,
    active: jnp.ndarray,
    *,
    make_operator: Optional[Callable[[Any], Any]] = None,
    make_preconditioner: Optional[Callable[[Any], Any]] = None,
) -> BatchSolveResult:
    """One batched serving step over a FIXED pool of B slots, mask-aware.

    The serving layer (:mod:`repro.serve`) keeps B device-resident
    :class:`RecycleState` slots and, each scheduler tick, serves whatever
    subset of slots has work with ONE :func:`solve_batch` call.  This
    entry point owns the masking semantics of that step:

    * ``active`` is the ``(B,)`` bool slot mask.  Inactive slots (empty,
      or resident tenants with no pending request this tick) are served a
      ZERO right-hand side: ``‖r₀‖ = 0 ≤ max(tol·0, atol)`` so they
      converge before iteration 1, their lanes freeze, and the
      cross-tenant matvec gate (``psum`` over the vmap axis) stops
      charging them the moment the last *active* tenant converges — an
      idle slot never stalls or poisons its neighbours.
    * Inactive slots' ``RecycleState`` passes through BIT-UNTOUCHED: the
      post-step merge restores their incoming state leaf-wise, so a
      resident-but-idle tenant's warm basis (and ``systems_solved``
      counter) survives any number of ticks it sits out.
    * Inactive slots' diagnostics are scrubbed: ``info``/``report``
      report 0 iterations / 0 matvecs / CONVERGED for them, so pool
      metrics can sum per-slot counters without first filtering (the k
      refresh matvecs an idle warm slot's lane *physically* rides along
      in the batched GEMM are not attributed to any tenant — they are
      pool overhead, visible only in wall-clock).

    Dispatch note: the B=1 degenerate case (exactly one active slot)
    should NOT come here — the vmapped while-loop lowering pays a masked
    select/broadcast tax that loses to plain :func:`solve` at B=1 (the
    ``batch/`` bench records it); :class:`repro.serve.SolveService`
    gathers the single slot and dispatches through :data:`solve_jit`
    instead.  This function stays total — it accepts any mask, including
    one-hot — so the fast path is an optimization, not a semantic fork.
    """
    spec = SolveSpec() if spec is None else spec
    if spec.method not in ("defcg", "deflsmr"):
        raise ValueError(
            "solve_pool_step carries per-slot RecycleState — it needs "
            f"spec.method='defcg' or 'deflsmr', got {spec.method!r}"
        )
    if state is None:
        state = _batched_zero_state(
            b_batch, spec, axes=1,
            systems=systems, make_operator=make_operator,
        )
    active = jnp.asarray(active, bool)
    b_masked = jax.tree_util.tree_map(
        lambda l: jnp.where(_slot_bcast(active, l), l, jnp.zeros_like(l)),
        b_batch,
    )
    res = solve_batch(
        systems,
        b_masked,
        spec,
        state,
        make_operator=make_operator,
        make_preconditioner=make_preconditioner,
    )
    state_out = jax.tree_util.tree_map(
        lambda new, old: jnp.where(_slot_bcast(active, new), new, old),
        res.state,
        state,
    )
    info = res.info
    zero = jnp.int32(0)
    masked_info = SolveInfo(
        iterations=jnp.where(active, info.iterations, zero),
        converged=jnp.where(active, info.converged, True),
        residual_norm=jnp.where(
            active, info.residual_norm, jnp.zeros_like(info.residual_norm)
        ),
        matvecs=jnp.where(active, info.matvecs, zero),
        residual_norms=info.residual_norms,
        breakdown=jnp.where(active, jnp.asarray(info.breakdown, bool), False),
        status=jnp.where(active, jnp.asarray(info.status, jnp.int32), zero),
        guard_fired=jnp.where(
            active, jnp.asarray(info.guard_fired, bool), False
        ),
    )
    report = SolveReport(
        status=masked_info.status,
        rung=jnp.where(active, res.report.rung, zero),
        guard_firings=jnp.asarray(masked_info.guard_fired, jnp.int32),
        matvecs=masked_info.matvecs,
    )
    x = jax.tree_util.tree_map(
        lambda l: jnp.where(_slot_bcast(active, l), l, jnp.zeros_like(l)),
        res.x,
    )
    return BatchSolveResult(x=x, info=masked_info, state=state_out, report=report)


solve_pool_step_jit = jax.jit(
    solve_pool_step,
    static_argnames=("spec", "make_operator", "make_preconditioner"),
)
