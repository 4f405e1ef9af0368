"""Krylov subspace recycling: harmonic-Ritz extraction + cross-system state.

This is the paper's §2.3.  After def-CG solves system ``i`` we have

    Z  = [W, P_ell]        (k + ell stacked vectors)
    AZ = [AW, AP_ell]

and the harmonic projection (Morgan 1995) asks for ``(θ, u)`` with

    (AZ)ᵀ (AZ u − θ Z u) = 0    ⇔    G u = θ F u,
    G = (AZ)ᵀ(AZ)  (SPD),   F = (AZ)ᵀ Z = ZᵀAZ  (symmetric for A = Aᵀ).

We reduce the generalized problem with a Cholesky of ``G``:

    G = LLᵀ,  w = Lᵀu :   (L⁻¹ F L⁻ᵀ) w = (1/θ) w,

a small ``(k+ell)²`` symmetric eigenproblem solved identically (replicated)
on every device — far cheaper than any distributed scheme at these sizes.
The k selected Ritz vectors ``W' = Z U`` (and ``A W' = AZ · U``, free) are
the recycled deflation space for the *next* system in the sequence.

Column equilibration: the generalized eigenproblem is invariant under
column scaling ``Z → Z D`` (``G → DGD``, ``F → DFD``, ``θ`` unchanged), so
we equilibrate to unit ``‖Z_i‖`` / unit ``‖AZ_i‖`` before factoring — this
keeps the reduction well-posed even when late CG directions have tiny
norms.

Two implementations share the same math:

* :func:`harmonic_ritz` — the pytree-native original (stacked pytree
  bases, static sizes).  Kept as the semantic oracle.
* :func:`harmonic_ritz_flat` — the device-resident engine: flat ``(m, n)``
  bases, ONE tall-skinny GEMM for all three grams
  (``kernels.ops.self_gram`` over ``S = [Z; AZ]``), and a traced validity
  mask instead of dynamic slicing, so a *dynamic* stored count needs no
  host round-trip.  :func:`solve_sequence` scans it across a whole
  sequence of systems without leaving the device.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import operators as ops_mod
from repro.core import pytree as pt
from repro.core.solvers import (
    DEFAULT_WAW_JITTER,
    CGResult,
    SolveInfo,
    _flat_operator,
    defcg,
    defcg_jit,
)
from repro.core.strategies import (
    HarmonicRitz,
    RecycleStrategy,
    _select_positive_ritz,
    extract_next_basis_core,
    harmonic_ritz_flat_core,
)

Pytree = Any


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class RecycleState:
    """First-class recycled-subspace state — the carry of every solve path.

    Replaces the bare ``(W, AW)`` pairs previously threaded through
    ``RecycleManager``, ``recycled_solve_jit``, ``hf_step`` and
    ``solve_sequence``'s scan carry.  A registered pytree node (with
    stable key names, so it round-trips through ``repro.checkpoint``
    by leaf path), it vmaps over a leading tenant axis (``solve_batch``)
    and shards like the solution vector under pjit.

    Attributes:
      W: flat ``(k, n)`` recycled basis rows.  Zero rows are empty slots
        (cold bootstrap / clamped extraction) — def-CG deflates them as
        exact no-ops, so an all-zero state is a valid "no recycling yet".
      AW: ``(k, n)`` A-products of ``W`` under the operator that produced
        them (stale until the next refresh).
      theta: ``(k,)`` harmonic Ritz values (0 = clamped slot).
      systems_solved: int32 scalar — how many solves fed this state.
      drift: scalar — the recycle strategy's carried drift measurement
        (the ``‖AW − A·W‖`` proxy read off the last extraction gram; see
        :class:`repro.core.strategies.WindowedRecombine`).  0 for
        strategies that do not guard and for cold states.
    """

    W: jnp.ndarray
    AW: jnp.ndarray
    theta: jnp.ndarray
    systems_solved: jnp.ndarray
    drift: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.float32(0.0)
    )

    @classmethod
    def zeros(cls, k: int, n: int, dtype=jnp.float32) -> "RecycleState":
        """A cold (empty) state: the first solve runs plain CG + record."""
        return cls(
            W=jnp.zeros((k, n), dtype),
            AW=jnp.zeros((k, n), dtype),
            theta=jnp.zeros((k,), dtype),
            systems_solved=jnp.int32(0),
            drift=jnp.zeros((), dtype),
        )

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return (
            (
                (ga("W"), self.W),
                (ga("AW"), self.AW),
                (ga("theta"), self.theta),
                (ga("systems_solved"), self.systems_solved),
                (ga("drift"), self.drift),
            ),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def harmonic_ritz(
    Z: Pytree,
    AZ: Pytree,
    k: int,
    *,
    select: str = "largest",
    jitter: float = 1e-10,
) -> Tuple[Pytree, Pytree, jnp.ndarray]:
    """Extract ``k`` harmonic Ritz pairs from the basis ``Z`` (see module doc).

    Args:
      Z, AZ: stacked bases of m ≥ k vectors and their A-products.
      k: number of Ritz vectors to keep.
      select: ``"largest"`` (deflate the top of the spectrum — the right
        choice for the paper's ``A = I + H½KH½`` whose spectrum clusters at
        1 with large outliers) or ``"smallest"``.
      jitter: relative diagonal regularization for the Cholesky of G.

    Returns:
      ``(W, AW, theta)`` — the recycled basis, its A-products, and the k
      harmonic Ritz values (approximate eigenvalues of A).  If fewer than
      ``k`` positive Ritz pairs survive the rank filter, the trailing
      slots are exact zeros (θ = 0).
    """
    m = pt.basis_size(Z)
    if k > m:
        raise ValueError(f"cannot extract k={k} Ritz vectors from m={m} basis")

    # Normalize columns BEFORE forming the grams: late CG directions are
    # orders of magnitude smaller than early ones, and computing ZᵀAZ at
    # mixed scales loses the small columns' entries to rounding (observed:
    # negative "Ritz values" from an SPD operator).  Column scaling is an
    # exact invariance of the generalized problem, so this is free.
    zn = jnp.sqrt(jnp.maximum(jnp.diag(pt.gram(Z, Z)), 1e-300))
    Z = pt.basis_scale_columns(Z, 1.0 / zn)
    AZ = pt.basis_scale_columns(AZ, 1.0 / zn)

    G = pt.gram(AZ, AZ)
    F = pt.gram(AZ, Z)
    F = 0.5 * (F + F.T)

    # Second-stage equilibration on ‖AZ_i‖.
    d = jnp.where(jnp.diag(G) > 0, jnp.diag(G), 1.0) ** -0.5
    G = G * d[:, None] * d[None, :]
    F = F * d[:, None] * d[None, :]

    # Rank-revealing reduction of the generalized problem: eigendecompose
    # G and *project out* its near-null directions (near-dependent Krylov
    # columns otherwise surface as spurious huge Ritz values; observed on
    # long recording windows).  Projected directions get ζ = 0 exactly and
    # the positivity filter below excludes them — shapes stay static.
    lam, qg = jnp.linalg.eigh(G)  # ascending
    eps = jnp.finfo(G.dtype).eps
    rcond = jnp.maximum(jnp.asarray(jitter, G.dtype), 100.0 * eps) * m
    good = lam > rcond * lam[-1]
    s = jnp.where(good, 1.0 / jnp.sqrt(jnp.maximum(lam, 1e-300)), 0.0)
    M = s[:, None] * pt.matmul(pt.matmul(qg.T, F), qg) * s[None, :]
    M = 0.5 * (M + M.T)
    zeta, Wm = jnp.linalg.eigh(M)  # ascending ζ = 1/θ

    w_sel, theta, slot_ok = _select_positive_ritz(zeta, Wm, k, select)

    # u = D · Qg S w  (undo reduction and equilibration).
    u = pt.matmul(qg, s[:, None] * w_sel)
    u = u * d[:, None]

    W = pt.basis_matmul(Z, u)
    AW = pt.basis_matmul(AZ, u)

    # Normalize the recycled vectors to unit 2-norm (pure conditioning);
    # clamped slots stay exactly zero.
    col_norms = jnp.sqrt(
        jnp.maximum(jnp.diag(pt.gram(W, W)), jnp.finfo(u.dtype).tiny)
    )
    col_scale = jnp.where(slot_ok, 1.0 / col_norms, 0.0)
    W = pt.basis_scale_columns(W, col_scale)
    AW = pt.basis_scale_columns(AW, col_scale)
    return W, AW, theta


harmonic_ritz_jit = jax.jit(
    harmonic_ritz, static_argnames=("k", "select", "jitter")
)


def harmonic_ritz_flat(
    Z: jnp.ndarray,
    AZ: jnp.ndarray,
    k: int,
    *,
    valid: Optional[jnp.ndarray] = None,
    select: str = "largest",
    jitter: float = 1e-10,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Device-resident harmonic Ritz over flat ``(m, n)`` row-stacked bases.

    The sequence-engine twin of :func:`harmonic_ritz`:

    * ``valid`` is an optional *traced* ``(m,)`` bool mask — rows whose
      slot is invalid (unfilled recording window, clamped basis columns)
      are zeroed and flow through the rank filter as exact nulls, so a
      dynamic stored count costs no host round-trip and no dynamic shapes;
    * the three gram passes (``ZZᵀ`` for column norms, ``G``, ``F``)
      collapse into ONE tall-skinny GEMM over ``S = [Z; AZ]``
      (:func:`repro.kernels.ops.self_gram`) — its quadrants are sliced on
      device.  Column equilibration is applied to the *gram entries*
      (exact invariance), not the O(m·n) basis data.

    Returns ``(W, AW, theta)`` of shapes ``(k, n), (k, n), (k,)``; slots
    past the surviving positive-Ritz count are exact zeros — downstream
    def-CG treats a zero column as a no-op deflation direction (see the
    jitter floor in ``solvers.defcg``).

    The math lives in :func:`repro.core.strategies.harmonic_ritz_flat_core`
    (this wrapper keeps the historical 3-tuple signature), which also
    serves the strategy layer's M-geometry extraction and drift proxy.
    """
    W, AW, theta, _ = harmonic_ritz_flat_core(
        Z, AZ, k, valid=valid, select=select, jitter=jitter
    )
    return W, AW, theta


def _extract_next_basis(
    w_flat: Optional[jnp.ndarray],
    aw_flat: Optional[jnp.ndarray],
    p_flat: jnp.ndarray,
    ap_flat: jnp.ndarray,
    stored,
    k: int,
    *,
    select: str = "largest",
    jitter: float = 1e-10,
):
    """One cross-system extraction on the flat engine (3-tuple wrapper
    over :func:`repro.core.strategies.extract_next_basis_core` — the
    strategy layer's shared masked extraction)."""
    W, AW, theta, _ = extract_next_basis_core(
        w_flat, aw_flat, p_flat, ap_flat, stored, k,
        select=select, jitter=jitter,
    )
    return W, AW, theta


def _apply_basis_flat(A, unravel, w_flat: jnp.ndarray) -> jnp.ndarray:
    """``A @ W`` for a flat ``(k, n)`` basis — one multi-RHS application
    through the operator's pytree coordinates."""
    basis = pt.unravel_basis(w_flat, unravel)
    return pt.ravel_basis(ops_mod.apply_to_basis(A, basis))


# Highest rung the recovery ladder can climb (see ``_one_recycled_solve``).
MAX_RECOVERY_RUNGS = 3


def _one_recycled_solve(
    A,
    b: Pytree,
    x0: Optional[Pytree],
    w: jnp.ndarray,
    aw_carry: jnp.ndarray,
    drift: jnp.ndarray,
    unravel,
    *,
    k: int,
    ell: int,
    tol: float,
    atol: float,
    maxiter: int,
    select: str,
    waw_jitter: float,
    refresh_aw: str,
    strategy: RecycleStrategy,
    M=None,
    record_residuals: bool = False,
    batch_axis: Optional[str] = None,
    recovery_rungs: int = 0,
    recovery_shift: float = 1e-6,
    stagnation_window: int = 0,
):
    """ONE system of the recycled def-CG step, on flat state.

    The single source of truth for per-system semantics — shared by the
    front-door :func:`repro.core.solve` and by :func:`solve_sequence`'s
    scan body, so the single-system and scan paths cannot drift apart.
    Both halves of the per-system policy are owned by the ``strategy``
    object (:mod:`repro.core.strategies`):

    * ``strategy.prepare`` decides which ``AW`` deflates this system and
      what it costs (exact k-matvec refresh / guarded stale / pure
      stale), reading the carried ``drift`` measurement;
    * ``strategy.transition`` consumes the recorded window — the
      ``(P, AP, α, β, stored)`` handoff from the solver's scan phase —
      and emits the next ``(W, AW, θ, drift)``.

    ``recovery_rungs > 0`` arms the escalating recovery ladder (the
    generalization of the old one-shot ``divergence_fallback``).  When
    the attempt ends broken (``info.breakdown``) or unconverged with a
    carried basis, a ``lax.while_loop`` climbs up to
    :data:`MAX_RECOVERY_RUNGS` re-solve rungs:

    1. **refresh-AW-and-redo** — keep ``W``, recompute ``AW = A·W``
       exactly (k matvecs, charged) and re-solve: repairs stale/poisoned
       basis *products* and transient matvec faults without discarding
       the subspace;
    2. **drop the basis** — re-solve with a zeroed ``W`` (the
       cold-bootstrap path: exact no-op deflation plus recording, so the
       extraction re-seeds the sequence);
    3. **escalated plain CG** — zero basis, preconditioner disabled, and
       the operator shifted to ``A + σI`` (σ = ``recovery_shift``): the
       last resort against a (numerically) indefinite or singular
       operator, trading a σ-sized bias for a finite answer.

    The loop traces ONE extra solver instance regardless of rung count
    (rung identity is a traced index: the shift is ``σ·𝟙[rung = 3]`` and
    the preconditioner is identity-gated), and on a clean solve it runs
    zero iterations — the clean path's iterates and matvec totals are
    untouched.  Every executed attempt's matvecs are charged to the
    reported total; the adopted solution is whichever attempt holds the
    smallest (finite, non-broken) residual, while the basis always comes
    from the last executed rung — a freshly re-seeded space beats
    carrying poison forward.  Rung 3 only fires on an actual breakdown
    (a merely maxiter-bound system is not re-solved against a shifted
    operator), and a basis-less system that fails *without* breakdown
    never enters the ladder (re-running the identical solve cannot
    help).

    Returns ``(x, info, w_next, aw_next, theta, drift_next, rung)``;
    ``theta`` is ``None`` when ``ell == 0`` (nothing recorded — callers
    carry their previous Ritz values, and the drift carry passes through
    unchanged), and ``rung`` is the int32 highest recovery rung executed
    (0 = clean / ladder disarmed).
    """
    m_flat = _flat_operator(M, unravel) if M is not None else None
    aw_used, refresh_matvecs, exact_aw, stale_guard = strategy.prepare(
        lambda ww: _apply_basis_flat(A, unravel, ww),
        w,
        aw_carry,
        drift,
        k=k,
        refresh_aw=refresh_aw,
        tol=tol,
        batch_axis=batch_axis,
    )
    result = defcg(
        A,
        b,
        x0,
        W=w,
        AW=aw_used,
        ell=ell,
        tol=tol,
        atol=atol,
        maxiter=maxiter,
        record_residuals=record_residuals,
        waw_jitter=waw_jitter,
        exact_aw=exact_aw,
        flat_recycle=True,
        M=M,
        batch_axis=batch_axis,
        stale_guard=stale_guard,
        stagnation_window=stagnation_window,
    )
    if result.recycle is not None and result.recycle.aw_used is not None:
        # The in-solve drift guard may have replaced the stale AW with a
        # fresh A·W — the transition must recombine what was USED.
        aw_used = result.recycle.aw_used
    info = result.info
    # The multi-RHS refresh is one fused pass but (when the strategy
    # spent it) k matvecs of operator work — the §2.2 overhead term,
    # reported honestly: zero on cold bootstraps and un-triggered guards.
    info = info._replace(
        matvecs=info.matvecs + refresh_matvecs.astype(info.matvecs.dtype)
    )
    if ell > 0:
        w_next, aw_next, theta, drift_next = strategy.transition(
            w,
            aw_used,
            result.recycle,
            k=k,
            select=select,
            m_apply=m_flat,
        )
    else:
        w_next, aw_next, theta, drift_next = w, aw_used, None, drift

    rung0 = jnp.int32(0)
    if recovery_rungs <= 0:
        return (
            result.x, info, w_next, aw_next, theta, drift_next, rung0,
        )

    # repro-lint: disable=host-sync-in-trace — recovery_rungs is static
    # Python config (jit-static via SolveSpec), not traced data.
    rungs = min(int(recovery_rungs), MAX_RECOVERY_RUNGS)
    had_basis = jnp.any(w != 0)
    zero_dtype = w.dtype

    def _eligible(i, info_c):
        """Per-lane: does rung ``i`` apply to this (still-bad) solve?"""
        bad_c = info_c.breakdown | jnp.logical_not(info_c.converged)
        return (
            bad_c
            & (had_basis | info_c.breakdown)
            & ((i < MAX_RECOVERY_RUNGS) | info_c.breakdown)
        )

    def ladder_cond(st):
        i, _, info_c, *_ = st
        elig = _eligible(i, info_c)
        if batch_axis is not None:
            # Under vmap a batched predicate would kill the loop — the
            # cross-lane any() is unbatched, and lanes mask per-slot
            # adoption in the body (a broken tenant is retired into its
            # own failure status without dragging the healthy lanes).
            elig = jax.lax.psum(elig.astype(jnp.int32), batch_axis) > 0
        return (i <= rungs) & elig

    def ladder_body(st):
        i, x_c, info_c, w_c, aw_c, th_c, d_c, rung_c = st
        is1 = i == jnp.int32(1)
        # Rung identity is traced, so every rung shares this ONE solver
        # instance: rung 1 keeps W with a freshly refreshed AW; rungs 2–3
        # zero the basis; rung 3 additionally shifts the operator and
        # gates the preconditioner to identity.
        w_att = jnp.where(is1, w, jnp.zeros_like(w))
        refresh_pred = is1 & had_basis
        if batch_axis is not None:
            refresh_pred = (
                jax.lax.psum(refresh_pred.astype(jnp.int32), batch_axis) > 0
            )
        aw_att = jax.lax.cond(
            refresh_pred,
            lambda _: _apply_basis_flat(A, unravel, w),
            lambda _: jnp.zeros_like(aw_carry),
            None,
        )
        aw_att = jnp.where(is1, aw_att, jnp.zeros_like(aw_att))
        refresh_charge = jnp.where(is1 & had_basis, k, 0).astype(jnp.int32)

        sigma = jnp.where(
            i >= MAX_RECOVERY_RUNGS, recovery_shift, 0.0
        ).astype(zero_dtype)

        def A_rec(v):
            return jax.tree_util.tree_map(
                lambda a_, v_: a_ + sigma * v_, A(v), v
            )

        M_rec = None
        if M is not None:
            use_m = i < MAX_RECOVERY_RUNGS

            def M_rec(v):  # noqa: F811 — identity-gated preconditioner
                return jax.tree_util.tree_map(
                    lambda m_, v_: jnp.where(use_m, m_, v_), M(v), v
                )

        res = defcg(
            A_rec,
            b,
            x0,
            W=w_att,
            AW=aw_att,
            ell=ell,
            tol=tol,
            atol=atol,
            maxiter=maxiter,
            record_residuals=record_residuals,
            waw_jitter=waw_jitter,
            exact_aw=True,
            flat_recycle=True,
            M=M_rec,
            batch_axis=batch_axis,
            stale_guard=None,
            stagnation_window=stagnation_window,
        )
        i2 = res.info
        if ell > 0:
            w2, aw2, th2, d2 = strategy.transition(
                w_att,
                aw_att,
                res.recycle,
                k=k,
                select=select,
                m_apply=m_flat,
            )
        else:
            w2, aw2, th2, d2 = w_att, aw_att, None, d_c

        elig = _eligible(i, info_c)
        # Keep whichever attempt holds the better residual (a broken or
        # non-finite incumbent loses naturally), but always carry the
        # rung's freshly extracted basis and the honest matvec total.
        warm_ok = jnp.isfinite(info_c.residual_norm) & (
            ~info_c.breakdown
        )
        take_x = elig & (
            (~warm_ok) | (i2.residual_norm < info_c.residual_norm)
        )
        selx = lambda a, b_: jnp.where(take_x, a, b_)  # noqa: E731
        sel = lambda a, b_: jnp.where(elig, a, b_)  # noqa: E731
        x_n = selx(pt.ravel(res.x), x_c)
        info_n = SolveInfo(
            iterations=selx(i2.iterations, info_c.iterations),
            converged=selx(i2.converged, info_c.converged),
            residual_norm=selx(i2.residual_norm, info_c.residual_norm),
            matvecs=sel(
                i2.matvecs + info_c.matvecs + refresh_charge,
                info_c.matvecs,
            ),
            residual_norms=(
                None
                if i2.residual_norms is None
                else selx(i2.residual_norms, info_c.residual_norms)
            ),
            breakdown=selx(i2.breakdown, info_c.breakdown),
            status=selx(i2.status, info_c.status),
            guard_fired=info_c.guard_fired,
        )
        th_n = None if th2 is None else sel(th2, th_c)
        return (
            i + 1,
            x_n,
            info_n,
            sel(w2, w_c),
            sel(aw2, aw_c),
            th_n,
            sel(d2, d_c),
            jnp.where(elig, i, rung_c).astype(jnp.int32),
        )

    st = (
        jnp.int32(1),
        pt.ravel(result.x),
        info,
        w_next,
        aw_next,
        theta,
        drift_next,
        rung0,
    )
    _, x_fin, info_fin, w_fin, aw_fin, th_fin, d_fin, rung_fin = (
        jax.lax.while_loop(ladder_cond, ladder_body, st)
    )
    # Terminal retirement: a solve that is STILL broken after the whole
    # ladder (a persistently-corrupted operator) must neither return
    # non-finite coordinates nor hand a poisoned subspace to the next
    # system/tenant.  The solution falls back to the finite warm start
    # (or zeros) and the carried state is zeroed — the sequence
    # re-bootstraps cold from the next system on.  Status/residual stay
    # honest: the report still says BREAKDOWN_*.
    x_safe = (
        jnp.zeros_like(x_fin)
        if x0 is None
        else pt.ravel(x0).astype(x_fin.dtype)
    )
    x_safe = jnp.where(jnp.isfinite(x_safe), x_safe, 0.0)
    x_fin = jnp.where(jnp.all(jnp.isfinite(x_fin)), x_fin, x_safe)
    retire = (
        info_fin.breakdown
        | ~jnp.all(jnp.isfinite(w_fin))
        | ~jnp.all(jnp.isfinite(aw_fin))
    )
    w_fin = jnp.where(retire, 0.0, w_fin)
    aw_fin = jnp.where(retire, 0.0, aw_fin)
    if th_fin is not None:
        th_fin = jnp.where(retire, 0.0, th_fin)
    d_fin = jnp.where(retire, jnp.zeros_like(d_fin), d_fin)
    return (
        unravel(x_fin), info_fin, w_fin, aw_fin, th_fin, d_fin, rung_fin,
    )


# ---------------------------------------------------------------------------
# The device-resident sequence engine
# ---------------------------------------------------------------------------


class SequenceResult(NamedTuple):
    """Stacked outputs of :func:`solve_sequence` (leading axis = system)."""

    x: Pytree  # per-system solutions
    info: SolveInfo  # per-system diagnostics (all fields stacked)
    theta: jnp.ndarray  # (num_systems, k) harmonic Ritz values
    W: jnp.ndarray  # final recycled basis, flat (k, n)
    AW: jnp.ndarray  # its A-products under the last refresh
    drift: Optional[jnp.ndarray] = None  # final strategy drift carry
    rung: Optional[jnp.ndarray] = None  # (num_systems,) recovery rung taken


def solve_sequence(
    systems: Any,
    b_seq: Pytree,
    W0: Optional[jnp.ndarray] = None,
    AW0: Optional[jnp.ndarray] = None,
    *,
    k: int,
    ell: int,
    make_operator: Optional[Callable[[Any], Any]] = None,
    make_preconditioner: Optional[Callable[[Any], Any]] = None,
    tol: float = 1e-5,
    atol: float = 0.0,
    maxiter: int = 1000,
    select: str = "largest",
    waw_jitter: float = DEFAULT_WAW_JITTER,
    refresh_aw: str = "exact",
    carry_x: bool = False,
    strategy: Optional[RecycleStrategy] = None,
    drift0: Optional[jnp.ndarray] = None,
    divergence_fallback: bool = True,
    batch_axis: Optional[str] = None,
    recovery_rungs: Optional[int] = None,
    recovery_shift: float = 1e-6,
    stagnation_window: int = 0,
    x_prev0: Optional[jnp.ndarray] = None,
) -> SequenceResult:
    """Solve a whole sequence of related SPD systems on-device.

    This is the paper's outer loop (§2.3, Fig. 1–2) as a single
    ``lax.scan``: the recycled basis ``(W, AW)`` and (optionally) the
    warm-start solution are carried as flat device arrays across systems,
    every solve runs the flat def-CG engine, the basis refresh is ONE
    multi-RHS operator application, and the harmonic-Ritz extraction is
    the masked flat form — zero host syncs between systems, so the whole
    sequence jits (and pjit-shards) as one XLA computation.

    Args:
      systems: a pytree of per-system operator data with a leading
        system axis on every leaf — either a stacked operator pytree
        (e.g. a ``KernelSystemOperator`` whose ``sqrt_h`` is ``(N, n)``)
        consumed directly, or raw data mapped through ``make_operator``.
      b_seq: stacked right-hand sides (leading system axis on each leaf).
      W0, AW0: optional initial flat ``(k, n)`` recycled basis and its
        A-products.  ``None`` bootstraps from zeros: system 1 then runs
        an exact no-op deflation (plain CG + recording), exactly how a
        sequence starts cold.
      make_operator: maps one system slice to an SPD operator
        (``None`` → the slice *is* the operator).  Must be a stable
        callable for jit caching.
      make_preconditioner: optional stable callable mapping the per-system
        operator to an SPD preconditioner apply ``M`` (``None`` → no
        preconditioning).  Every solve in the scan then runs the
        split-preconditioned def-CG (see :func:`repro.core.solvers.defcg`)
        — deflation and preconditioning compose.
      refresh_aw: ``"exact"`` — recompute ``A⁽ⁱ⁾W`` per system with one
        multi-RHS pass (k matvecs of accounted cost); ``"stale"`` — reuse
        the extraction's ``AW`` (zero matvecs, approximate deflation, the
        paper's cheap mode; def-CG spends one true matvec re-deriving r₀).
        Stale deflation is exact for an unchanged operator (multiple RHS)
        but can destabilize the conjugacy recurrence under drift —
        ``divergence_fallback`` (below) catches that on-device, and the
        :class:`repro.core.strategies.WindowedRecombine` strategy is the
        *guarded* form of this mode (prefer it over a bare
        ``refresh_aw="stale"`` for drifting sequences).
      carry_x: warm-start each system with the previous solution
        (Alg. 1's ``x_{-1}``).
      strategy: the :class:`repro.core.strategies.RecycleStrategy` owning
        the per-system refresh policy and end-of-solve transition
        (``None`` → :class:`repro.core.strategies.HarmonicRitz`, the
        incumbent behavior).  The strategy's drift measurement rides in
        the scan carry — still zero host syncs.
      drift0: initial drift carry (a previous ``SequenceResult.drift`` /
        ``RecycleState.drift``; ``None`` → 0).
      divergence_fallback: legacy switch for the per-system recovery
        ladder: ``True`` (default) arms the full ladder
        (``recovery_rungs=3``), ``False`` disarms it entirely.
        Superseded by ``recovery_rungs`` (which wins when given).
      batch_axis: vmap axis name for the all-tenants-converged matvec
        gate (see :func:`repro.core.solvers.defcg`); ``solve_batch``
        sets it.
      recovery_rungs: explicit rung count for the escalating recovery
        ladder each system of the scan runs on breakdown/non-convergence
        — see :func:`_one_recycled_solve` for the rung semantics
        (refresh-AW-and-redo → drop basis → shifted plain CG).  A failed
        attempt's matvecs are folded into the reported totals and the
        sequence continues from the rung's freshly extracted basis.
        ``None`` defers to ``divergence_fallback``.
      recovery_shift: σ of the rung-3 ``A + σI`` shift.
      stagnation_window: per-solve stalled-residual detector window
        (see :func:`repro.core.solvers.defcg`); 0 disables.
      x_prev0: initial flat ``(n,)`` warm-start carry for ``carry_x``
        mode — lets a chunked/resumed driver continue a sequence exactly
        where a previous call stopped (``None`` → zeros, the cold
        start).

    Returns:
      :class:`SequenceResult` with per-system solutions/diagnostics and
      the final basis, ready to seed the next call.  Its ``rung`` field
      records the per-system recovery rung taken (0 = clean).
    """
    if refresh_aw not in ("exact", "stale"):
        raise ValueError(f"unknown refresh_aw={refresh_aw!r}")
    if refresh_aw == "stale" and W0 is not None and AW0 is None:
        # A zero AW against a real W makes the deflated initial guess
        # garbage while the residual still converges — a silently wrong
        # "solution".  Stale mode never recomputes AW, so it must be fed.
        raise ValueError("refresh_aw='stale' with W0 requires AW0")
    strategy = HarmonicRitz() if strategy is None else strategy
    make_op = make_operator if make_operator is not None else (lambda s: s)

    b0 = jax.tree_util.tree_map(lambda l: l[0], b_seq)
    b0_flat, unravel = pt.ravel_vector(b0)
    n = b0_flat.shape[0]
    dtype = b0_flat.dtype

    w_init = jnp.zeros((k, n), dtype) if W0 is None else W0.astype(dtype)
    aw_init = (
        jnp.zeros((k, n), dtype)
        if (AW0 is None or W0 is None)
        else AW0.astype(dtype)
    )
    x_init = (
        jnp.zeros((n,), dtype) if x_prev0 is None else x_prev0.astype(dtype)
    )
    drift_init = (
        jnp.zeros((), dtype) if drift0 is None else drift0.astype(dtype)
    )
    if recovery_rungs is None:
        recovery_rungs = MAX_RECOVERY_RUNGS if divergence_fallback else 0

    def body(carry, xs):
        w, aw, drift, x_prev = carry
        sys_i, b = xs
        A = make_op(sys_i)
        x0 = unravel(x_prev) if carry_x else None
        M = (
            make_preconditioner(A)
            if make_preconditioner is not None
            else None
        )
        # Per-system semantics (refresh, accounting, extraction, and the
        # recovery ladder) live in ONE place, shared with the
        # single-system front door.
        x_out, info, w2, aw2, theta, drift2, rung = _one_recycled_solve(
            A,
            b,
            x0,
            w,
            aw,
            drift,
            unravel=unravel,
            k=k,
            ell=ell,
            tol=tol,
            atol=atol,
            maxiter=maxiter,
            select=select,
            waw_jitter=waw_jitter,
            refresh_aw=refresh_aw,
            strategy=strategy,
            M=M,
            batch_axis=batch_axis,
            recovery_rungs=recovery_rungs,
            recovery_shift=recovery_shift,
            stagnation_window=stagnation_window,
        )
        x_flat = pt.ravel(x_out)
        return (w2, aw2, drift2, x_flat), (x_out, info, theta, rung)

    (w_fin, aw_fin, drift_fin, _), (xs_out, infos, thetas, rungs) = (
        jax.lax.scan(
            body, (w_init, aw_init, drift_init, x_init), (systems, b_seq)
        )
    )
    return SequenceResult(
        x=xs_out, info=infos, theta=thetas, W=w_fin, AW=aw_fin,
        drift=drift_fin, rung=rungs,
    )


solve_sequence_jit = jax.jit(
    solve_sequence,
    static_argnames=(
        "k",
        "ell",
        "make_operator",
        "make_preconditioner",
        "tol",
        "atol",
        "maxiter",
        "select",
        "waw_jitter",
        "refresh_aw",
        "carry_x",
        "strategy",
        "divergence_fallback",
        "batch_axis",
        "recovery_rungs",
        "recovery_shift",
        "stagnation_window",
    ),
)


def _apply_basis_maybe_jit(A, W):
    """One multi-RHS ``A @ W`` — jitted when A is a pytree node
    (stable-closure operators hit the jit cache), eager otherwise."""
    try:
        return _apply_basis_jitted(A, W)
    except TypeError:  # A is a bare callable, not a registered pytree node
        return ops_mod.apply_to_basis(A, W)


@jax.jit
def _apply_basis_jitted(A, W):
    return ops_mod.apply_to_basis(A, W)


@dataclasses.dataclass
class RecycleManager:
    """Carries the recycled subspace across a *sequence* of SPD systems.

    This object is the host-driven convenience wrapper over the sequence
    engine: call :meth:`solve` once per system ``A⁽ⁱ⁾ x = b⁽ⁱ⁾``; it runs
    ``def-CG(k, ell)`` with the current recycled basis (plain CG +
    recording for the first system), then refreshes the basis by the flat
    masked harmonic-Ritz extraction — the stored count stays a device
    scalar (no host round-trip), and the ``AW`` refresh is one multi-RHS
    operator application.  Fully-jitted outer loops should scan
    :func:`solve_sequence` instead (one XLA computation, zero host
    involvement between systems); the manager adds host-side resilience
    (breakdown fallback) on the same primitives.

    ``refresh_aw`` controls how ``A⁽ⁱ⁺¹⁾W`` is obtained:

    * ``"exact"`` — recompute with one multi-RHS pass (k matvecs of
      operator work — the O(k n²) overhead the paper accounts for in
      §2.2).  Deflation identities hold exactly.
    * ``"stale"`` — reuse ``A⁽ⁱ⁾W = AZ·U`` from the extraction (zero
      matvecs; this matches the paper's ``O(n²(ℓ+1)k)`` cost accounting
      for obtaining *both* W and AW from stored quantities).  The
      deflation projector is then approximate, and with operator drift
      the error compounds through the direction recurrence: ``Wᵀr = 0``
      is no longer maintained, the CG step scalars lose their line-search
      property, and the solve can *diverge* outright (observed; the
      extreme form of the Fig. 2 stagnation).  The breakdown fallback
      below catches exactly this — it re-solves clean and, since the
      accounting fix, reports the true total cost including the failed
      attempt.  Stale mode is exact (and safe) when the operator is
      unchanged between systems — the multiple-RHS setting.  The
      ``strategy`` field generalizes this switch:
      :class:`repro.core.strategies.WindowedRecombine` is the guarded
      stale mode (drift measured for free, refresh only when needed).

    ``reuse_aw=True`` on a call additionally declares the operator
    unchanged since the previous solve (multiple RHS against one matrix).

    ``strategy`` selects the :class:`repro.core.strategies.RecycleStrategy`
    owning the refresh decision (its host-side
    ``manager_wants_refresh`` mirror) and the end-of-solve transition;
    the strategy's drift measurement is carried in ``state.drift``.

    The manager carries a :class:`RecycleState` (flat ``(k, n)`` device
    arrays): it shards like the solution vector, persists on-device across
    systems, and is checkpointable (``repro.checkpoint`` saves it with the
    train state).  ``W``/``AW``/``theta`` remain readable as properties.
    """

    k: int
    ell: int
    select: str = "largest"
    tol: float = 1e-5
    maxiter: int = 1000
    waw_jitter: float = DEFAULT_WAW_JITTER
    refresh_aw: str = "exact"  # "exact" | "stale" (see class docstring)
    strategy: RecycleStrategy = HarmonicRitz()
    use_jit: bool = True
    state: Optional[RecycleState] = None
    systems_solved: int = 0
    _has_aw: bool = False  # state.AW holds real A-products (not placeholder)

    @property
    def W(self) -> Optional[jnp.ndarray]:
        """Flat ``(m, n)`` recycled basis rows, or None before bootstrap."""
        return None if self.state is None else self.state.W

    @property
    def AW(self) -> Optional[jnp.ndarray]:
        """A-products of ``W`` (None when seeded without them)."""
        if self.state is None or not self._has_aw:
            return None
        return self.state.AW

    @property
    def theta(self) -> Optional[jnp.ndarray]:
        return None if self.state is None else self.state.theta

    def seed(self, W: Pytree, AW: Optional[Pytree] = None) -> None:
        """Seed the recycle space a priori (e.g. Nyström vectors — the
        paper's §1.1 'guessed projective space as first initialization').

        ``W`` is a stacked basis (pytree or flat ``(m, n)``) of at most
        ``self.k`` vectors; shape/k-consistency is validated HERE, with a
        host-side error, instead of surfacing as an XLA shape failure in
        the middle of the next solve.
        """
        w_flat = pt.ravel_basis(W)
        m = w_flat.shape[0]
        if not 1 <= m <= self.k:
            raise ValueError(
                f"seed basis has {m} vectors; RecycleManager(k={self.k}) "
                f"can carry between 1 and {self.k}"
            )
        aw_flat = None
        if AW is not None:
            if jax.tree_util.tree_structure(
                AW
            ) != jax.tree_util.tree_structure(W):
                raise ValueError(
                    "seed AW must have the same pytree structure as W, got "
                    f"{jax.tree_util.tree_structure(AW)} vs "
                    f"{jax.tree_util.tree_structure(W)}"
                )
            aw_flat = pt.ravel_basis(AW)
            if aw_flat.shape != w_flat.shape:
                raise ValueError(
                    f"seed AW shape {aw_flat.shape} does not match W "
                    f"shape {w_flat.shape}"
                )
        self.state = RecycleState(
            W=w_flat,
            AW=jnp.zeros_like(w_flat) if aw_flat is None else aw_flat,
            theta=jnp.zeros((m,), w_flat.dtype),
            systems_solved=jnp.int32(self.systems_solved),
            drift=jnp.zeros((), w_flat.dtype),
        )
        self._has_aw = aw_flat is not None

    def solve(
        self,
        A,
        b: Pytree,
        x0: Optional[Pytree] = None,
        *,
        reuse_aw: bool = False,
        tol: Optional[float] = None,
        maxiter: Optional[int] = None,
        record_residuals: bool = False,
        M=None,
    ) -> CGResult:
        tol = self.tol if tol is None else tol
        maxiter = self.maxiter if maxiter is None else maxiter
        if self.strategy.needs_preconditioner and M is None:
            # Without M the M-geometry transition would silently degrade
            # to the Euclidean extraction — the SolveSpec path rejects
            # this combination too (spec validation).
            raise ValueError(
                f"strategy={type(self.strategy).__name__} extracts in the "
                "preconditioner's geometry — pass M to every solve()"
            )

        w_flat = self.state.W if self.state is not None else None
        aw_flat = self.AW  # None when seeded without A-products
        # A basis with no A-products at all (seed() without AW) must be
        # refreshed even under reuse_aw — there is nothing to reuse.
        # Otherwise the refresh decision belongs to the strategy (exact
        # policy / drift guard / pure stale) — the host-side mirror of
        # ``strategy.prepare`` on the device paths.
        drift = (
            self.state.drift if self.state is not None else jnp.float32(0.0)
        )
        needs_fresh = w_flat is not None and (
            aw_flat is None
            or (
                not reuse_aw
                and self.strategy.manager_wants_refresh(
                    self.refresh_aw, drift, tol
                )
            )
        )
        if needs_fresh:
            _, unravel = pt.ravel_vector(b)
            basis = pt.unravel_basis(w_flat, unravel)
            aw = (
                _apply_basis_maybe_jit(A, basis)
                if self.use_jit
                else ops_mod.apply_to_basis(A, basis)
            )
            aw_flat = pt.ravel_basis(aw)

        solve_fn = defcg_jit if self.use_jit else defcg
        exact_aw = needs_fresh or reuse_aw or w_flat is None
        result = solve_fn(
            A,
            b,
            x0,
            W=w_flat,
            AW=aw_flat,
            ell=self.ell,
            tol=tol,
            maxiter=maxiter,
            record_residuals=record_residuals,
            waw_jitter=self.waw_jitter,
            exact_aw=exact_aw,
            flat_recycle=True,  # _refresh consumes (P, AP) flat
            M=M,
            # A stale solve gets the strategy's in-solve drift guard —
            # the same layer-2 protection the device paths arm through
            # strategy.prepare (its k-matvec refresh is charged by defcg).
            stale_guard=(
                None if exact_aw else self.strategy.in_solve_guard(tol)
            ),
        )
        if result.recycle is not None and result.recycle.aw_used is not None:
            # The in-solve guard may have refreshed — extract from what
            # the solve actually deflated with.
            aw_flat = result.recycle.aw_used
        # Charge what the refresh actually computed: a seeded basis may
        # hold fewer than self.k vectors.
        refresh_cost = w_flat.shape[0] if needs_fresh else 0

        if w_flat is not None and (
            bool(result.info.breakdown) or not bool(result.info.converged)
        ):
            # Resilience: a stale/ill-conditioned basis can poison the
            # conjugacy recurrences.  Drop it and re-solve clean — the
            # sequence continues with a freshly bootstrapped space.  The
            # failed attempt's matvecs (and the refresh spent on the
            # discarded basis) were still paid — fold them into the
            # reported total rather than silently dropping them.
            failed_matvecs = result.info.matvecs
            self.state = None
            self._has_aw = False
            w_flat = aw_flat = None
            result = solve_fn(
                A, b, x0,
                ell=self.ell, tol=tol, maxiter=maxiter,
                record_residuals=record_residuals,
                flat_recycle=True,
                M=M,
            )
            result = result._replace(
                info=result.info._replace(
                    matvecs=result.info.matvecs
                    + failed_matvecs
                    + refresh_cost
                )
            )
        elif refresh_cost:
            result = result._replace(
                info=result.info._replace(
                    matvecs=result.info.matvecs + refresh_cost
                )
            )
        self.systems_solved += 1
        self._refresh(result, w_flat, aw_flat, b=b, M=M)
        return result

    # -- internal ----------------------------------------------------------
    def _refresh(
        self,
        result: CGResult,
        w_flat: Optional[jnp.ndarray],
        aw_flat: Optional[jnp.ndarray],
        *,
        b: Pytree,
        M=None,
    ) -> None:
        rec = result.recycle
        if rec is None:
            return
        if int(rec.stored) == 0:
            # Nothing recorded (0-iteration solve: x0 was already exact) —
            # keep the current basis as-is.  In particular a None state
            # must stay None, not become a phantom zero basis that every
            # later solve "refreshes" for k wasted matvecs.  This scalar
            # read costs nothing extra: solve() already synced on
            # result.info.converged, so the value is sitting on the host
            # side of a completed computation — unlike the old path, it
            # gates no shapes and triggers no per-count recompiles.
            return
        # Strategy-owned transition on the flat masked extraction: the
        # dynamic stored count feeds the jitted extraction as a device
        # scalar (the pre-flat-engine path static-sliced on it,
        # recompiling for every distinct count).
        P, AP = rec.P, rec.AP  # already flat (flat_recycle=True)
        k = min(self.k, P.shape[0] + (0 if w_flat is None else w_flat.shape[0]))
        if self.strategy.needs_preconditioner and M is not None:
            # M-geometry needs the flat M⁻¹ apply — a per-call closure,
            # so this path runs eagerly (the front doors jit it whole).
            _, unravel = pt.ravel_vector(b)
            W_new, AW_new, theta, drift = self.strategy.transition(
                w_flat, aw_flat, rec, k=k, select=self.select,
                m_apply=_flat_operator(M, unravel),
            )
        elif self.use_jit:
            W_new, AW_new, theta, drift = _strategy_transition_jit(
                self.strategy, w_flat, aw_flat, rec, k, self.select
            )
        else:
            W_new, AW_new, theta, drift = self.strategy.transition(
                w_flat, aw_flat, rec, k=k, select=self.select
            )
        self.state = RecycleState(
            W=W_new,
            AW=AW_new,
            theta=theta,
            systems_solved=jnp.int32(self.systems_solved),
            drift=drift,
        )
        self._has_aw = True


_extract_next_basis_jit = jax.jit(
    _extract_next_basis, static_argnames=("k", "select", "jitter")
)


@functools.partial(
    jax.jit, static_argnames=("strategy", "k", "select")
)
def _strategy_transition_jit(strategy, w_flat, aw_flat, window, k, select):
    """Jitted strategy transition for the host-driven manager (strategies
    are hashable static config; the window rides in as a traced pytree)."""
    return strategy.transition(w_flat, aw_flat, window, k=k, select=select)


def recycled_solve_jit(
    A,
    b: Pytree,
    x0: Pytree,
    W: Pytree,
    *,
    k: int,
    ell: int,
    tol: float,
    maxiter: int,
    select: str = "largest",
) -> Tuple[Pytree, Pytree, CGResult]:
    """Single-shot, fully traceable solve+extract for jitted outer loops.

    One step of the sequence engine for callers that carry ``W`` in their
    own state (the Hessian-free optimizer): one multi-RHS ``AW`` refresh,
    a flat def-CG solve, and the masked flat extraction.  The recording
    window no longer needs a ``min_iters`` floor — a partially filled
    window extracts through the validity mask, so early-converging solves
    stop early instead of burning ``ell`` matvecs to fill buffers.

    Callers bootstrap with a random orthonormal basis, which is a valid
    (merely unhelpful) deflation space.  Returns ``(W_next, x, result)``.
    """
    AW = ops_mod.apply_to_basis(A, W)
    result = defcg(
        A,
        b,
        x0,
        W=W,
        AW=AW,
        ell=ell,
        tol=tol,
        maxiter=maxiter,
        flat_recycle=True,
    )
    _, unravel = pt.ravel_vector(b)
    w_flat = pt.ravel_basis(W)
    aw_flat = pt.ravel_basis(AW)
    W_next, _, _ = _extract_next_basis(
        w_flat,
        aw_flat,
        result.recycle.P,
        result.recycle.AP,
        result.recycle.stored,
        k,
        select=select,
    )
    result = result._replace(
        info=result.info._replace(
            matvecs=result.info.matvecs + pt.basis_size(W)
        )
    )
    return pt.unravel_basis(W_next, unravel), result.x, result


def random_orthonormal_basis(key, template: Pytree, k: int) -> Pytree:
    """k orthonormal random vectors shaped like ``template`` (bootstrap W)."""
    vs = []
    for i in range(k):
        key, sub = jax.random.split(key)
        v = pt.tree_random_like(sub, template)
        for u in vs:
            v = pt.tree_axpy(-pt.tree_dot(u, v), u, v)
        v = pt.tree_scale(1.0 / pt.tree_norm(v), v)
        vs.append(v)
    return pt.basis_from_vectors(vs)
