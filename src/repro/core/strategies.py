"""Recycle strategies: the end-of-solve transition, made a pluggable axis.

The paper fixes ONE policy for what survives a solve: harmonic-Ritz
extraction of ``k`` vectors from ``[W, P]`` followed by an exact
``A⁽ⁱ⁺¹⁾W`` refresh (k matvecs).  Related work treats both halves as free
design choices — POD-augmented selection (Carlberg et al.) and the
recycling-space taxonomy of the Soodhalter/de Sturler/Kilmer survey vary
*what* is kept and *in which inner product*.  This module makes that axis
explicit: a :class:`RecycleStrategy` owns the transition

    (recording window, old state)  →  (next W, next AW, θ, drift)

plus the pre-solve refresh policy, and is selected declaratively via
``SolveSpec.strategy``.

The window handoff contract
---------------------------

A strategy consumes only what the flat def-CG engine already recorded
(:class:`repro.core.solvers.RecycleData`): the first-ℓ search directions
``P`` and products ``AP`` written by the masked scan phase, the dynamic
``stored`` count, and the CG coefficients ``(α, β)`` of those iterations.
Everything is "readily available" in the paper's §2.3 sense — a
transition costs ZERO extra matvecs.  Whatever basis the strategy
returns, def-CG treats exact-zero rows as no-op deflation directions, so
clamped/degraded extractions never change shapes.

Concrete strategies
-------------------

* :class:`HarmonicRitz` — the incumbent: harmonic-Ritz extraction over
  ``Z = [W, P]`` in the Euclidean geometry, with the refresh policy taken
  from ``spec.refresh_aw`` (``"exact"`` spends k matvecs per system
  rebuilding ``AW``; ``"stale"`` reuses the extraction products).
* :class:`WindowedRecombine` — the paper-faithful O(n²(ℓ+1)k) accounting:
  BOTH ``W' = uᵀZ`` and ``AW' = uᵀAZ`` are rebuilt by recombining stored
  columns (one stacked two-block GEMM,
  :func:`repro.kernels.ops.recombine_blocks`) and the next solve runs on
  the stale products — zero refresh matvecs.  A per-system drift guard
  watches the asymmetry of the extraction gram ``F = (AZ)Zᵀ``: for exact
  data ``F`` is symmetric (A = Aᵀ), and under operator drift its W–P
  cross block is exactly ``Pᵀ(A⁽ⁱ⁾ − A_stale)W`` — a FREE measurement of
  ``‖AW − A·W‖`` projected on the Krylov window, read off a gram the
  extraction computes anyway.  When the measured drift exceeds
  ``guard``, the NEXT solve pays one full k-matvec refresh; below it, the
  sequence runs at the paper's accounting.  (The guard is retrospective —
  it reacts one system after drift appears; the sequence engine's
  divergence fallback covers the catastrophic case in the same pass.)
* :class:`MGeometryHarmonic` — harmonic extraction in the geometry of the
  preconditioner: with ``M⁻¹`` applied inside the grams, the extracted θ
  approximate eigenvalues of the EFFECTIVE operator ``M⁻¹A`` (the one the
  preconditioned iteration actually sees), so ``select`` targets the ends
  of the effective spectrum and deflation cleans up exactly what the
  preconditioner leaves behind.  Algebra: the split-preconditioned def-CG
  is plain def-CG on ``Ã = M^{-1/2} A M^{-1/2}`` with bases mapped by
  ``M^{1/2}``; harmonic Ritz of ``Ã`` over the mapped window needs
  ``G̃ = (AZ)ᵀ M⁻¹ (AZ)`` and ``F̃ = (AZ)ᵀZ`` — both computable with the
  preconditioner APPLY only (no square roots), and the recombination
  ``W' = Z U`` maps back for free.  Validated against a dense
  M^{1/2}-similarity reference in ``tests/test_strategies.py``.

Strategies are frozen dataclasses holding only static config: hashable
(they ride inside the jit-static ``SolveSpec``) and registered as pytree
nodes with zero children (they also pass through traced positions
untouched).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import pytree as pt
from repro.core.solvers import DRIFT_NOISE_FLOOR_EPS, RecycleData
from repro.kernels import ops as kops

FlatApply = Callable[[jnp.ndarray], jnp.ndarray]


def _drift_threshold(guard: float, tol: float, dtype) -> jnp.ndarray:
    """``guard × tol`` floored at the working dtype's drift-noise level
    (:data:`repro.core.solvers.DRIFT_NOISE_FLOOR_EPS` × eps) — the one
    comparison scale shared by every guard layer."""
    return jnp.maximum(
        jnp.asarray(guard * tol, dtype),
        DRIFT_NOISE_FLOOR_EPS * jnp.finfo(dtype).eps,
    )


def _gated_basis_apply(apply_basis, pred, w, fallback, batch_axis):
    """``apply_basis(w)`` where ``pred``, else ``fallback`` — as a REAL
    branch even under ``vmap``.

    A per-lane predicate would lower ``lax.cond`` to a ``select`` under
    ``solve_batch``'s vmap, making every tenant pay the refresh GEMM
    every system; with the axis name the branch predicate becomes the
    cross-tenant any (unbatched), and the per-lane choice is a cheap
    ``where`` on the result — no tenant computes the operator unless
    SOME tenant's guard fired.
    """
    if batch_axis is None:
        # repro-lint: disable=cond-batched-pred — this is the explicitly
        # UNBATCHED branch; the vmapped path below reduces with psum.
        return jax.lax.cond(pred, apply_basis, lambda _: fallback, w)
    any_pred = jax.lax.psum(pred.astype(jnp.int32), batch_axis) > 0
    out = jax.lax.cond(any_pred, apply_basis, lambda _: fallback, w)
    return jnp.where(pred, out, fallback)


def _register_strategy(cls):
    """Register a strategy as a LEAF-less pytree node: all fields are
    static aux data, so a strategy is hashable jit-static config that can
    also sit inside traced containers without contributing leaves."""

    def flatten(s):
        return (), tuple(
            getattr(s, f.name) for f in dataclasses.fields(s)
        )

    def unflatten(aux, children):
        del children
        return cls(*aux)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


# ---------------------------------------------------------------------------
# The extraction core (flat, masked, optionally M-geometry)
# ---------------------------------------------------------------------------


def _select_positive_ritz(zeta, Wm, k: int, select: str):
    """Pick ``k`` Ritz pairs by θ = 1/ζ, clamped to the positive count.

    ζ ≤ 0 can only arise from rounding or masked/projected-out directions
    (A SPD ⇒ θ > 0) — never select it.  When fewer than ``k`` positive
    pairs survive the rank filter, the trailing slots are masked to exact
    zeros (θ = 0, zero eigenvector column) rather than argsorting the
    ``±inf`` sentinel keys into the selection, which manufactured ~1e300
    "Ritz values" normalized from near-zero vectors.

    Returns ``(w_sel, theta, slot_ok)`` with shapes ``(m, k), (k,), (k,)``.
    """
    npos = jnp.sum(zeta > 0)
    slot_ok = jnp.arange(k) < jnp.minimum(npos, k)
    if select == "largest":
        order = jnp.argsort(jnp.where(zeta > 0, zeta, jnp.inf))[:k]
    elif select == "smallest":
        order = jnp.argsort(jnp.where(zeta > 0, zeta, -jnp.inf))[::-1][:k]
    else:
        raise ValueError(f"unknown select={select!r}")
    w_sel = Wm[:, order] * slot_ok[None, :].astype(Wm.dtype)
    zeta_sel = jnp.where(slot_ok, zeta[order], 1.0)
    theta = jnp.where(slot_ok, 1.0 / zeta_sel, 0.0)
    return w_sel, theta, slot_ok


def harmonic_ritz_flat_core(
    Z: jnp.ndarray,
    AZ: jnp.ndarray,
    k: int,
    *,
    valid: Optional[jnp.ndarray] = None,
    select: str = "largest",
    jitter: float = 1e-10,
    m_apply: Optional[FlatApply] = None,
    psum_axis: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Masked flat harmonic-Ritz extraction; the strategies' shared math.

    Extends the device-resident extraction (see
    :func:`repro.core.recycle.harmonic_ritz_flat`, which wraps this) with
    two strategy-layer capabilities:

    * ``m_apply`` — an optional flat ``r ↦ M⁻¹r`` apply.  When given, the
      left gram becomes ``G = (AZ) M⁻¹ (AZ)ᵀ`` (one extra gram block in
      the SAME stacked self-gram GEMM over ``S = [Z; AZ; M⁻¹AZ]``) so the
      extracted pairs are harmonic Ritz of the preconditioned operator
      ``M⁻¹A`` mapped back to original coordinates — the M-geometry of
      :class:`MGeometryHarmonic`.
    * the fourth return ``fasym`` — the relative asymmetry
      ``‖F − Fᵀ‖_F / ‖F‖_F`` of the raw (equilibrated, pre-symmetrized)
      cross gram ``F = (AZ)Zᵀ``.  For exact data F is symmetric; with a
      stale ``AW`` block its W–P quadrant equals ``Pᵀ(A − A_stale)W``, so
      this scalar is a free ``‖AW − A·W‖`` proxy — the
      :class:`WindowedRecombine` drift guard.

    The recombination ``[W'; AW'] = [uᵀZ; uᵀAZ]`` is ONE stacked
    two-block GEMM (:func:`repro.kernels.ops.recombine_blocks`) — with a
    stale-mode strategy this is where the next basis AND its operator
    products come from, at zero matvecs.

    ``psum_axis`` names a mesh axis the length-n coordinate dimension is
    sharded over (the sharded engine's ``"solve"`` axis): the stacked
    self-gram and the column norms — the only n-reductions here — are
    computed per-shard and ``psum``-combined, everything downstream (the
    (2m, 2m) eigenproblems, the selection) is replicated arithmetic, and
    the recombination GEMM stays per-shard.  ``None`` (the default) is
    the unsharded path, bit-identical to before the axis existed.

    Returns ``(W, AW, theta, fasym)`` of shapes
    ``(k, n), (k, n), (k,), ()`` — n per-shard under ``psum_axis``.
    """
    m = Z.shape[0]
    if k > m:
        raise ValueError(f"cannot extract k={k} Ritz vectors from m={m} basis")
    if valid is not None:
        vz = valid.astype(Z.dtype)[:, None]
        Z = Z * vz
        AZ = AZ * vz

    S2 = jnp.concatenate([Z, AZ], axis=0)  # (2m, n): gram + recombination
    if m_apply is None:
        full = kops.self_gram(S2)  # (2m, 2m)
        if psum_axis is not None:
            # Per-shard gram over the local n-columns; ONE collective
            # replicates the full (2m, 2m) gram on every shard.
            full = jax.lax.psum(full, psum_axis)
        # Quadrants: ⎡ZZᵀ  ·⎤ — diag(ZZᵀ) are the column norms, the lower
        #            ⎣F    G⎦   blocks are the projection grams.
        zz = jnp.diag(full[:m, :m])
        F_raw = full[m:, :m]
        G = full[m:, m:]
    else:
        # M-geometry: one taller stack S = [Z; AZ; M⁻¹AZ] — the same
        # single self-gram GEMM now also contains G = (AZ)(M⁻¹AZ)ᵀ.
        MAZ = jax.vmap(m_apply)(AZ)
        full = kops.self_gram(jnp.concatenate([S2, MAZ], axis=0))
        if psum_axis is not None:
            full = jax.lax.psum(full, psum_axis)
        zz = jnp.diag(full[:m, :m])
        F_raw = full[m : 2 * m, :m]
        G = full[m : 2 * m, 2 * m :]
        G = 0.5 * (G + G.T)  # M⁻¹ symmetric ⇒ symmetric to rounding

    dz = jnp.where(zz > 0, jax.lax.rsqrt(zz), 0.0)
    G = G * dz[:, None] * dz[None, :]
    F = F_raw * dz[:, None] * dz[None, :]

    # Drift proxy BEFORE symmetrization throws the signal away: the
    # antisymmetric part of the (scale-equilibrated) F gram.
    fnorm = jnp.sqrt(jnp.sum(F * F))
    fasym = jnp.sqrt(jnp.sum((F - F.T) ** 2)) / jnp.maximum(
        fnorm, jnp.finfo(F.dtype).tiny
    )
    fasym = jnp.where(fnorm > 0, fasym, 0.0)
    F = 0.5 * (F + F.T)

    # Second-stage equilibration on ‖AZ_i‖ (M-geometry: ‖AZ_i‖_{M⁻¹}).
    d = jnp.where(jnp.diag(G) > 0, jnp.diag(G), 1.0) ** -0.5
    G = G * d[:, None] * d[None, :]
    F = F * d[:, None] * d[None, :]

    # Rank-revealing reduction of the generalized problem: eigendecompose
    # G and project out its near-null directions (masked rows and
    # near-dependent Krylov columns surface as λ ≈ 0).  Projected
    # directions get ζ = 0 exactly and the positivity filter excludes
    # them — shapes stay static.
    lam, qg = jnp.linalg.eigh(G)
    eps = jnp.finfo(G.dtype).eps
    rcond = jnp.maximum(jnp.asarray(jitter, G.dtype), 100.0 * eps) * m
    good = lam > rcond * lam[-1]
    s = jnp.where(good, 1.0 / jnp.sqrt(jnp.maximum(lam, 1e-300)), 0.0)
    M = s[:, None] * pt.matmul(pt.matmul(qg.T, F), qg) * s[None, :]
    M = 0.5 * (M + M.T)
    zeta, Wm = jnp.linalg.eigh(M)

    w_sel, theta, slot_ok = _select_positive_ritz(zeta, Wm, k, select)

    # u folds the reduction and BOTH equilibrations, so it applies to the
    # raw (unnormalized) bases: u = D_z · D · Qg S w.
    u = pt.matmul(qg, s[:, None] * w_sel)
    u = u * (d * dz)[:, None]
    u = u.astype(Z.dtype)

    # ONE pass over the stored bases rebuilds both blocks: W' = uᵀZ and
    # AW' = uᵀAZ — for a stale-mode strategy this GEMM IS the refresh.
    WA = kops.recombine_blocks(S2, u)  # (2k, n)
    W, AW = WA[:k], WA[k:]

    wsq = jnp.sum(W * W, axis=1)
    if psum_axis is not None:
        wsq = jax.lax.psum(wsq, psum_axis)
    wn = jnp.sqrt(jnp.maximum(wsq, jnp.finfo(u.dtype).tiny))
    col_scale = jnp.where(slot_ok, 1.0 / wn, 0.0).astype(W.dtype)
    W = W * col_scale[:, None]
    AW = AW * col_scale[:, None]
    return W, AW, theta, fasym


def extract_next_basis_core(
    w_flat: Optional[jnp.ndarray],
    aw_flat: Optional[jnp.ndarray],
    p_flat: jnp.ndarray,
    ap_flat: jnp.ndarray,
    stored,
    k: int,
    *,
    select: str = "largest",
    jitter: float = 1e-10,
    m_apply: Optional[FlatApply] = None,
    psum_axis: Optional[str] = None,
):
    """One cross-system extraction on the flat engine.

    ``Z = [W, P]`` with a traced validity mask: W rows are valid where
    nonzero (clamped slots are exact zeros), P rows where their index is
    below the dynamic ``stored`` count.  Shape-static throughout.
    ``psum_axis`` (see :func:`harmonic_ritz_flat_core`) marks the
    n-dimension as sharded — the W-row validity norms join the gram's
    cross-shard reductions.  Returns ``(W, AW, theta, fasym)``.
    """
    ell = p_flat.shape[0]
    p_valid = jnp.arange(ell) < stored
    if w_flat is None:
        Z, AZ, valid = p_flat, ap_flat, p_valid
    else:
        Z = jnp.concatenate([w_flat, p_flat], axis=0)
        AZ = jnp.concatenate([aw_flat, ap_flat], axis=0)
        wsq = jnp.sum(w_flat * w_flat, axis=1)
        if psum_axis is not None:
            wsq = jax.lax.psum(wsq, psum_axis)
        w_valid = wsq > 0
        valid = jnp.concatenate([w_valid, p_valid])
    return harmonic_ritz_flat_core(
        Z, AZ, k, valid=valid, select=select, jitter=jitter,
        m_apply=m_apply, psum_axis=psum_axis,
    )


# ---------------------------------------------------------------------------
# The strategy protocol
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RecycleStrategy:
    """Owner of the per-system refresh policy and end-of-solve transition.

    Subclasses implement:

    * :meth:`prepare` — decide, BEFORE the solve, which ``AW`` deflates
      system i and what it costs:
      ``(aw_used, refresh_matvecs, exact_aw, stale_guard)``.
      ``exact_aw`` must be a *python* bool (it selects a static def-CG
      code path: whether the ``r₀ = r − AW·c`` shortcut is trusted or one
      true matvec re-derives the initial residual); ``stale_guard``
      (static float or None) arms def-CG's in-solve drift guard — the
      free ``‖(A·W − AW)c‖`` measurement in the stale setup that
      refreshes ``AW`` before a too-stale recurrence can diverge (see
      :func:`repro.core.solvers.defcg`).
    * :meth:`transition` — consume the recorded window
      (:class:`repro.core.solvers.RecycleData`) AFTER the solve and emit
      ``(W', AW', theta, drift)``.  ``drift`` is the strategy's own
      carried scalar (stored in ``RecycleState.drift``); strategies that
      do not guard return 0.
    * :meth:`manager_wants_refresh` — the host-side mirror of
      :meth:`prepare`'s refresh decision, for :class:`RecycleManager`.

    Instances are frozen, hashable, and leaf-less pytree nodes — valid
    both as jit-static config (inside ``SolveSpec``) and inside traced
    containers.
    """

    def prepare(
        self,
        apply_basis: FlatApply,
        w: jnp.ndarray,
        aw_carry: jnp.ndarray,
        drift: jnp.ndarray,
        *,
        k: int,
        refresh_aw: str,
        tol: float = 1e-5,
        batch_axis: Optional[str] = None,
    ):
        raise NotImplementedError

    def transition(
        self,
        w: Optional[jnp.ndarray],
        aw: Optional[jnp.ndarray],
        window: RecycleData,
        *,
        k: int,
        select: str = "largest",
        jitter: float = 1e-10,
        m_apply: Optional[FlatApply] = None,
    ):
        raise NotImplementedError

    def manager_wants_refresh(self, refresh_aw: str, drift, tol: float) -> bool:
        raise NotImplementedError

    def in_solve_guard(self, tol: float):
        """Static ``defcg(stale_guard=…)`` threshold, or None (no
        in-solve guard) — lets host-driven callers arm the same layer-2
        protection the device paths get from :meth:`prepare`."""
        del tol
        return None

    @property
    def needs_preconditioner(self) -> bool:
        """Whether the transition is meaningless without an ``M`` apply."""
        return False


def _zero_drift(ref: jnp.ndarray) -> jnp.ndarray:
    return jnp.zeros((), ref.dtype)


@_register_strategy
@dataclasses.dataclass(frozen=True)
class HarmonicRitz(RecycleStrategy):
    """The incumbent policy, expressed against the strategy interface.

    Transition: Euclidean harmonic-Ritz extraction over ``[W, P]``.
    Refresh: per ``spec.refresh_aw`` — ``"exact"`` recomputes ``AW`` with
    one multi-RHS pass (k matvecs, charged; skipped and uncharged on a
    cold all-zero basis), ``"stale"`` reuses the recombined products
    unconditionally (exact only for an unchanged operator).
    """

    def prepare(self, apply_basis, w, aw_carry, drift, *, k, refresh_aw,
                tol=1e-5, batch_axis=None):
        del drift, tol
        if refresh_aw == "stale":
            return aw_carry, jnp.int32(0), False, None
        # Cold bootstrap (all-zero W): A @ 0 = 0 — skip the k operator
        # passes and their accounting.
        has_w = jnp.any(w != 0)
        aw = _gated_basis_apply(
            apply_basis, has_w, w, jnp.zeros_like(w), batch_axis
        )
        return aw, k * has_w.astype(jnp.int32), True, None

    def transition(self, w, aw, window, *, k, select="largest",
                   jitter=1e-10, m_apply=None):
        del m_apply  # Euclidean geometry
        W, AW, theta, _ = extract_next_basis_core(
            w, aw, window.P, window.AP, window.stored, k,
            select=select, jitter=jitter,
        )
        return W, AW, theta, _zero_drift(W)

    def manager_wants_refresh(self, refresh_aw, drift, tol):
        del drift, tol
        return refresh_aw == "exact"


@_register_strategy
@dataclasses.dataclass(frozen=True)
class WindowedRecombine(RecycleStrategy):
    """Zero-matvec windowed refresh with a drift guard.

    The paper's §2.3 accounting made real: both ``W'`` and ``AW'`` come
    from recombining stored columns (one
    :func:`repro.kernels.ops.recombine_blocks` GEMM), the next solve
    deflates with the stale products, and one true matvec re-derives
    ``r₀`` — per-system cost ``iterations + 2`` matvecs, no k-matvec
    refresh.  The transition also measures drift for free (the
    antisymmetric part of the extraction gram ``F``, see
    :func:`harmonic_ritz_flat_core`); when the measured value exceeds
    ``guard`` the NEXT solve pays one full refresh, restoring exact
    deflation before the stale recurrence can destabilize.

    The guard is two-layered, both layers free of speculative matvecs:

    1. *pre-solve* — when the CARRIED drift measurement (the gram
       asymmetry recorded by the previous transition) already exceeds
       ``guard``, :meth:`prepare` refreshes up front with the fused
       multi-RHS pass (persistent-drift fast path);
    2. *in-solve* — ``defcg``'s ``stale_guard``: the stale setup's
       ``‖r_true − r_shortcut‖ = ‖(A·W − AW)c‖`` residual, measured on
       THIS system before the first iteration, triggers a refresh-and-
       redo of the deflated guess.  This is what actually protects a
       system hit by sudden drift — a retrospective signal cannot.

    ``guard`` is measured in units of the solve TOLERANCE: refresh when
    the observed staleness exceeds ``guard × tol``.  That scale is not
    arbitrary — the stale μ-recurrence reinjects un-deflated W-components
    every iteration and the deflated-out spectrum amplifies them
    geometrically (measured on the GP Newton family: staleness ≈ 10×tol
    diverges outright, ≈ tol converges at the exact path's iteration
    count), so "safe to skip the refresh" is exactly "stale error below
    the residual target", whatever the tolerance.  The default keeps a
    10× margin.  ``guard = inf`` never refreshes (the paper's pure cheap
    mode, correct for multiple-RHS sequences); ``guard = 0`` refreshes
    on ANY measured drift.  Both layers floor their thresholds at
    ~500·eps of the working dtype (see :meth:`in_solve_guard`): drift
    below rounding noise is indistinguishable from an unchanged
    operator — where stale products are exact and a refresh buys
    nothing — so even ``guard = 0`` skips the refresh there (and a
    freshly refreshed AW can never re-trigger a second refresh in the
    same solve), while any above-noise drift still pays exactly one
    k-matvec refresh per system.
    """

    guard: float = 0.1

    def in_solve_guard(self, tol: float) -> float:
        """The (static) threshold armed as ``defcg(stale_guard=…)``.

        def-CG additionally floors it at ~500·eps of the WORKING dtype
        (the drift measurement carries rounding-level terms even with an
        exact AW — ~1e-16 in f64, ~1e-7 in f32), so an already-refreshed
        AW can never re-trigger a second k-matvec refresh in the same
        solve — ``guard = 0`` then means "refresh every carried basis
        once", not twice, in either precision.
        """
        return self.guard * tol

    def prepare(self, apply_basis, w, aw_carry, drift, *, k, refresh_aw,
                tol=1e-5, batch_axis=None):
        del refresh_aw  # policy is the guard, not the spec flag
        # Same dtype-aware noise floor as the in-solve guard: the carried
        # gram-asymmetry measurement of an UNCHANGED operator is pure
        # rounding (~eps), and must not buy k-matvec refreshes.
        threshold = _drift_threshold(self.guard, tol, w.dtype)
        has_w = jnp.any(w != 0)
        refresh = has_w & (drift > threshold)
        aw = _gated_basis_apply(apply_basis, refresh, w, aw_carry, batch_axis)
        # exact_aw=False even when the guard just refreshed: the stale
        # branch needs the true-matvec r₀ re-derivation, and the branch
        # choice is traced — one uniformly-safe static code path.
        return aw, k * refresh.astype(jnp.int32), False, self.in_solve_guard(tol)

    def transition(self, w, aw, window, *, k, select="largest",
                   jitter=1e-10, m_apply=None):
        del m_apply
        W, AW, theta, fasym = extract_next_basis_core(
            w, aw, window.P, window.AP, window.stored, k,
            select=select, jitter=jitter,
        )
        return W, AW, theta, fasym.astype(W.dtype)

    def manager_wants_refresh(self, refresh_aw, drift, tol):
        del refresh_aw
        # The host-side mirror of prepare(): same tol-scaled threshold,
        # same dtype noise floor.
        d = jnp.asarray(drift)
        return bool(d > _drift_threshold(self.guard, tol, d.dtype))


@_register_strategy
@dataclasses.dataclass(frozen=True)
class MGeometryHarmonic(RecycleStrategy):
    """Harmonic extraction in the preconditioner's geometry.

    Identical refresh policy to exact :class:`HarmonicRitz` (the point is
    extraction geometry, not refresh accounting), but the transition
    passes the ``M⁻¹`` apply into the grams so θ approximate eigenvalues
    of the EFFECTIVE operator ``M⁻¹A`` — ``select`` then deliberately
    targets what the preconditioner leaves behind, instead of re-deflating
    spectrum the preconditioner already compressed.  Requires a
    preconditioned spec (``SolveSpec`` validation enforces it); with no
    ``M`` at transition time it degrades to the Euclidean extraction.
    """

    def prepare(self, apply_basis, w, aw_carry, drift, *, k, refresh_aw,
                tol=1e-5, batch_axis=None):
        del drift, refresh_aw, tol
        has_w = jnp.any(w != 0)
        aw = _gated_basis_apply(
            apply_basis, has_w, w, jnp.zeros_like(w), batch_axis
        )
        return aw, k * has_w.astype(jnp.int32), True, None

    def transition(self, w, aw, window, *, k, select="largest",
                   jitter=1e-10, m_apply=None):
        W, AW, theta, _ = extract_next_basis_core(
            w, aw, window.P, window.AP, window.stored, k,
            select=select, jitter=jitter, m_apply=m_apply,
        )
        return W, AW, theta, _zero_drift(W)

    def manager_wants_refresh(self, refresh_aw, drift, tol):
        del refresh_aw, drift, tol
        return True

    @property
    def needs_preconditioner(self) -> bool:
        return True
