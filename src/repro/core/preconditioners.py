"""Preconditioners and a-priori low-rank subspaces.

The paper contrasts *recycled* subspaces against the ML-standard *a-priori*
low-rank approximations (Nyström / inducing points, §1.1) and notes the
latter can seed the former.  This module provides:

* :func:`jacobi` — diagonal preconditioning (given the diagonal);
* :func:`randomized_nystrom` — a randomized Nyström low-rank eigensketch of
  a matrix-free SPD operator (sketch → QR → Rayleigh–Ritz), usable both as
  (a) a preconditioner ``M⁻¹ = U (Λ+σ)⁻¹ Uᵀ + (I − UUᵀ)/σ_scale`` and
  (b) an initial deflation basis: its stacked ``U`` (as flat ``(k, n)``
      rows) with a zero ``AW`` is a :class:`repro.core.RecycleState` that
      ``repro.core.solve`` refreshes exactly before the first solve — the
      paper's 'missing link' initialization.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_factor, cho_solve

from repro.core import pytree as pt

Pytree = Any


# Preconditioners are *registered pytree nodes* (data in children, no
# closures), so the jitted solver entry points treat ``M`` as a traced
# argument: a Newton loop that rebuilds its preconditioner every system
# (new diag, new sketch) reuses one compiled solve instead of recompiling.
# ``eq=False`` keeps instances hashable (identity) for any caller that
# still routes them through a static argument.


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)
class JacobiPreconditioner:
    """``M⁻¹ r = r / diag`` (elementwise, pytree-wise)."""

    diag: Pytree

    def __call__(self, r: Pytree) -> Pytree:
        return jax.tree_util.tree_map(lambda rl, dl: rl / dl, r, self.diag)

    def tree_flatten(self):
        return (self.diag,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)
class NystromPreconditioner:
    """``M⁻¹`` from a rank-r Nyström eigensketch ``(U, Λ)`` of ``A``:

        M⁻¹ r = r + U ((λ_min+σ)/(Λ+σ) − 1) Uᵀ r

    (Frangella et al. form; the unsketched bulk is treated as
    ≈ (λ_min+σ) I).  ``U`` is a stacked basis (leading axis = rank) in
    descending eigenvalue order, as :func:`randomized_nystrom` returns.
    """

    U: Pytree
    lam: jnp.ndarray
    sigma: jnp.ndarray

    def __call__(self, r: Pytree) -> Pytree:
        lam_min = self.lam[-1]
        c = pt.basis_dot(self.U, r)
        scale = (lam_min + self.sigma) / (self.lam + self.sigma) - 1.0
        return pt.tree_add(r, pt.basis_combine(self.U, scale * c))

    def tree_flatten(self):
        return (self.U, self.lam, self.sigma), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)
class WoodburyKernelPreconditioner:
    """``M⁻¹`` for the Newton-system family ``A_i = I + H½ᵢ K H½ᵢ``.

    The right way to Nyström-precondition a *sequence* whose drift lives
    entirely in ``H``: sketch the INVARIANT ``K ≈ U Λ Uᵀ`` once (per
    hyperparameter setting — it amortizes across every Newton iteration
    and every tenant), then per system take

        M = I + H½ U Λ Uᵀ H½,
        M⁻¹ r = r − H½ U C⁻¹ Uᵀ H½ r,   C = Λ⁻¹ + Uᵀ H U   (Woodbury)

    so the preconditioner tracks the drifting ``H`` exactly at the cost
    of one r×r Cholesky per system (O(r²n) build, O(rn) apply — no
    operator matvecs at all).  Built by
    :func:`kernel_nystrom_preconditioner`; a sketch of ``A_i`` itself
    (:class:`NystromPreconditioner`) goes stale as ``H`` moves.
    """

    sqrt_h: jnp.ndarray  # (n,)
    U: jnp.ndarray  # (r, n) row-stacked sketch basis of K
    chol_c: jnp.ndarray  # Cholesky factor of C = Λ⁻¹ + UᵀHU
    lower: bool = dataclasses.field(default=False)

    def __call__(self, r: jnp.ndarray) -> jnp.ndarray:
        t = pt.matmul(self.U, self.sqrt_h * r)
        s = cho_solve((self.chol_c, self.lower), t)
        return r - self.sqrt_h * pt.matmul(s, self.U)

    def tree_flatten(self):
        return (self.sqrt_h, self.U, self.chol_c), (self.lower,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, lower=aux[0])


def kernel_nystrom_preconditioner(
    U: jnp.ndarray, lam: jnp.ndarray, sqrt_h: jnp.ndarray
) -> WoodburyKernelPreconditioner:
    """Bind a (once-per-hyperparameter) Nyström sketch of ``K`` to one
    system's ``H½`` — see :class:`WoodburyKernelPreconditioner`.

    ``(U, lam)`` come from :func:`randomized_nystrom` of the *kernel*
    operator ``v ↦ K v`` (NOT of ``A``); ``U`` is ``(r, n)`` row-stacked.
    Non-positive Ritz values (rank-deficient sketch tails) are clipped
    out — their ``Λ⁻¹`` diverges, which Woodbury turns into an exact
    no-op for that direction.
    """
    U = pt.ravel_basis(U) if not isinstance(U, jnp.ndarray) or U.ndim != 2 else U
    lam_floor = 1e-12 * jnp.maximum(jnp.max(lam), 1.0)
    lam_safe = jnp.maximum(lam, lam_floor)
    uhu = pt.matmul(U * (sqrt_h * sqrt_h)[None, :], U.T)
    C = jnp.diag(1.0 / lam_safe) + uhu
    C = 0.5 * (C + C.T)
    chol, lower = cho_factor(C)
    return WoodburyKernelPreconditioner(sqrt_h, U, chol, lower=bool(lower))


def jacobi(diag: Pytree) -> JacobiPreconditioner:
    """``M⁻¹ r = r / diag`` (elementwise, pytree-wise)."""
    return JacobiPreconditioner(diag)


def randomized_nystrom(
    A,
    template: Pytree,
    rank: int,
    key,
    *,
    oversample: int = 8,
) -> Tuple[Pytree, jnp.ndarray]:
    """Randomized Nyström/Rayleigh–Ritz eigensketch of an SPD operator.

    Sketch ``Y = A Ω`` with ``rank+oversample`` Gaussian probes, orthonormalize
    (modified Gram–Schmidt over pytrees), Rayleigh–Ritz on ``QᵀAQ``, keep the
    top ``rank`` pairs.  Costs ``rank+oversample`` matvecs — this is exactly
    the "a-priori subspace, chosen before the solve" cost profile of
    spectral methods the paper compares against.

    Returns ``(U, lam)``: a stacked basis of ``rank`` approximate
    eigenvectors (descending eigenvalue order) and their Ritz values.
    """
    m = rank + oversample
    probes = []
    for _ in range(m):
        key, sub = jax.random.split(key)
        probes.append(pt.tree_random_like(sub, template))

    # Y = A Ω, then modified Gram–Schmidt.
    ys = [A(p) for p in probes]
    qs: list = []
    for y in ys:
        for q in qs:
            y = pt.tree_axpy(-pt.tree_dot(q, y), q, y)
        nrm = pt.tree_norm(y)
        y = jax.tree_util.tree_map(lambda l: l / jnp.maximum(nrm, 1e-30), y)
        qs.append(y)
    Q = pt.basis_from_vectors(qs)

    AQ = pt.basis_map_vectors(A, Q)
    T = pt.gram(Q, AQ)
    T = 0.5 * (T + T.T)
    lam, V = jnp.linalg.eigh(T)  # ascending
    order = jnp.argsort(lam)[::-1][:rank]
    U = pt.basis_matmul(Q, V[:, order])
    return U, lam[order]


def nystrom_preconditioner(
    U: Pytree, lam: jnp.ndarray, sigma: float
) -> NystromPreconditioner:
    """``M⁻¹`` from a Nyström sketch, for ``A ≈ U Λ Uᵀ + σ-bulk``:

        M⁻¹ r = U ((λ_min+σ)/(Λ+σ) − 1) Uᵀ r + r

    scaled so the unsketched bulk is treated as ≈ (λ_min+σ) I.  Standard
    randomized-Nyström PCG preconditioner (Frangella et al. form).
    """
    return NystromPreconditioner(U, lam, jnp.asarray(sigma, lam.dtype))
