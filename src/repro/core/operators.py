"""Matrix-free symmetric positive-definite linear operators.

The solvers in :mod:`repro.core.solvers` only ever touch ``A`` through
``A @ v`` (a matvec on a pytree).  This module provides the operator
abstraction plus the concrete operators the framework uses:

* :func:`from_matrix` — an explicit dense matrix (tests / small problems);
* :class:`KernelSystemOperator` — the paper's GP-classification Newton
  system ``A = I + H^{1/2} K H^{1/2}`` (Eq. 10), matrix-free over the fused
  Gram-matvec kernel so the ``n x n`` Gram matrix is never materialized;
* :class:`GGNOperator` — damped Gauss-Newton matvec through an arbitrary
  model (``G v = Jᵀ H_L J v + λ v`` via ``jvp``/``vjp``), the Hessian-free
  workhorse that carries the paper's technique to LM-scale training;
* shift/scale/sum composition helpers.

Operators are registered as pytree nodes so they can cross ``jit``
boundaries as arguments.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.flatten_util
import jax.numpy as jnp

from repro.core import pytree as pt

Pytree = Any
Matvec = Callable[[Pytree], Pytree]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LinearOperator:
    """A linear operator ``v ↦ A v`` — symmetric by default, rectangular
    when an adjoint is supplied.

    Attributes:
      matvec: the matvec closure.  Must be pure and jit-compatible.
      matvec_cost_flops: optional static estimate of flops per matvec,
        used by benchmark accounting (``None`` → unknown).
      matmat: optional multi-RHS closure ``V ↦ A V`` over column-stacked
        ``(n, r)`` arrays (array-vector operators only).  When present,
        :func:`apply_to_basis` refreshes a whole recycled basis in one
        operator application instead of r sequential matvecs.
      rmatvec: optional adjoint closure ``u ↦ Aᵀ u``.  ``None`` declares
        the operator SYMMETRIC (the historical contract of this repo:
        every SPD solve path assumes it), in which case :attr:`T` is the
        operator itself.  Supplying it opens the rectangular / least-
        squares workload: LSMR touches ``A`` only through
        ``matvec``/``rmatvec`` pairs.
    """

    matvec: Matvec
    matvec_cost_flops: Optional[float] = None
    matmat: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None
    rmatvec: Optional[Matvec] = None

    def __call__(self, v: Pytree) -> Pytree:
        return self.matvec(v)

    def __matmul__(self, v: Pytree) -> Pytree:
        return self.matvec(v)

    @property
    def T(self) -> "LinearOperator":
        """The adjoint operator ``u ↦ Aᵀ u``.

        Symmetric operators (``rmatvec is None``) are their own adjoint;
        rectangular ones get a fresh operator with the closures swapped,
        so ``op.T.T`` round-trips.
        """
        if self.rmatvec is None:
            return self
        return LinearOperator(
            self.rmatvec, self.matvec_cost_flops, None, self.matvec
        )

    def basis_matvec(self, basis: Pytree) -> Pytree:
        """``A`` applied to every vector of a stacked basis (leading axis).

        One ``matmat`` call when available (the basis rows become columns),
        else a vmapped matvec sweep.
        """
        if self.matmat is not None:
            return self.matmat(jnp.swapaxes(basis, 0, 1)).swapaxes(0, 1)
        return pt.basis_map_vectors(self.matvec, basis)

    # -- composition ------------------------------------------------------
    def shifted(self, sigma) -> "LinearOperator":
        """``A + sigma I`` (square operators only)."""

        def mv(v, base=self.matvec):
            return pt.tree_axpy(sigma, v, base(v))

        mm = None
        if self.matmat is not None:

            def mm(vs, base=self.matmat):
                return base(vs) + sigma * vs

        rmv = None
        if self.rmatvec is not None:

            def rmv(u, base=self.rmatvec):
                return pt.tree_axpy(sigma, u, base(u))

        return LinearOperator(mv, self.matvec_cost_flops, mm, rmv)

    def scaled(self, c) -> "LinearOperator":
        def mv(v, base=self.matvec):
            return pt.tree_scale(c, base(v))

        mm = None
        if self.matmat is not None:

            def mm(vs, base=self.matmat):
                return c * base(vs)

        rmv = None
        if self.rmatvec is not None:

            def rmv(u, base=self.rmatvec):
                return pt.tree_scale(c, base(u))

        return LinearOperator(mv, self.matvec_cost_flops, mm, rmv)

    def __add__(self, other: "LinearOperator") -> "LinearOperator":
        def mv(v, a=self.matvec, b=other.matvec):
            return pt.tree_add(a(v), b(v))

        cost = None
        if self.matvec_cost_flops is not None and other.matvec_cost_flops is not None:
            cost = self.matvec_cost_flops + other.matvec_cost_flops
        mm = None
        if self.matmat is not None and other.matmat is not None:

            def mm(vs, a=self.matmat, b=other.matmat):
                return a(vs) + b(vs)

        rmv = None
        if self.rmatvec is not None and other.rmatvec is not None:

            def rmv(u, a=self.rmatvec, b=other.rmatvec):
                return pt.tree_add(a(u), b(u))

        return LinearOperator(mv, cost, mm, rmv)

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (), (self.matvec, self.matvec_cost_flops, self.matmat, self.rmatvec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del children
        return cls(*aux)


@jax.tree_util.register_pytree_node_class
class DenseMatrixOperator(LinearOperator):
    """Dense matrix as an operator — with the matrix as a pytree LEAF.

    The base :class:`LinearOperator` flattens with zero children (its
    closures are aux data), which is right for opaque callables but
    wrong for an explicit matrix: aux data is part of the jit cache key,
    so a closure-wrapped matrix retraced ``solve_jit`` for EVERY new
    system (the trace-audit gate's retrace-budget check catches exactly
    this).  Here the matrix is the child — two operators over same-shape
    matrices share one trace, vmap batches over a stacked leading axis,
    and the matrix shards like any other array.

    Rectangular ``(m, n)`` matrices are supported: ``matvec`` maps
    ``(n,) → (m,)`` and :attr:`rmatvec`/:attr:`T` apply ``matᵀ`` —
    which is what the LSMR front door consumes.  Square SPD usage is
    unchanged (the SPD solvers never call ``rmatvec``).
    """

    def __init__(self, mat: jnp.ndarray):
        self.mat = mat
        # Unflatten may pass non-array sentinels (treedef manipulation);
        # the matvec is never called on those, but __init__ must survive.
        shape = getattr(mat, "shape", None)
        m, n = (shape[-2], shape[-1]) if shape and len(shape) >= 2 else (0, 0)

        def mv(v):
            return pt.matmul(mat, v)

        def rmv(u):
            return pt.matmul(jnp.swapaxes(mat, -2, -1), u)

        LinearOperator.__init__(
            self, mv, matvec_cost_flops=2.0 * m * n, matmat=mv, rmatvec=rmv
        )

    @property
    def T(self) -> "DenseMatrixOperator":
        return DenseMatrixOperator(jnp.swapaxes(self.mat, -2, -1))

    def tree_flatten(self):
        return (self.mat,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        (mat,) = children
        return cls(mat)


def from_matrix(mat: jnp.ndarray) -> DenseMatrixOperator:
    """Explicit dense SPD matrix as an operator over flat ``(n,)`` vectors.

    The matrix is carried as a traced pytree leaf (see
    :class:`DenseMatrixOperator`): solves over different same-shape
    matrices hit one compiled trace instead of retracing per system.
    """
    return DenseMatrixOperator(mat)


def from_callable(fn: Matvec, cost: Optional[float] = None) -> LinearOperator:
    return LinearOperator(fn, cost)


def apply_to_basis(op, basis: Pytree) -> Pytree:
    """``A @ [w_1 … w_m]`` as ONE multi-RHS operator application.

    The cross-system refresh of the recycled basis (``AW`` for the next
    system's operator) is the paper's §2.2 overhead term: issued as m
    sequential matvecs it costs m operator passes; operators that expose
    ``basis_matvec`` (all the concrete ones here) amortize it into a
    single pass — e.g. the fused RBF Gram kernel forms each K-tile once
    for all m right-hand sides.  Falls back to a vmapped matvec sweep for
    bare callables.
    """
    bm = getattr(op, "basis_matvec", None)
    if bm is not None:
        return bm(basis)
    return pt.basis_map_vectors(op, basis)


# ---------------------------------------------------------------------------
# The paper's Newton-system operator (GP classification, Eq. 10)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KernelSystemOperator:
    """``A v = v + H^{1/2} · K (H^{1/2} · v)`` — Kuss–Rasmussen restructuring.

    ``kernel_matvec`` computes ``K u`` matrix-free (fused Pallas kernel on
    TPU, chunked-jnp elsewhere) and must also accept column-stacked
    ``(n, r)`` right-hand sides (both the fused kernel and a dense
    ``K @ V`` do); ``sqrt_h`` is the elementwise vector ``H^{1/2}`` (H
    diagonal for logistic likelihood).  Eigenvalues of ``A`` are confined
    to ``[1, n·max(K)/4]`` which is what makes CG and def-CG well behaved
    on this family (paper §3).
    """

    kernel_matvec: Matvec
    sqrt_h: jnp.ndarray
    matvec_cost_flops: Optional[float] = None

    def matvec(self, v):
        return v + self.sqrt_h * self.kernel_matvec(self.sqrt_h * v)

    def basis_matvec(self, basis: jnp.ndarray) -> jnp.ndarray:
        """``A`` on an ``(m, n)`` stacked basis — one fused multi-RHS
        Gram pass (each K-tile formed once for all m vectors)."""
        v = (basis * self.sqrt_h[None, :]).T  # (n, m) column-stacked
        return basis + self.sqrt_h[None, :] * self.kernel_matvec(v).T

    def __call__(self, v):
        return self.matvec(v)

    def __matmul__(self, v):
        return self.matvec(v)

    def tree_flatten(self):
        return (self.sqrt_h,), (self.kernel_matvec, self.matvec_cost_flops)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (sqrt_h,) = children
        kernel_matvec, cost = aux
        return cls(kernel_matvec, sqrt_h, cost)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RBFKernelSystemOperator:
    """The GP Newton operator with its DATA as pytree leaves — shardable.

    Same math as :class:`KernelSystemOperator` specialized to the RBF
    Gram kernel, ``A v = v + H^{1/2} · K(X, X) (H^{1/2} · v)``, but the
    training data ``x`` and the likelihood diagonal ``sqrt_h`` are
    pytree CHILDREN instead of being baked into a matvec closure.  That
    is what makes the operator mesh-shardable (DESIGN.md §5): under the
    sharded engine each device keeps a ROW block of ``x``/``sqrt_h``
    local, the matvec all-gathers the scaled vector once per iteration,
    and the local K-tiles are formed and consumed on the fly
    (:func:`repro.kernels.ops.rbf_matvec_rect`) — n = 10⁵–10⁶ solves
    never materialize the n×n Gram matrix.  On one device it behaves
    exactly like ``KernelSystemOperator`` over the fused/chunked Gram
    matvec (and, being leaf-carrying, same-shape systems share one
    ``solve_jit`` trace, like :class:`DenseMatrixOperator`).

    ``theta``/``lengthscale``/``block``/``impl`` are static aux data —
    hyperparameter *values* bake into the trace; the kernel wrapper
    pre-scales inputs so the Pallas kernel itself never recompiles.
    """

    x: jnp.ndarray  # (n, d) training inputs
    sqrt_h: jnp.ndarray  # (n,) H^{1/2} diagonal
    theta: float = 1.0
    lengthscale: float = 1.0
    block: int = 1024
    impl: str = "auto"

    def kernel_matvec(self, u: jnp.ndarray) -> jnp.ndarray:
        """``K(X, X) @ u`` — (n,) or column-stacked (n, r)."""
        from repro.kernels import ops as kops

        return kops.rbf_matvec(
            self.x, u, self.theta, self.lengthscale,
            impl=self.impl, block=self.block,
        )

    def matvec(self, v: jnp.ndarray) -> jnp.ndarray:
        return v + self.sqrt_h * self.kernel_matvec(self.sqrt_h * v)

    def basis_matvec(self, basis: jnp.ndarray) -> jnp.ndarray:
        v = (basis * self.sqrt_h[None, :]).T  # (n, m) column-stacked
        return basis + self.sqrt_h[None, :] * self.kernel_matvec(v).T

    def __call__(self, v):
        return self.matvec(v)

    def __matmul__(self, v):
        return self.matvec(v)

    def tree_flatten(self):
        return (self.x, self.sqrt_h), (
            self.theta, self.lengthscale, self.block, self.impl,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        x, sqrt_h = children
        theta, lengthscale, block, impl = aux
        return cls(x, sqrt_h, theta, lengthscale, block, impl)


# ---------------------------------------------------------------------------
# Gauss-Newton operator — Hessian-free optimization at LM scale
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GGNOperator:
    """Damped generalized Gauss-Newton matvec ``(Jᵀ H_L J + λ I) v``.

    ``model_fn(params) -> outputs`` is the network up to its final linear
    outputs; ``loss_hvp(outputs, tangent_out) -> tangent_out'`` applies the
    (tiny, typically diagonal or per-token-softmax) loss Hessian.  The GGN
    is SPD for convex losses, which is exactly the setting def-CG needs.

    One matvec = one ``jvp`` + one loss-Hessian apply + one ``vjp`` —
    roughly 3x a forward pass, entirely expressible in XLA so the full
    Hessian-free step (def-CG loop included) jits and shards under pjit.
    """

    model_fn: Callable[[Pytree], Pytree]
    loss_hvp: Callable[[Pytree, Pytree], Pytree]
    params: Pytree
    damping: jnp.ndarray = dataclasses.field(default_factory=lambda: jnp.float32(0.0))
    matvec_cost_flops: Optional[float] = None

    def matvec(self, v: Pytree) -> Pytree:
        outputs, jv = jax.jvp(self.model_fn, (self.params,), (v,))
        hjv = self.loss_hvp(outputs, jv)
        _, vjp_fn = jax.vjp(self.model_fn, self.params)
        (gv,) = vjp_fn(hjv)
        return pt.tree_axpy(self.damping, v, gv)

    def basis_matvec(self, basis: Pytree) -> Pytree:
        """GGN applied to a stacked basis: the model is linearized ONCE
        and the (linear) tangent/cotangent maps are vmapped over the m
        vectors — two forward passes total instead of 2m."""
        outputs, jvp_fn = jax.linearize(self.model_fn, self.params)
        _, vjp_fn = jax.vjp(self.model_fn, self.params)

        def one(v):
            hjv = self.loss_hvp(outputs, jvp_fn(v))
            (gv,) = vjp_fn(hjv)
            return pt.tree_axpy(self.damping, v, gv)

        return jax.vmap(one)(basis)

    def __call__(self, v):
        return self.matvec(v)

    def __matmul__(self, v):
        return self.matvec(v)

    def tree_flatten(self):
        return (self.params, self.damping), (
            self.model_fn,
            self.loss_hvp,
            self.matvec_cost_flops,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        params, damping = children
        model_fn, loss_hvp, cost = aux
        return cls(model_fn, loss_hvp, params, damping, cost)


# ---------------------------------------------------------------------------
# Gauss-Newton Jacobian operator — the rectangular least-squares workhorse
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GaussNewtonOperator:
    """The Jacobian ``J`` of a residual map as a rectangular operator.

    ``residual_fn(params) -> residuals`` is the model's residual map
    (e.g. ``predictions − targets``); the operator exposes the two
    products LSMR consumes:

    * ``matvec(v) = J v`` — one ``jvp`` through the residual map;
    * ``rmatvec(u) = Jᵀ u`` — one ``vjp``.

    Solving ``min ‖J δ + r‖² + λ‖δ‖²`` with :func:`repro.core.lsmr.lsmr`
    is the TRUE Gauss-Newton step — unlike :class:`GGNOperator` (which
    squares ``J`` into ``JᵀH_LJ`` and hands an SPD system to CG), the
    least-squares path never forms the normal-equations operator, so its
    conditioning is κ(J), not κ(J)².  Domain is the params pytree, range
    the residual pytree — both cross the flat engine through their own
    ravel/unravel pair.
    """

    residual_fn: Callable[[Pytree], Pytree]
    params: Pytree
    matvec_cost_flops: Optional[float] = None

    def matvec(self, v: Pytree) -> Pytree:
        return jax.jvp(self.residual_fn, (self.params,), (v,))[1]

    def rmatvec(self, u: Pytree) -> Pytree:
        _, vjp_fn = jax.vjp(self.residual_fn, self.params)
        (jtv,) = vjp_fn(u)
        return jtv

    def residuals(self) -> Pytree:
        """``r(params)`` — the right-hand side is ``−r`` for a GN step."""
        return self.residual_fn(self.params)

    @property
    def T(self) -> LinearOperator:
        return LinearOperator(
            self.rmatvec, self.matvec_cost_flops, None, self.matvec
        )

    def __call__(self, v):
        return self.matvec(v)

    def __matmul__(self, v):
        return self.matvec(v)

    def tree_flatten(self):
        return (self.params,), (self.residual_fn, self.matvec_cost_flops)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (params,) = children
        residual_fn, cost = aux
        return cls(residual_fn, params, cost)


def adjoint_matvec(op) -> Matvec:
    """The ``u ↦ Aᵀ u`` closure of ``op``.

    Operators without an ``rmatvec`` are symmetric by this repo's
    contract (every SPD solve path already relies on it), so their
    adjoint is their own matvec.  This is the single place the LSMR
    engine resolves adjoints through.
    """
    rmv = getattr(op, "rmatvec", None)
    if rmv is not None:
        return rmv
    return op.matvec if hasattr(op, "matvec") else op


def materialize(op, template: Pytree) -> jnp.ndarray:
    """Densify a small operator (tests only): returns the matrix of ``op``
    in the coordinate system of ``template``'s raveled pytree."""
    flat, unravel = jax.flatten_util.ravel_pytree(template)
    n = flat.shape[0]

    def col(i):
        e = unravel(jnp.zeros_like(flat).at[i].set(1.0))
        out, _ = jax.flatten_util.ravel_pytree(op(e))
        return out

    return jax.vmap(col, out_axes=1)(jnp.arange(n))
