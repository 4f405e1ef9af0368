"""Vector-space operations over arbitrary pytrees.

Every iterative solver in :mod:`repro.core` treats "a vector" as an
arbitrary pytree of arrays (a flat ``(n,)`` array, a dict of model
parameters, ...).  This module provides the small linear-algebra
vocabulary the solvers need — inner products, AXPYs, and *stacked bases*.

A **basis** is a pytree with the same structure as a vector but where every
leaf carries one extra *leading* axis of size ``m``: it represents ``m``
stacked vectors (e.g. the deflation space ``W`` of def-CG).  Basis
operations (``basis_dot``, ``basis_combine``, ``gram``) are the tall-skinny
GEMMs of subspace recycling; under pjit they lower to per-shard contractions
plus a single all-reduce, which is exactly the collective profile we want on
a TPU mesh.

All functions are pure and jit-compatible.
"""

from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.flatten_util
import jax.numpy as jnp

Pytree = Any


# ---------------------------------------------------------------------------
# Elementary vector-space ops
# ---------------------------------------------------------------------------


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def tree_scale(alpha, a: Pytree) -> Pytree:
    return jax.tree_util.tree_map(lambda x: alpha * x, a)


def tree_axpy(alpha, x: Pytree, y: Pytree) -> Pytree:
    """``y + alpha * x`` (the BLAS axpy, pytree-wise)."""
    return jax.tree_util.tree_map(lambda xl, yl: yl + alpha * xl, x, y)


def tree_zeros_like(a: Pytree) -> Pytree:
    return jax.tree_util.tree_map(jnp.zeros_like, a)


def tree_dot(a: Pytree, b: Pytree):
    """Global inner product ``<a, b>`` reduced over every leaf.

    Accumulates in at least float32 regardless of the storage dtype so that
    bf16 solver states do not destroy CG's scalar recurrences.
    """
    leaves = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(
            lambda x, y: jnp.sum(
                x.astype(_acc_dtype(x.dtype)) * y.astype(_acc_dtype(y.dtype))
            ),
            a,
            b,
        )
    )
    return functools.reduce(jnp.add, leaves)


def tree_norm(a: Pytree):
    return jnp.sqrt(tree_dot(a, a))


def tree_random_like(key, a: Pytree, dtype=None) -> Pytree:
    """Standard-normal pytree with the structure/shapes of ``a``."""
    leaves, treedef = jax.tree_util.tree_flatten(a)
    keys = jax.random.split(key, len(leaves))
    new = [
        jax.random.normal(k, l.shape, dtype or l.dtype)
        for k, l in zip(keys, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, new)


# Every contraction in the solvers runs at full f32 precision.  The TPU
# default for f32 operands is one bf16 pass (~3 significant digits): the
# deflation coefficients μ = (WᵀAW)⁻¹(AW)ᵀr then miss the solve's tolerance
# and a recycled def-CG stalls.  On CPU the flag changes nothing.
HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a @ b`` at full f32 precision (see :data:`HIGHEST`)."""
    return jnp.matmul(a, b, precision=HIGHEST)


def vdot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``aᵀb`` of two flat vectors at full f32 precision."""
    return jnp.vdot(a, b, precision=HIGHEST)


def _acc_dtype(dtype):
    """Accumulation dtype: keep f64 as f64, promote everything real to f32+."""
    if dtype == jnp.float64:
        return jnp.float64
    return jnp.promote_types(dtype, jnp.float32)


# ---------------------------------------------------------------------------
# Flat-vector packing (the solver fast path)
# ---------------------------------------------------------------------------
#
# The CG/def-CG inner loop runs on *contiguous* ``(n,)`` arrays: a solve
# flattens its pytree once at entry, iterates on flat state (one fused HBM
# pass instead of a tree_map per op — DESIGN.md §8), and unflattens once at
# exit.  Bases flatten to 2-D ``(m, n)`` arrays whose column order matches
# :func:`ravel_vector`, so flat GEMVs agree with ``basis_dot`` et al.


def ravel_vector(tree: Pytree):
    """Flatten a pytree vector to ``(flat, unravel)``.

    ``flat`` is a contiguous ``(n,)`` array (leaves concatenated in
    ``tree_leaves`` order, mixed dtypes promoted); ``unravel`` maps a flat
    array back to the original structure.  For an already-flat ``(n,)``
    array this is the identity (no copy after XLA fusion).
    """
    return jax.flatten_util.ravel_pytree(tree)


def ravel(tree: Pytree) -> jnp.ndarray:
    """Just the flat ``(n,)`` array of :func:`ravel_vector`."""
    return jax.flatten_util.ravel_pytree(tree)[0]


def ravel_basis(basis: Pytree) -> jnp.ndarray:
    """Flatten a stacked basis to a 2-D ``(m, n)`` array.

    Row ``i`` equals ``ravel(basis_vector(basis, i))`` — column order (and
    dtype promotion) match :func:`ravel_vector`, so ``flat_basis @ flat_v``
    computes the same inner products as :func:`basis_dot`.
    """
    leaves = jax.tree_util.tree_leaves(basis)
    m = leaves[0].shape[0]
    dtype = functools.reduce(
        jnp.promote_types, [l.dtype for l in leaves[1:]], leaves[0].dtype
    )
    return jnp.concatenate(
        [l.reshape(m, -1).astype(dtype) for l in leaves], axis=1
    )


def unravel_basis(flat: jnp.ndarray, unravel) -> Pytree:
    """Inverse of :func:`ravel_basis` given a vector ``unravel`` (vmapped)."""
    return jax.vmap(unravel)(flat)


# ---------------------------------------------------------------------------
# Stacked bases
# ---------------------------------------------------------------------------


def basis_from_vectors(vectors: Sequence[Pytree]) -> Pytree:
    """Stack a list of vectors into a basis (new leading axis)."""
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls, axis=0), *vectors)


def basis_size(basis: Pytree) -> int:
    """Number of stacked vectors ``m`` (static)."""
    leaf = jax.tree_util.tree_leaves(basis)[0]
    return leaf.shape[0]


def basis_vector(basis: Pytree, i) -> Pytree:
    """Extract vector ``i`` from a basis."""
    return jax.tree_util.tree_map(
        lambda l: jax.lax.dynamic_index_in_dim(l, i, axis=0, keepdims=False),
        basis,
    )


def basis_dot(basis: Pytree, v: Pytree) -> jnp.ndarray:
    """``Bᵀ v`` — shape ``(m,)``.  One tall-skinny GEMV per leaf + reduce."""

    def leaf_dot(bl, vl):
        m = bl.shape[0]
        return matmul(
            bl.reshape(m, -1).astype(_acc_dtype(bl.dtype)),
            vl.reshape(-1).astype(_acc_dtype(vl.dtype)),
        )

    leaves = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(leaf_dot, basis, v)
    )
    return functools.reduce(jnp.add, leaves)


def basis_combine(basis: Pytree, coef: jnp.ndarray) -> Pytree:
    """``B coef`` — linear combination of the stacked vectors, shape of one vector."""

    def leaf_comb(bl):
        m = bl.shape[0]
        flat = matmul(
            coef.astype(_acc_dtype(bl.dtype)),
            bl.reshape(m, -1).astype(_acc_dtype(bl.dtype)),
        )
        return flat.reshape(bl.shape[1:]).astype(bl.dtype)

    return jax.tree_util.tree_map(leaf_comb, basis)


def basis_matmul(basis: Pytree, mat: jnp.ndarray) -> Pytree:
    """``B @ mat`` for ``mat`` of shape ``(m, j)`` — returns a ``j``-vector basis."""

    def leaf_mm(bl):
        m = bl.shape[0]
        flat = matmul(
            mat.T.astype(_acc_dtype(bl.dtype)),
            bl.reshape(m, -1).astype(_acc_dtype(bl.dtype)),
        )
        return flat.reshape((mat.shape[1],) + bl.shape[1:]).astype(bl.dtype)

    return jax.tree_util.tree_map(leaf_mm, basis)


def gram(a: Pytree, b: Pytree) -> jnp.ndarray:
    """``Aᵀ B`` for two bases — the small ``(ma, mb)`` Gram matrix."""

    def leaf_gram(al, bl):
        ma, mb = al.shape[0], bl.shape[0]
        return matmul(
            al.reshape(ma, -1).astype(_acc_dtype(al.dtype)),
            bl.reshape(mb, -1).astype(_acc_dtype(bl.dtype)).T,
        )

    leaves = jax.tree_util.tree_leaves(jax.tree_util.tree_map(leaf_gram, a, b))
    return functools.reduce(jnp.add, leaves)


def basis_concat(a: Pytree, b: Pytree) -> Pytree:
    """Concatenate two bases along the stacking axis: ``[A, B]``."""
    return jax.tree_util.tree_map(
        lambda al, bl: jnp.concatenate([al, bl], axis=0), a, b
    )


def basis_zeros(template: Pytree, m: int) -> Pytree:
    """An all-zero basis of ``m`` vectors shaped like ``template``."""
    return jax.tree_util.tree_map(
        lambda l: jnp.zeros((m,) + l.shape, l.dtype), template
    )


def basis_set(basis: Pytree, v: Pytree, i) -> Pytree:
    """Functionally set stacked vector ``i`` to ``v`` (dynamic index ok)."""
    return jax.tree_util.tree_map(
        lambda bl, vl: jax.lax.dynamic_update_index_in_dim(
            bl, vl.astype(bl.dtype), i, axis=0
        ),
        basis,
        v,
    )


def basis_slice(basis: Pytree, m: int) -> Pytree:
    """First ``m`` vectors of a basis (static ``m``)."""
    return jax.tree_util.tree_map(lambda l: l[:m], basis)


def basis_scale_columns(basis: Pytree, scales: jnp.ndarray) -> Pytree:
    """Scale stacked vector ``i`` by ``scales[i]``."""

    def leaf(bl):
        shape = (bl.shape[0],) + (1,) * (bl.ndim - 1)
        return bl * scales.reshape(shape).astype(bl.dtype)

    return jax.tree_util.tree_map(leaf, basis)


def basis_map_vectors(fn, basis: Pytree) -> Pytree:
    """Apply a vector->vector function across the stacking axis (vmapped)."""
    return jax.vmap(fn)(basis)
