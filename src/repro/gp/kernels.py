"""GP kernel functions and matrix-free Gram operators."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import sharded
from repro.kernels import ops as kops
from repro.kernels import ref as kref


@dataclasses.dataclass(frozen=True)
class RBFKernel:
    """Gaussian/RBF kernel  k(x, x') = θ² exp(−‖x−x'‖² / 2λ²)  (paper §3)."""

    theta: float = 1.0
    lengthscale: float = 1.0

    def gram(self, x: jnp.ndarray) -> jnp.ndarray:
        """Materialized K(X, X) — only for the Cholesky baseline / small n."""
        return kref.rbf_gram(x, self.theta, self.lengthscale)

    def cross(self, xa: jnp.ndarray, xb: jnp.ndarray) -> jnp.ndarray:
        d2 = (
            jnp.sum(xa * xa, 1)[:, None]
            + jnp.sum(xb * xb, 1)[None, :]
            - 2.0 * (xa @ xb.T)
        )
        return (self.theta**2) * jnp.exp(
            -0.5 * jnp.maximum(d2, 0.0) / self.lengthscale**2
        )

    def matvec_fn(
        self, x: jnp.ndarray, *, impl: str = "auto", block: int = 256,
        mesh=None,
    ) -> "GramMatvec":
        """Matrix-free ``v ↦ K v`` over the fused kernel (K never built);
        with ``mesh``, split by rows over its ``"solve"`` axis."""
        return GramMatvec(x, self.theta, self.lengthscale, impl, block, mesh)


@jax.tree_util.register_pytree_node_class
class GramMatvec:
    """``v ↦ K(X, X) v`` over the fused RBF kernel, as a pytree callable.

    ``x``, ``theta`` and ``lengthscale`` are leaves and ``impl``/``block``
    static aux data, so a jitted function that takes it as an argument
    compiles once per shape for every data set and hyperparameter of that
    shape, and never bakes ``x`` into its executable as a constant.
    ``v`` may be ``(n,)`` or column-stacked ``(n, r)``.

    With a ``mesh`` (a 1-D ``"solve"`` mesh, static aux data too) ``x``
    and ``v`` are row-sharded over it: each device all-gathers the data
    and the vector and applies its own row block against all columns
    (``kernels.ops.rbf_matvec_rect`` under ``shard_map``), so a pass is
    split over the chips.  A Pallas call is one custom call that GSPMD
    cannot split; without the ``shard_map`` one chip would run the whole
    pass.

    The Gram function (``kernels.ops.rbf_matvec``, or ``rbf_matvec_rect``
    on a mesh) is bound when the matvec is made and kept in the aux data
    too: a jitted caller's cache is keyed on the function it traced, so
    one that replaces the module's function (a fault-injection test) is
    traced anew, not served a stale executable.
    """

    __slots__ = ("x", "theta", "lengthscale", "impl", "block", "gram", "mesh")

    def __init__(self, x, theta, lengthscale, impl: str = "auto",
                 block: int = 256, mesh=None):
        self.x, self.theta, self.lengthscale = x, theta, lengthscale
        self.impl, self.block, self.mesh = impl, block, mesh
        self.gram = kops.rbf_matvec if mesh is None else kops.rbf_matvec_rect

    def __call__(self, v: jnp.ndarray) -> jnp.ndarray:
        if self.mesh is None:
            return self.gram(
                self.x, v, self.theta, self.lengthscale,
                impl=self.impl, block=self.block,
            )
        return self._sharded(v)

    def _sharded(self, v: jnp.ndarray) -> jnp.ndarray:
        ax = sharded.SOLVE_AXIS
        v_spec = P(ax) if v.ndim == 1 else P(ax, None)

        def local(x_loc, v_loc, theta, lengthscale):
            return self.gram(
                x_loc, sharded.gather_x(x_loc), sharded.gather_v(v_loc),
                theta, lengthscale, impl=self.impl, block=self.block,
            )

        return jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(ax, None), v_spec, P(), P()), out_specs=v_spec,
            check_vma=False,
        )(self.x, v, jnp.asarray(self.theta), jnp.asarray(self.lengthscale))

    def tree_flatten(self):
        return (self.x, self.theta, self.lengthscale), (
            self.impl, self.block, self.gram, self.mesh,
        )

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        mv = cls.__new__(cls)
        mv.x, mv.theta, mv.lengthscale = leaves
        mv.impl, mv.block, mv.gram, mv.mesh = aux
        return mv


@jax.tree_util.register_pytree_node_class
class DenseMatvec:
    """``v ↦ K v`` over a materialized ``K``, as a pytree callable whose
    one leaf is ``K``."""

    __slots__ = ("k",)

    def __init__(self, k: jnp.ndarray):
        self.k = k

    def __call__(self, v: jnp.ndarray) -> jnp.ndarray:
        return self.k @ v

    def tree_flatten(self):
        return (self.k,), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)
