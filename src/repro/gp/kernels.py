"""GP kernel functions and matrix-free Gram operators."""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp

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
        self, x: jnp.ndarray, *, impl: str = "auto", block: int = 256
    ) -> Callable[[jnp.ndarray], jnp.ndarray]:
        """Matrix-free ``v ↦ K v`` over the fused kernel (K never built)."""

        def mv(v: jnp.ndarray) -> jnp.ndarray:
            return kops.rbf_matvec(
                x, v, self.theta, self.lengthscale, impl=impl, block=block
            )

        return mv
