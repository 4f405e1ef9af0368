"""Inducing-point / subset-of-data baseline (paper §3.1).

The paper compares recycled iterative solvers against the ML-standard
*a-priori low-rank* route: pick m ≪ n representer points X_m, run the full
Laplace optimization on the m-point subproblem (O(m³)), and induce the
remaining latents through the conditional mean

    E[f_{n−m} | f_m] = K_{(n−m)m} K_mm⁻¹ f_m .

The training-set objective log p(y | f) is then evaluated with the induced
latents over the *full* set — that is the accuracy axis of paper Fig. 4;
the cost axis is the (linear-in-n) wall time of the subset solve.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.gp.kernels import RBFKernel
from repro.gp.laplace import LaplaceResult, laplace_gpc, logistic_quantities


@dataclasses.dataclass
class InducingResult:
    logp_full: float  # log p(y|f) with induced latents on the full set
    subset_result: LaplaceResult
    m: int
    seconds: float


def subset_gpc(
    x: jnp.ndarray,
    y: jnp.ndarray,
    kernel: RBFKernel,
    m: int,
    *,
    key=None,
    newton_tol: float = 1.0,
    max_newton: int = 30,
    jitter: float = 1e-6,
) -> InducingResult:
    """Randomly-selected subset-of-data GPC (the paper's Fig. 4 baseline)."""
    n = x.shape[0]
    key = key if key is not None else jax.random.PRNGKey(0)
    idx = jax.random.permutation(key, n)[:m]
    xm, ym = x[idx], y[idx]

    t0 = time.perf_counter()
    sub = laplace_gpc(
        xm, ym, kernel,
        spec=None, newton_tol=newton_tol, max_newton=max_newton,
    )

    # Induce the full latent vector through the conditional mean.
    kmm = kernel.gram(xm) + jitter * jnp.eye(m, dtype=x.dtype)
    knm = kernel.cross(x, xm)  # (n, m)
    alpha = jnp.linalg.solve(kmm, sub.f)
    f_full = knm @ alpha
    # Keep the subset's own (exact) latents at the subset points.
    f_full = f_full.at[idx].set(sub.f)
    jax.block_until_ready(f_full)
    seconds = time.perf_counter() - t0

    logp_full, _, _ = logistic_quantities(f_full, y)
    return InducingResult(
        logp_full=float(logp_full), subset_result=sub, m=m, seconds=seconds
    )
