"""End-to-end paper reproduction driver: GP classification, Laplace mode.

Runs the paper's §3 experiment (Table 1 columns) on the synthetic
infinite-digits 3-vs-5 task: the Newton loop solves Eq. (10) per iteration
with Cholesky (exact), CG, and def-CG(8,12) with harmonic-Ritz recycling,
reporting log p(y|f), relative error and cumulative solver time.

    PYTHONPATH=src python examples/gpc_digits.py --n 1000
"""

import argparse

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from repro.core import SolveSpec  # noqa: E402
from repro.data import make_infinite_digits  # noqa: E402
from repro.gp import RBFKernel, laplace_gpc  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=800)
    ap.add_argument("--theta", type=float, default=3.0)
    ap.add_argument("--lengthscale", type=float, default=3.0)
    ap.add_argument("--tol", type=float, default=1e-5)
    args = ap.parse_args()

    x, y = make_infinite_digits(args.n, seed=0, noise=0.10)
    x, y = jnp.asarray(x, jnp.float64), jnp.asarray(y, jnp.float64)
    kernel = RBFKernel(theta=args.theta, lengthscale=args.lengthscale)
    kd = kernel.gram(x)

    specs = {
        "cholesky": None,
        "cg": SolveSpec(method="cg", tol=args.tol, maxiter=2000),
        "defcg": SolveSpec(
            method="defcg", k=8, ell=12, tol=args.tol, maxiter=2000
        ),
    }
    runs = {}
    for solver, spec in specs.items():
        runs[solver] = laplace_gpc(
            x, y, kernel, spec=spec, newton_tol=1.0,
            k_dense=kd, dense_matvec=True,
        )
        r = runs[solver]
        print(f"{solver:9s} newtons={len(r.trace.logp)} "
              f"logp={r.logp:10.3f} "
              f"solver_time={r.trace.cumulative_time[-1]:6.2f}s "
              f"iters={r.trace.solver_iterations}")

    chol = runs["cholesky"]
    acc = float(jnp.mean(jnp.sign(chol.f) == y))
    cg_it = sum(runs["cg"].trace.solver_iterations[1:])
    def_it = sum(runs["defcg"].trace.solver_iterations[1:])
    print(f"\ntrain accuracy (exact mode): {acc:.3f}")
    print(f"def-CG iteration saving after system 1: {1 - def_it/cg_it:.0%} "
          f"(paper: ~25%)")


if __name__ == "__main__":
    main()
