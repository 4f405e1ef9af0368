"""Shared benchmark scaffolding.

Every benchmark prints ``name,us_per_call,derived`` CSV rows (harness
contract) plus a human-readable table to stderr.  GP problem sizes default
to CPU-feasible values; set ``REPRO_BENCH_N`` to scale up.
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import use_compile_cache

jax.config.update("jax_enable_x64", True)
use_compile_cache()

BENCH_N = int(os.environ.get("REPRO_BENCH_N", "1200"))


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# Every emit() is also recorded here so run.py can dump a machine-readable
# BENCH_solvers.json next to the CSV stream (perf-trajectory tracking).
RESULTS: list = []  # (name, us_per_call, derived)


def emit(name: str, us_per_call: float, derived: str = ""):
    RESULTS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def timed(fn, *args, warmup: int = 0, repeats: int = 1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn(*args)
        jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / repeats
    return out, dt


def gpc_problem(n: int = None, seed: int = 0, theta: float = 3.0,
                lengthscale: float = 3.0, noise: float = 0.10):
    """The paper's task at CPU scale: synthetic 3-vs-5, RBF kernel."""
    from repro.data import make_infinite_digits
    from repro.gp import RBFKernel

    n = n or BENCH_N
    x, y = make_infinite_digits(n, seed=seed, noise=noise)
    x = jnp.asarray(x, jnp.float64)
    y = jnp.asarray(y, jnp.float64)
    kernel = RBFKernel(theta=theta, lengthscale=lengthscale)
    return x, y, kernel
