"""Sharded-engine benchmarks: per-iteration cost, collective counts,
and the n = 1e5 sharded RBF matvec.

Device counts {1, 4, 8} come from ``xla_force_host_platform_device_count``
(set at the top of run.py before jax initializes), so on this box the
"devices" are host threads — the numbers to watch are the per-iteration
TIME TREND and the per-while-body COLLECTIVE COUNTS (the one-all-reduce
contract, DESIGN.md §5), not absolute multi-device speedup.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import emit, log, timed

_N = 4096  # divisible by every benched device count
_MAXITER = 40


def _dense_system(n=_N, seed=0):
    from repro.core.operators import DenseMatrixOperator

    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    eigs = np.logspace(0, 2, n)
    A = DenseMatrixOperator(mat=jnp.asarray((q * eigs) @ q.T))
    b = jnp.asarray(rng.standard_normal(n))
    return A, b


def _bench_defcg_per_iteration():
    from repro.core import sharded
    from repro.core.api import SolveSpec
    from repro.core.recycle import RecycleState
    from repro.launch import hlo_stats
    from repro.launch.mesh import make_solve_mesh

    A, b = _dense_system()
    # tol=0 never converges: every run spends exactly _MAXITER
    # iterations, so us/iter is a clean division.
    spec = SolveSpec(
        method="defcg", k=8, ell=12, tol=0.0, atol=0.0, maxiter=_MAXITER
    )
    st = RecycleState.zeros(8, _N, jnp.float64)

    n_avail = jax.device_count()
    for nd in (1, 4, 8):
        if nd > n_avail:
            log(f"shard/defcg d{nd}: skipped ({n_avail} devices)")
            continue
        mesh = make_solve_mesh(nd)
        res, dt = timed(
            lambda: sharded.solve_sharded(A, b, spec, st, mesh=mesh),
            warmup=1,
            repeats=3,
        )
        iters = int(res.info.iterations)
        # Pin the communication contract alongside the timing: every
        # while body (recording scan + while phase) of the compiled
        # sharded def-CG must hold exactly ONE all-reduce.
        hlo = (
            sharded.lower_sharded(A, b, spec, st, mesh=mesh)
            .compile()
            .as_text()
        )
        per_body = hlo_stats.while_body_collectives(hlo)
        ars = sorted(c.get("all-reduce", 0) for c in per_body.values())
        emit(
            f"shard/defcg_iter_d{nd}",
            dt / iters * 1e6,
            f"n={_N};iters={iters};allreduce_per_body="
            + ",".join(map(str, ars)),
        )
        log(
            f"shard/defcg d{nd}: {dt / iters * 1e6:8.1f} us/iter  "
            f"while-body all-reduce counts {ars}"
        )


def _bench_rbf_matvec_1e5():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import sharded
    from repro.core.operators import RBFKernelSystemOperator
    from repro.launch.mesh import make_solve_mesh

    n = 100_000
    if jax.device_count() < 8:
        log("shard/rbf_matvec_1e5: skipped (<8 devices)")
        return
    mesh = make_solve_mesh(8)
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.standard_normal((n, 2)), jnp.float32)
    sqrt_h = jnp.asarray(0.5 + rng.random(n), jnp.float32)
    v = jnp.asarray(rng.standard_normal(n), jnp.float32)
    A = RBFKernelSystemOperator(
        x=X, sqrt_h=sqrt_h, theta=1.0, lengthscale=2.0,
        impl="chunked", block=512,
    )
    kind, aux, leaves, leaf_specs = sharded._plan_operator(
        A, need_adjoint=False
    )

    def one_matvec(leaves, v_loc):
        apply, _, _ = sharded._make_applies(kind, aux, leaves)
        return apply(v_loc)

    fn = jax.jit(
        jax.shard_map(
            one_matvec,
            mesh=mesh,
            in_specs=(leaf_specs, P("solve")),
            out_specs=P("solve"),
            check_vma=False,
        )
    )
    v_sh = jax.device_put(v, NamedSharding(mesh, P("solve")))
    out, dt = timed(fn, leaves, v_sh, warmup=0, repeats=1)
    assert bool(jnp.all(jnp.isfinite(out)))
    emit(
        "shard/rbf_matvec_1e5",
        dt * 1e6,
        f"n={n};d=2;f32;8shards;K_never_materialized",
    )
    log(f"shard/rbf matvec n=1e5 (8 shards, f32): {dt:8.2f} s")


def run():
    _bench_defcg_per_iteration()
    _bench_rbf_matvec_1e5()
