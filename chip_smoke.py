"""Smoke run of the main path on a TPU: the paper's GP classification.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded solve on a 4-chip mesh

One chip runs, in one process and through the normal entry points:

1. kernel check: one RBF Gram matvec on the Pallas kernel and on the plain
   XLA (``chunked``) path, both against a float64 host reference on the
   first rows;
2. the Laplace GP-classification Newton sequence, ``laplace_gpc`` with
   def-CG(8, 12) and harmonic-Ritz recycling, at the widths of
   ``configs/gpc_mnist.py``, then the same sequence with the RBF matvec on
   ``chunked``; the two must agree;
3. serving: two tenants of ``repro.serve.SolveService``, each with two
   related Newton systems.

Every solve must converge on its first attempt: a recovery-ladder rung
above 0, or more matvecs than a clean attempt costs, fails the run.

``--chips 4`` runs only the mesh phase: the same Newton sequence through
``laplace_gpc(..., mesh=make_solve_mesh(4))`` at n = 2^17 (the data's
rows, the latent and the recycled basis split over a 4-chip ``"solve"``
mesh, every system solved by the sharded def-CG and the driver's Gram
passes split over the chips), held to the same checks, and the
one-all-reduce-per-iteration contract read from the compiled HLO.

The chip path is float32 with x64 off: the Mosaic kernels take no 64-bit
types.  The script fails (non-zero exit, no result line) when JAX finds no
TPU, and when any check fails.  Its last line is one JSON object naming
the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SEED = 0
# The paper config is n = 2^20.  One Gram matvec there is 2·n²·d ≈ 1.7e15
# flops, so a 12-system Newton sequence of ~100 matvecs would take far
# longer than a smoke run; 2^15 keeps every width and cuts only n.
N_ONE_CHIP = 2**15
# The sharded phase runs in the regime the mesh exists for (n ≥ 1e5).
N_MESH = 2**17
LOGP_RTOL = 1e-3  # f32 Newton sequences, Pallas vs XLA Gram matvec
MATVEC_RTOL = 1e-4  # f32 Gram matvec vs the float64 host reference
REF_ROWS = 256
# A clean warm def-CG solve costs its iterations plus k matvecs for the AW
# refresh and one or two residuals; more means a failed attempt was retried.
MATVEC_SLACK = 2


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events; the rest of a phase's wall time is run time."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


class Phase:
    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.seconds
        print(f"[{self.name}] start", flush=True)
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        comp = self.clock.seconds - self.c0
        print(
            f"[{self.name}] wall_s={wall} compile_s={comp} "
            f"run_s={wall - comp}",
            flush=True,
        )


def digits(n: int, seed: int):
    from repro.data import make_infinite_digits

    x, y = make_infinite_digits(n, seed=seed)
    return jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32)


def host_rbf_rows(x, v, theta, lengthscale, rows):
    """``K(X, X)[:rows] @ v`` in float64 on the host."""
    xs = np.asarray(x, np.float64) / lengthscale
    d2 = (
        np.sum(xs[:rows] ** 2, 1)[:, None]
        + np.sum(xs**2, 1)[None, :]
        - 2.0 * xs[:rows] @ xs.T
    )
    return theta**2 * np.exp(-0.5 * np.maximum(d2, 0.0)) @ np.asarray(
        v, np.float64
    )


def phase_kernel_check(x, cfg):
    from repro.kernels import ops as kops

    v = jax.random.normal(jax.random.PRNGKey(SEED), (x.shape[0],), x.dtype)
    ref = host_rbf_rows(x, v, cfg.theta, cfg.lengthscale, REF_ROWS)
    for impl in ("auto", "chunked"):
        mv = jax.jit(lambda x, v, impl=impl: kops.rbf_matvec(
            x, v, cfg.theta, cfg.lengthscale, impl=impl, block=cfg.block
        ))
        y = np.asarray(jax.block_until_ready(mv(x, v)), np.float64)
        t0 = time.perf_counter()
        jax.block_until_ready(mv(x, v))
        seconds = time.perf_counter() - t0
        err = np.max(np.abs(y[:REF_ROWS] - ref)) / np.max(np.abs(ref))
        print(f"  rbf_matvec impl={impl}: rel_err_vs_f64={err} "
              f"second_call_s={seconds}", flush=True)
        check(np.all(np.isfinite(y)), f"rbf_matvec impl={impl} not finite")
        check(err <= MATVEC_RTOL, f"rbf_matvec impl={impl} error {err}")


def newton_sequence(x, y, cfg, impl, mesh=None):
    from repro.core import SolveSpec
    from repro.gp import RBFKernel, laplace_gpc

    spec = SolveSpec(
        method="defcg", k=cfg.k, ell=cfg.ell, tol=cfg.tol,
        maxiter=cfg.maxiter,
    )
    res = laplace_gpc(
        x, y, RBFKernel(theta=cfg.theta, lengthscale=cfg.lengthscale),
        spec=spec, newton_tol=cfg.newton_tol, max_newton=cfg.max_newton,
        impl=impl, block=cfg.block, dense_matvec=False, mesh=mesh,
    )
    tr = res.trace
    solve_s = np.diff([0.0] + tr.cumulative_time).tolist()
    print(
        f"  impl={impl}: systems={len(tr.solver_iterations)} "
        f"iterations={tr.solver_iterations} matvecs={tr.solver_matvecs} "
        f"rungs={tr.solver_rungs} solve_s={solve_s} "
        f"converged={tr.solver_converged} "
        f"logp={res.logp} newton_converged={res.converged}",
        flush=True,
    )
    its = tr.solver_iterations
    check(all(tr.solver_converged), f"impl={impl}: a Newton system diverged")
    check(max(its) < cfg.maxiter, f"impl={impl}: hit maxiter {its}")
    # The recovery ladder must not have fired: its fallback attempt would
    # report a cold CG's iterations and hide the failed recycled one.
    check(all(r == 0 for r in tr.solver_rungs),
          f"impl={impl}: recovery ladder fired, rungs {tr.solver_rungs}")
    check(
        all(mv <= i + cfg.k + MATVEC_SLACK
            for i, mv in zip(its, tr.solver_matvecs)),
        f"impl={impl}: matvecs {tr.solver_matvecs} exceed iterations {its}",
    )
    check(len(its) >= 2, f"impl={impl}: only one Newton system")
    check(
        all(i < its[0] for i in its[1:]),
        f"impl={impl}: recycled systems not below system 1: {its}",
    )
    check(np.isfinite(res.logp), f"impl={impl}: log p(y|f) not finite")
    return res


def newton_system(x, y, f, cfg):
    """``laplace_gpc``'s Newton system at latent ``f`` as an operator:
    ``(A, b, bg)`` with ``A = I + H½KH½`` matrix-free."""
    from repro.core.operators import RBFKernelSystemOperator
    from repro.gp.laplace import newton_system as laplace_system

    k_mv = kernel_matvec(x, cfg)
    sqrt_h, b, bg = laplace_system(f, y, k_mv)
    a = RBFKernelSystemOperator(
        x, sqrt_h, cfg.theta, cfg.lengthscale, cfg.block
    )
    return a, b, bg


def newton_step(x, cfg, a, bg, sol):
    """The next latent ``f`` from the solution of :func:`newton_system`."""
    from repro.gp.laplace import newton_step as laplace_step

    return laplace_step(kernel_matvec(x, cfg), a.sqrt_h, bg, sol)[1]


def kernel_matvec(x, cfg):
    from repro.gp import RBFKernel

    return RBFKernel(theta=cfg.theta, lengthscale=cfg.lengthscale).matvec_fn(
        x, block=cfg.block
    )


def phase_serve(x, y, cfg):
    """Two tenants, each a GP classification client sending its first two
    Newton systems; tenant t1 classifies with 1 label in 8 flipped."""
    from repro.core import SolveSpec
    from repro.serve import SolveService

    spec = SolveSpec(
        method="defcg", k=cfg.k, ell=cfg.ell, tol=cfg.tol,
        maxiter=cfg.maxiter,
    )
    svc = SolveService(spec, slots=2)
    flip = jnp.where(jnp.arange(x.shape[0]) % 8 == 3, -1.0, 1.0)
    labels = {"t0": y, "t1": y * flip.astype(y.dtype)}
    sessions = {t: svc.session(t) for t in labels}
    latent = {t: jnp.zeros_like(y) for t in labels}
    iters = {t: [] for t in labels}
    for round_ in range(2):
        tickets, systems = {}, {}
        for t in labels:
            a, b, bg = systems[t] = newton_system(x, labels[t], latent[t], cfg)
            tickets[t] = sessions[t].submit(a, b)
        for t in labels:
            r = sessions[t].result(tickets[t])
            print(
                f"  tenant={t} ticket={round_} iterations={r.iterations} "
                f"matvecs={r.matvecs} rung={r.rung} converged={r.converged} "
                f"status={r.status} tick={r.tick}",
                flush=True,
            )
            check(r.ok, f"serve ticket {t}/{round_} did not converge")
            check(r.rung == 0,
                  f"serve ticket {t}/{round_}: recovery rung {r.rung}")
            check(r.matvecs <= r.iterations + cfg.k + MATVEC_SLACK,
                  f"serve ticket {t}/{round_}: {r.matvecs} matvecs for "
                  f"{r.iterations} iterations")
            check(bool(np.all(np.isfinite(np.asarray(r.x)))),
                  f"serve ticket {t}/{round_} not finite")
            iters[t].append(r.iterations)
            a, _, bg = systems[t]
            latent[t] = newton_step(x, cfg, a, bg, r.x)
    for t in labels:
        check(
            iters[t][1] <= iters[t][0],
            f"tenant {t}: second solve took more iterations {iters[t]}",
        )
        sessions[t].close()
    print(f"  serve metrics: {svc.metrics_snapshot()['pool']}", flush=True)


def run_one_chip(clock):
    from repro.configs.gpc_mnist import CONFIG

    cfg = CONFIG
    print(
        f"config={cfg.name} n={N_ONE_CHIP} (paper n={cfg.n}) d={cfg.d} "
        f"theta={cfg.theta} lengthscale={cfg.lengthscale} "
        f"defcg(k={cfg.k}, ell={cfg.ell}) tol={cfg.tol} "
        f"maxiter={cfg.maxiter} block={cfg.block} dtype=float32",
        flush=True,
    )
    with Phase("data", clock):
        x, y = digits(N_ONE_CHIP, SEED)
        check(x.shape == (N_ONE_CHIP, cfg.d), f"data shape {x.shape}")
    with Phase("kernel_check", clock):
        phase_kernel_check(x, cfg)
    with Phase("gp_newton_pallas", clock):
        main = newton_sequence(x, y, cfg, "auto")
    with Phase("gp_newton_chunked", clock):
        ref = newton_sequence(x, y, cfg, "chunked")
    rel = abs(main.logp - ref.logp) / abs(ref.logp)
    print(
        f"  logp pallas={main.logp} chunked={ref.logp} rel_diff={rel} "
        f"(bound {LOGP_RTOL})",
        flush=True,
    )
    check(rel <= LOGP_RTOL, f"log p(y|f) differs by {rel}")
    with Phase("serve", clock):
        phase_serve(x, y, cfg)


def run_mesh(clock, n_chips):
    from repro.configs.gpc_mnist import CONFIG as cfg
    from repro.core import SolveSpec, sharded
    from repro.core.operators import RBFKernelSystemOperator
    from repro.launch import hlo_stats
    from repro.launch.mesh import make_solve_mesh

    check(len(jax.devices()) >= n_chips,
          f"{n_chips} chips asked for, JAX sees {len(jax.devices())}")
    mesh = make_solve_mesh(n_chips)
    print(f"mesh phase: n={N_MESH} d={cfg.d} chips={n_chips} "
          f"defcg(k={cfg.k}, ell={cfg.ell}) tol={cfg.tol} dtype=float32",
          flush=True)
    with Phase("data", clock):
        x, y = digits(N_MESH, SEED)
    # The sharded engine has no recovery ladder: every system must
    # converge on its first attempt (newton_sequence's checks).
    with Phase("gp_newton_mesh", clock):
        res = newton_sequence(x, y, cfg, "auto", mesh=mesh)
    devs = {s.device for s in res.f.addressable_shards}
    print(f"  f: {res.f.sharding} on devices {sorted(d.id for d in devs)}",
          flush=True)
    check(len(devs) == n_chips, f"f lives on {len(devs)} devices")
    with Phase("hlo_collectives", clock):
        spec = SolveSpec(method="defcg", k=cfg.k, ell=cfg.ell, tol=cfg.tol,
                         maxiter=cfg.maxiter)
        a = RBFKernelSystemOperator(
            x, jnp.full_like(y, 0.5), cfg.theta, cfg.lengthscale, cfg.block
        )
        low = sharded.lower_sharded(a, y, spec, None, mesh=mesh)
        per_body = hlo_stats.while_body_collectives(low.compile().as_text())
    print(f"  while-body collectives: {per_body}", flush=True)
    # Loops without collectives (scans inside the matvec) are not Krylov
    # iterations; every loop that communicates must do one all-reduce.
    krylov = {body: c for body, c in per_body.items() if c}
    check(bool(krylov), "no communicating while loop in the sharded solve")
    for body, counts in krylov.items():
        check(counts.get("all-reduce", 0) == 1,
              f"{body}: {counts} — want one all-reduce per iteration")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    jax.config.update("jax_enable_x64", False)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}"
        )
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())} "
          f"jax={jax.__version__}", flush=True)
    clock = CompileClock()
    if args.chips == 1:
        run_one_chip(clock)
    else:
        run_mesh(clock, args.chips)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))


if __name__ == "__main__":
    main()
