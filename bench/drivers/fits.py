"""Whole Laplace GP-classification fits, back to back.

Each fit is one call of the program's front door,
``repro.gp.laplace_gpc(x, y, RBFKernel(theta, lengthscale), spec=SolveSpec(
"defcg", k, ell, tol, maxiter), impl, block, dense_matvec=False)``: Newton
from ``f = 0`` with def-CG and harmonic-Ritz recycling across its Newton
systems, each system's operator ``RBFKernelSystemOperator`` over the fused
Gram kernel.  No state is carried from one fit to the next.

The data set is the configuration's (``data_seed``).  The traffic's
``row_orders`` orders of its rows, also drawn from ``data_seed``, are
fitted in turn; the run's seed picks the order the window starts from.
A row order moves def-CG by a few iterations, so every seed runs the same
cycle of work and a window's length stays the same from seed to seed.

The window runs fits until one ends at or after ``--seconds``; it counts
whole fits only, and its length is that of the fits it ran.

The program's ``newton_system``, ``solve_jit`` and ``newton_step`` are
wrapped, from here, in benchmark spans that also keep what each returned,
for the comparison with the reference after the window.  A sample of
the window's fits (reservoir sampling from the seed) is kept.
"""

from __future__ import annotations

import time

import numpy as np

from bench import data, harness
from bench.reference import RowReference

SYSTEM_KEYS = ("sqrt_h", "b", "bg", "x", "a", "f_next")


class Fits:
    def __init__(self, config: dict, traffic: dict, seed: int, impl: str = "auto"):
        harness.use_program()
        from repro.core import SolveSpec
        from repro.gp import RBFKernel
        from repro.gp import laplace

        self.cfg, self.traffic, self.impl = config, traffic, impl
        self.laplace = laplace
        self.kernel = RBFKernel(config["theta"], config["lengthscale"])
        self.spec = SolveSpec(
            method=config["method"], k=config["k"], ell=config["ell"],
            tol=config["tol"], maxiter=config["maxiter"],
        )
        x, y = data.digits(config["n"], config["data_seed"], config["pixels"])
        self.orders = [
            data.shuffled(x, y, [config["data_seed"], r])[:2]
            for r in range(traffic["row_orders"])
        ]
        del x, y
        self.rng = np.random.default_rng([seed, 1])
        self.next_order = int(self.rng.integers(len(self.orders)))
        self.kept = harness.Reservoir(traffic["check_fits"], self.rng)
        self.fits: list = []
        self._systems: list = []
        self._orig = {
            name: getattr(laplace, name)
            for name in ("newton_system", "solve_jit", "newton_step")
        }

    # -- the program, wrapped in spans that keep what it returned --------
    def _newton_system(self, f, y, k_mv):
        with harness.span("newton_system"):
            out = self._orig["newton_system"](f, y, k_mv)
        self._systems.append({"sqrt_h": out[0], "b": out[1], "bg": out[2]})
        return out

    def _solve(self, *args, **kwargs):
        with harness.span("solve"):
            res = self._orig["solve_jit"](*args, **kwargs)
        if self._systems:
            self._systems[-1]["x"] = res.x
        return res

    def _newton_step(self, k_mv, sqrt_h, bg, sol):
        with harness.span("newton_step"):
            a, f = self._orig["newton_step"](k_mv, sqrt_h, bg, sol)
        if self._systems:
            self._systems[-1].update(a=a, f_next=f)
        return a, f

    def _fit(self, max_newton: int):
        """One fit on the next row order; returns ``(order, result)``."""
        lp = self.laplace
        order = self.next_order
        self.next_order = (order + 1) % len(self.orders)
        x, y = self.orders[order]
        self._systems = []
        with harness.patched(
            lp, newton_system=self._newton_system, solve_jit=self._solve,
            newton_step=self._newton_step,
        ):
            res = lp.laplace_gpc(
                x, y, self.kernel, spec=self.spec,
                newton_tol=self.cfg["newton_tol"], max_newton=max_newton,
                impl=self.impl, block=self.cfg["block"], dense_matvec=False,
            )
        if len(self._systems) != len(res.trace.solver_iterations) or any(
            set(SYSTEM_KEYS) - set(s) for s in self._systems
        ):
            raise RuntimeError(
                "laplace_gpc no longer calls newton_system, solve_jit and "
                "newton_step once per Newton system: the benchmark cannot "
                "see what each system returned"
            )
        return order, res

    # -- set-up, window, check --------------------------------------------
    def warm_up(self) -> None:
        """One short fit: a cold system, then a warm one."""
        self._fit(self.traffic["warmup_newton"])

    def window(self, seconds: float) -> dict:
        t_start = time.perf_counter()
        while True:
            with harness.span("fit"):
                t0 = time.perf_counter()
                order, res = self._fit(self.cfg["max_newton"])
                t1 = time.perf_counter()
            tr = res.trace
            self.fits.append({
                "order": order,
                "wall_s": t1 - t0,
                "solve_s": float(np.diff([0.0] + tr.cumulative_time).sum()),
                "iterations": list(tr.solver_iterations),
                "matvecs": list(tr.solver_matvecs),
                "rungs": list(tr.solver_rungs),
                "converged": list(tr.solver_converged),
                "logp": res.logp,
            })
            self.kept.add((order, self._systems))
            if t1 - t_start >= seconds:
                break
        per_fit = [sum(f["matvecs"]) for f in self.fits]
        print(f"window: {len(self.fits)} fits, matvecs per fit {per_fit}",
              flush=True)
        return {"window_s": t1 - t_start, "fits": self.fits}

    def host_records(self):
        """The kept fits on the host; the device copies are dropped."""
        import jax

        kept = jax.device_get(self.kept.items)
        wanted = sorted({order for order, _ in kept})
        xs = {r: np.asarray(self.orders[r][0]) for r in wanted}
        self.kept, self.orders = None, None
        return xs, kept

    def check(self, host) -> dict:
        """The reference's readings over every kept fit's systems, and the
        window's systems that were not solved."""
        xs, kept = host
        n = self.cfg["n"]
        rows = self.rng.choice(n, min(n, self.traffic["check_rows"]), replace=False)
        refs = {
            r: RowReference(x, self.cfg["theta"], self.cfg["lengthscale"], rows)
            for r, x in xs.items()
        }
        gram, gap = [], []
        for order, systems in kept:
            for s in systems:
                g, r = refs[order].check_system(**s, tol=self.cfg["tol"])
                gram += g
                gap.append(r)
        _, unsolved = attempted_failed({"fits": self.fits})
        return {"gram_err": max(gram), "solve_gap": max(gap),
                "unsolved": float(unsolved)}


def attempted_failed(record: dict):
    """Newton systems run, and those that did not converge or ended above
    recovery rung 0."""
    attempted = failed = 0
    for fit in record["fits"]:
        for conv, rung in zip(fit["converged"], fit["rungs"]):
            attempted += 1
            failed += int((not conv) or rung > 0)
    return attempted, failed


def end_to_end(record: dict) -> dict:
    return {"fit_s": record["window_s"] / len(record["fits"])}


def make(config, traffic, seed, impl="auto"):
    return Fits(config, traffic, seed, impl)
