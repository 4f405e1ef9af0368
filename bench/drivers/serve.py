"""Tenants in a closed loop on the multi-tenant solve service.

``tenants`` GP-classification clients share one ``repro.serve.SolveService``
of ``slots`` slots.  Each has its own data set, drawn from the
configuration's ``data_seed`` and its index and put in an order drawn from
the run's seed and its index, and runs Laplace fits back to back through the service: it builds
its Newton system with the program's ``newton_system``, submits it with
``Session.submit``, and once the service has served it, redeems the ticket
with ``Session.result``, takes the Newton step (``newton_step``) and
submits the next system.  A fit ends as ``laplace_gpc``'s does (the change
of Psi under ``newton_tol``, or ``max_newton`` systems); the client then
flips ``1/flip_every`` of its labels (drawn from the ``data_seed``, so
every seed flips the same points) and starts the next fit from ``f = 0``,
keeping its slot's recycled state.

The loop ticks the service (``SolveService.tick``) whenever every client
has submitted, so each tick serves every tenant with work.  The window
submits no system after ``--seconds``: it ends with the tick that serves
the last ones, and every system it submitted is served inside it.
Latency is submit to redeem on the host clock.
"""

from __future__ import annotations

import time

import numpy as np

from bench import data, harness
from bench.reference import RowReference


class Tenant:
    def __init__(self, index, x, y, perm, session, k_mv, data_seed):
        self.index, self.x, self.y = index, x, y
        self.position = np.argsort(perm)  # original row -> its row here
        self.flip_rng = np.random.default_rng([data_seed, index, 2])
        self.session, self.k_mv = session, k_mv
        self.f = None
        self.psi_prev = -np.inf
        self.systems = 0
        self.fits = 0
        self.pending = None


class Serve:
    def __init__(self, config: dict, traffic: dict, seed: int, impl: str = "auto"):
        harness.use_program()
        import jax.numpy as jnp
        from repro.core import SolveSpec
        from repro.core import pytree as pt
        from repro.core.operators import RBFKernelSystemOperator
        from repro.gp import RBFKernel
        from repro.gp import laplace
        from repro.serve import SolveService

        self.cfg, self.traffic, self.impl = config, traffic, impl
        self.jnp, self.pt, self.laplace = jnp, pt, laplace
        self.operator = RBFKernelSystemOperator
        spec = SolveSpec(
            method=config["method"], k=config["k"], ell=config["ell"],
            tol=config["tol"], maxiter=config["maxiter"],
        )
        self.service = SolveService(spec, slots=traffic["slots"])
        kernel = RBFKernel(config["theta"], config["lengthscale"])
        self.tenants = []
        for i in range(traffic["tenants"]):
            x, y, perm = data.shuffled(
                *data.digits(config["n"], [config["data_seed"], i], config["pixels"]),
                [seed, i],
            )
            self.tenants.append(Tenant(
                i, x, y, perm, self.service.session(f"t{i}"),
                kernel.matvec_fn(x, impl=impl, block=config["block"]),
                config["data_seed"],
            ))
        self.rng = np.random.default_rng([seed, 3])
        self.kept = harness.Reservoir(traffic["check_tickets"], self.rng)
        self.tickets: list = []
        self.recording = False

    # -- one client ---------------------------------------------------------
    def _submit(self, t: Tenant) -> None:
        with harness.span("client"):
            if t.f is None:
                t.f = self.jnp.zeros_like(t.y)
            sqrt_h, b, bg = self.laplace.newton_system(t.f, t.y, t.k_mv)
            op = self.operator(
                t.x, sqrt_h, self.cfg["theta"], self.cfg["lengthscale"],
                self.cfg["block"], self.impl,
            )
            ticket = t.session.submit(op, b)
        t.pending = {
            "ticket": ticket, "t_submit": time.perf_counter(),
            "sqrt_h": sqrt_h, "b": b, "bg": bg,
        }

    def _redeem(self, t: Tenant, now: float) -> None:
        p, t.pending = t.pending, None
        with harness.span("client"):
            r = t.session.result(p["ticket"], drive=False)
            a, f = self.laplace.newton_step(t.k_mv, p["sqrt_h"], p["bg"], r.x)
            logp, _, _ = self.laplace.logistic_quantities(f, t.y)
            psi = float(logp - 0.5 * self.pt.vdot(a, f))
        t.systems += 1
        t.f = f
        if self.recording:
            ticket = p["ticket"]
            self.tickets.append({
                "tenant": t.index, "seq": ticket.seq, "tick": r.tick,
                "latency_s": now - p["t_submit"],
                "iterations": r.iterations, "matvecs": r.matvecs,
                "rung": r.rung, "converged": r.converged,
                "misrouted": int(r.tenant != ticket.tenant or r.seq != ticket.seq),
            })
            self.kept.add({
                "tenant": t.index, "sqrt_h": p["sqrt_h"], "b": p["b"],
                "bg": p["bg"], "x": r.x, "a": a, "f_next": f,
            })
        if abs(psi - t.psi_prev) < self.cfg["newton_tol"] or (
            t.systems >= self.cfg["max_newton"]
        ):
            self._next_fit(t)
        else:
            t.psi_prev = psi

    def _next_fit(self, t: Tenant) -> None:
        n = t.y.shape[0]
        flip = t.flip_rng.choice(n, n // self.traffic["flip_every"], replace=False)
        t.y = t.y.at[self.jnp.asarray(t.position[flip])].multiply(-1.0)
        t.f, t.psi_prev, t.systems = None, -np.inf, 0
        t.fits += 1

    def _loop(self, done) -> float:
        """Tick and redeem until ``done(now)``; returns the end time."""
        for t in self.tenants:
            self._submit(t)
        while True:
            with harness.span("tick"):
                self.service.tick()
            now = time.perf_counter()
            stop = done(now)
            for t in self.tenants:
                if t.pending and self.service.poll(t.pending["ticket"]):
                    self._redeem(t, now)
                    if not stop:
                        self._submit(t)
            if stop and not any(t.pending for t in self.tenants):
                return time.perf_counter()

    # -- set-up, window, check --------------------------------------------
    def warm_up(self) -> None:
        """Every tenant through one whole fit, so the window starts from
        carried state."""
        self._loop(lambda now: all(t.fits >= 1 for t in self.tenants))

    def window(self, seconds: float) -> dict:
        self.recording = True
        t0 = time.perf_counter()
        t1 = self._loop(lambda now: now - t0 >= seconds)
        self.recording = False
        print(f"window: {len(self.tickets)} systems, iterations "
              f"{sum(t['iterations'] for t in self.tickets)}", flush=True)
        return {
            "window_s": t1 - t0, "slots": self.traffic["slots"],
            "tickets": self.tickets,
        }

    def host_records(self):
        import jax

        kept = jax.device_get(self.kept.items)
        wanted = sorted({r["tenant"] for r in kept})
        xs = {i: np.asarray(self.tenants[i].x) for i in wanted}
        self.kept, self.tenants = None, []
        self.service = None
        return xs, kept

    def check(self, host) -> dict:
        xs, kept = host
        n = self.cfg["n"]
        misrouted = sum(t["misrouted"] for t in self.tickets)
        gram, gap = [], []
        refs = {}
        for rec in kept:
            i = rec.pop("tenant")
            if i not in refs:
                rows = self.rng.choice(
                    n, min(n, self.traffic["check_rows"]), replace=False
                )
                refs[i] = RowReference(
                    xs[i], self.cfg["theta"], self.cfg["lengthscale"], rows
                )
            g, r = refs[i].check_system(**rec, tol=self.cfg["tol"])
            gram += g
            gap.append(r)
        _, unsolved = attempted_failed({"tickets": self.tickets})
        return {
            "gram_err": max(gram), "solve_gap": max(gap),
            "misrouted": float(misrouted), "unsolved": float(unsolved),
        }


def attempted_failed(record: dict):
    tickets = record["tickets"]
    failed = sum(int((not t["converged"]) or t["rung"] > 0) for t in tickets)
    return len(tickets), failed


def end_to_end(record: dict) -> dict:
    lat = [t["latency_s"] for t in record["tickets"]]
    return {
        "serve_solves_per_s": len(lat) / record["window_s"],
        "serve_p95_s": float(np.percentile(lat, 95)),
    }


def make(config, traffic, seed, impl="auto"):
    return Serve(config, traffic, seed, impl)
