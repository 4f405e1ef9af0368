"""``correct`` comes out false for the control and for each fault the
cells can have, and true for the sound path.

Each case drives a whole run (``bench/run.py``'s ``run_cell``: set-up,
window, reference check, limits) on the CPU at a small size, skipping only
the look for a chip; the program runs its plain-XLA kernels.  The faults
are planted underneath the timed path:

* ``state_unchanged``: the solve returns the zero vector and, honestly,
  the residual norm ``|b|`` of that answer, as a step that leaves its
  state as it was;
* ``half_batch``: the Gram product sums over half of the points and
  doubles the result, the mean taken over the rest;
* ``answer_altered``: the solve's answer is changed by 1e-3 where it is
  produced;
* ``answers_swapped`` (serve): the pool step hands each slot's answer to
  the next slot;
* ``unconverged``: the solver's iteration cap is cut to 3, so the Newton
  systems end unconverged.

There is no exchange between chips in a one-chip cell.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from bench import control, flops, harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

N = 1024
SEED = 2**31 + 101


def run(workload, impl="chunked", config=None, **traffic):
    cell = harness.Cell(workload)
    cell.config = dict(cell.config, n=N, **(config or {}))
    cell.traffic = dict(cell.traffic, **traffic)
    result, compared = bench_run.run_cell(
        cell, SEED, 0.0, False, jax.devices()[:1], impl=impl,
        peak=flops.peaks("TPU v5 lite"),
    )
    return result["correct"], {r["name"]: r["value"] for r in compared}


def over_limit(workload, readings):
    """The numbers that read above their limit."""
    limits = harness.Cell(workload).limits
    return {k for k, v in readings.items() if v > limits[k]["limit"]}


def test_sound_fit_is_correct():
    correct, readings = run("gpc-mnist.fit")
    assert correct, readings


def test_sound_serve_is_correct():
    correct, readings = run("gpc-usps.serve", tenants=3, slots=3)
    assert correct, readings


def test_control_is_not_correct():
    with control.control_in_place():
        correct, readings = run("gpc-mnist.fit", impl=control.CONTROL)
    assert not correct, readings


def _fault(name):
    from repro.gp import laplace
    from repro.kernels import ops

    solve = laplace.solve_jit
    gram = ops.rbf_matvec

    def unchanged(op, b, *args, **kwargs):
        res = solve(op, b, *args, **kwargs)
        info = res.info._replace(residual_norm=jnp.linalg.norm(b))
        return res._replace(x=jnp.zeros_like(res.x), info=info)

    def altered(*args, **kwargs):
        res = solve(*args, **kwargs)
        return res._replace(x=res.x * (1.0 + 1e-3))

    def half(x, v, theta, lengthscale, **kw):
        keep = (jnp.arange(x.shape[0]) % 2 == 0).astype(v.dtype)
        keep = keep if v.ndim == 1 else keep[:, None]
        return gram(x, 2.0 * keep * v, theta, lengthscale, **kw)

    if name == "half_batch":
        return harness.patched(ops, rbf_matvec=half)
    return harness.patched(
        laplace, solve_jit=unchanged if name == "state_unchanged" else altered
    )


@pytest.mark.parametrize("fault, caught_by", [
    ("state_unchanged", "solve_gap"),
    ("half_batch", "gram_err"),
    ("answer_altered", "solve_gap"),
])
def test_fault_in_fit_is_not_correct(fault, caught_by):
    harness.use_program()
    with _fault(fault):
        correct, readings = run("gpc-usps.fit")
    assert not correct, readings
    assert caught_by in over_limit("gpc-usps.fit", readings), readings


def test_unconverged_systems_are_not_correct():
    correct, readings = run("gpc-usps.fit", config={"maxiter": 3})
    assert not correct, readings
    assert readings["unsolved"] > 0, readings


def test_answers_swapped_in_serve_is_not_correct():
    harness.use_program()
    from repro.serve import scheduler

    step = scheduler.solve_pool_step_jit

    def swapped(*args, **kwargs):
        res = step(*args, **kwargs)
        return res._replace(x=jnp.roll(res.x, 1, axis=0))

    with harness.patched(scheduler, solve_pool_step_jit=swapped):
        correct, readings = run("gpc-usps.serve", tenants=3, slots=3)
    assert not correct, readings
