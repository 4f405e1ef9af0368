"""The Newton driver of ``laplace_gpc``: its compiled phases and reads.

``newton_system`` and ``newton_step`` are jitted over a pytree Gram
matvec whose data are leaves, so one executable serves every data set of
a shape and a second fit compiles nothing.  All on the CPU with the
``chunked`` kernel at small n.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SolveSpec
from repro.gp import RBFKernel, laplace_gpc
from repro.gp.kernels import DenseMatvec, GramMatvec
from repro.gp.laplace import newton_step, newton_system
from repro.runtime import spans

N, D = 40, 3
KERNEL = RBFKernel(1.3, 1.7)
SPEC = SolveSpec("defcg", k=4, ell=6, tol=1e-8, maxiter=200)


def _data(seed, dtype=jnp.float64):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((N, D)), dtype)
    y = jnp.asarray(np.where(rng.standard_normal(N) > 0, 1.0, -1.0), dtype)
    f = jnp.asarray(0.5 * rng.standard_normal(N), dtype)
    return x, y, f


def _since(mark):
    """The ring's records after the span ``mark``."""
    recs = spans.recent()
    return recs[[r.id for r in recs].index(mark.id) + 1:]


def _eager(x, y, f, sol):
    """The Newton system and step written out over a dense K in float64."""
    x, y, f, sol = (np.asarray(v, np.float64) for v in (x, y, f, sol))
    k = np.asarray(RBFKernel(KERNEL.theta, KERNEL.lengthscale).gram(
        jnp.asarray(x)))
    pi = 1.0 / (1.0 + np.exp(-f))
    hdiag = pi * (1.0 - pi)
    sqrt_h = np.sqrt(hdiag)
    bg = hdiag * f + (y + 1.0) / 2.0 - pi
    a = bg - sqrt_h * sol
    return sqrt_h, sqrt_h * (k @ bg), bg, a, k @ a


def test_jitted_phases_match_eager_formulas():
    x, y, f = _data(0, jnp.float32)
    k_mv = KERNEL.matvec_fn(x, impl="chunked", block=16)
    assert isinstance(k_mv, GramMatvec)
    sqrt_h, b, bg = newton_system(f, y, k_mv)
    sol = jnp.asarray(np.random.default_rng(1).standard_normal(N), jnp.float32)
    a, f_next = newton_step(k_mv, sqrt_h, bg, sol)
    want = _eager(x, y, f, sol)
    for got, ref in zip((sqrt_h, b, bg, a, f_next), want):
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5, atol=2e-5)


def test_data_are_arguments_of_one_executable():
    """Two data sets of one shape share an executable and give their own
    results: ``x`` is a leaf of the matvec, not a baked constant."""
    (x1, y1, f1), (x2, y2, f2) = _data(10), _data(11)
    mv1 = KERNEL.matvec_fn(x1, impl="chunked", block=16)
    mv2 = KERNEL.matvec_fn(x2, impl="chunked", block=16)
    assert jax.tree_util.tree_leaves(mv1)[0] is x1
    out1 = newton_system(f1, y1, mv1)
    before = newton_system._cache_size()
    out2 = newton_system(f1, y1, mv2)
    assert newton_system._cache_size() == before
    assert not np.allclose(np.asarray(out1[1]), np.asarray(out2[1]))
    np.testing.assert_allclose(
        np.asarray(out2[1]), _eager(x2, y1, f1, f1)[1], rtol=1e-10, atol=1e-12
    )
    a1, g1 = newton_step(mv1, *out1[::2], f2)
    before = newton_step._cache_size()
    a2, g2 = newton_step(mv2, *out1[::2], f2)
    assert newton_step._cache_size() == before
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    assert not np.allclose(np.asarray(g1), np.asarray(g2))
    # Nothing of the data set is a constant of the traced program.
    jaxpr = jax.make_jaxpr(newton_system.__wrapped__)(f2, y2, mv2)
    assert not any(np.shape(c) == (N, D) for c in jaxpr.consts)


def test_second_fit_compiles_nothing():
    with spans.span("test.mark") as mark:
        pass
    runs = []
    for seed in (20, 21):
        x, y, _ = _data(seed)
        runs.append(laplace_gpc(x, y, KERNEL, spec=SPEC, impl="chunked",
                                max_newton=4))
    recs = _since(mark)
    fits = [r for r in recs if r.name == "laplace.fit"]
    assert len(fits) == 2
    assert all(len(r.trace.psi) >= 2 for r in runs)
    second = fits[1]
    inside = [r for r in recs if second.start_ns <= r.start_ns
              and r.end_ns <= second.end_ns]
    assert {r.name for r in inside} >= {
        "laplace.system", "laplace.newton_system", "laplace.solve",
        "laplace.newton_step"}
    assert sum(r.attrs.get("compiles", 0) for r in inside) == 0
    assert runs[0].logp != runs[1].logp


@pytest.mark.parametrize("other", ["dense_matvec", "cholesky"])
def test_dense_and_cholesky_paths_agree_with_spec_path(other):
    x, y, _ = _data(30)
    spec_run = laplace_gpc(x, y, KERNEL, spec=SPEC, impl="chunked",
                           newton_tol=1e-6)
    if other == "dense_matvec":
        with spans.span("test.mark") as mark:
            pass
        res = laplace_gpc(x, y, KERNEL, spec=SPEC, impl="chunked",
                          newton_tol=1e-6, dense_matvec=True)
        recs = _since(mark)
        (fit,) = [r for r in recs if r.name == "laplace.fit"]
        assert fit.attrs["syncs"] == 2 * fit.attrs["systems"]
        assert all(c > 0 for c in res.trace.solver_iterations)
    else:
        res = laplace_gpc(x, y, KERNEL, spec=None, newton_tol=1e-6)
    assert res.converged and spec_run.converged
    assert res.logp == pytest.approx(spec_run.logp, rel=1e-8)
    assert res.psi == pytest.approx(spec_run.psi, rel=1e-8)
    np.testing.assert_allclose(
        np.asarray(res.f), np.asarray(spec_run.f), rtol=0, atol=1e-6
    )


def test_dense_matvec_is_a_pytree_callable():
    x, _, _ = _data(40)
    k = KERNEL.gram(x)
    mv = DenseMatvec(k)
    (leaf,) = jax.tree_util.tree_leaves(mv)
    assert leaf is k
    v = jnp.arange(N, dtype=k.dtype)
    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda m, v: m(v))(mv, v)), np.asarray(k @ v),
        rtol=1e-12,
    )
    np.testing.assert_allclose(
        np.asarray(mv(v)),
        np.asarray(KERNEL.matvec_fn(x, impl="chunked", block=16)(v)),
        rtol=1e-10,
    )
