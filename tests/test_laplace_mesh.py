"""Laplace GP classification over a solve mesh: ``laplace_gpc(mesh=)``.

The rows of the data, the latent ``f`` and the recycled basis are split
over 4 of the 8 forced host devices; each Newton system is solved by the
sharded def-CG and the driver's two Gram passes a system run under
``shard_map``.  Checked in float64 with the ``chunked`` kernel at
n = 512, d = 16 against the dense Cholesky Newton and the one-device
front door.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import SolveSpec
from repro.gp import RBFKernel, laplace, laplace_gpc
from repro.launch.mesh import make_solve_mesh
from repro.runtime import spans

N, D, SHARDS = 512, 16, 4
KERNEL = RBFKernel(2.0, 2.0)
SPEC = SolveSpec(method="defcg", k=8, ell=12, tol=1e-10, maxiter=500)
NEWTON_TOL = 1e-4
# Each Newton system is solved to a relative residual SPEC.tol, and
# A = I + H½KH½ has no eigenvalue below 1, so two solutions of one system
# differ by at most 2·tol·‖b‖.  The next latent f = K(bg − H½x) carries
# that through ‖K‖·‖H½‖ ≤ n·θ²·½, and near the mode Newton's steps
# shrink an error in f rather than grow it: f̂ agrees to n·θ²·tol
# relative (2e-7 here).
F_RTOL = N * KERNEL.theta**2 * SPEC.tol


@pytest.fixture(scope="module")
def mesh():
    return make_solve_mesh(SHARDS)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((N, D))
    y = np.sign(x @ rng.standard_normal(D) + 0.5 * rng.standard_normal(N))
    return jnp.asarray(x), jnp.asarray(y)


def _fit(x, y, **kw):
    kw.setdefault("spec", SPEC)
    return laplace_gpc(x, y, KERNEL, newton_tol=NEWTON_TOL, impl="chunked",
                       block=64, **kw)


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _since(mark):
    recs = spans.recent()
    return recs[[r.id for r in recs].index(mark.id) + 1:]


def test_mesh_fit_matches_dense_cholesky_newton(data, mesh):
    x, y = data
    got = _fit(x, y, mesh=mesh)
    want = laplace_gpc(x, y, KERNEL, spec=None, newton_tol=NEWTON_TOL)
    assert len(got.trace.psi) == len(want.trace.psi) >= 3
    assert _rel(got.f, want.f) <= F_RTOL
    # Ψ is stationary at the mode, so an f̂ error of F_RTOL moves it by a
    # second-order amount; 1e-9 relative leaves room for float64 sums.
    assert got.psi == pytest.approx(want.psi, rel=1e-9)
    assert got.converged and want.converged


def test_mesh_fit_matches_one_device_front_door(data, mesh):
    x, y = data
    got = _fit(x, y, mesh=mesh)
    want = _fit(x, y)
    # The sharded stopping test rides a one-step ‖r‖² recurrence, which
    # may cross the threshold one iteration before or after the
    # one-device fresh reduction (test_sharded_engine pins the same).
    its_got, its_want = got.trace.solver_iterations, want.trace.solver_iterations
    assert len(its_got) == len(its_want) >= 3
    assert all(abs(a - b) <= 1 for a, b in zip(its_got, its_want))
    # The basis is carried: every warm system beats the cold first one.
    assert max(its_got[1:]) < its_got[0]
    assert all(got.trace.solver_converged) and got.trace.solver_rungs == [
        0] * len(its_got)
    assert _rel(got.f, want.f) <= F_RTOL
    devices = {s.device for s in got.f.addressable_shards}
    assert len(devices) == SHARDS


@pytest.mark.parametrize("r", [1, 8])
def test_sharded_gram_matvec_matches_unsharded(data, mesh, r):
    x, _ = data
    v = jnp.asarray(np.random.default_rng(r).standard_normal((N, r)))
    v = v[:, 0] if r == 1 else v
    want = KERNEL.matvec_fn(x, impl="chunked", block=64)(v)
    rows = P("solve") if r == 1 else P("solve", None)
    xs = jax.device_put(x, NamedSharding(mesh, P("solve", None)))
    vs = jax.device_put(v, NamedSharding(mesh, rows))
    got = jax.jit(lambda mv, u: mv(u))(
        KERNEL.matvec_fn(xs, impl="chunked", block=64, mesh=mesh), vs)
    assert got.shape == want.shape
    assert got.sharding.is_equivalent_to(NamedSharding(mesh, rows), v.ndim)
    # Each row's sum runs over the same columns in the same order; only
    # where the rows live differs, so float64 rounding alone.
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_mesh_none_traces_the_one_device_programs(data, mesh):
    """``mesh=None`` keeps the one-device Gram pass: no ``shard_map`` and
    no collective enters the driver's programs; the mesh path has both."""
    x, y = data
    f = jnp.zeros(N)
    plain = str(jax.make_jaxpr(laplace.newton_system)(
        f, y, KERNEL.matvec_fn(x, impl="chunked", block=64)))
    assert "shard_map" not in plain and "all_gather" not in plain
    xs, ys, _ = laplace.place(mesh, x, y)
    split = str(jax.make_jaxpr(laplace.newton_system)(
        f, ys, KERNEL.matvec_fn(xs, impl="chunked", block=64, mesh=mesh)))
    assert "shard_map" in split and "all_gather" in split


def test_mesh_fit_spans_and_second_fit(data, mesh):
    x, y = data
    with spans.span("test.mark") as mark:
        pass
    first = _fit(x, y, mesh=mesh)
    xs, ys, moved = laplace.place(mesh, x, y)
    assert moved == x.nbytes + y.nbytes
    second = _fit(xs, ys, mesh=mesh)
    recs = _since(mark)
    fits = [r for r in recs if r.name == "laplace.fit"]
    places = [r for r in recs if r.name == "laplace.place"]
    assert [f.attrs["shards"] for f in fits] == [SHARDS, SHARDS]
    assert [p.parent for p in places] == [f.id for f in fits]
    assert places[0].attrs["moved_bytes"] == x.nbytes + y.nbytes
    assert places[1].attrs["moved_bytes"] == 0
    # The documented count: 2 reads a system, and one more in the first
    # fit, whose place waited for the data it moved.
    systems = [len(r.trace.psi) for r in (first, second)]
    assert fits[0].attrs["syncs"] == 2 * systems[0] + 1
    assert fits[1].attrs["syncs"] == 2 * systems[1]
    inside = [r for r in recs if fits[1].start_ns <= r.start_ns
              and r.end_ns <= fits[1].end_ns]
    assert sum(r.attrs.get("compiles", 0) for r in inside) == 0
    assert second.trace.solver_iterations == first.trace.solver_iterations
    with spans.span("test.mark") as mark:
        pass
    _fit(x, y)
    recs = _since(mark)
    (one_device,) = [r for r in recs if r.name == "laplace.fit"]
    assert one_device.attrs["shards"] == 1
    assert not [r for r in recs if r.name == "laplace.place"]


def test_mesh_needs_the_spec_path(data, mesh):
    x, y = data
    with pytest.raises(ValueError, match="mesh="):
        laplace_gpc(x, y, KERNEL, spec=None, mesh=mesh)
    with pytest.raises(ValueError, match="mesh="):
        laplace_gpc(x, y, KERNEL, spec=SPEC, dense_matvec=True, mesh=mesh)
