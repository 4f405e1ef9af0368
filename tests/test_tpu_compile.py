"""Compile the main path for a described TPU v5e chip, without a chip.

The TPU compiler is installed alongside JAX and compiles for a topology
that is described rather than attached.  This catches what interpret mode
cannot: kernels Mosaic refuses (int64 block indices under x64, 64-bit
refs), tiles that overflow VMEM, and a solve whose Pallas dispatch does
not reach the kernels.  Nothing runs, so these tests say nothing about
results or times.

The topology is described inside a module-scoped fixture and never at
import: only one process may load the TPU library, and the fixture lets
every pytest worker collect the same tests while only the worker that
runs this file loads it.  All such compiles live in this one file.

Shapes are the chip smoke run's: n = 32768 points of d = 784 features,
def-CG(k=8, ell=12), float32; the four-chip fit's programs compile at
n = 65536 over a described 2x2 mesh.
"""

import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs.gpc_mnist import CONFIG as GPC
from repro.core import api as api_mod
from repro.core.api import SolveSpec, solve, solve_pool_step
from repro.core.operators import RBFKernelSystemOperator
from repro.core.recycle import RecycleState
from repro.gp import laplace
from repro.gp.kernels import GramMatvec
from repro.kernels import ops as kops
from repro.kernels.rbf_matvec import R_VPU, SYM_OUT_BYTES

# The benchmark's pass count, read from the kernel's output shape.
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.readers import passes  # noqa: E402

N, D, K, ELL = 32768, GPC.d, GPC.k, GPC.ell


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def auto_is_pallas(monkeypatch):
    """Steer ``impl="auto"`` to the Pallas kernels, as on a TPU backend."""
    real = kops._resolve
    monkeypatch.setattr(
        kops, "_resolve",
        lambda impl, op, *a: real("pallas" if impl == "auto" else impl, op, *a),
    )


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# name -> (fn, shapes); every fn goes through the ops dispatch on "pallas".
_P = dict(impl="pallas")
KERNELS = {
    "rbf_matvec": (
        lambda x, v: kops.rbf_matvec(x, v, 3.0, 3.0, block=GPC.block, **_P),
        [(N, D), (N,)],
    ),
    "rbf_matvec_k": (
        lambda x, v: kops.rbf_matvec(x, v, 3.0, 3.0, block=GPC.block, **_P),
        [(N, D), (N, K)],
    ),
    "rbf_matvec_rect": (
        lambda xr, xc, v: kops.rbf_matvec_rect(
            xr, xc, v, 3.0, 3.0, block=GPC.block, **_P
        ),
        [(N // 4, D), (N, D), (N,)],
    ),
    "fused_cg_update": (
        lambda x, r, p, ap, a: kops.fused_cg_update(x, r, p, ap, a, **_P),
        [(N,), (N,), (N,), (N,), ()],
    ),
    "fused_cg_update_aw": (
        lambda x, r, p, ap, a, aw: kops.fused_cg_update(
            x, r, p, ap, a, aw, **_P
        ),
        [(N,), (N,), (N,), (N,), (), (K, N)],
    ),
    "fused_rz_reduce": (
        lambda r, z, aw: kops.fused_rz_reduce(r, z, aw, **_P),
        [(N,), (N,), (K, N)],
    ),
    "fused_deflate_direction": (
        lambda r, p, b, w, mu: kops.fused_deflate_direction(
            r, p, b, w, mu, **_P
        ),
        [(N,), (N,), (), (K, N), (K,)],
    ),
    "self_gram": (
        lambda s: kops.self_gram(s, **_P),
        [(2 * (K + ELL), N)],
    ),
    "recombine_blocks": (
        lambda s, u: kops.recombine_blocks(s, u, **_P),
        [(2 * (K + ELL), N), (K + ELL, K)],
    ),
    "lsmr_update": (
        lambda x, hb, h, v, c0, c1, c2: kops.lsmr_update(
            x, hb, h, v, c0, c1, c2, **_P
        ),
        [(N,), (N,), (N,), (N,), (), (), ()],
    ),
}


@pytest.mark.parametrize("x64", [False, True], ids=["x64_off", "x64_on"])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name, x64):
    fn, shapes = KERNELS[name]
    with jax.enable_x64(x64):
        hlo = _compile_text(fn, *(_f32(one_chip, *s) for s in shapes))
    assert "tpu_custom_call" in hlo


def test_paper_config_block_is_largest_that_fits_vmem(one_chip):
    """``GPC.block`` compiles; twice that overflows v5e VMEM on the
    wide-V MXU body, which is what caps the block (the VPU body at r = 1
    would fit twice the block)."""
    x, v = _f32(one_chip, N, D), _f32(one_chip, N, 1)
    wide = _f32(one_chip, N, 8 if R_VPU < 8 else R_VPU + 1)

    def mv(block):
        return lambda x, v: kops.rbf_matvec(x, v, 3.0, 3.0, impl="pallas",
                                            block=block)

    assert "tpu_custom_call" in _compile_text(mv(GPC.block), x, v)
    assert "tpu_custom_call" in _compile_text(mv(GPC.block), x, wide)
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile_text(mv(2 * GPC.block), x, wide)


def _gram_out_shapes(hlo: str):
    return re.findall(r"%rbf_gram_matvec[.\d]* = (f32\[[\d,]*\])", hlo)


def test_narrow_gram_call_writes_one_pass_per_output(one_chip):
    """At r = 1 and r = k (the symmetric VPU body) the kernel's output
    is the rank-2 ``f32[8,N]`` accumulator, V on lanes, that
    ``bench/readers.passes`` counts as one pass: not a rank-3 array of
    lane or pair partial sums."""
    for name in ("rbf_matvec", "rbf_matvec_k"):
        fn, shapes = KERNELS[name]
        with jax.enable_x64(False):
            hlo = _compile_text(fn, *(_f32(one_chip, *s) for s in shapes))
        (shape,) = _gram_out_shapes(hlo)
        assert shape == f"f32[8,{N}]" and passes(shape) == 1


def test_square_pass_past_the_band_output_compiles_for_v5e(one_chip):
    """Past ``SYM_OUT_BYTES`` of output (2^18 rows here) a square pass
    at r = k takes the full grid, whose output is ``(n_pad, 8)``: the
    symmetric body's whole-length output would overflow VMEM."""
    fn, _ = KERNELS["rbf_matvec_k"]
    n = 2 * SYM_OUT_BYTES // (4 * 8)
    with jax.enable_x64(False):
        hlo = _compile_text(fn, _f32(one_chip, n, D), _f32(one_chip, n, K))
    assert _gram_out_shapes(hlo) == [f"f32[{n},8]"]


def _state_shapes(sharding, batch=()):
    zero = jax.eval_shape(lambda: RecycleState.zeros(K, N, jnp.float32))
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(batch + l.shape, l.dtype,
                                       sharding=sharding),
        zero,
    )


SPEC = SolveSpec(method="defcg", k=K, ell=ELL, tol=1e-5, maxiter=200)


def _operator(x, sqrt_h):
    return RBFKernelSystemOperator(x, sqrt_h, 3.0, 3.0, GPC.block, "auto")


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
def test_f32_defcg_solve_compiles_for_v5e(one_chip, auto_is_pallas, warm):
    """One whole f32 def-CG solve (x64 off, as on the chip) reaches the
    Pallas kernels: the RBF matvec and the fused vector passes."""

    def run(x, sqrt_h, b, state):
        res = solve(_operator(x, sqrt_h), b, SPEC, state if warm else None)
        return res.x, res.info.iterations, res.state

    with jax.enable_x64(False):
        hlo = _compile_text(
            run, _f32(one_chip, N, D), _f32(one_chip, N), _f32(one_chip, N),
            _state_shapes(one_chip),
        )
    assert "tpu_custom_call" in hlo
    assert "rbf_gram_matvec" in hlo and "fused_cg_update" in hlo


def test_f32_pool_step_compiles_for_v5e(one_chip, auto_is_pallas):
    """The serving tick's batched step: the same kernels under vmap."""
    slots = 2

    def step(x, sqrt_h, b, state, active):
        res = solve_pool_step(_operator(x, sqrt_h), b, SPEC, state, active)
        return res.x, res.info.iterations, res.state

    with jax.enable_x64(False):
        hlo = _compile_text(
            step,
            _f32(one_chip, slots, N, D), _f32(one_chip, slots, N),
            _f32(one_chip, slots, N), _state_shapes(one_chip, (slots,)),
            jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
        )
    assert "rbf_gram_matvec" in hlo
    # One rank-3 output under vmap: a pass per slot.
    shapes = set(_gram_out_shapes(hlo))
    assert shapes == {f"f32[{slots},8,{N}]"}
    assert {passes(s) for s in shapes} == {slots}


@pytest.fixture(scope="module")
def solve_mesh(topo):
    return Mesh(np.array(topo.devices), ("solve",))


def test_sharded_newton_path_compiles_for_v5e_mesh(solve_mesh):
    """The four-chip fit's programs at its shapes (n = 2^16 rows over a
    2x2 v5e): the driver's Gram pass runs the one ``rbf_gram_matvec``
    kernel on each chip's row block after an all-gather, and the warm
    sharded def-CG solve reaches the same kernel."""
    n = 2 * N
    rows = NamedSharding(solve_mesh, P("solve", None))
    vec = NamedSharding(solve_mesh, P("solve"))
    basis = NamedSharding(solve_mesh, P(None, "solve"))
    rep = NamedSharding(solve_mesh, P())
    x, v = _f32(rows, n, D), _f32(vec, n)
    k_mv = GramMatvec(x, 3.0, 3.0, "pallas", GPC.block, solve_mesh)
    state = RecycleState(
        W=_f32(basis, K, n), AW=_f32(basis, K, n), theta=_f32(rep, K),
        systems_solved=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        drift=_f32(rep),
    )
    op = RBFKernelSystemOperator(x, v, 3.0, 3.0, GPC.block, "pallas")
    with jax.enable_x64(False):
        driver = laplace.newton_system.lower(v, v, k_mv).compile().as_text()
        solve_hlo = api_mod.solve_jit.lower(
            op, v, SPEC, state, x0=v, mesh=solve_mesh
        ).compile().as_text()
    for hlo in (driver, solve_hlo):
        assert "rbf_gram_matvec" in hlo and "all-gather" in hlo
    assert "all-reduce" in solve_hlo


@pytest.mark.parametrize(
    "op, call",
    [
        ("rbf_matvec",
         lambda a: kops.rbf_matvec(a[:, None], a, 1.0, 1.0, impl="pallas")),
        ("fused_cg_update",
         lambda a: kops.fused_cg_update(a, a, a, a, 0.5, impl="pallas")),
        ("fused_rz_reduce", lambda a: kops.fused_rz_reduce(a, a, impl="pallas")),
        ("lsmr_update",
         lambda a: kops.lsmr_update(a, a, a, a, 1.0, 1.0, 1.0, impl="pallas")),
        ("self_gram", lambda a: kops.self_gram(a[None], impl="pallas")),
    ],
    ids=lambda v: v if isinstance(v, str) else "",
)
def test_pallas_refuses_float64(op, call):
    """64-bit data never reaches a Mosaic kernel: the dispatch raises and
    names the op and the dtype, instead of rerouting the data (CPU only)."""
    with jax.enable_x64(True):
        a = jnp.ones(256, jnp.float64)
        with pytest.raises(ValueError, match=f"{op}: .*float64"):
            call(a)
