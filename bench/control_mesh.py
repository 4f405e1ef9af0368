"""The control of the comparison for a cell whose Gram passes are split
over a mesh of chips.

    python3 bench/control_mesh.py --workload gpc-mnist-mesh.fit \
        --seeds 21 22 23 --seconds 1

``bench/control.py`` puts a plain Gram product at ``HIGH`` (three bf16
passes, float32 accumulation) in the place of the program's
``repro.kernels.ops.rbf_matvec``.  On a mesh the program calls that
function nowhere: every Gram pass, the Newton driver's two a system and
each of the sharded def-CG's, applies one chip's row block against all
the columns through ``repro.kernels.ops.rbf_matvec_rect``.  This script
replaces that function too, for ``impl="control"``, by the same ``HIGH``
product over a row block and all columns, so that the control reaches
every pass of the sharded path; the rest of the timed path is unchanged.
It prints one JSON line per seed and side, as ``bench/control.py`` does.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import control, harness  # noqa: E402


def control_rect_gram_matvec(x_rows, x_cols, v, theta, lengthscale):
    """``K(X_rows, X_cols) @ v`` at ``HIGH``, row block by row block."""
    import jax
    import jax.numpy as jnp

    squeeze = v.ndim == 1
    v2 = (theta**2) * (v[:, None] if squeeze else v)
    xr, xc = x_rows / lengthscale, x_cols / lengthscale
    m = xr.shape[0]
    pad = (-m) % control.ROW_BLOCK
    sq = jnp.sum(xc * xc, axis=1)
    xb = jnp.pad(xr, ((0, pad), (0, 0))).reshape(-1, control.ROW_BLOCK, xr.shape[1])

    def rows(xi):
        sqi = jnp.sum(xi * xi, axis=1)
        d2 = jnp.maximum(
            sqi[:, None] + sq[None, :] - 2.0 * control.high_matmul(xi, xc.T), 0.0
        )
        return control.high_matmul(jnp.exp(-0.5 * d2), v2)

    out = jax.lax.map(rows, xb).reshape(-1, v2.shape[1])[:m]
    return out[:, 0] if squeeze else out


@contextlib.contextmanager
def control_in_place():
    """``bench/control.py``'s replacement, and the same for the
    rectangular Gram product; any other ``impl`` reaches the program."""
    harness.use_program()
    from repro.kernels import ops

    program = ops.rbf_matvec_rect

    def rbf_matvec_rect(x_rows, x_cols, v, theta, lengthscale, *,
                        impl="auto", block=256):
        if impl == control.CONTROL:
            return control_rect_gram_matvec(x_rows, x_cols, v, theta, lengthscale)
        return program(x_rows, x_cols, v, theta, lengthscale, impl=impl,
                       block=block)

    with control.control_in_place(), harness.patched(
        ops, rbf_matvec_rect=rbf_matvec_rect
    ):
        yield


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    import jax

    jax.config.update("jax_enable_x64", False)
    harness.use_program()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    cell = harness.Cell(args.workload)
    runs = [("program", "auto", s) for s in args.program_seeds]
    runs += [("control", control.CONTROL, s) for s in args.seeds]
    with control_in_place():
        for side, impl, seed in runs:
            r = control.readings(cell, seed, args.seconds, impl)
            ok, _ = harness.compare(r, cell.limits)
            print(json.dumps({"workload": cell.name, "side": side,
                              "seed": seed, "readings": r,
                              "correct": ok}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
