"""The control of the comparison: readings of the program and of the
reference put in its place one precision step down.

    python3 bench/control.py --workload gpc-mnist.fit --seeds 1 2 3 \
        --program-seeds 4 5 6 7 8 9 10 11 12 13 14 15 --seconds 1

The configurations run float32 with every contraction at ``HIGHEST``
(six bf16 passes on the TPU).  The nearest precision below is ``HIGH``,
three bf16 passes: the control replaces the program's RBF Gram product
(``repro.kernels.ops.rbf_matvec``, which the Newton driver, the
operators and the service all call) by a plain row-blocked Gram product
whose two contractions split each float32 operand into a high and a low
bfloat16 half and keep the three products ``hi*hi + hi*lo + lo*hi``,
accumulated in float32: the ``HIGH`` algorithm, written out so that it
runs the same on any backend.  The rest of the timed path is unchanged.

For each seed the script runs the cell's set-up and a window of
``--seconds`` in this one process, then prints the same readings that
decide ``correct`` in a run, one JSON line per seed and side.  The
benchmark's own runs never run the control.
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

from bench import harness  # noqa: E402

CONTROL = "control"
ROW_BLOCK = 256


def _split(a):
    """``(hi, lo)`` bfloat16 halves of a float32 array: ``hi`` keeps the top
    16 bits (cut by a mask, which no compiler folds away), ``lo`` rounds
    the exact remainder ``a - hi``."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(0xFFFF0000)
    hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def high_matmul(a, b):
    """``a @ b`` by three bf16 products, float32 accumulation."""
    import jax.numpy as jnp

    (ah, al), (bh, bl) = _split(a), _split(b)

    def mm(p, q):
        return jnp.matmul(p, q, preferred_element_type=jnp.float32)

    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def control_gram_matvec(x, v, theta, lengthscale):
    """``K(X, X) @ v`` at ``HIGH``, row block by row block."""
    import jax
    import jax.numpy as jnp

    squeeze = v.ndim == 1
    v2 = (theta**2) * (v[:, None] if squeeze else v)
    xs = x / lengthscale
    n = xs.shape[0]
    pad = (-n) % ROW_BLOCK
    sq = jnp.sum(xs * xs, axis=1)
    xb = jnp.pad(xs, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, xs.shape[1])
    sqb = jnp.pad(sq, (0, pad)).reshape(-1, ROW_BLOCK)

    def rows(args):
        xi, sqi = args
        d2 = jnp.maximum(sqi[:, None] + sq[None, :] - 2.0 * high_matmul(xi, xs.T), 0.0)
        return high_matmul(jnp.exp(-0.5 * d2), v2)

    out = jax.lax.map(rows, (xb, sqb)).reshape(-1, v2.shape[1])[:n]
    return out[:, 0] if squeeze else out


@contextlib.contextmanager
def control_in_place():
    """Route ``impl="control"`` Gram products to :func:`control_gram_matvec`;
    every other ``impl`` reaches the program's kernel as before."""
    harness.use_program()
    from repro.kernels import ops

    program = ops.rbf_matvec

    def rbf_matvec(x, v, theta, lengthscale, *, impl="auto", block=256):
        if impl == CONTROL:
            return control_gram_matvec(x, v, theta, lengthscale)
        return program(x, v, theta, lengthscale, impl=impl, block=block)

    with harness.patched(ops, rbf_matvec=rbf_matvec):
        yield


def readings(cell, seed: int, seconds: float, impl: str) -> dict:
    """One seed's readings, the same that decide ``correct`` in a run."""
    work = cell.driver().make(cell.config, cell.traffic, seed, impl)
    work.warm_up()
    work.window(seconds)
    return work.check(work.host_records())


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
    runs += [("control", CONTROL, s) for s in args.seeds]
    with control_in_place():
        for side, impl, seed in runs:
            r = readings(cell, seed, args.seconds, impl)
            ok, _ = harness.compare(r, cell.limits)
            print(json.dumps({"workload": cell.name, "side": side,
                              "seed": seed, "readings": r,
                              "correct": ok}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
