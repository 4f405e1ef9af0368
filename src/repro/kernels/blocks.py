"""Helpers shared by the Pallas kernels and their XLA twins.

Mosaic accepts only int32 block indices.  An index map that returns a
Python int (``lambda i: (i, 0)``) returns int64 once ``jax_enable_x64`` is
on, and the TPU compiler then refuses the kernel.  :func:`block_spec`
casts every index to int32, so each kernel compiles whatever the x64 flag
says.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Every contraction of the kernels and their twins runs at full f32
# precision: the TPU default for f32 operands is one bf16 pass, which
# breaks deflation (see core/pytree.py) and the Gram matvec (see
# kernels/rbf_matvec.py).
F32 = jax.lax.Precision.HIGHEST


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def block_spec(block_shape, index_map, **kwargs) -> pl.BlockSpec:
    """``pl.BlockSpec`` whose index map returns int32 indices."""

    def i32_map(*grid_idx):
        return tuple(jnp.asarray(v, jnp.int32) for v in index_map(*grid_idx))

    return pl.BlockSpec(block_shape, i32_map, **kwargs)
