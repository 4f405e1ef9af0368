"""The Gram flops the fits required (``bench/flops.py``: the engine's
products, each k-column refresh once, two driver passes per system) over
the traced window's length times the chip's bf16 peak."""

from bench.readers import fit_flops, mfu


def read(run):
    return mfu(run, fit_flops(run))
