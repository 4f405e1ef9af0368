"""The Gram flops the active tenants' systems required (``bench/flops.py``)
over the traced window's length times the chip's bf16 peak."""

from bench.readers import mfu, serve_flops


def read(run):
    return mfu(run, serve_flops(run))
