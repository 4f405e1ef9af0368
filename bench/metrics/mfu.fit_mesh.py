"""The Gram flops the fits required (``bench/flops.py``: the engine's
products, each k-column refresh once, two driver passes per system) over
the traced window's length times the chips' bf16 peak: ``mfu.fit``
with every chip of the mesh counted."""

from bench.readers import fit_flops, mfu


def read(run):
    share = mfu(run, fit_flops(run))
    return None if share is None else share / run.cell.chips
