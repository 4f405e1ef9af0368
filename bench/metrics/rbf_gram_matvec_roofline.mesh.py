"""The fused Gram kernel's share of its roofline over a mesh, from the
device trace.  On a mesh of ``chips`` every ``rbf_gram_matvec`` event,
on every device, applies one chip's row block against all columns: it
counts as ``1/chips`` of a square pass of one column, whose least time
is ``flops.gram_bound_s(n, d, 1) / chips``.  The sum of those bounds over
the events' device time, in percent (a lower bound for the k-column
refreshes, as in ``rbf_gram_matvec_roofline.fit``)."""

from bench.readers import kernel_roofline


def read(run):
    share = kernel_roofline(run, "rbf_gram_matvec")
    return None if share is None else share / run.cell.chips
