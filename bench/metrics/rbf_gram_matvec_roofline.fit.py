"""The fused Gram kernel's share of its roofline in the fit cells, from
the device trace: every ``rbf_gram_matvec`` event counted as one pass
of one column (a lower bound for the k-column refreshes)."""

from bench.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "rbf_gram_matvec")
