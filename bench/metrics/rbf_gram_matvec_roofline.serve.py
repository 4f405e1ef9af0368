"""The fused Gram kernel's share of its roofline in the serve cell, from
the device trace: a vmapped ``rbf_gram_matvec`` event of the pool step
counts one single-column pass per slot, a client's event one (a lower
bound for the k-column refreshes)."""

from bench.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "rbf_gram_matvec")
