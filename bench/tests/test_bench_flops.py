"""The benchmark's work counts and peak table."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench import flops  # noqa: E402


def test_one_gram_pass_at_mnist_size():
    assert flops.gram_flops(2**15, 784) == pytest.approx(1.686e12, rel=1e-3)
    assert flops.gram_flops(2**15, 784) == 2 * 2**30 * 785
    assert flops.gram_bytes(2**15, 784) == 4 * (2**15 * 784 + 2 * 2**15)


def test_k_column_pass_counts_columns_once():
    n, d, k = 7291, 256, 8
    assert flops.gram_flops(n, d, k) == 2.0 * n * n * (d + k)
    # A warm system: 20 engine products of which k are one refresh, plus
    # the driver's two single passes.
    cold = flops.system_flops(n, d, 20, refreshed=False, k=k)
    warm = flops.system_flops(n, d, 20, refreshed=True, k=k)
    assert cold == 22 * flops.gram_flops(n, d)
    assert warm == 14 * flops.gram_flops(n, d) + flops.gram_flops(n, d, k)


def test_v5e_peaks_and_bound():
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    # d = 784 is compute-bound: 1.686e12 / 197e12 = 8.56 ms.
    bound = flops.gram_bound_s(2**15, 784, 1, peak)
    assert bound == pytest.approx(flops.gram_flops(2**15, 784) / 197e12)
    assert bound == pytest.approx(8.557e-3, rel=1e-3)


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
