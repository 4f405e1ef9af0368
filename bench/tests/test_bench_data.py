"""The seeded digit renderer."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np  # noqa: E402

from bench import data  # noqa: E402


def test_same_seed_same_data():
    a = [np.asarray(v) for v in data.digits(64, 2**31 + 5)]
    b = [np.asarray(v) for v in data.digits(64, 2**31 + 5)]
    c = [np.asarray(v) for v in data.digits(64, 2**31 + 6)]
    assert all(np.array_equal(p, q) for p, q in zip(a, b))
    assert not np.array_equal(a[0], c[0])


def test_mnist_and_usps_layouts():
    x, y = (np.asarray(v) for v in data.digits(100, 3))
    assert x.shape == (100, 784) and x.dtype == np.float32
    assert 0.0 <= x.min() and x.max() <= 1.0
    assert sorted(np.unique(y)) == [-1.0, 1.0] and y.sum() == 0
    x16, y16 = (np.asarray(v) for v in data.digits(100, 3, pixels=16))
    assert x16.shape == (100, 256)
    assert -1.0 <= x16.min() and x16.max() <= 1.0 + 1e-6
    np.testing.assert_array_equal(y16, y)
    # Area resampling keeps the mean ink: 16x16 in [-1, 1] is 2*mean - 1.
    np.testing.assert_allclose(x16.mean(1), 2 * x.mean(1) - 1, atol=1e-5)


def test_glyphs_match_the_program_renderer():
    """The vectorised renderer draws the same kind of glyph as the loop in
    repro.data.digits: pixel statistics agree."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
    from repro.data import make_infinite_digits

    x, _ = data.digits(2000, 1)
    xo, _ = make_infinite_digits(2000, seed=1)
    x = np.asarray(x)
    assert abs(x.mean() - xo.mean()) < 0.01 * xo.mean()
    assert abs(x.std() - xo.std()) < 0.02 * xo.std()
