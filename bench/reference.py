"""The plain reference that decides ``correct``: float64 on the host.

It imports nothing of the program.  For a sample of rows ``S`` drawn from
the run's seed it forms the RBF Gram rows ``K[S, :]`` in float64 from the
data alone, and with them checks, on those rows, what the timed path
produced:

* ``gram_err``: a Gram product ``y = K v`` (the Newton right-hand side
  ``b = H^1/2 K bg``, and the next latent ``f = K a``, the last of which
  is the fit's result), as ``|y_S - (K v)_S| / |(K |v|)_S|``: the error
  against the scale of the sum that forms each entry.  Relative to
  ``|(K v)_S|`` instead, a vector whose terms cancel (labels that
  disagree, a Newton step near the mode) would multiply the reading by
  the cancellation and not by any fault of the arithmetic.
* ``solve_gap``: a Newton system's solution ``x`` of
  ``(I + H^1/2 K H^1/2) x = b``, as the true relative residual
  ``|b_S - (A x)_S| / |b_S|`` less the configuration's ``tol``: the
  engine is to stop once ``|r| <= tol * |b|``, so a sound solve's true
  residual sits at ``tol`` or under it, and what lies beyond is the drift
  of the solve's arithmetic or a wrong answer.  Nothing the program says
  about itself enters the number.

Every product is formed in float64 from the float32 vectors the path
multiplied, so a reading is the path's own error and not the rounding of
its inputs.
"""

from __future__ import annotations

import numpy as np


def ratio(num: np.ndarray, den: np.ndarray) -> float:
    d = float(np.linalg.norm(den))
    return float(np.linalg.norm(num)) / d if d > 0 else float("inf")


class RowReference:
    """Float64 Gram rows ``K[rows, :]`` of one data set."""

    def __init__(self, x, theta: float, lengthscale: float, rows):
        xs = np.asarray(x, np.float64) / lengthscale
        self.rows = np.asarray(rows)
        sq = np.sum(xs * xs, axis=1)
        d2 = sq[self.rows, None] + sq[None, :] - 2.0 * (xs[self.rows] @ xs.T)
        self.k_rows = theta**2 * np.exp(-0.5 * np.maximum(d2, 0.0))

    def check_system(self, sqrt_h, bg, b, x, a, f_next, tol):
        """``(gram errors, solve gap)`` of one Newton system of the path.

        The path posed ``(I + H^1/2 K H^1/2) x = b`` with ``b = H^1/2 K bg``,
        returned ``x`` as solved to the relative residual ``tol``, and
        formed the next latent ``f_next = K a``.
        """
        S, K = self.rows, self.k_rows
        sqrt_h, bg, b, x, a, f_next = (
            np.asarray(v, np.float64) for v in (sqrt_h, bg, b, x, a, f_next)
        )
        gram = [
            ratio(b[S] - sqrt_h[S] * (K @ bg), sqrt_h[S] * (K @ np.abs(bg))),
            ratio(f_next[S] - K @ a, K @ np.abs(a)),
        ]
        ax = x[S] + sqrt_h[S] * (K @ (sqrt_h * x))
        return gram, ratio(b[S] - ax, b[S]) - tol
