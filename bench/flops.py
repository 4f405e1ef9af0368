"""Work counts of the GP Newton path, from shapes alone.

They count what the algorithm needs, the same whatever implements it: a
Gram pass over ``n`` points of published width ``d`` applied to ``r``
columns costs the distance GEMM and the ``K @ V`` product,
``2 n^2 (d + r)`` flops (the exponentials and the row norms are left
out), and reads ``x`` once and ``V`` and the output once each,
``4 (n d + 2 n r)`` bytes in float32.  Padding that a kernel adds (``d``
to a multiple of 128, ``r`` to 8) is not work.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")
F32_BYTES = 4


def gram_flops(n: int, d: int, r: int = 1) -> float:
    """Flops of one Gram pass ``K(X, X) @ V`` with ``V`` of ``r`` columns."""
    return 2.0 * n * n * (d + r)


def gram_bytes(n: int, d: int, r: int = 1) -> float:
    """HBM bytes of one Gram pass: ``x`` read once, ``V`` read, ``K V``
    written."""
    return float(F32_BYTES * (n * d + 2 * n * r))


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown device raises."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def gram_bound_s(n: int, d: int, r: int, peak: dict) -> float:
    """Least time the chip could take for one Gram pass: the larger of its
    flops over the bf16 peak and its bytes over the HBM bandwidth."""
    return max(
        gram_flops(n, d, r) / peak["bf16_flops_per_s"],
        gram_bytes(n, d, r) / peak["hbm_bytes_per_s"],
    )


def system_flops(n: int, d: int, matvecs: int, refreshed: bool, k: int) -> float:
    """Required Gram flops of one Newton system.

    ``matvecs`` is what the engine reports.  A warm system's ``AW``
    refresh is ``k`` of those products but one ``k``-column pass, counted
    once as ``2 n^2 (d + k)``.  The Newton driver adds two single passes
    (``K bg`` for the right-hand side, ``K a`` for the next latent).
    """
    single = matvecs - (k if refreshed else 0) + 2
    out = single * gram_flops(n, d, 1)
    if refreshed:
        out += gram_flops(n, d, k)
    return out
