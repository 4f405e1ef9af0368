"""Arithmetic that several per-layer metrics share.

Each takes the run (``bench/run.py``'s ``Run``: the window's record, the
reduced trace, the configuration and the chip's peaks) and returns a
number, or ``None`` where the run holds nothing to read.  Shares are in
percent where the metric's unit is ``%``.
"""

from __future__ import annotations

from bench import flops


def passes(shape: str) -> int:
    """Gram passes in one kernel event, from its output shape: a vmapped
    call ``f32[8,7424,8]`` computes 8 (one per slot), ``f32[7424,8]`` one."""
    dims = [int(t) for t in shape.split("[", 1)[-1].rstrip("]").split(",") if t]
    out = 1
    for dim in dims[:-2]:
        out *= dim
    return out


def kernel_roofline(run, kernel: str):
    """Sum over the kernel's events of the least time the chip could take
    (one Gram pass of one column per pass the event computes) over the
    sum of their device time, in percent."""
    events = (run.trace or {}).get("events", {}).get(kernel) or []
    seconds = sum(e["seconds"] for e in events)
    if not events or seconds <= 0:
        return None
    n, d = run.config["n"], run.config["d"]
    bound = flops.gram_bound_s(n, d, 1, run.peak)
    return 100.0 * bound * sum(passes(e["shape"]) for e in events) / seconds


def fit_flops(run) -> float:
    """Required Gram flops of every fit in the window."""
    n, d, k = run.config["n"], run.config["d"], run.config["k"]
    total = 0.0
    for fit in run.record["fits"]:
        for i, mv in enumerate(fit["matvecs"]):
            total += flops.system_flops(n, d, mv, refreshed=i > 0, k=k)
    return total


def serve_flops(run) -> float:
    """Required Gram flops of the active tenants' systems in the window:
    a tenant's first system is cold, every later one refreshes its
    carried basis."""
    n, d, k = run.config["n"], run.config["d"], run.config["k"]
    return sum(
        flops.system_flops(n, d, t["matvecs"], refreshed=t["seq"] > 0, k=k)
        for t in run.record["tickets"]
    )


def mfu(run, required_flops: float):
    """Required flops over the traced window times the bf16 peak, in
    percent."""
    if not run.trace:
        return None
    window = run.trace["window_s"]
    return 100.0 * required_flops / (window * run.peak["bf16_flops_per_s"])


def idle_share(run):
    """1 - device busy / window, from the trace."""
    if not run.trace:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
