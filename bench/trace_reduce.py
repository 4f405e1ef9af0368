"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

A trace holds device planes (``/device:TPU:<i>``), whose ``XLA Ops`` line
gives what ran on each chip and when, and the host plane (``/host:CPU``),
whose Python thread's line holds the benchmark's own spans
(``jax.profiler.TraceAnnotation`` names starting with ``bench.``).  Both
are on one clock.  A TPU op event is named by its HLO instruction
(``%rbf_gram_matvec.1 = f32[32768,8]{...} custom-call(...)``); it is
counted under the instruction's name without ``%`` and the numeric
suffix (``rbf_gram_matvec``), and a Pallas kernel's instruction takes the
kernel's name.

:func:`reduce_trace` returns, for the window span ``bench.window``:

* ``window_s``: its length;
* ``busy_s``: the union of the intervals in which an op ran on a device,
  clipped to the window and averaged over the devices;
* ``device_ops``: seconds per op name, each op's own time (less the ops
  nested in it), summed over devices;
* ``idle_gaps``: the window's idle seconds, each gap given to the
  innermost benchmark span open at its midpoint (``"none"`` if no span);
* ``spans``: every benchmark span inside the window with the device busy
  seconds inside it (per device, averaged);
* ``events``: the op events whose names are asked for, with their
  durations and output shapes, for the kernel rooflines.

Reading uses ``jax.profiler.ProfileData`` only, which needs no
accelerator.
"""

from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW = "bench.window"
HOST_PLANE = "/host:CPU"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
NS = 1e-9

Interval = Tuple[float, float]
_SUFFIX = re.compile(r"(\.(\d+|clone))+$")


def op_name(event_name: str) -> Tuple[str, str]:
    """``(name, output shape)`` of a device op event: ``"%rbf_gram_matvec.1
    = f32[32768,8]{1,0} custom-call(...)"`` gives ``("rbf_gram_matvec",
    "f32[32768,8]")``, ``"%cond.2.clone.2 = ..."`` gives ``"cond"``; a name
    that is no HLO text is kept as it is."""
    head, sep, rest = event_name.partition(" = ")
    name = _SUFFIX.sub("", head.lstrip("%"))
    shape = rest.split("{")[0].split(" ")[0] if sep else ""
    return name, shape


def self_times(ops) -> List[float]:
    """Each op's own seconds: its duration less that of the ops nested in
    it (a ``while`` holds its body's ops, a ``conditional`` its branch's)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [ops[i][2] - ops[i][1] for i in range(len(ops))]
    stack: List[int] = []
    for i in order:
        s, e = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def find_xplane(log_dir: str | Path) -> Path:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by sorted merged intervals."""
    i = max(bisect.bisect_right(merged, (lo, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        total += max(0.0, min(e, hi) - max(s, lo))
        i += 1
    return total


def _gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _load_planes(path: Path):
    """The trace's planes; a gzipped ``.xplane.pb.gz`` is read as well."""
    import gzip

    from jax.profiler import ProfileData

    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes())).planes
    return ProfileData.from_file(str(path)).planes


def reduce_trace(path: str | Path, kernels: Sequence[str] = ()) -> dict:
    """Reduce one trace file; see the module docstring."""
    spans: List[Tuple[str, float, float]] = []
    devices: Dict[str, List[Tuple[str, float, float, str]]] = {}
    for plane in _load_planes(Path(path)):
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * NS
                        spans.append((ev.name, s, s + ev.duration_ns * NS))
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns * NS
                    name, shape = op_name(ev.name)
                    ops.append((name, s, s + ev.duration_ns * NS, shape))
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if not windows:
        raise ValueError(f"trace {path} has no {WINDOW!r} span")
    lo, hi = windows[0]
    spans = sorted(
        (sp for sp in spans if sp[1] >= lo and sp[2] <= hi and sp[0] != WINDOW),
        key=lambda sp: (sp[1], -sp[2]),
    )
    return _reduce(lo, hi, spans, devices, kernels)


def _reduce(lo, hi, spans, devices, kernels) -> dict:
    n_dev = max(len(devices), 1)
    merged = {
        dev: union(
            (max(s, lo), min(e, hi)) for _, s, e, _ in ops if e > lo and s < hi
        )
        for dev, ops in devices.items()
    }
    busy = sum(covered(m, lo, hi) for m in merged.values()) / n_dev
    device_ops: Dict[str, float] = {}
    events: Dict[str, List[dict]] = {k: [] for k in kernels}
    for ops in devices.values():
        for (name, s, e, shape), own in zip(ops, self_times(ops)):
            if s < lo or s >= hi:
                continue
            device_ops[name] = device_ops.get(name, 0.0) + own
            if name in events:
                events[name].append({"seconds": e - s, "shape": shape})
    gaps: Dict[str, float] = {}
    starts = [s for _, s, _ in spans]
    for m in merged.values():
        for g0, g1 in _gaps(m, lo, hi):
            owner = _innermost(spans, starts, 0.5 * (g0 + g1))
            gaps[owner] = gaps.get(owner, 0.0) + (g1 - g0) / n_dev
    span_rows = [
        {
            "name": name,
            "seconds": e - s,
            "busy_s": sum(covered(m, s, e) for m in merged.values()) / n_dev,
        }
        for name, s, e in spans
    ]
    return {
        "window_s": hi - lo,
        "busy_s": busy,
        "devices": len(devices),
        "device_ops": dict(sorted(device_ops.items(), key=lambda kv: -kv[1])),
        "idle_gaps": dict(sorted(gaps.items(), key=lambda kv: -kv[1])),
        "spans": span_rows,
        "events": events,
    }


def _innermost(spans, starts, t: float) -> str:
    """Name of the latest-starting span that covers ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        name, _, e = spans[i]
        if e >= t:
            return name[len(SPAN_PREFIX):]
        i -= 1
    return "none"
