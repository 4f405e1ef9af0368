"""The program's own spans and counters in a run's window.

``repro.runtime.spans`` keeps the program's last spans in a ring, each
with its name, parent, ``perf_counter_ns`` start and end and attrs (the
``syncs`` counter among them).  The readers here pick out the records of
the window that ``bench/run.py`` just ran, and check them against the
window's record.  Each returns ``None`` where the program keeps no spans
(a program from before them) or where the ring does not hold the whole
window: a reading of part of a window is no reading.  They read in
the traced run only, like every per-layer metric: its window (the
traffic's ``trace_seconds``) is one the ring can hold whole.
"""

from __future__ import annotations


def _records(run):
    """The ring's closed records, oldest first, or ``None``."""
    if run.trace is None:
        return None
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return [r for r in spans.recent() if r.end_ns is not None]


def fits(run):
    """``(fits, waits)``: the window's ``laplace.fit`` spans, one per fit
    of ``run.record["fits"]`` with as many Newton systems, and the
    ``laplace.wait`` spans inside them; or ``None``."""
    records = _records(run)
    want = run.record["fits"]
    if records is None or not want:
        return None
    spans = [r for r in records if r.name == "laplace.fit"][-len(want):]
    if len(spans) != len(want) or any(
        s.attrs.get("systems") != len(f["matvecs"]) for s, f in zip(spans, want)
    ):
        return None
    parent = {r.id: r.parent for r in records}
    ids = {s.id for s in spans}

    def inside(r) -> bool:
        p = r.parent
        while p is not None and p not in ids:
            p = parent.get(p)
        return p is not None

    return spans, [r for r in records if r.name == "laplace.wait" and inside(r)]


def ticks(run):
    """``(ticks, tickets, fetches)``: the ``serve.tick`` spans, the
    ``serve.ticket`` records and the ``serve.fetch`` waits of the window,
    whose tickets are ``run.record["tickets"]``; or ``None``.

    A window's tickets are the last ones redeemed, one per ticket of the
    record, with the same tick numbers; its ticks are those numbered in
    their range that started after the first of them was submitted."""
    records = _records(run)
    want = run.record["tickets"]
    if records is None or not want:
        return None
    tickets = [r for r in records if r.name == "serve.ticket"][-len(want):]
    if sorted(r.attrs["tick"] for r in tickets) != sorted(t["tick"] for t in want):
        return None
    lo = min(r.attrs["tick"] for r in tickets)
    hi = max(r.attrs["tick"] for r in tickets)
    first = min(r.start_ns for r in tickets)
    spans = [
        r for r in records if r.name == "serve.tick"
        and lo <= r.attrs["tick"] <= hi and r.start_ns >= first
    ]
    if len(spans) != hi - lo + 1:
        return None
    ids = {s.id for s in spans}
    fetches = [r for r in records if r.name == "serve.fetch" and r.parent in ids]
    return spans, tickets, fetches
