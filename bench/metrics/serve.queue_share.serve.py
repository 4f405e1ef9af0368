"""Share of the window's systems' time in the service spent queued: the
sum over its ``serve.ticket`` records of submit to the start of the tick
that served it, over the sum of submit to redeem."""

from bench.program_spans import ticks


def read(run):
    found = ticks(run)
    if found is None:
        return None
    _, tickets, _ = found
    waited = sum(t.attrs["tick_start_ns"] - t.start_ns for t in tickets)
    return waited / sum(t.end_ns - t.start_ns for t in tickets)
