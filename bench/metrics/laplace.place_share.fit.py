"""Share of the window's ``laplace.fit`` spans spent putting the fit's
data on the mesh: the seconds of their ``laplace.place`` spans over
theirs.  About 0 when the data was placed once, before the window."""

from bench.program_spans import fits


def read(run):
    found = fits(run)
    if found is None:
        return None
    spans, _ = found
    from repro.runtime import spans as program

    ids = {s.id for s in spans}
    placed = [r for r in program.recent()
              if r.name == "laplace.place" and r.parent in ids]
    if len(placed) != len(spans):
        return None
    return sum(r.seconds for r in placed) / sum(s.seconds for s in spans)
