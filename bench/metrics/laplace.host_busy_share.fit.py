"""Share of the window's ``laplace.fit`` spans in which ``laplace_gpc``
was not waiting for the device: 1 - the ``laplace.wait`` seconds inside
them over their seconds."""

from bench.program_spans import fits


def read(run):
    found = fits(run)
    if found is None:
        return None
    spans, waits = found
    return 1.0 - sum(w.seconds for w in waits) / sum(
        s.seconds for s in spans
    )
