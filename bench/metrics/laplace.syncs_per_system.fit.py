"""Device reads per Newton system: the ``syncs`` counter of the window's
``laplace.fit`` spans (one per ``laplace.wait``, each of which drains the
device's queue) over their Newton systems."""

from bench.program_spans import fits


def read(run):
    found = fits(run)
    if found is None:
        return None
    spans, _ = found
    return sum(s.attrs.get("syncs", 0) for s in spans) / sum(
        s.attrs["systems"] for s in spans
    )
