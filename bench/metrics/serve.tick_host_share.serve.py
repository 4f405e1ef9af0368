"""Share of the window's ``serve.tick`` spans that the scheduler spent on
its own host work (admit, batch, dispatch, scatter): 1 - the
``serve.fetch`` seconds inside them (the waits for the pool step's
diagnostics) over their seconds."""

from bench.program_spans import ticks


def read(run):
    found = ticks(run)
    if found is None:
        return None
    spans, _, fetches = found
    return 1.0 - sum(f.seconds for f in fetches) / sum(s.seconds for s in spans)
