"""Share of the benchmark's ``tick`` spans (``SolveService.tick``: admit,
batch, pool step, ``device_get``, scatter) in which the device ran no op,
from the trace."""


def read(run):
    ticks = [s for s in (run.trace or {}).get("spans", []) if s["name"] == "bench.tick"]
    length = sum(s["seconds"] for s in ticks)
    if length <= 0:
        return None
    return 1.0 - sum(s["busy_s"] for s in ticks) / length
