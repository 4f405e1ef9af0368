"""Share of the traced window that the devices spent in collectives: the
own device time of every all-gather, all-reduce, collective-permute and
reduce-scatter op (their async start and done halves included), summed
over the devices, over the window times the devices."""

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter")


def read(run):
    if not run.trace or not run.trace.get("devices"):
        return None
    own = sum(
        seconds for name, seconds in run.trace["device_ops"].items()
        if name.startswith(COLLECTIVES)
    )
    return own / (run.trace["window_s"] * run.trace["devices"])
