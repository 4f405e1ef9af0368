"""Share of the pool step's slot-iterations that served no tenant: the
vmapped step runs every slot until the slowest active one converges, so
per tick it costs slots x the largest iteration count, of which the
active tenants use the sum of their own (``ServedResult.iterations``)."""


def read(run):
    ticks = {}
    for t in run.record["tickets"]:
        ticks.setdefault(t["tick"], []).append(t["iterations"])
    paid = sum(run.record["slots"] * max(its) for its in ticks.values())
    if paid <= 0:
        return None
    return 1.0 - sum(sum(its) for its in ticks.values()) / paid
