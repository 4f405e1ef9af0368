"""Share of a fit's wall time outside the Newton driver's solves: the
host work of ``laplace_gpc`` (the Newton system and step, the host syncs)
and what it waits for, from the program's ``NewtonTrace`` solve seconds."""


def read(run):
    fits = run.record["fits"]
    wall = sum(f["wall_s"] for f in fits)
    return 1.0 - sum(f["solve_s"] for f in fits) / wall
