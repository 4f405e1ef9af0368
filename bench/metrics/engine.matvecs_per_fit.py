"""Operator products per fit, as the engine reports them
(``NewtonTrace.solver_matvecs`` summed over a fit's Newton systems)."""


def read(run):
    fits = run.record["fits"]
    return sum(sum(f["matvecs"]) for f in fits) / len(fits)
