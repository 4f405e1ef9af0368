"""Share of the traced window in which no op ran on the device."""

from bench.readers import idle_share


def read(run):
    return idle_share(run)
