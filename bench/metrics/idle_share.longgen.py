"""Device: share of the traced window in which no operation ran, in %:
device trace."""
from bench.lib import readings


def read(run):
    return readings.idle_share(run)
