"""90th percentile over the window's requests of (last token - first token)
/ (tokens - 1), in ms: host clock."""
from bench.lib import readings


def read(run):
    return readings.p90(readings.tpots_ms(run.window))
