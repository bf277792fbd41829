"""90th percentile of time to first token, in ms, over every request due in
the window, timed from when it was due (open loop): host clock."""
from bench.lib import readings


def read(run):
    return readings.p90(readings.ttfts_ms(run.window))
