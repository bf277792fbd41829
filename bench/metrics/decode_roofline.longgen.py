"""Attention and matmuls of the decode step: the least time the roofline
allows for the window's steps (every weight once, the attended K/V rows)
over their device time, in %: device trace."""
from bench.lib import readings


def read(run):
    return readings.decode_roofline(run)
