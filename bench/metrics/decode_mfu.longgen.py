"""Whole decode step: the FLOPs its tokens need over its device time, as a
share of the chip's bf16 peak: device trace."""
from bench.lib import readings


def read(run):
    return readings.decode_mfu(run)
