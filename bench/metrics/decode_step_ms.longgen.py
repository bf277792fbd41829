"""Fused decode: device time inside the harness's decode-step spans per
step, in ms: device trace."""
from bench.lib import readings


def read(run):
    return readings.decode_step_ms(run)
