"""Admission prefill: the FLOPs of the window's real prompt tokens over the
device time inside the harness's admission spans, as a share of the chip's
bf16 peak: device trace."""
from bench.lib import counts, readings


def read(run):
    dev = readings.span_device_s(run, "bench.admit")
    adm = run.window.admissions
    if dev is None or not adm or not run.peaks:
        return None
    flops = sum(counts.prefill(run.w, n) for _, _, lens in adm for n in lens)
    return flops / (dev * run.peaks["bf16_flops"] * run.chips) * 100
