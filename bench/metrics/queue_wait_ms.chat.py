"""Scheduler: mean time from a request falling due to the start of the
admission round that takes it, in ms, over the window's requests: host
clock."""
import math


def read(run):
    win = run.window
    waits = [win.logs[r].admit_start - win.logs[r].due for r in win.counted
             if win.logs[r].due is not None
             and not math.isnan(win.logs[r].admit_start)]
    return sum(waits) / len(waits) * 1e3 if waits else None
