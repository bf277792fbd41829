"""Scheduler: share of the window in which decode stood still for an
admission round, in %: host clock around ``prefill_pending``."""


def read(run):
    win = run.window
    if not win.admissions or win.seconds <= 0:
        return None
    return sum(d for _, d, _ in win.admissions) / win.seconds * 100
