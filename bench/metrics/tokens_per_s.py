"""Every output token delivered in the window, over the window's wall time:
host clock.  In-flight requests' tokens count."""


def read(run):
    win = run.window
    return win.tokens / win.seconds if win.seconds > 0 else None
