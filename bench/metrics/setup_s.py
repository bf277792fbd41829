"""Process start to window open: weights built on the device from the seed,
the cell's shapes warmed (from the compile cache after the first run), and
any backlog the traffic needs: host clock."""


def read(run):
    return run.setup_s
