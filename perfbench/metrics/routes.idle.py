"""Share of the route cell's window in which no operation ran on the
device."""


def read(run):
    tr = run["trace"]
    return None if tr is None else 100.0 * tr.idle_share
