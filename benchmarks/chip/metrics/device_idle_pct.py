"""device_idle_pct.<cell kind>: 100 * (1 - busy / window) from the
trace, where busy is the union of the intervals in which an operation
ran on a chip."""


def read(run):
    t = run["trace"]
    return None if t is None else 100.0 * t.idle_share
