"""device: 1 minus the union of the intervals in which any operation
runs on the device, over the traced window."""


def read(run):
    t = run["trace"]
    return t["idle_pct"] if t else None
