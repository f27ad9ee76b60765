"""Input rows scanned by every request that completed correctly in the
window, over the whole window's seconds, in millions a second."""


def read(run):
    done = sum(1 for r in run["requests"] if not r["failed"])
    return run["work"]["rows"] * done / run["window_s"] / 1e6
