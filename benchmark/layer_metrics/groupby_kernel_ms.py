"""Pallas aggregate kernel: device time of the events named after the
engine's two kernels, per request, from the device trace."""

KERNELS = ("dense_groupby_small", "dense_groupby_factored")


def read(run):
    t = run["trace"]
    if not t:
        return None
    secs = [s for name, s in t["ops_s"].items()
            if any(k in name for k in KERNELS)]
    if not secs:
        return None
    return sum(secs) * 1e3 / len(run["requests"])
