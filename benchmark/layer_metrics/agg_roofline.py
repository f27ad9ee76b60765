"""kernels: the least time the chip could take for a request's
scan-aggregate work (`harness/work.py`: the larger of bytes over the
chip's bytes/s and operations over its op/s) over the device's busy
time per request in the trace. It divides by all the device did, not
by one kernel, so it still reads when a kernel is replaced."""


def read(run):
    t = run["trace"]
    if not t or not t.get("busy_s"):
        return None
    busy_per_request = t["busy_s"] / len(run["requests"])
    return 100.0 * run["least"]["seconds"] / busy_per_request
