"""Process start to the first timed request: imports, device, data from
the seed, service start, first submission of each query (compile or
cache load), warm-up."""


def read(run):
    return run["setup_s"]
