"""ingest: the share of the window's table scans that the device-table
cache answered: growth of `device_cache_hits` over the scans the
window's queries made (a query file's `rows` names its table). A
streamed scan never asks the cache and reads 0."""

COUNTER = "spark_tpu_device_cache_hits"


def read(run):
    per_request = sum(1 for q in run["cell"].queries
                      if isinstance(q["rows"], dict) and "table" in q["rows"])
    scans = per_request * len(run["requests"])
    if not scans or COUNTER not in run["counters_after"]:
        return None
    hits = run["counters_after"][COUNTER] \
        - run["counters_before"].get(COUNTER, 0.0)
    return 100.0 * hits / scans
