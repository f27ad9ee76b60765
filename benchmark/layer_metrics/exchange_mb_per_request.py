"""mesh: the payload the window's mesh stages routed between shards,
per request: growth of the counter `exchange_bytes` (the sum of every
exchange's live rows times its row width, validity included) over the
window's requests, in MB. A program without the counter reads
nothing."""

COUNTER = "spark_tpu_exchange_bytes"


def read(run):
    if COUNTER not in run["counters_after"] or not run["requests"]:
        return None
    grown = run["counters_after"][COUNTER] \
        - run["counters_before"].get(COUNTER, 0.0)
    return grown / 1e6 / len(run["requests"])
