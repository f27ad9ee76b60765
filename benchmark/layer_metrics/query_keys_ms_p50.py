"""executor: per request, what a query spends on the keys it looks its
own earlier work up by, each a rendering of a plan tree and most of
them a `cache_token` (a `stat` of every file of every scan):
`stream.verdict` (a resident query's walk for what streams: a scan's
estimated rows, the device-table cache's key, the residency verdict),
`replan.key` (the re-plan capacities' key and their application),
`stage.lookup` (the stage cache's key and its get, once a dispatch
attempt) and `plan.fingerprint` (the data cache's key); the median
over requests. A program without these spans reads nothing."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "stream.verdict", "replan.key",
                               "stage.lookup", "plan.fingerprint")
