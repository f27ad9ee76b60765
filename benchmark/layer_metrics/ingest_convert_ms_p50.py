"""ingest: per request, the sum of the `chunk.convert` spans (one per
column on the consumer thread: Arrow to the padded numpy buffer:
combine, decimal limb copy, cast, pad); the median over requests."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "chunk.convert")
