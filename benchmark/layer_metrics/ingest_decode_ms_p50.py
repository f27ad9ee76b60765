"""ingest: per request, the sum of the `chunk.decode` spans (the prefetch
worker, or the consumer with prefetch off, taking one chunk's record
batches off the Parquet scanner and slicing them: a wait on the
scanner's own read-ahead threads, not CPU time); the median over requests."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "chunk.decode")
