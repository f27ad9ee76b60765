"""ingest: per request, the sum of the `chunk.unify` spans (dictionary
-encoding a chunk's string columns and unifying their dictionaries
with the stream's, on the thread that decoded it); the median over
requests."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "chunk.unify")
