"""ingest: per request, the sum of the `chunk.put` spans (one per column
on the consumer thread: the `jax.device_put` calls returning, i.e.
staging, not the transfer's end); the median over requests. The
`ingest_put_bytes` counter over it is a rate."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "chunk.put")
