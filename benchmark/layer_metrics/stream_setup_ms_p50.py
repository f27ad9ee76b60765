"""executor: per request, a streamed scan's set-up on the consumer
thread: `stream.open` (the chunk driver from its entry to the first
chunk's taking: retriers, the source's chunk stream, the prefetcher, a
checkpoint's skip), `prefetch.start` (the prefetch worker's thread made
and started, before the first wait for a chunk) and `stream.begin`
(the chunk program found or built on the first chunk); the median over
requests. A request that streams nothing, or a program without these
spans, reads nothing."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "stream.open", "prefetch.start",
                               "stream.begin")
