"""ingest: the share of the padded host buffers the window's streamed
chunks were filled into that had been used before: growth of
`ingest_buffers_reused` over its growth plus that of
`ingest_buffers_allocated` (one a column buffer drawn from the pool /
made new). A program without the counters, or a window in which no
chunk was filled, reads nothing."""

REUSED = "spark_tpu_ingest_buffers_reused"
ALLOCATED = "spark_tpu_ingest_buffers_allocated"


def read(run):
    before, after = run["counters_before"], run["counters_after"]
    reused = after.get(REUSED, 0.0) - before.get(REUSED, 0.0)
    drawn = reused + after.get(ALLOCATED, 0.0) - before.get(ALLOCATED, 0.0)
    return 100.0 * reused / drawn if drawn else None
