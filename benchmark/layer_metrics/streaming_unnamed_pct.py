"""executor: the `streaming` span's self time on the consumer thread:
per request, the share of its length that none of `chunk.wait`,
`chunk.to_device`, `chunk.launch` and `stream.drain` covers (their
union, so nothing counts twice); the median over requests. What the
tree does not name of a streamed request."""

from benchmark.layer_metrics import span_sums

CHILDREN = ("chunk.wait", "chunk.to_device", "chunk.launch", "stream.drain")


def read(run):
    return span_sums.self_time_pct(run, "streaming", CHILDREN)
