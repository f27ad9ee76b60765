"""service (whole request): what of a request no span names. Per
request, over its queries: the client's time (each query's send to its
last byte, so a request of two queries is framed query by query and
the harness's own time between them is left out) that the union of the
query's engine spans, clipped to it, does not cover, as a share of
that time; the median over requests. Every span counts but `streaming`
(`span_cover`). A program whose recorder starts after the request
(before PR 39) reads its spans early by that much, and what sticks out
before the send is clipped. A query without spans is left out; a run
without any reads nothing."""

from benchmark.harness import stats
from benchmark.layer_metrics import span_cover


def read(run):
    shares = []
    for r in run["requests"]:
        length = bare = 0.0
        for q in r["queries"]:
            spans = span_cover.cover(q)
            if not spans:
                continue
            length += q["t_done"] - q["t_send"]
            bare += span_cover.bare(q["t_send"], q["t_done"], spans)
        if length > 0:
            shares.append(100.0 * bare / length)
    return stats.median(shares) if shares else None
