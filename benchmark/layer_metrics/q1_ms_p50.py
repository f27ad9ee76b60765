"""service (whole request): the median client time of the traced
requests (send to the last byte of the last answer). The SF10
cell holds six or seven requests in a window, so their median is no
steadier than the rate and stands here, beside it, not among the
end-to-end metrics."""

from benchmark.harness import stats


def read(run):
    return stats.median([r["client_ms"] for r in run["requests"]])
