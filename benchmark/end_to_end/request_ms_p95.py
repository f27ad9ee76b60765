"""95th percentile over all requests of the window, client clock."""

from benchmark.harness import stats


def read(run):
    return stats.percentile([r["client_ms"] for r in run["requests"]], 0.95)
