"""Median over all requests of the window, client clock from send to
the last byte of the last answer."""

from benchmark.harness import stats


def read(run):
    return stats.median([r["client_ms"] for r in run["requests"]])
