"""HTTP front end and codec: per query, the client's time minus the
status record's `elapsed_ms` (admission to result); the median."""

from benchmark.harness import stats


def read(run):
    ms = [q["client_ms"] - q["elapsed_ms"]
          for r in run["requests"] for q in r["queries"]
          if q.get("elapsed_ms") is not None]
    return stats.median(ms) if ms else None
