"""executor: per query, the engine's `execution` phase plus `streaming`
(where a chunk driver ran, ingest waits included); the median."""

from benchmark.harness import stats


def read(run):
    ms = [sum(q["phase_times_s"].get(k, 0.0)
              for k in ("execution", "streaming")) * 1e3
          for r in run["requests"] for q in r["queries"]
          if q.get("phase_times_s")]
    return stats.median(ms) if ms else None
