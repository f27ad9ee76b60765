"""parse / optimize / plan: per query, `analysis + optimization +
planning` of the engine's phase times; the median over the window."""

from benchmark.harness import stats


def read(run):
    ms = [sum(q["phase_times_s"].get(k, 0.0)
              for k in ("analysis", "optimization", "planning")) * 1e3
          for r in run["requests"] for q in r["queries"]
          if q.get("phase_times_s")]
    return stats.median(ms) if ms else None
