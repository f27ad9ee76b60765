"""ingest: per request, the growth of the `ingest_stall_ms` counter
(time the chunk driver waited for the host's decode) plus the queries'
`ingest` phase (a resident scan's load); the median over requests."""

from benchmark.harness import stats

COUNTER = "spark_tpu_ingest_stall_ms"


def read(run):
    before = run["counters_before"]
    ms = []
    for r in run["requests"]:
        after = r.get("counters_after")
        if after is None:
            return None
        phases = [q["phase_times_s"] for q in r["queries"]
                  if q.get("phase_times_s")]
        if COUNTER not in after and not any("ingest" in p for p in phases):
            return None
        ms.append(after.get(COUNTER, 0.0) - before.get(COUNTER, 0.0)
                  + sum(p.get("ingest", 0.0) for p in phases) * 1e3)
        before = after
    return stats.median(ms) if ms else None
