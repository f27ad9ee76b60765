"""mesh: the least time the cell's chips together could take for a
request's scan-aggregate work (`harness/work.py`'s count of bytes and
operations against `chips` times one chip's peaks from `peaks.json`)
over the busy time per request in the trace, which is the mean over
the chips. `agg_roofline` divides by one chip's peak and so does not
apply to a cell of several."""

from benchmark.harness import work


def read(run):
    t = run["trace"]
    if not t or not t.get("busy_s") or not run["requests"]:
        return None
    chips = run["cell"].chips
    peak = {k: chips * run["peak"][k] for k in ("bytes_per_s", "ops_per_s")}
    least = work.least_seconds(run["work"], peak)["seconds"]
    return 100.0 * least / (t["busy_s"] / len(run["requests"]))
