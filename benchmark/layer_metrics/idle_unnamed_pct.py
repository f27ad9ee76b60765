"""device: of the device's idle time inside the requests, the share
that no engine span covers: the host time that holds the chip back and
has no name. The idle time is the trace's gaps (device 0's, as
`attribute_gaps` reads them) clipped to the `bench.request` intervals:
between requests the harness reads `/metrics`, which is not the
program's. The spans are put on the trace's clock as `attribute_gaps`
puts them: request i's send is annotation i's start. Every span counts
but `streaming` (`span_cover`). Over the whole traced window, not a
median. A run without a trace, without spans or without idle time
inside a request reads nothing."""

from benchmark.layer_metrics import span_cover


def read(run):
    t = run.get("trace")
    if not t or not t.get("requests_ns"):
        return None
    idle = bare = 0.0
    found = False
    for (a_ns, b_ns), req in zip(t["requests_ns"], run["requests"]):
        spans = [(a_ns + (s0 - req["t_send"]) * 1e9,
                  a_ns + (s1 - req["t_send"]) * 1e9)
                 for q in req["queries"] for s0, s1 in span_cover.cover(q)]
        found = found or bool(spans)
        for g0, g1 in t.get("gaps_ns") or []:
            lo, hi = max(g0, a_ns), min(g1, b_ns)
            if hi > lo:
                idle += hi - lo
                bare += span_cover.bare(lo, hi, spans)
    if not found or idle <= 0:
        return None
    return 100.0 * bare / idle
