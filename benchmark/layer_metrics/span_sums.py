"""Shared by the readers that go by span name: per request, the sum of
the named engine spans' durations over the request's queries; then the
median over requests. The harness hands a reader `name`, `t0`, `t1` of
each span, so the names are the contract (PERF.md section 3)."""

from benchmark.harness import stats
from benchmark.harness.trace import union_ns


def request_spans(request, names):
    """(t0, t1) of the request's spans with one of `names`."""
    return [(s["t0"], s["t1"]) for q in request["queries"]
            for s in q.get("spans") or [] if s["name"] in names]


def median_ms(run, *names):
    """None where no request holds such a span (the program does not
    record it, or the cell does not pass through it)."""
    per_request = [request_spans(r, names) for r in run["requests"]]
    if not any(per_request):
        return None
    return stats.median([sum(t1 - t0 for t0, t1 in spans) * 1e3
                         for spans in per_request])


def self_time_pct(run, parent, children):
    """Per request, the share of the `parent` spans' length that no
    span named in `children` covers (their union inside it, so children
    that overlap count once); the median over requests. None where no
    request holds both a parent and a child."""
    shares = []
    found_child = False
    for r in run["requests"]:
        inner = request_spans(r, children)
        found_child = found_child or bool(inner)
        length = covered = 0.0
        for p0, p1 in request_spans(r, (parent,)):
            length += p1 - p0
            covered += union_ns((max(a, p0), min(b, p1))
                                for a, b in inner if b > p0 and a < p1)
        if length > 0:
            shares.append(100.0 * (1.0 - covered / length))
    if not shares or not found_child:
        return None
    return stats.median(shares)
