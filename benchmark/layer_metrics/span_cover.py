"""Shared by the two readers that ask what no span names
(`request_unnamed_pct`, `idle_unnamed_pct`): a query's engine spans as
cover, and the part of an interval that a cover leaves bare. Every
span counts but a container that has work of its own, for which its
children stand: `streaming` covers a streamed request from end to end,
so counting it would hide what its children leave open. The harness
hands a reader `name`, `t0`, `t1` of each span, on the client's clock
(`harness/entries.py`)."""

from benchmark.harness.trace import union_ns

CONTAINERS = ("streaming",)


def cover(query):
    """(t0, t1) of the query's spans, the containers left out."""
    return [(s["t0"], s["t1"]) for s in query.get("spans") or []
            if s["name"] not in CONTAINERS]


def bare(lo, hi, intervals):
    """The length of [lo, hi) that no interval covers: the intervals
    are clipped to it, and where they overlap they count once."""
    return (hi - lo) - union_ns((max(a, lo), min(b, hi))
                                for a, b in intervals if b > lo and a < hi)
