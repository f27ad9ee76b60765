"""PR 39's readers on hand-made runs: the four that sum spans by name,
and the two that ask what no span names, of a request
(`request_unnamed_pct`) and of the device's idle time inside the
requests (`idle_unnamed_pct`)."""

import json
import os

import pytest

from benchmark.harness import spec
from benchmark.layer_metrics import span_cover

SUM_READERS = {
    "frontend_ms_p50": ("http.accept", "http.read", "parse", "finish",
                        "encode", "http.write"),
    "query_keys_ms_p50": ("stream.verdict", "replan.key", "stage.lookup",
                          "plan.fingerprint"),
    "query_events_ms_p50": ("end_event", "stage_event"),
    "stream_setup_ms_p50": ("stream.open", "prefetch.start", "stream.begin"),
}
READERS = sorted(SUM_READERS) + ["idle_unnamed_pct", "request_unnamed_pct"]


def span(name, t0, t1):
    return {"name": name, "t0": t0, "t1": t1}


def query(t_send, t_done, *spans):
    return {"t_send": t_send, "t_done": t_done, "spans": list(spans)}


def run_of(*requests, trace=None):
    """Each request a list of queries; its send is its first query's."""
    return {"requests": [{"t_send": qs[0]["t_send"], "queries": list(qs)}
                         for qs in requests], "trace": trace}


def read(metric, run):
    return spec.module("layer_metrics", metric).read(run)


@pytest.mark.parametrize("metric", READERS)
def test_a_run_without_such_spans_reads_nothing(metric):
    """The parent commit, a cell that bypasses the layer, a record
    whose timeline has aged out: left out of the line, never a fault."""
    trace = {"requests_ns": [(0.0, 1e9)], "gaps_ns": [(0.0, 1e9)]}
    assert read(metric, run_of([{"t_send": 0.0, "t_done": 1.0}],
                               trace=trace)) is None
    assert read(metric, {"requests": [], "trace": None}) is None
    if metric in SUM_READERS:
        assert read(metric, run_of(
            [query(0.0, 1.0, span("dispatch", 0.0, 1.0))])) is None


@pytest.mark.parametrize("metric", sorted(SUM_READERS))
def test_a_sum_reader_takes_its_names_and_the_median(metric):
    names = SUM_READERS[metric]
    # request 1: every name once for 2 ms, over two queries, another
    # name apart; requests 2 and 3: the first name alone, 1 and 50 ms
    first = [span(n, i, i + 0.002) for i, n in enumerate(names)]
    run = run_of(
        [query(0.0, 9.0, *first[:1], span("dispatch", 0.0, 9.0)),
         query(9.0, 18.0, *first[1:])],
        [query(0.0, 1.0, span(names[0], 0.0, 0.001))],
        [query(0.0, 1.0, span(names[0], 0.0, 0.050))])
    assert read(metric, run) == pytest.approx(2.0 * len(names))


def test_request_unnamed_counts_overlaps_once_and_clips():
    # ten seconds of client time; covered [2, 6) by two spans that
    # overlap, and [0, 1) of a span that began before the send (a
    # recorder anchored early), and [9, 10) of one that ends after
    # the last byte: four seconds bare
    q = query(10.0, 20.0, span("parse", 12.0, 15.0),
              span("dispatch", 14.0, 16.0), span("dispatch.sync", 14.5, 15.5),
              span("queue", 8.0, 11.0), span("http.write", 19.0, 23.0))
    assert read("request_unnamed_pct", run_of([q])) == pytest.approx(40.0)
    # `streaming` is a container with work of its own: its children
    # stand for it, so what they leave open shows
    streamed = query(0.0, 10.0, span("streaming", 0.0, 10.0),
                     span("chunk.wait", 1.0, 9.0))
    assert read("request_unnamed_pct", run_of([streamed])) \
        == pytest.approx(20.0)
    assert span_cover.cover(streamed) == [(1.0, 9.0)]
    # the median over requests, each its own share
    def with_bare(x):
        return [query(0.0, 10.0, span("dispatch", 0.0, 10.0 - x))]
    assert read("request_unnamed_pct",
                run_of(with_bare(1.0), with_bare(9.0), with_bare(3.0))) \
        == pytest.approx(30.0)


def test_a_request_of_two_queries_is_framed_query_by_query():
    # each query 4 s on the client's clock with 1 s bare; the two
    # seconds between them are the harness's and count for nothing,
    # and the first query's span does not cover the second's time
    run = run_of([query(0.0, 4.0, span("dispatch", 1.0, 9.0)),
                  query(6.0, 10.0, span("dispatch", 6.0, 9.0)),
                  {"t_send": 10.0, "t_done": 11.0}])  # no timeline: left out
    assert read("request_unnamed_pct", run) == pytest.approx(25.0)


def test_idle_unnamed_reads_the_gaps_inside_the_requests():
    # the trace's clock: request 0 is [100 s, 104 s) there and its
    # send is second 10 of the client's clock
    ns = 1e9
    trace = {"requests_ns": [(100 * ns, 104 * ns)],
             "gaps_ns": [(101 * ns, 103 * ns)]}
    half = run_of([query(10.0, 14.0, span("optimize", 11.0, 12.0))],
                  trace=trace)
    assert read("idle_unnamed_pct", half) == pytest.approx(50.0)
    # a gap all under spans that overlap; `streaming` alone covers none
    full = run_of([query(10.0, 14.0, span("parse", 10.5, 12.5),
                         span("egress", 12.0, 13.5))], trace=trace)
    assert read("idle_unnamed_pct", full) == pytest.approx(0.0)
    none = run_of([query(10.0, 14.0, span("streaming", 10.0, 14.0),
                         span("chunk.launch", 13.5, 13.6))], trace=trace)
    assert read("idle_unnamed_pct", none) == pytest.approx(100.0)
    # what lies between requests is the harness's: a gap that runs
    # from request 0 through to request 1 counts only inside them,
    # and each request's spans sit at its own annotation
    trace = {"requests_ns": [(100 * ns, 104 * ns), (110 * ns, 114 * ns)],
             "gaps_ns": [(103 * ns, 112 * ns)]}
    two = run_of([query(10.0, 14.0, span("egress", 13.0, 14.0))],
                 [query(30.0, 34.0, span("optimize", 30.0, 31.0))],
                 trace=trace)
    assert read("idle_unnamed_pct", two) == pytest.approx(100.0 / 3)
    # no idle time inside a request: nothing to share out
    busy = dict(trace, gaps_ns=[(105 * ns, 109 * ns)])
    assert read("idle_unnamed_pct", dict(two, trace=busy)) is None


def test_bare_is_the_length_no_interval_covers():
    assert span_cover.bare(0.0, 10.0, []) == pytest.approx(10.0)
    assert span_cover.bare(0.0, 10.0, [(-5.0, 2.0), (1.0, 3.0), (8.0, 20.0),
                                       (30.0, 40.0)]) == pytest.approx(5.0)


@pytest.mark.parametrize("metric", READERS)
def test_the_entries_of_benchmark_json(metric):
    with open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry["moves"] == "rows_per_s"
    assert entry["better"] == "lower"
    assert entry["source"] == "program_span"
    assert entry["unit"] == ("%" if metric.endswith("_pct") else "ms")
    # each lists its cells, and every one of them reports `rows_per_s`
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    served = {w["name"] for w in bench["workloads"]
              if w["name"] != "agg-linear-keys.sum"}
    assert set(entry["workloads"]) == {
        "request_unnamed_pct": cells, "idle_unnamed_pct": cells,
        "query_keys_ms_p50": cells, "frontend_ms_p50": served,
        "query_events_ms_p50": served,
        "stream_setup_ms_p50": {"tpch-sf10.q1", "tpch-sf10.q6"}}[metric]
    # new entries stand at the end of their list
    assert [m["name"] for m in bench["per_layer"]].index(metric) \
        >= len(bench["per_layer"]) - 6
