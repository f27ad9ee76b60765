"""The readers that go by span name, on hand-made runs: sums by name,
the median over requests, `None` without spans, and a union that
counts overlapping children once."""

import json
import os

import pytest

from benchmark.harness import spec
from benchmark.layer_metrics import span_sums

SPAN_READERS = {
    "ingest_decode_ms_p50": "chunk.decode",
    "ingest_unify_ms_p50": "chunk.unify",
    "ingest_convert_ms_p50": "chunk.convert",
    "ingest_put_ms_p50": "chunk.put",
    "chunk_launch_ms_p50": "chunk.launch",
    "stream_drain_ms_p50": "stream.drain",
    "egress_ms_p50": "egress",
}


def span(name, t0, t1):
    return {"name": name, "t0": t0, "t1": t1}


def run_of(*requests):
    """Each request a list of queries, each query a list of spans."""
    return {"requests": [{"queries": [{"spans": q} for q in queries]}
                         for queries in requests]}


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_a_reader_sums_its_name_and_takes_the_median(metric):
    name = SPAN_READERS[metric]
    read = spec.module("layer_metrics", metric).read
    run = run_of(
        # two queries of one request: 10 + 20 + 5 ms, another name apart
        [[span(name, 0.0, 0.010), span(name, 0.5, 0.520),
          span("other", 0.0, 9.0)], [span(name, 1.0, 1.005)]],
        [[span(name, 0.0, 0.100)]],
        [[span(name, 0.0, 0.001)]])
    assert read(run) == pytest.approx(35.0)
    # no such span anywhere (the parent commit, a cell that bypasses
    # the layer, a record without spans): nothing to report
    assert read(run_of([[span("other", 0.0, 1.0)]])) is None
    assert read({"requests": [{"queries": [{}]}]}) is None
    assert read({"requests": []}) is None
    # a request without one counts as nothing spent there
    assert read(run_of([[span(name, 0.0, 0.004)]], [[]])) \
        == pytest.approx(2.0)


def test_streaming_unnamed_counts_overlapping_children_once():
    read = spec.module("layer_metrics", "streaming_unnamed_pct").read
    streaming = span("streaming", 10.0, 20.0)
    run = run_of([[
        streaming,
        span("chunk.wait", 10.0, 12.0),
        span("chunk.to_device", 12.0, 15.0),
        # nested under to_device and on the other thread: not counted
        span("chunk.convert", 12.0, 14.0), span("chunk.decode", 10.0, 20.0),
        # overlaps to_device by one second, and runs past the parent
        span("chunk.launch", 14.0, 16.0), span("stream.drain", 19.5, 21.0)]])
    # covered: [10, 16) and [19.5, 20) of ten seconds
    assert read(run) == pytest.approx(35.0)
    # the median over requests, each its own share
    run = run_of(
        [[streaming, span("chunk.launch", 10.0, 19.0)]],
        [[streaming, span("chunk.launch", 10.0, 15.0)]],
        [[streaming, span("chunk.launch", 10.0, 20.0)]])
    assert read(run) == pytest.approx(10.0)
    # a program that names none of the children says nothing, rather
    # than 100; so does a request that did not stream
    assert read(run_of([[streaming]])) is None
    assert read(run_of([[span("chunk.launch", 0.0, 1.0)]])) is None
    assert read({"requests": []}) is None


def test_span_readers_are_the_ones_benchmark_json_lists():
    with open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]
                  if m["source"] == "program_span"}
    for name in list(SPAN_READERS) + ["streaming_unnamed_pct"]:
        assert listed[name]["moves"] == "rows_per_s"
        assert listed[name]["better"] == "lower"
    assert span_sums.median_ms(run_of([[span("a", 0.0, 1.0)]]), "a", "b") \
        == pytest.approx(1000.0)
