"""The reader of the buffer pool's two counters, on hand-made runs:
the reused share of what the window drew, and `None` from a program
without the counters or a window that filled no chunk."""

import json
import os

import pytest

from benchmark.harness import spec

REUSED = "spark_tpu_ingest_buffers_reused"
ALLOCATED = "spark_tpu_ingest_buffers_allocated"


def run_of(before, after):
    return {"requests": [{}] * 4, "counters_before": before,
            "counters_after": after}


def test_reuse_share_is_the_counters_growth():
    read = spec.module("layer_metrics", "ingest_buffer_reuse_pct").read
    # warm: 7 columns x 4 chunks x 4 requests drawn, none made new
    assert read(run_of({REUSED: 35.0, ALLOCATED: 21.0},
                       {REUSED: 147.0, ALLOCATED: 21.0})) == 100.0
    # a set of seven made new inside the window
    assert read(run_of({REUSED: 35.0, ALLOCATED: 21.0},
                       {REUSED: 140.0, ALLOCATED: 28.0})) \
        == pytest.approx(100.0 * 105 / 112)
    # only new ones: the first stream of a process
    assert read(run_of({}, {ALLOCATED: 21.0})) == 0.0
    # the parent commit has neither counter; a cell that bypasses the
    # stream grows neither
    assert read(run_of({"spark_tpu_ingest_chunks": 4.0},
                       {"spark_tpu_ingest_chunks": 20.0})) is None
    assert read(run_of({REUSED: 35.0, ALLOCATED: 21.0},
                       {REUSED: 35.0, ALLOCATED: 21.0})) is None
    assert read(run_of({}, {})) is None


def test_the_metric_is_listed_for_the_cell_that_streams():
    with open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == "ingest_buffer_reuse_pct"]
    assert entry == {
        "name": "ingest_buffer_reuse_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "ingest",
        "moves": "rows_per_s", "workloads": ["tpch-sf10.q1"]}
