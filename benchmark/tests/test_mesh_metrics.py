"""The four readers the four-chip cell brought, on hand-made traces and
counters: a collective's device time counted once over nested events
and averaged over the chips, a roofline that scales with `chips`, the
skew of the fullest shard, the payload exchanged a request; and each
reading nothing where the program or the trace holds nothing to read
(the parent commit, a one-chip cell)."""

import json
import os
import types

import pytest

from benchmark.harness import spec, trace, work
from benchmark.layer_metrics import (exchange_mb_per_request, exchange_ms,
                                     mesh_roofline, shard_skew_pct)

US = 1e3  # ns
CELL = "tpch-sf10-mesh4.q1q15max"
NEW = ("exchange_ms", "mesh_roofline", "shard_skew_pct",
       "exchange_mb_per_request")

# two chips, a window of 1 ms holding two requests. On chip 0 a loop
# [0, 400) holds an all-to-all [100, 300), inside which the trace nests
# the all-to-all's own slice [150, 250); then two all-reduces and a
# fusion. Chip 1 spends twice as long in its all-to-all and has no
# nest. XLA:TPU names a collective after its opcode or after the JAX
# primitive it came from (`all_to_all.4`, `psum.2`, `pmax.2`): both
# count.
CHIP0 = [(0 * US, 400 * US, "while.6"),
         (100 * US, 200 * US, "all-to-all.4"),
         (150 * US, 100 * US, "all-to-all.4"),
         (500 * US, 10 * US, "psum.2"),
         (520 * US, 30 * US, "all-reduce-done.2"),
         (600 * US, 300 * US, "fusion.71")]
CHIP1 = [(100 * US, 400 * US, "all_to_all.4"),
         (500 * US, 10 * US, "pmax.2"),
         (520 * US, 30 * US, "all-reduce-done.2"),
         (600 * US, 100 * US, "all_gather.9"),
         (700 * US, 100 * US, "collective-permute.1"),
         (800 * US, 100 * US, "reduce-scatter.3")]
WINDOW = (0.0, 1000 * US)


def _cell(chips):
    return types.SimpleNamespace(chips=chips)


def test_a_collectives_time_is_counted_once_and_averaged_over_chips():
    reduced = trace.reduce_events([CHIP0, CHIP1], WINDOW)
    run = {"trace": reduced, "requests": [{}, {}]}
    # chip 0: the nested slice is the leaf, 100 us, + 10 + 30;
    # chip 1: 400 + 10 + 30 + 100 + 100 + 100; mean, over two requests
    want_us = ((100 + 40) + (400 + 40 + 300)) / 2 / 2
    assert exchange_ms.read(run) == pytest.approx(want_us / 1e3)
    # the loop and the fusion are no collectives
    only = trace.reduce_events([[CHIP0[0], CHIP0[-1]]], WINDOW)
    assert exchange_ms.read({"trace": only, "requests": [{}]}) is None
    assert exchange_ms.read({"trace": None, "requests": [{}]}) is None
    assert exchange_ms.read({"trace": reduced, "requests": []}) is None


def test_the_roofline_scales_with_the_cells_chips():
    peak = {"bytes_per_s": 819e9, "ops_per_s": 197e12}
    w = {"rows": 120_000_000, "bytes": 4_320_000_000, "ops": 540_000_000}
    run = {"trace": {"busy_s": 0.9}, "requests": [{}] * 4, "work": w,
           "peak": peak, "cell": _cell(4)}
    four = mesh_roofline.read(run)
    one = mesh_roofline.read(dict(run, cell=_cell(1)))
    # one chip's peak is what `agg_roofline` divides by
    assert one == pytest.approx(
        100 * work.least_seconds(w, peak)["seconds"] / (0.9 / 4))
    assert four == pytest.approx(one / 4)
    assert 0 < four < 100
    assert mesh_roofline.read(dict(run, trace=None)) is None
    assert mesh_roofline.read(dict(run, trace={"busy_s": 0.0})) is None
    assert mesh_roofline.read(dict(run, requests=[])) is None


@pytest.mark.parametrize("fullest, total, want", [
    (250.0, 1000.0, 0.0),     # four equal shards
    (500.0, 1000.0, 100.0),   # one shard holds twice its share
    (1000.0, 1000.0, 300.0),  # one shard holds everything
])
def test_shard_skew_is_the_fullest_shard_over_an_even_share(fullest, total,
                                                            want):
    run = {"cell": _cell(4),
           "counters_before": {shard_skew_pct.FULLEST: 10.0,
                               shard_skew_pct.TOTAL: 40.0},
           "counters_after": {shard_skew_pct.FULLEST: 10.0 + fullest,
                              shard_skew_pct.TOTAL: 40.0 + total}}
    assert shard_skew_pct.read(run) == pytest.approx(want)


def test_the_counter_readers_read_nothing_without_their_counters():
    """The parent commit has no such counter; a window in which no
    mesh stage ran moves none."""
    quiet = {"cell": _cell(4), "requests": [{}],
             "counters_before": {}, "counters_after": {}}
    assert shard_skew_pct.read(quiet) is None
    assert exchange_mb_per_request.read(quiet) is None
    still = {shard_skew_pct.FULLEST: 5.0, shard_skew_pct.TOTAL: 20.0}
    assert shard_skew_pct.read(dict(quiet, counters_before=still,
                                    counters_after=still)) is None


def test_exchange_mb_per_request_is_the_counters_growth():
    name = exchange_mb_per_request.COUNTER
    run = {"requests": [{}] * 4, "counters_before": {name: 1e6},
           "counters_after": {name: 1e6 + 4 * 2.5e6}}
    assert exchange_mb_per_request.read(run) == pytest.approx(2.5)
    assert exchange_mb_per_request.read(dict(run, requests=[])) is None


def test_the_new_metrics_are_the_cells_alone_and_it_joins_no_one_chip_share():
    with open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "rows_per_s"
        assert listed[name]["layer"] == "mesh"
    # `agg_roofline` divides by one chip's peak
    assert CELL not in listed["agg_roofline"]["workloads"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
