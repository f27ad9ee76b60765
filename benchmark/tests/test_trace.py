"""The trace reduction on a small synthetic trace."""

import pytest

from benchmark.harness import trace
from benchmark.layer_metrics import (agg_roofline, device_idle_pct,
                                     groupby_kernel_ms)

MS = 1e6  # ns

# one device, a window of 100 ms holding two requests. A loop
# [10, 50) holds a kernel [10, 40) and a fusion [40, 50); a second
# kernel [60, 80) overlaps a copy [70, 90).
EVENTS = [(10 * MS, 40 * MS, "while.6"),
          (10 * MS, 30 * MS, "dense_groupby_factored.10"),
          (40 * MS, 10 * MS, "fusion.71"),
          (60 * MS, 20 * MS, "dense_groupby_factored.10"),
          (70 * MS, 20 * MS, "copy.1")]
WINDOW = (0.0, 100 * MS)


def test_union_counts_overlap_and_nesting_once():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25


def test_busy_idle_ops_and_gaps():
    r = trace.reduce_events([EVENTS], WINDOW)
    assert r["busy_s"] == pytest.approx(0.070)      # [10,50) + [60,90)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["idle_pct"] == pytest.approx(30.0)
    # the loop is no leaf: its body counts, it does not
    assert "while.6" not in r["ops_s"]
    assert r["ops_s"]["dense_groupby_factored.10"] == pytest.approx(0.050)
    assert r["ops_s"]["fusion.71"] == pytest.approx(0.010)
    assert r["gaps_ns"] == [(0.0, 10 * MS), (50 * MS, 60 * MS),
                            (90 * MS, 100 * MS)]


def test_window_clips_events():
    r = trace.reduce_events([EVENTS], (20 * MS, 65 * MS))
    assert r["busy_s"] == pytest.approx(0.035)      # [20,50) + [60,65)
    assert r["idle_pct"] == pytest.approx(100 * 10 / 45)


def test_two_devices_are_averaged():
    r = trace.reduce_events([EVENTS, [(0.0, 100 * MS, "fusion.1")]], WINDOW)
    assert r["busy_s"] == pytest.approx((0.070 + 0.100) / 2)


def test_nothing_to_read_is_nothing():
    assert trace.reduce_events([], None) == {}
    assert trace.reduce_events([[]], None) == {}


def test_op_name_is_the_instruction_name():
    long = ("%dense_groupby_factored.10 = f32[256,1,136,512]{3,2,1,0} "
            "custom-call(s32[16777216]{0} %fusion.71), "
            "custom_call_target=\"tpu_custom_call\"")
    assert trace.op_name(long) == "dense_groupby_factored.10"
    assert trace.op_name("jit_run(123)") == "jit_run(123)"


def _run(reduced, n_requests=2):
    return {"trace": reduced, "requests": [{}] * n_requests,
            "least": {"seconds": 0.0035, "bound": "bytes"}}


def test_readers_on_the_synthetic_trace():
    run = _run(trace.reduce_events([EVENTS], WINDOW))
    assert groupby_kernel_ms.read(run) == pytest.approx(25.0)  # 50 ms / 2
    assert device_idle_pct.read(run) == pytest.approx(30.0)
    # 3.5 ms least against 35 ms busy a request
    assert agg_roofline.read(run) == pytest.approx(10.0)


def test_readers_return_nothing_without_a_trace_or_a_kernel():
    assert groupby_kernel_ms.read(_run(None)) is None
    assert agg_roofline.read(_run({})) is None
    no_kernel = trace.reduce_events([[(0.0, MS, "fusion.1")]], None)
    assert groupby_kernel_ms.read(_run(no_kernel)) is None


def test_gaps_go_to_the_span_that_covers_them():
    reduced = trace.reduce_events([EVENTS], WINDOW)
    reduced["requests_ns"] = [(0.0, 55 * MS), (55 * MS, 88 * MS)]
    requests = [
        {"t_send": 100.0, "t_done": 100.055, "queries": [{"spans": [
            {"name": "streaming", "t0": 100.0, "t1": 100.012},
            {"name": "dispatch", "t0": 100.012, "t1": 100.055}]}]},
        {"t_send": 200.0, "t_done": 200.033, "queries": [{"spans": []}]}]
    gaps = dict(trace.attribute_gaps(reduced, requests))
    assert gaps["streaming"] == pytest.approx(0.010)          # [0, 10)
    assert gaps["dispatch"] == pytest.approx(0.010)           # [50, 60)
    assert gaps["between requests"] == pytest.approx(0.010)   # [90, 100)
