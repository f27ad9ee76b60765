"""Percentiles and the rate, with a window that holds a stall."""

import pytest

from benchmark.end_to_end import (request_ms_p50, request_ms_p95, rows_per_s,
                                  setup_s)
from benchmark.harness import stats


def test_percentile_interpolates_between_order_statistics():
    v = [10.0, 20.0, 30.0, 40.0]
    assert stats.percentile(v, 0.5) == 25.0
    assert stats.percentile(v, 0.95) == pytest.approx(38.5)
    assert stats.percentile([7.0], 0.95) == 7.0
    assert stats.median([3.0, 1.0, 2.0]) == 2.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def _run(times_ms, rows=1000, failed=()):
    requests = [{"client_ms": t, "failed": i in failed}
                for i, t in enumerate(times_ms)]
    return {"requests": requests, "window_s": sum(times_ms) / 1e3,
            "work": {"rows": rows}, "setup_s": 12.5}


def test_a_stall_lowers_the_rate_though_the_median_stands():
    steady = _run([100.0] * 10)
    stalled = _run([100.0] * 9 + [1100.0])  # one request waits a second
    assert request_ms_p50.read(steady) == request_ms_p50.read(stalled) == 100
    assert rows_per_s.read(steady) == pytest.approx(10 * 1000 / 1.0 / 1e6)
    assert rows_per_s.read(stalled) == pytest.approx(10 * 1000 / 2.0 / 1e6)
    assert request_ms_p95.read(stalled) > 500      # the tail shows it too


def test_a_failed_request_scans_no_rows_but_its_time_counts():
    run = _run([100.0] * 10, failed={3})
    assert rows_per_s.read(run) == pytest.approx(9 * 1000 / 1.0 / 1e6)
    assert setup_s.read(run) == 12.5
