"""The roofline's bytes and operations for both cells, by hand."""

import pytest

from benchmark.harness import spec, work


def test_q1_bytes_by_hand():
    q = spec.load_cell("tpch-sf10.q1").queries[0]
    rows = 60_000_000
    w = work.query_work(q, rows, 4)
    # 4 decimals x 8 + 2 dictionary codes x 4 + 1 date x 4 = 44 B a row;
    # an output row: 2 codes x 4 + 7 decimals x 8 + 1 count x 8 = 72 B
    assert w["bytes"] == rows * 44 + 4 * 72
    assert w["ops"] == rows * 8
    least = work.least_seconds(w, work.peaks("TPU v5 lite"))
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(2.640000288e9 / 819e9)


def test_linear_keys_bytes_by_hand():
    cell = spec.load_cell("agg-linear-keys.sum")
    n, groups = cell.config["rows"], cell.config["groups"]
    assert (n, groups) == (20 << 22, 65536)
    w = work.query_work(cell.queries[0], n, groups)
    assert w["bytes"] == 83_886_080 * 8 + 65536 * 16 == 672_137_216
    assert w["ops"] == 83_886_080
    least = work.least_seconds(w, work.peaks("TPU v5 lite"))
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(0.00082068, rel=1e-5)


def test_an_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        work.peaks("TPU v9 imaginary")
