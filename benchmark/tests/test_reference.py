"""The plain references: Q1 equal to `spark_tpu/tpch/golden.py`'s `q1`
at SF0.01 (an independent pandas implementation), the linear keys equal
to counting them."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from benchmark.datagen import lineitem as G
from benchmark.reference import linear_keys, q1

SF, PARTS, SEED = 0.01, 3, 2147483659


def test_q1_equals_the_pandas_golden(tmp_path):
    from spark_tpu.tpch import golden
    d = tmp_path / "parts"
    d.mkdir()
    tables = []
    for p in range(PARTS):
        G.write_part(SF, SEED, PARTS, p, str(d))
        tables.append(G.part_table(SF, SEED, PARTS, p))
    # the golden reads one file
    pq.write_table(pa.concat_tables(tables),
                   os.path.join(tmp_path, "lineitem.parquet"))
    # the date filter has rows to cut, so leaving it out reads wrong
    ship = pq.read_table(str(d), columns=["l_shipdate"])["l_shipdate"]
    cut = int((ship.cast(pa.int32()).to_numpy() > q1.SHIP_LIMIT).sum())
    assert 0.005 * len(ship) < cut < 0.03 * len(ship)
    want = golden.q1(str(tmp_path))
    got = q1.compute({}, {"lineitem": str(d)}, None)
    assert got["keys"] == ["l_returnflag", "l_linestatus"]
    table = got["table"]
    assert list(table) == list(want.columns)
    assert table["l_returnflag"] == list(want["l_returnflag"])
    assert table["l_linestatus"] == list(want["l_linestatus"])
    assert table["count_order"] == list(want["count_order"])
    for c in want.columns[2:-1]:
        np.testing.assert_allclose([float(v) for v in table[c]], want[c],
                                   rtol=1e-9, atol=1e-6, err_msg=c)


def test_q1_avg_rounds_half_up_at_scale_6():
    # 1.00 / 3 = 0.333333|3 -> 0.333333 ; 2.00 / 3 = 0.666666|6 -> 0.666667
    assert str(q1._avg(100, 2, 3)) == "0.333333"
    assert str(q1._avg(200, 2, 3)) == "0.666667"
    assert str(q1._avg(5, 6, 10)) == "0.000001"  # 0.0000005 rounds up


def test_linear_keys_equals_counting():
    config = {"rows": 1 << 14, "groups": 1 << 6}
    got = linear_keys.compute(config, {}, None)
    ids = np.arange(config["rows"], dtype=np.int64)
    k = ids & (config["groups"] - 1)
    want = np.bincount(k, weights=k).astype(np.int64)
    assert (got["table"]["sum(k)"] == want).all()
    assert (got["table"]["k"] == np.arange(64)).all()
