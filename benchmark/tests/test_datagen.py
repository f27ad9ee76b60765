"""The generator at SF0.01: row counts, types, seed-determinism."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from benchmark.datagen import lineitem as G

SF, PARTS = 0.01, 3


def _whole(seed):
    return pa.concat_tables([G.part_table(SF, seed, PARTS, p)
                             for p in range(PARTS)])


def test_rows_and_types():
    t = _whole(2147483659)
    # 15,000 orders of 1..7 lines, 4 on average
    assert 57_000 < t.num_rows < 63_000
    assert t.num_columns == 16
    types = {f.name: f.type for f in t.schema}
    for c in ("l_orderkey", "l_partkey", "l_suppkey"):
        assert types[c] == pa.int64()
    for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"):
        assert types[c] == pa.decimal128(15, 2)
    for c in ("l_shipdate", "l_commitdate", "l_receiptdate"):
        assert types[c] == pa.date32()
    for c in ("l_returnflag", "l_linestatus", "l_shipmode",
              "l_shipinstruct", "l_comment"):
        assert types[c] == pa.string()
    t.validate(full=True)
    assert set(t["l_returnflag"].to_pylist()) == {"A", "N", "R"}
    assert set(t["l_linestatus"].to_pylist()) == {"F", "O"}
    assert set(t["l_shipmode"].to_pylist()) == set(G.SHIPMODES)
    assert set(t["l_shipinstruct"].to_pylist()) == set(G.SHIPINSTRUCT)
    # clause 4.2.2: a text string of 10..43 characters, 26.5 on average,
    # all but every row its own
    comments = t["l_comment"].to_pylist()
    lengths = [len(c) for c in comments]
    assert min(lengths) == 10 and max(lengths) == 43
    assert 26 < sum(lengths) / len(lengths) < 27
    assert len(set(comments)) > 0.99 * len(comments)
    # clause 4.2.3: the price is the quantity times the part's retail
    # price; ship dates run to 1998-12-01, past Q1's 1998-09-02
    def cents(c):
        return np.array([int(v.scaleb(2)) for v in t[c].to_pylist()])

    part = t["l_partkey"].to_numpy()
    retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    assert 90000 <= retail.min() and retail.max() <= 209899
    assert (cents("l_extendedprice")
            == cents("l_quantity") // 100 * retail).all()
    ship = t["l_shipdate"].cast(pa.int32()).to_numpy()
    order_last = G.LAST_ORDER
    assert G.START < ship.min() and order_last < ship.max() <= order_last + 121
    keys = t["l_orderkey"].to_numpy()
    assert keys.min() == 1 and keys.max() == 15_000
    assert (keys[1:] >= keys[:-1]).all()


def test_the_seed_decides_the_rows():
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    assert _whole(big).equals(_whole(big))
    assert not _whole(big).equals(_whole(big + 1))


def test_parts_are_written_once_and_found_again(tmp_path):
    from concurrent.futures import ThreadPoolExecutor
    root = str(tmp_path)
    with ThreadPoolExecutor(2) as pool:
        d, futures = G.submit(pool, SF, 7, PARTS, root)
        assert len(futures) == PARTS
        rows = G.finish(d, futures, PARTS)
        assert rows == G.rows(d) == pq.read_table(d).num_rows
        assert pq.read_metadata(
            os.path.join(d, "part-0000.parquet")).num_row_groups == G.GROUPS
        again, none = G.submit(pool, SF, 7, PARTS, root)
        assert again == d and none == []
        # another seed's data takes the place of this one's
        d2, f2 = G.submit(pool, SF, 8, PARTS, root)
        G.finish(d2, f2, PARTS)
        assert G.find(root, 7, PARTS) is None
        assert G.find(root, 8, PARTS) is not None
        assert os.listdir(root) == ["seed8"]
