"""What the cell `tpch-sf10-mesh4.q1q15max` brought: the `q15max`
reference against a brute-force recomputation in `Decimal`s at the
`.small.json` sizes, its floating controls, a rehearsal of the cell on
four of the CPU's virtual devices, and the rest of a run with only
`q15max` broken underneath (the cell's request is Q1 then `q15max`, so
the faults of `test_faults.py` break both): one Parquet part left out,
the date filter left out, one shard's partial table dropped before the
exchange, one unit in the last place of the maximum."""

import datetime
import os
from decimal import Decimal

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.harness import cell as C
from benchmark.harness import compare
from benchmark.reference import q15max
from benchmark.tests import rehearsal

SEED = 2147483659
CELL = "tpch-sf10-mesh4.q1q15max"
NEW = ("exchange_ms", "mesh_roofline", "shard_skew_pct",
       "exchange_mb_per_request")


def _directory(size, tmp_path):
    cell = rehearsal.small_cell(CELL, size)
    with C.worker_pool(cell) as pool:
        tables, _ = C.finish_data(
            C.submit_data(cell, pool, SEED, str(tmp_path)))
    return tables["lineitem"]


def _brute_force(directory):
    """The view in Python `Decimal`s, row by row, and how many rows of
    how many the quarter keeps."""
    lo, hi = datetime.date(1996, 1, 1), datetime.date(1996, 4, 1)
    revenue, kept, rows = {}, 0, 0
    for f in sorted(os.listdir(directory)):
        if not f.endswith(".parquet"):
            continue
        t = pq.read_table(os.path.join(directory, f),
                          columns=q15max.COLUMNS).to_pydict()
        for supp, ext, disc, ship in zip(*(t[c] for c in q15max.COLUMNS)):
            rows += 1
            if lo <= ship < hi:
                kept += 1
                revenue[supp] = revenue.get(supp, 0) + ext * (1 - disc)
    return revenue, kept, rows


@pytest.mark.parametrize("size", ["rehearsal", "control"])
def test_the_reference_equals_a_brute_force_recomputation(size, tmp_path):
    d = _directory(size, tmp_path)
    revenue, kept, rows = _brute_force(d)
    # the quarter keeps 3.8% of the rows, some 22 a supplier: the date
    # filter has rows to cut, and every supplier ships in the quarter
    assert 0.03 * rows < kept < 0.045 * rows
    assert len(revenue) >= 100 and kept > 15 * len(revenue)
    got = q15max.compute({}, {"lineitem": d}, None)
    assert got["keys"] == ["one"] and got["table"]["one"] == [1]
    (top,) = got["table"]["max_revenue"]
    assert isinstance(top, Decimal) and top.as_tuple().exponent == -4
    assert top == max(revenue.values())
    suppliers, sums = q15max.view(q15max.partials(d, None))
    assert suppliers.tolist() == sorted(revenue)
    assert [Decimal(int(s)).scaleb(-4) for s in sums] \
        == [revenue[s] for s in sorted(revenue)]


def test_the_floating_controls_read_a_gap(tmp_path):
    d = _directory("control", tmp_path)
    exact = q15max.compute({}, {"lineitem": d}, None)
    gaps = {}
    for precision in q15max.CONTROLS:
        ctl = q15max.compute({}, {"lineitem": d}, None, precision)
        answer = {c: compare.as_served(v) for c, v in ctl["table"].items()}
        v = compare.judge([{"queries": [{"query": "q15max", "status": "ok",
                                         "answer": answer}]}],
                          {"q15max": exact}, {}, {})
        assert v["correct"] is False, precision
        gaps[precision] = v["numbers"]["value_gap"]["value"]
    # a sum over some 22 rows: float64 is a unit in the last place off
    assert 0 < gaps["float64"] < 1e-14 < gaps["float32"] < 1e-5


def test_a_rehearsal_on_four_virtual_devices_reports_the_mesh(tmp_path):
    line = rehearsal.run(CELL, SEED, True, str(tmp_path))
    assert line["correct"] is True, line["_stderr"]
    assert line["failed"] == 0 and line["attempted"] == 4
    assert all(n["value"] == 0 for n in line["compared"].values())
    assert line["device"]["count"] >= 4
    for name in NEW:
        assert name in line["metrics"], (name, line["_stderr"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["exchange_ms"] > 0 and m["exchange_mb_per_request"] > 0
    assert m["shard_skew_pct"] >= 0
    # at a rehearsal's size both scans are held: two stages a request
    assert m["stage_dispatches_per_request"] == 2.0
    assert m["device_cache_hit_pct"] == 100.0


def _with_q15max_text(monkeypatch, edit):
    small = rehearsal.small_cell

    def edited(workload, size="rehearsal"):
        cell = small(workload, size)
        for q in cell.queries:
            if q["name"] == "q15max":
                text = edit(q["text"])
                assert text != q["text"]
                q["text"] = text
        return cell

    monkeypatch.setattr(rehearsal, "small_cell", edited)


def _q15max_gap(line):
    """A fault that leaves Q1 alone shows in `value_gap` by `q15max`."""
    assert line["correct"] is False, line["_stderr"]
    assert line["compared"]["not_ok"]["value"] == 0
    assert line["compared"]["rows_off"]["value"] == 0
    assert line["failed"] == line["attempted"]
    return line["compared"]["value_gap"]["value"]


def test_the_date_filter_left_out_is_not_correct(tmp_path, monkeypatch):
    def edit(text):
        a, b = text.index("where"), text.index("group by")
        return text[:a] + text[b:]

    _with_q15max_text(monkeypatch, edit)
    line = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    assert _q15max_gap(line) > 1.0  # some 26 quarters in place of one


def test_one_part_left_out_is_not_correct(tmp_path, monkeypatch):
    from spark_tpu.io import sources
    scan = sources.ParquetSource.__init__

    def short_scan(self, path, *a, **kw):
        part = os.path.join(str(tmp_path), "short")
        if not os.path.isdir(part):
            os.makedirs(part)
            for f in sorted(f for f in os.listdir(path)
                            if f.endswith(".parquet"))[:-1]:
                os.symlink(os.path.join(path, f), os.path.join(part, f))
        scan(self, part, *a, **kw)

    monkeypatch.setattr(sources.ParquetSource, "__init__", short_scan)
    line = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    assert line["correct"] is False
    assert line["compared"]["value_gap"]["value"] > 0.1


def test_one_shards_partial_table_dropped_is_not_correct(tmp_path,
                                                         monkeypatch):
    """The exchange between chips left out for one of them: shard 0's
    partial sums by supplier never reach the final aggregate. Q1's
    exchange is left alone, so the gap is `q15max`'s."""
    import jax
    from spark_tpu.columnar import Batch
    from spark_tpu.parallel import shuffle
    sound = shuffle.exchange_hash

    def dropped(batch, key_names, ctx, **kw):
        if "l_suppkey" in key_names:
            here = jax.lax.axis_index(ctx.axis_name) != 0
            batch = Batch(batch.columns, batch.selection_mask() & here)
        return sound(batch, key_names, ctx, **kw)

    monkeypatch.setattr(shuffle, "exchange_hash", dropped)
    line = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    assert _q15max_gap(line) > 0.01


def test_one_unit_in_the_last_place_of_the_maximum_is_not_correct(
        tmp_path, monkeypatch):
    from spark_tpu.execution.executor import QueryExecution
    sound = QueryExecution.collect

    def altered(self):
        table = sound(self)
        if "max_revenue" not in table.column_names:
            return table
        i = table.column_names.index("max_revenue")
        (top,) = table.column(i).to_pylist()
        one_unit = Decimal(1).scaleb(top.as_tuple().exponent)
        return table.set_column(
            i, table.field(i),
            pa.array([top + one_unit], type=table.field(i).type))

    monkeypatch.setattr(QueryExecution, "collect", altered)
    line = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    # one ten-thousandth of a dollar in a million dollars
    assert 0 < _q15max_gap(line) < 1e-9
