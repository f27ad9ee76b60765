"""What the cell `tpch-sf1-q5.q5` brought: the SUPPLIER, NATION and
REGION generators against `lineitem.py`'s rows of the same seed and
clause 4.2.3's fixed rows, the `q5` reference against a brute-force
merge in pandas, its floating controls, a rehearsal of the cell on the
CPU, and the rest of a run with Q5 broken underneath: SUPPLIER's part
cut, one predicate left out, one unit in the last place of a revenue."""

import json
import os
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.datagen import lineitem as GL
from benchmark.datagen import nation as GN
from benchmark.datagen import region as GR
from benchmark.datagen import supplier as GS
from benchmark.harness import cell as C
from benchmark.harness import compare, spec
from benchmark.reference import q5
from benchmark.tests import rehearsal

SEED = 2147483659
CELL = "tpch-sf1-q5.q5"
NEW = ("join_widest_rows_per_request",)
SF = 0.01
ASIA = {"CHINA", "INDIA", "INDONESIA", "JAPAN", "VIETNAM"}


def test_the_cells_entries_are_the_issues_and_stand_last():
    with open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = spec.load_cell(CELL, bench)
    assert cell.config["conf"] == {} and cell.chips == 1
    assert set(cell.config["tables"]) == {"lineitem", "orders", "customer",
                                          "supplier", "nation", "region"}
    assert [q["reference"] for q in cell.queries] == ["q5"]
    assert {m["name"] for m in cell.end_to_end} == {"rows_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and "device_cache_hit_pct" not in names
    for m in cell.per_layer:
        assert callable(spec.module("layer_metrics", m["name"]).read)
    # put at the end of their lists
    assert bench["configs"][-1]["name"] == cell.config["name"]
    assert bench["workloads"][-1]["name"] == CELL
    assert tuple(m["name"] for m in bench["per_layer"][-len(NEW):]) == NEW
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            assert m["workloads"][-1] == CELL
    widest = bench["per_layer"][-1]
    assert widest["workloads"] == ["tpch-sf1-join.q3", CELL]


def _tables(tmp_path, seed=SEED):
    cell = rehearsal.small_cell(CELL)
    with C.worker_pool(cell) as pool:
        tables, rows = C.finish_data(
            C.submit_data(cell, pool, seed, str(tmp_path)))
    return tables, rows


# -- the generators -----------------------------------------------------------

def test_supplier_nation_and_region_have_the_clauses_columns_and_rows():
    s = GS.part_table(SF, SEED, 1, 0)
    n = GN.part_table(SF, SEED, 1, 0)
    r = GR.part_table(SF, SEED, 1, 0)
    for t in (s, n, r):
        t.validate(full=True)
    assert (s.num_rows, s.num_columns) == (100, 7)
    assert {f.name: f.type for f in s.schema} == {
        "s_suppkey": pa.int64(), "s_name": pa.string(),
        "s_address": pa.string(), "s_nationkey": pa.int64(),
        "s_phone": pa.string(), "s_acctbal": pa.decimal128(15, 2),
        "s_comment": pa.string()}
    assert s["s_suppkey"].to_pylist() == list(range(1, 101))
    assert s["s_name"][6].as_py() == "Supplier#000000007"
    assert set(s["s_nationkey"].to_pylist()) <= set(range(25))
    for phone, nk in zip(s["s_phone"].to_pylist(),
                         s["s_nationkey"].to_pylist()):
        assert len(phone) == 15 and int(phone[:2]) == nk + 10
    lengths = [len(c) for c in s["s_comment"].to_pylist()]
    assert GS.COMMENT_MIN <= min(lengths) <= max(lengths) <= GS.COMMENT_MAX
    # clause 4.2.3's fixed rows, whatever the scale
    assert GN.part_table(1.0, SEED, 1, 0).equals(n)
    assert n["n_nationkey"].to_pylist() == list(range(25))
    assert list(zip(n["n_name"].to_pylist(),
                    n["n_regionkey"].to_pylist()))[:5] == [
        ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
        ("EGYPT", 4)]
    assert {name for name, rk in zip(n["n_name"].to_pylist(),
                                     n["n_regionkey"].to_pylist())
            if rk == 2} == ASIA
    assert r["r_regionkey"].to_pylist() == list(range(5))
    assert r["r_name"].to_pylist() == ["AFRICA", "AMERICA", "ASIA",
                                       "EUROPE", "MIDDLE EAST"]
    assert {f.name: f.type for f in n.schema} == {
        "n_nationkey": pa.int64(), "n_name": pa.string(),
        "n_regionkey": pa.int64(), "n_comment": pa.string()}
    assert {f.name: f.type for f in r.schema} == {
        "r_regionkey": pa.int64(), "r_name": pa.string(),
        "r_comment": pa.string()}


def test_every_line_has_its_supplier(tmp_path):
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    s = GS.part_table(SF, big, 1, 0)
    li = pa.concat_tables([GL.part_table(SF, big, 2, p) for p in range(2)])
    supp = li["l_suppkey"].to_numpy()
    assert supp.min() == 1 and supp.max() == s.num_rows
    assert len(np.unique(supp)) == s.num_rows  # and every supplier lines
    # every nation has suppliers at SF1
    nations = GS.part_table(1.0, big, 1, 0)["s_nationkey"].to_numpy()
    assert set(np.unique(nations)) == set(range(25))
    assert GS.part_table(SF, big, 1, 0).equals(s)
    assert not GS.part_table(SF, big + 1, 1, 0).equals(s)


def test_the_new_tables_are_written_once_and_found_again(tmp_path):
    tables, rows = _tables(tmp_path)
    assert rows["supplier"] == 100 and rows["nation"] == 25
    assert rows["region"] == 5 and rows["lineitem"] > 50_000
    cell = rehearsal.small_cell(CELL)
    with C.worker_pool(cell) as pool:
        data = C.submit_data(cell, pool, SEED, str(tmp_path))
    assert all(not futures for _g, _d, futures, _p in data.values())


# -- the reference ------------------------------------------------------------

def _merged(tables):
    """Q5 by pandas merges, in Python `Decimal`s."""
    import datetime

    def read(name):
        return pq.read_table(tables[name]).to_pandas()

    r = read("region")
    n = read("nation").merge(r[r["r_name"] == "ASIA"],
                             left_on="n_regionkey", right_on="r_regionkey")
    s = read("supplier").merge(n, left_on="s_nationkey",
                               right_on="n_nationkey")
    o = read("orders")
    o = o[(o["o_orderdate"] >= datetime.date(1994, 1, 1))
          & (o["o_orderdate"] < datetime.date(1995, 1, 1))]
    c = read("customer").merge(o, left_on="c_custkey", right_on="o_custkey")
    li = pq.read_table(tables["lineitem"], columns=q5.COLUMNS).to_pandas()
    m = li.merge(c, left_on="l_orderkey", right_on="o_orderkey").merge(
        s, left_on=["l_suppkey", "c_nationkey"],
        right_on=["s_suppkey", "s_nationkey"])
    m["revenue"] = [e * (1 - d) for e, d in zip(m["l_extendedprice"],
                                                m["l_discount"])]
    return {k: sum(v, Decimal(0))
            for k, v in m.groupby("n_name")["revenue"]}, len(m), len(li)


def test_the_reference_equals_a_brute_force_merge(tmp_path):
    tables, _ = _tables(tmp_path)
    want, joined, lines = _merged(tables)
    # the region, the year and the shared nation keep about a quarter
    # of a percent of the lines: each has rows to cut
    assert 0.001 * lines < joined < 0.006 * lines
    assert set(want) == ASIA
    got = q5.compute({}, tables, None)
    assert got["keys"] == q5.KEYS
    assert dict(zip(got["table"]["n_name"], got["table"]["revenue"])) == want
    assert all(isinstance(v, Decimal) and v.as_tuple().exponent == -4
               for v in got["table"]["revenue"])


def test_the_floating_controls_read_a_gap(tmp_path):
    cell = rehearsal.small_cell(CELL, "control")
    with C.worker_pool(cell) as pool:
        tables, _ = C.finish_data(
            C.submit_data(cell, pool, SEED, str(tmp_path)))
    exact = q5.compute({}, tables, None)
    gaps = {}
    for precision in q5.CONTROLS:
        ctl = q5.compute({}, tables, None, precision)
        answer = {c: compare.as_served(v) for c, v in ctl["table"].items()}
        v = compare.judge([{"queries": [{"query": "q5", "status": "ok",
                                         "answer": answer}]}],
                          {"q5": exact}, {}, {})
        assert v["correct"] is False, precision
        assert v["numbers"]["rows_off"]["value"] == 0
        gaps[precision] = v["numbers"]["value_gap"]["value"]
    assert 0 < gaps["float64"] < 1e-13 < gaps["float32"] < 1e-4


# -- the cell, rehearsed ------------------------------------------------------

def test_a_rehearsal_reports_the_joins_metrics(tmp_path):
    line = rehearsal.run(CELL, SEED, True, str(tmp_path))
    assert line["correct"] is True, line["_stderr"]
    assert line["failed"] == 0 and line["attempted"] == 5
    assert all(n["value"] == 0 for n in line["compared"].values())
    cell = rehearsal.small_cell(CELL)
    for m in cell.per_layer:
        # `aggregate.kernelMode` auto runs the Pallas kernel on a TPU
        # alone: on the CPU the aggregate scatters, and the chip's
        # traced run reads it (PERF.md section 5)
        if m["name"] != "groupby_kernel_ms":
            assert m["name"] in line["metrics"], (m["name"],
                                                  line["_stderr"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    widest = m["join_widest_rows_per_request"]
    assert widest == int(widest) > 0
    assert widest < m["join_rows_per_request"]
    assert m["stage_dispatches_per_request"] == 1.0
    # the second warm-up request compiled nothing the window needs
    untraced = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    assert untraced["correct"] is True and untraced["failed"] == 0
    assert set(untraced["metrics"]) == {"rows_per_s", "setup_s"}


def test_the_new_reader_reads_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, whose program has no such
    counter: nothing, and no exception."""
    from benchmark.layer_metrics import join_widest_rows_per_request as w
    run = {"requests": [{}], "counters_before": {}, "counters_after": {}}
    assert w.read(run) is None
    run = {"requests": [{}, {}], "counters_before": {w.COUNTER: 10.0},
           "counters_after": {w.COUNTER: 410.0}}
    assert w.read(run) == 200.0


def test_a_program_of_before_the_domain_estimate_is_told_so_at_once(
        tmp_path, monkeypatch):
    """The driver runs a new cell at the parent too, under this PR's
    benchmark files; a program that registers no `join_widest_rows`
    plans Q5 with an 18 M-row join at SF1, so `supplier.submit` ends the
    run with the reason once its part is under way."""
    from spark_tpu.observability import metrics
    GS.needs_a_program_that_orders_q5()     # this program: nothing
    monkeypatch.setattr(metrics, "is_registered_metric",
                        lambda name: name != GS.WIDEST_COUNTER)
    cell = rehearsal.small_cell(CELL)
    with pytest.raises(SystemExit, match="join_widest_rows"):
        with C.worker_pool(cell) as pool:
            C.submit_data(cell, pool, SEED, str(tmp_path))


# -- planted faults -----------------------------------------------------------

def _wrong(line):
    assert line["correct"] is False, line["_stderr"]
    assert line["compared"]["not_ok"]["value"] == 0
    assert line["failed"] == line["attempted"]
    return line["compared"]


def _with_text(monkeypatch, edit):
    small = rehearsal.small_cell

    def edited(workload, size="rehearsal"):
        cell = small(workload, size)
        for q in cell.queries:
            text = edit(q["text"])
            assert text != q["text"]
            q["text"] = text
        return cell

    monkeypatch.setattr(rehearsal, "small_cell", edited)


def test_supplier_cut_to_half_its_rows_is_not_correct(tmp_path, monkeypatch):
    """SUPPLIER is one part: the scan is handed a part of its first
    half, and the lines of the other half's suppliers drop out."""
    from spark_tpu.io import sources
    scan = sources.ParquetSource.__init__

    def short_scan(self, path, name, *a, **kw):
        if name == "supplier":
            part = os.path.join(str(tmp_path), "short")
            if not os.path.isdir(part):
                os.makedirs(part)
                t = pq.read_table(path)
                pq.write_table(t.slice(0, t.num_rows // 2),
                               os.path.join(part, "part-0000.parquet"))
            path = part
        scan(self, path, name, *a, **kw)

    monkeypatch.setattr(sources.ParquetSource, "__init__", short_scan)
    line = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    compared = _wrong(line)
    assert compared["rows_off"]["value"] + compared["value_gap"]["value"] > 0


@pytest.mark.parametrize("conjunct,grows", [
    ("    and r_name = 'ASIA'\n", "rows"),
    ("    and c_nationkey = s_nationkey\n", "values")])
def test_one_predicate_left_out_is_not_correct(conjunct, grows, tmp_path,
                                               monkeypatch):
    def edit(text):
        assert text.count(conjunct) == 1
        return text.replace(conjunct, "")

    _with_text(monkeypatch, edit)
    line = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    compared = _wrong(line)
    if grows == "rows":  # every region's nations answer
        assert compared["rows_off"]["value"] == line["attempted"]
    else:  # every nation's revenue grows some 25 times
        assert compared["rows_off"]["value"] == 0
        assert compared["value_gap"]["value"] > 5


def test_one_unit_in_the_last_place_of_a_revenue_is_not_correct(
        tmp_path, monkeypatch):
    from spark_tpu.execution.executor import QueryExecution
    sound = QueryExecution.collect

    def altered(self):
        table = sound(self)
        if "revenue" not in table.column_names:
            return table
        i = table.column_names.index("revenue")
        values = table.column(i).to_pylist()
        values[-1] += Decimal(1).scaleb(values[-1].as_tuple().exponent)
        return table.set_column(i, table.field(i),
                                pa.array(values, type=table.field(i).type))

    monkeypatch.setattr(QueryExecution, "collect", altered)
    line = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    compared = _wrong(line)
    assert compared["rows_off"]["value"] == 0
    # one ten-thousandth of a dollar in some hundred thousand dollars
    assert 0 < compared["value_gap"]["value"] < 1e-8
