"""What the cell `tpch-sf1.q1q6` brought: the Q6 reference against
`spark_tpu/tpch/golden.py`'s `q6` (an independent pandas
implementation), the three readers of the dispatch path on hand-made
runs, and the rest of a run with only Q6 broken underneath (the cell's
request is Q1 then Q6, so the faults of `test_faults.py` break both):
an altered Q6 answer, Q6 with its filter left out, Q6 with one conjunct
left out."""

import os
from decimal import Decimal

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.datagen import lineitem as G
from benchmark.harness import spec
from benchmark.reference import q6
from benchmark.tests import rehearsal
from benchmark.tests.test_span_metrics import run_of, span

SF, PARTS, SEED = 0.01, 3, 2147483659
CELL = "tpch-sf1.q1q6"


def test_q6_equals_the_pandas_golden(tmp_path):
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
    # the filter keeps about 1.9% of the rows: each conjunct has rows
    # to cut, so leaving one out reads wrong
    kept = q6.kept_rows(str(d))
    assert 0.015 * G.rows(str(d)) < kept < 0.023 * G.rows(str(d))
    got = q6.compute({}, {"lineitem": str(d)}, None)
    assert got["keys"] == ["one"] and got["table"]["one"] == [1]
    (revenue,) = got["table"]["revenue"]
    assert isinstance(revenue, Decimal) and revenue.as_tuple().exponent == -4
    want = golden.q6(str(tmp_path))["revenue"][0]
    assert float(revenue) == pytest.approx(float(want), rel=1e-12)


@pytest.mark.parametrize("metric, name", [
    ("dispatch_launch_ms_p50", "dispatch.launch"),
    ("dispatch_sync_ms_p50", "dispatch.sync")])
def test_a_dispatch_reader_sums_both_queries_of_a_request(metric, name):
    read = spec.module("layer_metrics", metric).read
    run = run_of(
        [[span(name, 0.0, 0.030), span("dispatch", 0.0, 0.040)],
         [span(name, 1.0, 1.010)]],
        [[span(name, 0.0, 0.020)], [span(name, 1.0, 1.004)]],
        [[span(name, 0.0, 0.050)], []])
    assert read(run) == pytest.approx(40.0)
    assert read(run_of([[span("dispatch", 0.0, 1.0)]])) is None
    assert read({"requests": []}) is None


def test_stage_dispatches_per_request_is_the_counters_growth():
    read = spec.module("layer_metrics", "stage_dispatches_per_request").read
    name = "spark_tpu_stage_dispatches"
    run = {"requests": [{}] * 20, "counters_before": {name: 4.0},
           "counters_after": {name: 44.0}}
    assert read(run) == 2.0
    # a program without the counter (the parent commit) says nothing
    assert read(dict(run, counters_after={})) is None
    assert read(dict(run, requests=[])) is None


def _with_q6_text(monkeypatch, edit):
    """Rehearse the cell with Q6's text edited: what a program that
    dropped that part of the query would answer."""
    small = rehearsal.small_cell

    def edited(workload, size="rehearsal"):
        cell = small(workload, size)
        for q in cell.queries:
            if q["name"] == "q6":
                text = edit(q["text"])
                assert text != q["text"]
                q["text"] = text
        return cell

    monkeypatch.setattr(rehearsal, "small_cell", edited)


def test_an_altered_q6_answer_is_not_correct(tmp_path, monkeypatch):
    from spark_tpu.execution.executor import QueryExecution
    sound = QueryExecution.collect

    def altered(self):
        table = sound(self)
        if "revenue" not in table.column_names:
            return table
        i = table.column_names.index("revenue")
        (revenue,) = table.column(i).to_pylist()
        one_unit = Decimal(1).scaleb(revenue.as_tuple().exponent)
        return table.set_column(
            i, table.field(i),
            pa.array([revenue + one_unit], type=table.field(i).type))

    monkeypatch.setattr(QueryExecution, "collect", altered)
    line = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    assert line["correct"] is False
    # one ten-thousandth of a dollar in a million dollars
    assert 0 < line["compared"]["value_gap"]["value"] < 1e-9
    assert line["compared"]["rows_off"]["value"] == 0
    assert line["failed"] == line["attempted"]


@pytest.mark.parametrize("left_out", [
    "where", "l_shipdate >=", "l_shipdate <", "l_discount between",
    "l_quantity <"])
def test_q6_with_its_filter_left_out_is_not_correct(left_out, tmp_path,
                                                    monkeypatch):
    def edit(text):
        if left_out == "where":
            return text[:text.index("where")] + ") q6\n"
        lines = [ln for ln in text.splitlines()
                 if left_out not in ln]
        out = "\n".join(lines)
        # the conjunct that now comes first carries no `and`
        return out.replace("where\n        and ", "where\n        ")

    _with_q6_text(monkeypatch, edit)
    line = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    assert line["correct"] is False, line["_stderr"]
    assert line["compared"]["value_gap"]["value"] > 0.1
    assert line["compared"]["not_ok"]["value"] == 0
