"""The rest of a run with the timed path broken underneath: `correct`
has to come out false. (The harness's look for a TPU is skipped; the
sizes are the rehearsal's.) The faults these cells can have:

- an answer altered where it is produced (`QueryExecution.collect`,
  which both the service and the DataFrame API go through);
- half of the rows left out (a scan sees every second Parquet part, a
  range is half as long), the sums taken over the rest;
- a query that recovered (a fault event, a fault counter that grew).

A step that returns its state unchanged and an exchange between chips
left out do not apply: the cells keep no state between requests and
take one chip.
"""

import os

import numpy as np
import pyarrow as pa
import pytest

from benchmark.harness import compare
from benchmark.tests import rehearsal

SEED = 2147483659
CELLS = rehearsal.cells()


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(workload, tmp_path):
    line = rehearsal.run(workload, SEED, False, str(tmp_path), seconds=0.3)
    assert line["correct"] is True, line["_stderr"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert list(line)[-2] == "compared"  # last but the test's own key
    assert all(n["value"] == 0 for n in line["compared"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_an_altered_answer_is_not_correct(workload, tmp_path, monkeypatch):
    from spark_tpu.execution.executor import QueryExecution
    sound = QueryExecution.collect

    def altered(self):
        table = sound(self)
        i = table.num_columns - 1          # a count or a sum, not a key
        col = table.column(i).to_pylist()
        col[0] = col[0] + 1                # one unit in one row
        return table.set_column(i, table.field(i),
                                pa.array(col, type=table.field(i).type))

    monkeypatch.setattr(QueryExecution, "collect", altered)
    line = rehearsal.run(workload, SEED, False, str(tmp_path), seconds=0.3)
    assert line["correct"] is False
    assert line["compared"]["value_gap"]["value"] > 0
    assert line["failed"] == line["attempted"]
    assert line["metrics"]["rows_per_s"]["value"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_half_of_the_rows_left_out_is_not_correct(workload, tmp_path,
                                                  monkeypatch):
    from spark_tpu import SparkTpuSession
    from spark_tpu.io import sources
    scan, span = sources.ParquetSource.__init__, SparkTpuSession.range

    def half_scan(self, path, *a, **kw):
        part = os.path.join(str(tmp_path), "half")
        if not os.path.isdir(part):
            os.makedirs(part)
            for f in sorted(os.listdir(path))[::2]:
                if f.endswith(".parquet"):
                    os.symlink(os.path.join(path, f), os.path.join(part, f))
        scan(self, part, *a, **kw)

    monkeypatch.setattr(sources.ParquetSource, "__init__", half_scan)
    monkeypatch.setattr(SparkTpuSession, "range",
                        lambda self, n, *a, **kw: span(self, n // 2))
    line = rehearsal.run(workload, SEED, False, str(tmp_path), seconds=0.3)
    assert line["correct"] is False
    assert line["compared"]["value_gap"]["value"] > 0.1


def _request(answer, **extra):
    return {"queries": [dict({"query": "q", "status": "ok",
                              "answer": answer}, **extra)]}


REF = {"q": {"keys": ["k"], "table": {"k": np.arange(3),
                                      "s": np.array([10, 20, 30])}}}
GOOD = {"k": np.arange(3), "s": np.array([10, 20, 30])}


def test_a_recovered_query_is_not_correct():
    ok = compare.judge([_request(GOOD)], REF, {}, {})
    assert ok["correct"] and ok["numbers"]["recovered"]["value"] == 0
    ev = compare.judge([_request(GOOD, fault_events=[{"action": "retry"}])],
                       REF, {}, {})
    assert not ev["correct"] and ev["numbers"]["recovered"]["value"] == 1
    grew = compare.judge([_request(GOOD)], REF,
                         {"spark_tpu_fault_oom_rung": 1.0},
                         {"spark_tpu_fault_oom_rung": 3.0})
    assert not grew["correct"] and grew["numbers"]["recovered"]["value"] == 2


def test_rows_missing_twice_or_unanswered_are_not_correct():
    missing = {"k": np.arange(2), "s": np.array([10, 20])}
    twice = {"k": np.array([0, 1, 1]), "s": np.array([10, 20, 30])}
    for bad in (missing, twice, None):
        v = compare.judge([_request(bad)], REF, {}, {})
        assert not v["correct"] and v["numbers"]["rows_off"]["value"] == 1
    late = compare.judge([{"queries": [{"query": "q", "answer": None,
                                        "status": "unanswered"}]}],
                         REF, {}, {})
    assert not late["correct"] and late["numbers"]["not_ok"]["value"] == 1
    # rows in another order are the same answer
    shuffled = {"k": np.array([2, 0, 1]), "s": np.array([30, 10, 20])}
    assert compare.judge([_request(shuffled)], REF, {}, {})["correct"]
