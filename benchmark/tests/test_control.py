"""The control comes out as not correct, at a size a test can hold; the
exact reference in the program's place comes out correct."""

import pytest

from benchmark.harness import cell as C
from benchmark.harness import compare, spec
from benchmark.tests import control, rehearsal


def _tables(cell, pool, tmp_path):
    tables, _ = C.finish_data(
        C.submit_data(cell, pool, 2147483659, str(tmp_path)))
    return tables


@pytest.mark.parametrize("workload", rehearsal.cells())
def test_every_control_fails_the_comparison(workload, tmp_path):
    cell = rehearsal.small_cell(workload, "control")
    with C.worker_pool(cell) as pool:
        verdicts = control.control_verdicts(
            cell, _tables(cell, pool, tmp_path), pool)
    assert verdicts
    for key, v in verdicts.items():
        assert v["correct"] is False, key
        assert v["numbers"]["value_gap"]["value"] > 0, key


@pytest.mark.parametrize("workload", rehearsal.cells())
def test_the_exact_reference_passes(workload, tmp_path):
    cell = rehearsal.small_cell(workload)
    q = cell.queries[0]
    with C.worker_pool(cell) as pool:
        exact = spec.module("reference", q["reference"]).compute(
            cell.config, _tables(cell, pool, tmp_path), pool)
    answer = {c: compare.as_served(v) for c, v in exact["table"].items()}
    v = compare.judge([{"queries": [{"query": q["name"], "status": "ok",
                                     "answer": answer}]}],
                      {q["name"]: exact}, {}, {})
    assert v["correct"] is True
    assert all(n["value"] == 0 for n in v["numbers"].values())
