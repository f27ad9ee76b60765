"""What the cell `tpch-sf10-q5-mesh4.q5` brought: its entries, the reader
of `exchange_fill_pct` on made-up counters, a rehearsal of the cell on
four of the CPU's virtual devices, and the rest of a run with Q5 broken
underneath: SUPPLIER's part cut, and one shard's LINEITEM rows dropped
before the exchange that hashes them to the shards (under the plan the
cell runs at SF10, which the rehearsal's size takes only from SF10's
estimates)."""

import json
import os

import pyarrow.parquet as pq
import pytest

from benchmark.harness import spec
from benchmark.layer_metrics import exchange_fill_pct
from benchmark.tests import rehearsal
from tests.test_q5_mesh import at_sf10  # noqa: F401 — a fixture

SEED = 2147483659
CELL = "tpch-sf10-q5-mesh4.q5"
MESH4 = "tpch-sf10-mesh4.q1q15max"
NEW = ("exchange_fill_pct",)
#: one chip's peak, one scan a query, nothing streamed
NOT_LISTED = ("agg_roofline", "device_cache_hit_pct", "ingest_stall_ms_p50",
              "ingest_decode_ms_p50", "ingest_put_ms_p50",
              "chunk_launch_ms_p50", "stream_drain_ms_p50")


def _bench():
    with open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cells_entries_stand_last():
    bench = _bench()
    cell = spec.load_cell(CELL, bench)
    assert cell.config["conf"] == {"spark_tpu.sql.mesh.size": 4}
    assert cell.chips == 4 and cell.config["scale_factor"] == 10
    tables = cell.config["tables"]
    assert {t: tables[t]["parts"] for t in tables} == {
        "lineitem": 24, "orders": 24, "customer": 1, "supplier": 1,
        "nation": 1, "region": 1}
    # tpch-sf10's and tpch-sf10-mesh4's LINEITEM: the same files a seed
    assert tables["lineitem"] == spec.load_cell(MESH4, bench).config[
        "tables"]["lineitem"]
    assert [q["reference"] for q in cell.queries] == ["q5"]
    assert {m["name"] for m in cell.end_to_end} == {"rows_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and not names & set(NOT_LISTED)
    for m in cell.per_layer:
        assert callable(spec.module("layer_metrics", m["name"]).read)
    assert bench["configs"][-1]["name"] == cell.config["name"]
    assert bench["workloads"][-1]["name"] == CELL
    assert tuple(m["name"] for m in bench["per_layer"][-len(NEW):]) == NEW
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL
    fill = bench["per_layer"][-1]
    assert fill["workloads"] == [MESH4, CELL] and fill["layer"] == "mesh"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) * 2 \
        <= len(bench["workloads"])


def test_the_fill_reads_the_rows_over_the_slots():
    r = exchange_fill_pct.ROWS
    s = exchange_fill_pct.SLOTS
    run = {"counters_before": {r: 100.0, s: 1000.0},
           "counters_after": {r: 550.0, s: 2000.0}}
    assert exchange_fill_pct.read(run) == pytest.approx(45.0)


def test_the_fill_reads_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, whose program has no slot
    counter, and in a window with no exchange: nothing, and no
    exception."""
    r, s = exchange_fill_pct.ROWS, exchange_fill_pct.SLOTS
    assert exchange_fill_pct.read({"counters_before": {},
                                   "counters_after": {r: 5.0}}) is None
    run = {"counters_before": {r: 5.0, s: 10.0},
           "counters_after": {r: 5.0, s: 10.0}}
    assert exchange_fill_pct.read(run) is None


def test_a_rehearsal_reports_the_cells_metrics(tmp_path):
    line = rehearsal.run(CELL, SEED, True, str(tmp_path))
    assert line["correct"] is True, line["_stderr"]
    assert line["failed"] == 0 and line["attempted"] == 5
    assert line["device"]["count"] >= 4
    for m in rehearsal.small_cell(CELL).per_layer:
        # the Pallas kernel runs on a TPU alone (as in tpch-sf1-q5.q5)
        if m["name"] != "groupby_kernel_ms":
            assert m["name"] in line["metrics"], (m["name"],
                                                  line["_stderr"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["exchange_fill_pct"] <= 100
    assert m["stage_dispatches_per_request"] == 1.0
    assert m["join_widest_rows_per_request"] < m["join_rows_per_request"]
    untraced = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    assert untraced["correct"] is True and untraced["failed"] == 0
    assert set(untraced["metrics"]) == {"rows_per_s", "setup_s"}


# -- planted faults -----------------------------------------------------------

def _wrong(line):
    assert line["correct"] is False, line["_stderr"]
    assert line["compared"]["not_ok"]["value"] == 0
    assert line["failed"] == line["attempted"]
    return line["compared"]


def test_supplier_cut_to_half_its_rows_is_not_correct(tmp_path, monkeypatch):
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
    compared = _wrong(rehearsal.run(CELL, SEED, False, str(tmp_path),
                                    seconds=0.3))
    assert compared["rows_off"]["value"] + compared["value_gap"]["value"] > 0


def test_one_shards_lines_dropped_before_the_exchange_are_not_correct(
        tmp_path, monkeypatch, at_sf10):  # noqa: F811
    """Under the cell's SF10 plan, LINEITEM is hashed to the shards by
    `l_orderkey`: shard 0's lines never leave it, and a quarter of
    every nation's revenue is missing."""
    import jax
    from spark_tpu.columnar import Batch
    from spark_tpu.parallel import shuffle
    sound = shuffle.exchange_hash
    dropped_from = []

    def dropped(batch, key_names, ctx, **kw):
        if tuple(key_names) == ("l_orderkey",):
            dropped_from.append(kw.get("tag"))
            here = jax.lax.axis_index(ctx.axis_name) != 0
            batch = Batch(batch.columns, batch.selection_mask() & here)
        return sound(batch, key_names, ctx, **kw)

    monkeypatch.setattr(shuffle, "exchange_hash", dropped)
    compared = _wrong(rehearsal.run(CELL, SEED, False, str(tmp_path),
                                    seconds=0.3))
    assert dropped_from, "the plan exchanged no LINEITEM by its key"
    assert compared["value_gap"]["value"] > 0.1


def test_the_sound_run_under_the_sf10_plan_is_correct(tmp_path, at_sf10):  # noqa: F811,E501
    """The control of the test above: the same plan left alone."""
    line = rehearsal.run(CELL, SEED, False, str(tmp_path), seconds=0.3)
    assert line["correct"] is True, line["_stderr"]
