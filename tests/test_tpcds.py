"""TPC-DS tranche-1: golden parity, cost-based join-reorder on/off
identity, and plan-stability snapshots.

The TPC-DS analog of test_tpch/test_sql's parity suites plus the
reference's `TPCDSQueryTestSuite.scala:54` plan-golden discipline:
committed physical-plan snapshots under tests/tpcds_plans/ guard
against silent plan churn (regenerate intentionally with
SPARK_TPU_REGEN_TPCDS_PLANS=1 after a deliberate planner change)."""

import os

import pandas as pd
import pytest

from spark_tpu.tpcds import QUERIES, SQL_QUERIES, register_tables
from spark_tpu.tpcds import golden as G
from spark_tpu.tpcds.datagen import write_parquet

SF = 0.01
CBO_KEY = "spark_tpu.sql.cbo.joinReorder"
PLAN_DIR = os.path.join(os.path.dirname(__file__), "tpcds_plans")

#: queries whose reorder decisions must change the join SEQUENCE at
#: this scale (the acceptance gate: >= 3 multi-join queries reordered;
#: kind "order", not a mere probe/build orientation flip). 10 of the
#: 21 tranche queries re-sequence at SF0.01; these three keep the
#: tier-1 wall-clock down (q61 re-sequences too but costs ~23s alone)
REORDER_CHANGED = ("q19", "q73", "q79")
#: plan-stability snapshot subset
PLAN_SNAPSHOT = ("q3", "q19", "q55", "q73", "q96")


@pytest.fixture(scope="session")
def tpcds_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tpcds") / "sf_small")
    write_parquet(path, SF)
    return path


@pytest.fixture(scope="session")
def tpcds_session(session, tpcds_path):
    register_tables(session, tpcds_path)
    return session


def _norm(df: pd.DataFrame) -> pd.DataFrame:
    return G.normalize_decimals(df.copy())


def _check(got: pd.DataFrame, qname: str, path: str) -> None:
    want = G.GOLDEN[qname](path)
    got = _norm(got)[list(want.columns)].reset_index(drop=True)
    G.compare(got, want, float_atol=1e-4)


@pytest.fixture()
def _no_runtime_filters(tpcds_session):
    """The parity sweeps run with runtime filters OFF: rf injection
    compiles a creation-chain stage per eligible join, which is ~55%
    of the snowflake queries' tier-1 wall-clock, and rf is
    results-identical on/off by design. rf-on TPC-DS coverage lives in
    the reorder and event-log tests, which keep the default."""
    key = "spark_tpu.sql.runtimeFilter.enabled"
    tpcds_session.conf.set(key, False)
    yield
    tpcds_session.conf.set(key, True)


@pytest.mark.parametrize("qname", sorted(SQL_QUERIES))
def test_tpcds_sql_parity(tpcds_session, tpcds_path,
                          _no_runtime_filters, qname):
    got = tpcds_session.sql(SQL_QUERIES[qname]).to_pandas()
    _check(got, qname, tpcds_path)


@pytest.mark.parametrize("qname", sorted(QUERIES))
def test_tpcds_dataframe_parity(tpcds_session, tpcds_path,
                                _no_runtime_filters, qname):
    got = QUERIES[qname](tpcds_session).to_pandas()
    _check(got, qname, tpcds_path)


@pytest.mark.parametrize("qname", REORDER_CHANGED)
def test_tpcds_reorder_on_off_identical(tpcds_session, tpcds_path,
                                        qname):
    """cbo.joinReorder on vs off: byte-identical results, at golden
    parity with the runtime filters on; off restores the frontend order
    (no decisions logged at all)."""
    tpcds_session.conf.set(CBO_KEY, True)
    qe_on = tpcds_session.sql(SQL_QUERIES[qname])._qe()
    on = qe_on.collect().to_pandas()
    assert qe_on.reorder_decisions is not None
    tpcds_session.conf.set(CBO_KEY, False)
    try:
        qe_off = tpcds_session.sql(SQL_QUERIES[qname])._qe()
        off = qe_off.collect().to_pandas()
        assert qe_off.reorder_decisions == []  # rule disabled: no log
    finally:
        tpcds_session.conf.set(CBO_KEY, True)
    pd.testing.assert_frame_equal(on, off)
    _check(on, qname, tpcds_path)
    # a genuine SEQUENCE change (kind "order"), not just a probe/build
    # orientation flip
    changed = [d for d in qe_on.reorder_decisions
               if d["kind"] == "order"]
    assert changed, qe_on.reorder_decisions
    # the physical trees genuinely differ (the order change is not
    # just a log entry)
    assert qe_on.executed_plan.describe() != \
        qe_off.executed_plan.describe()
    # every decision carries the per-join estimates the explain /
    # history surfaces show
    assert all(len(d["est_rows"]) == len(d["order"]) - 1
               for d in qe_on.reorder_decisions)


def test_tpcds_reorder_explain_annotation(tpcds_session):
    qe = tpcds_session.sql(SQL_QUERIES["q19"])._qe()
    text = qe.explain()
    assert "== Join Reorder ==" in text
    assert "reorder: yes" in text
    assert "->" in text  # chosen order arrow
    # an unreordered single-join query reads "reorder: no"
    qe2 = tpcds_session.sql(
        "select count(*) as c from store_sales, date_dim "
        "where ss_sold_date_sk = d_date_sk")._qe()
    assert "reorder: no" in qe2.explain()


def test_tpcds_reorder_event_log_and_grading(tpcds_session, tmp_path):
    """Reorder decisions land in the event log (`reorder` record) and
    the cbo-reorder join estimates are graded by prediction_report."""
    from spark_tpu import history
    tpcds_session.conf.set("spark_tpu.sql.eventLog.dir", str(tmp_path))
    try:
        qe = tpcds_session.sql(SQL_QUERIES["q19"])._qe()
        qe.collect()
    finally:
        tpcds_session.conf.set("spark_tpu.sql.eventLog.dir", "")
    events = history.read_event_log(str(tmp_path))
    assert len(events) == 1
    reorder = events.iloc[0]["reorder"]
    assert reorder["enabled"] and reorder["changed"], reorder
    assert any(d["changed"] for d in reorder["regions"])
    graded = history.grade_predictions(qe.plan_predictions,
                                       qe.last_metrics)
    cbo = [g for g in graded if g["basis"] == "cbo-reorder"]
    assert cbo, graded
    report = history.prediction_report(events)
    assert (report["basis"] == "cbo-reorder").any(), report


def test_parquet_footer_stats(tpcds_path):
    """ParquetSource.column_stats: per-column min/max + null counts
    merged across row groups, cached, no row data touched."""
    from spark_tpu.io.sources import ParquetSource
    src = ParquetSource(os.path.join(tpcds_path, "store_sales.parquet"))
    stats = src.column_stats()
    q = stats["ss_quantity"]
    assert q["min"] == 1 and q["max"] == 100
    assert stats["ss_promo_sk"]["null_count"] > 0
    assert stats["ss_sold_date_sk"]["min"] >= 2450000
    assert src.column_stats() is stats  # cached


def test_reorder_selectivity_uses_footer_stats(tpcds_path):
    """Range selectivities interpolate against footer min/max instead
    of the flat default."""
    from spark_tpu.io.sources import ParquetSource
    from spark_tpu.plan.join_reorder import (SEL_RANGE,
                                             estimate_selectivity)
    from spark_tpu.functions import col, lit
    src = ParquetSource(os.path.join(tpcds_path, "store_sales.parquet"))
    stats = src.column_stats()
    low = estimate_selectivity((col("ss_quantity") <= lit(10)), stats)
    high = estimate_selectivity((col("ss_quantity") <= lit(90)), stats)
    assert low < SEL_RANGE < high
    # no stats for the column -> the flat default
    assert estimate_selectivity((col("nope") <= lit(10)), stats) \
        == SEL_RANGE


@pytest.mark.parametrize("qname", PLAN_SNAPSHOT)
def test_tpcds_plan_stability(tpcds_session, qname):
    """Plan fingerprints are stable across planner runs AND match the
    committed snapshot (the TPCDSQueryTestSuite plan-golden analog).
    Regenerate with SPARK_TPU_REGEN_TPCDS_PLANS=1 after an intended
    planner change."""
    a = tpcds_session.sql(SQL_QUERIES[qname])._qe().executed_plan \
        .describe()
    b = tpcds_session.sql(SQL_QUERIES[qname])._qe().executed_plan \
        .describe()
    assert a == b, f"{qname}: plan fingerprint unstable across runs"
    path = os.path.join(PLAN_DIR, f"{qname}.plan.txt")
    if os.environ.get("SPARK_TPU_REGEN_TPCDS_PLANS"):
        os.makedirs(PLAN_DIR, exist_ok=True)
        with open(path, "w") as f:
            f.write(a + "\n")
    assert os.path.exists(path), \
        f"missing plan golden {path}; regenerate with " \
        f"SPARK_TPU_REGEN_TPCDS_PLANS=1"
    want = open(path).read().rstrip("\n")
    assert a == want, \
        f"{qname}: physical plan drifted from the committed golden " \
        f"(SPARK_TPU_REGEN_TPCDS_PLANS=1 to accept)"
