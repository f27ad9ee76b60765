"""Set operations (INTERSECT/EXCEPT), grouping analytics (ROLLUP/CUBE/
GROUPING SETS), user accumulators, and transient-failure retry."""

import numpy as np
import pandas as pd
import pytest

from spark_tpu import functions as F
from spark_tpu.functions import col, udf


@pytest.fixture
def two_frames(session):
    l = pd.DataFrame({"k": [1, 2, None, 3], "s": ["a", "b", None, "c"]})
    r = pd.DataFrame({"k": [2, None, 4], "s": ["b", None, "d"]})
    session.register_table("so_l", l)
    session.register_table("so_r", r)
    return session.table("so_l"), session.table("so_r"), l, r


def test_intersect_with_nulls(two_frames):
    a, b, _, _ = two_frames
    out = a.intersect(b).to_pandas().sort_values(
        "k", na_position="last").reset_index(drop=True)
    assert out["k"].tolist()[0] == 2.0
    assert pd.isna(out["k"][1]) and pd.isna(out["s"][1])
    assert len(out) == 2  # NULL row matches NULL row


def test_except_and_subtract(two_frames):
    a, b, _, _ = two_frames
    out = a.except_(b).to_pandas().sort_values("k").reset_index(drop=True)
    assert out["k"].tolist() == [1.0, 3.0]
    assert a.subtract(b).to_pandas().shape == out.shape


def test_sql_intersect_except(session, two_frames):
    out = session.sql(
        "SELECT k FROM so_l INTERSECT SELECT k FROM so_r").to_pandas()
    got = sorted([x for x in out["k"] if not pd.isna(x)])
    assert got == [2.0] and out["k"].isna().sum() == 1
    out2 = session.sql(
        "SELECT k FROM so_l EXCEPT SELECT k FROM so_r").to_pandas()
    assert sorted(out2["k"].dropna()) == [1.0, 3.0]


def test_rollup_cube_grouping_sets(session):
    pdf = pd.DataFrame({"a": ["x", "x", "y", "y"], "b": [1, 2, 1, 2],
                        "v": [10.0, 20.0, 30.0, 40.0]})
    session.register_table("ga_t", pdf)
    roll = session.sql(
        "SELECT a, b, sum(v) AS s FROM ga_t GROUP BY ROLLUP(a, b) "
        "ORDER BY a, b").to_pandas()
    assert len(roll) == 7  # 4 leaves + 2 subtotals + 1 grand total
    grand = roll[roll["a"].isna() & roll["b"].isna()]
    assert grand["s"].tolist() == [100.0]
    sub_x = roll[(roll["a"] == "x") & roll["b"].isna()]
    assert sub_x["s"].tolist() == [30.0]

    cube = session.sql(
        "SELECT a, b, sum(v) AS s FROM ga_t GROUP BY CUBE(a, b) "
        "ORDER BY a, b, s").to_pandas()
    assert len(cube) == 9  # 4 + 2 + 2 + 1
    b_only = cube[cube["a"].isna() & (cube["b"] == 1)]
    assert b_only["s"].tolist() == [40.0]

    gs = session.sql(
        "SELECT a, sum(v) AS s FROM ga_t "
        "GROUP BY GROUPING SETS((a), ()) ORDER BY a").to_pandas()
    assert gs["s"].tolist() == [30.0, 70.0, 100.0][0:len(gs)] or \
        sorted(gs["s"]) == [30.0, 70.0, 100.0]


def test_null_group_keys_merge_after_union(session):
    """The set-op machinery exposed this engine bug: two NULL group keys
    with DIFFERENT dead payloads (e.g. post-union dictionary remap) must
    land in ONE group."""
    l = pd.DataFrame({"s": ["a", None], "v": [1.0, 2.0]})
    r = pd.DataFrame({"s": ["b", None], "v": [4.0, 8.0]})
    u = (session.create_dataframe(l, "ng_l")
         .union(session.create_dataframe(r, "ng_r")))
    out = (u.group_by(col("s")).agg(F.sum(col("v")).alias("sv"))
           .to_pandas())
    null_rows = out[out["s"].isna()]
    assert len(null_rows) == 1
    assert null_rows["sv"].tolist() == [10.0]


def test_user_accumulator_in_udf(session):
    acc = session.long_accumulator("nulls_seen")
    pdf = pd.DataFrame({"x": [1.0, None, 3.0, None]})
    session.register_table("acc_t", pdf)

    @udf(returnType="double")
    def watch(v):
        if v is None:
            acc.add(1)
            return None
        return v

    out = session.table("acc_t").select(watch(col("x")).alias("y")) \
        .to_pandas()
    assert acc.value == 2
    assert out["y"].isna().sum() == 2


def test_transient_failure_retries(session, monkeypatch):
    """A transient (channel-error) stage failure retries with a fresh
    compile instead of surfacing (maxTaskFailures seat)."""
    from spark_tpu.execution.executor import QueryExecution

    calls = {"n": 0}
    orig = QueryExecution._compile_stage

    def flaky(self, root, mesh=None, args=None):
        fn = orig(self, root, mesh, args)
        def wrapper(*a, **k):
            if calls["n"] == 0:
                calls["n"] += 1
                raise RuntimeError(
                    "UNAVAILABLE: Socket closed (simulated)")
            return fn(*a, **k)
        return wrapper

    monkeypatch.setattr(QueryExecution, "_compile_stage", flaky)
    with pytest.warns(UserWarning, match="transient stage failure"):
        out = session.range(100).agg(F.sum(col("id")).alias("s")) \
            .to_pandas()
    assert int(out["s"][0]) == 4950
    assert calls["n"] == 1


def test_intersect_binds_tighter_than_union(session):
    """Code-review r5: standard SQL precedence — INTERSECT before
    UNION. A UNION ALL B INTERSECT C == A UNION ALL (B INTERSECT C)."""
    session.register_table("p1", pd.DataFrame({"a": [1, 2, 2]}))
    session.register_table("p2", pd.DataFrame({"a": [2, 5]}))
    session.register_table("p3", pd.DataFrame({"a": [1, 5]}))
    out = session.sql(
        "SELECT a FROM p1 UNION ALL SELECT a FROM p2 "
        "INTERSECT SELECT a FROM p3").to_pandas()
    assert sorted(out["a"].tolist()) == [1, 2, 2, 5]


def test_rollup_with_qualified_ref_and_bare_grouping_set(session):
    session.register_table("q1t", pd.DataFrame({"a": [1, 2, 2]}))
    out = session.sql(
        "SELECT q1t.a, count(*) AS c FROM q1t GROUP BY ROLLUP(a) "
        "ORDER BY a").to_pandas()
    assert out["c"].tolist() == [3, 1, 2]
    out2 = session.sql(
        "SELECT a, sum(a) AS s FROM q1t "
        "GROUP BY GROUPING SETS (a, ()) ORDER BY a").to_pandas()
    assert out2["s"].tolist() == [5, 1, 4]


def test_except_all_clear_error(session, two_frames):
    a, b, _, _ = two_frames
    from spark_tpu.expr import AnalysisError
    with pytest.raises(AnalysisError, match="EXCEPT ALL"):
        a.exceptAll(b)
