"""Runtime-filter subsystem tests: injection rule (plan == plan style
predicates), golden TPC-H parity with filters on/off, metric
observability, and the mesh test asserting probe-side shuffled rows
drop on a selective join."""

import numpy as np
import pandas as pd
import pytest

from spark_tpu import functions as F
from spark_tpu.functions import col, lit

RTF_KEY = "spark_tpu.sql.runtimeFilter.enabled"
THRESH_KEY = "spark_tpu.sql.runtimeFilter.creationSideThreshold"
MESH_KEY = "spark_tpu.sql.mesh.size"
BCAST_KEY = "spark_tpu.sql.autoBroadcastJoinThreshold"


@pytest.fixture
def tables(session):
    rs = np.random.RandomState(7)
    fact = pd.DataFrame({
        "k": rs.randint(0, 1000, 20000).astype(np.int64),
        "v": np.arange(20000, dtype=np.int64)})
    dim = pd.DataFrame({
        "k2": np.arange(1000, dtype=np.int64),
        "flag": (np.arange(1000) % 10).astype(np.int64),
        "name": [f"n{i % 37}" for i in range(1000)]})
    session.register_table("rtf_fact", fact)
    session.register_table("rtf_dim", dim)
    return session


def _selective_join(session):
    d = session.table("rtf_dim").filter(col("flag") == lit(0))
    return session.table("rtf_fact").join(
        d, left_on=col("k"), right_on=col("k2"))


def _count_rf(plan) -> int:
    from spark_tpu.plan import physical as P
    seen = [0]

    def walk(n):
        if isinstance(n, P.RuntimeFilterExec):
            seen[0] += 1
        for c in n.children:
            walk(c)

    walk(plan)
    return seen[0]


# -- injection rule -----------------------------------------------------------

def test_injected_when_build_selective(tables):
    plan = _selective_join(tables)._qe().executed_plan
    assert _count_rf(plan) == 1, plan.tree_string()


def test_not_injected_without_selective_build(tables):
    df = tables.table("rtf_fact").join(
        tables.table("rtf_dim"), left_on=col("k"), right_on=col("k2"))
    plan = df._qe().executed_plan
    assert _count_rf(plan) == 0, plan.tree_string()


def test_not_injected_when_disabled(tables):
    tables.conf.set(RTF_KEY, False)
    plan = _selective_join(tables)._qe().executed_plan
    assert _count_rf(plan) == 0, plan.tree_string()


def test_not_injected_over_creation_threshold(tables):
    tables.conf.set(THRESH_KEY, 64)  # bytes: everything is too big
    plan = _selective_join(tables)._qe().executed_plan
    assert _count_rf(plan) == 0, plan.tree_string()


def test_not_injected_on_left_outer(tables):
    d = tables.table("rtf_dim").filter(col("flag") == lit(0))
    df = tables.table("rtf_fact").join(
        d, left_on=col("k"), right_on=col("k2"), how="left")
    plan = df._qe().executed_plan
    assert _count_rf(plan) == 0, plan.tree_string()


def test_creation_side_descends_through_build_join(tables):
    """The build side is itself a join; the filter must extract the
    chain the key column originates from (InjectRuntimeFilter's
    extractSelectiveFilterOverScan shape, the TPC-H Q3 top join)."""
    d = tables.table("rtf_dim").filter(col("flag") == lit(0))
    mid = tables.table("rtf_fact").filter(col("v") < lit(10000)).join(
        d, left_on=col("k"), right_on=col("k2"))
    big = tables.table("rtf_fact").join(
        mid, left_on=col("v"), right_on=col("v"))
    plan = big._qe().executed_plan
    assert _count_rf(plan) >= 1, plan.tree_string()


# -- execution parity + metrics ----------------------------------------------

def _run_with_metrics(df):
    qe = df._qe()
    qe.execute_batch()
    got = df.to_pandas().sort_values("v").reset_index(drop=True)
    return got, qe.last_metrics


def test_parity_and_metrics_single_chip(tables):
    got, metrics = _run_with_metrics(_selective_join(tables))
    rtf = {k: v for k, v in metrics.items() if k.startswith("rtf_")}
    assert rtf.get("rtf_tested_rf0", 0) == 20000, rtf
    assert rtf.get("rtf_pruned_rf0", 0) > 0, rtf
    # host milliseconds of trace time (ExecContext.host), merged into
    # last_metrics beside the device's counts
    assert isinstance(rtf.get("rtf_build_ms_rf0"), float), rtf
    tables.conf.set(RTF_KEY, False)
    want, metrics_off = _run_with_metrics(_selective_join(tables))
    assert not any(k.startswith("rtf_") for k in metrics_off)
    pd.testing.assert_frame_equal(got, want)


def test_parity_string_keys(tables):
    """Dictionary-encoded string keys hash by VALUE: two independently
    encoded dictionaries must agree through the filter."""
    def build():
        d = tables.table("rtf_dim").filter(col("flag") == lit(3))
        return (tables.table("rtf_dim")
                .join(d, left_on=col("name"), right_on=col("name"))
                .group_by(col("flag")).agg(F.count().alias("c")))

    got = build().to_pandas().sort_values("flag").reset_index(drop=True)
    tables.conf.set(RTF_KEY, False)
    want = build().to_pandas().sort_values("flag").reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want)


def test_mesh_shuffled_rows_drop(tables):
    """On a selective shuffle join over the mesh, the probe exchange
    must route measurably fewer rows with runtime filters on — the
    rows never crossing ICI is the whole point of the subsystem."""
    tables.conf.set(BCAST_KEY, 1)  # force the shuffle strategy
    tables.conf.set(MESH_KEY, 8)

    def probe_exchange_tag(plan):
        from spark_tpu.plan import physical as P
        hit = []

        def walk(n):
            if isinstance(n, P.JoinExec) and \
                    isinstance(n.children[0], P.ExchangeExec):
                hit.append(n.children[0].tag)
            for c in n.children:
                walk(c)

        walk(plan)
        assert hit, plan.tree_string()
        return hit[0]

    def routed(enabled):
        tables.conf.set(RTF_KEY, enabled)
        qe = _selective_join(tables)._qe()
        qe.execute_batch()
        tag = probe_exchange_tag(qe.executed_plan)
        m = qe.last_metrics
        rtf = {k: v for k, v in m.items() if k.startswith("rtf_")}
        return m[f"exch_rows_{tag}"], rtf

    on_rows, rtf_on = routed(True)
    off_rows, rtf_off = routed(False)
    assert rtf_on.get("rtf_pruned_rf0", 0) > 0, rtf_on
    assert not rtf_off
    # with the filter, the probe exchange routes only surviving rows
    assert on_rows < off_rows, (on_rows, off_rows)

    # and results stay identical
    tables.conf.set(RTF_KEY, True)
    got = (_selective_join(tables).to_pandas()
           .sort_values("v").reset_index(drop=True))
    tables.conf.set(RTF_KEY, False)
    want = (_selective_join(tables).to_pandas()
            .sort_values("v").reset_index(drop=True))
    pd.testing.assert_frame_equal(got, want)


def test_all_null_string_key_does_not_crash(session):
    """An all-None object key column becomes an all-NULL string column
    with a 0-entry dictionary; the filter kernel must not jnp.take from
    the empty hash table (it crashed the whole query)."""
    left = pd.DataFrame({"s": pd.Series([None, None], dtype=object),
                         "v": np.arange(2, dtype=np.int64)})
    right = pd.DataFrame({"s2": ["a", "b", "c", "d"],
                          "flag": np.array([0, 1, 0, 1], dtype=np.int64)})
    session.register_table("rtf_null_l", left)
    session.register_table("rtf_null_r", right)

    def build():
        r = session.table("rtf_null_r").filter(col("flag") == lit(0))
        return session.table("rtf_null_l").join(
            r, left_on=col("s"), right_on=col("s2"))

    got = build().to_pandas()
    session.conf.set(RTF_KEY, False)
    want = build().to_pandas()
    assert len(got) == 0 and len(want) == 0


def test_nan_build_key_does_not_poison_bounds(session):
    """A valid (non-NULL) NaN among the float build keys must not
    poison the min/max bounds: NaN propagating through min/max made
    every probe compare False and silently emptied the join."""
    left = pd.DataFrame({"fk": np.arange(100, dtype=np.float64),
                         "v": np.arange(100, dtype=np.int64)})
    base = np.arange(50, dtype=np.float64)
    # sqrt(-1)*sqrt(-1) -> NaN, computed (not ingested); index 8 has
    # flag == 0, so the NaN SURVIVES the build-side filter and reaches
    # the bounds computation
    base[8] = -1.0
    right = pd.DataFrame({"rk": base,
                          "flag": (np.arange(50) % 2).astype(np.int64)})
    session.register_table("rtf_nan_l", left)
    session.register_table("rtf_nan_r", right)

    def build():
        r = (session.table("rtf_nan_r").filter(col("flag") == lit(0))
             .select((F.sqrt(col("rk")) * F.sqrt(col("rk"))).alias("k2"),
                     col("flag")))
        return session.table("rtf_nan_l").join(
            r, left_on=col("fk"), right_on=col("k2"))

    got = build().to_pandas().sort_values("v").reset_index(drop=True)
    session.conf.set(RTF_KEY, False)
    want = build().to_pandas().sort_values("v").reset_index(drop=True)
    assert len(want) > 0  # the join itself must match real rows
    pd.testing.assert_frame_equal(got, want)


# -- TPC-H golden parity with filters on/off ---------------------------------

@pytest.mark.parametrize("qname", ["q3", "q5"])
def test_tpch_golden_parity_on_off(session, tmp_path_factory, qname):
    from spark_tpu.tpch import golden as G
    from spark_tpu.tpch import queries as Q
    from spark_tpu.tpch.datagen import write_parquet

    path = str(tmp_path_factory.mktemp("tpch_rtf") / "sf")
    write_parquet(path, 0.002)
    Q.register_tables(session, path)

    def norm(df):
        out = df.copy()
        for c in out.columns:
            if len(out) and out[c].dtype == object and \
                    out[c].iloc[0].__class__.__name__ == "Decimal":
                out[c] = out[c].astype(float)
        if qname == "q5":
            out = out.sort_values("n_name")
        return out.reset_index(drop=True)

    session.conf.set(RTF_KEY, True)
    qe = Q.QUERIES[qname](session)._qe()
    assert _count_rf(qe.executed_plan) >= 1, qe.executed_plan.tree_string()
    qe.execute_batch()
    pruned = sum(v for k, v in qe.last_metrics.items()
                 if k.startswith("rtf_pruned_"))
    assert pruned > 0, qe.last_metrics
    got = norm(Q.QUERIES[qname](session).to_pandas())
    session.conf.set(RTF_KEY, False)
    off = norm(Q.QUERIES[qname](session).to_pandas())
    # byte-identical: same dtypes, same values, same order
    pd.testing.assert_frame_equal(got, off)
    want = norm(G.GOLDEN[qname](path)) if qname == "q5" else \
        G.GOLDEN[qname](path)
    G.compare(got, want)


def test_second_execution_of_a_filtered_join_asks_for_the_same_stage(tables):
    """The pruned-row counts used to re-seed the guarded join's output
    capacity DOWN after the converged run, "for the next compile": the
    next execution's stage key was new and it compiled again. That went
    (PR 37): what a run converged to is what the next run asks for, so
    the stage cache's misses grow on the first execution only, and the
    milliseconds of the trace that made the stage are still reported
    on a hit, from the host's record beside the stage-cache entry."""
    misses = tables.metrics.counter("compile_cache_misses")
    qe = _selective_join(tables)._qe()
    qe.execute_batch()
    assert qe.last_metrics["rtf_tested_rf0"] == 20000
    assert qe.last_metrics["rtf_pruned_rf0"] > 0
    build_ms = qe.last_metrics["rtf_build_ms_rf0"]
    assert isinstance(build_ms, float) and build_ms > 0
    after_first = misses.value
    for _ in range(2):
        qe2 = _selective_join(tables)._qe()
        _, flags, _ = qe2.execute_batch()
        assert misses.value == after_first
        assert not any(bool(v) for k, v in flags.items()
                       if k.startswith("join_overflow_")), flags
        assert qe2.last_metrics["rtf_build_ms_rf0"] == build_ms
        assert qe2.executed_plan.describe() == qe.executed_plan.describe()


def test_event_log_carries_rtf_metrics(tables, tmp_path):
    from spark_tpu import history
    log_dir = str(tmp_path / "events")
    tables.conf.set("spark_tpu.sql.eventLog.dir", log_dir)
    _selective_join(tables)._qe().execute_batch()
    tables.conf.set("spark_tpu.sql.eventLog.dir", "")
    df = history.read_event_log(log_dir)
    assert any(c.startswith("rtf_pruned_") for c in df.columns), df.columns
    summary = history.runtime_filter_summary(df)
    assert len(summary) >= 1
    row = summary.iloc[-1]
    assert row["tested"] == 20000 and row["pruned"] > 0
    assert 0.0 < row["ratio"] <= 1.0


# -- semi-aware creation sides (runtimeFilter.semiAwareCreation) --------------

SEMI_KEY = "spark_tpu.sql.runtimeFilter.semiAwareCreation"


def _count_creation_semis(plan) -> int:
    from spark_tpu.plan import physical as P
    seen = [0]

    def walk(n):
        if isinstance(n, P.JoinExec) and n.how == "left_semi" \
                and n.creation_side:
            seen[0] += 1
        for c in n.children:
            walk(c)

    walk(plan)
    return seen[0]


@pytest.fixture
def semi_tables(session):
    session.conf.set(THRESH_KEY, 1 << 30)
    # t3 carries BOTH a physical k (disjoint from probe keys) and x
    # (the real join domain); t2 is the selective other side
    session.register_table("sa_t3", pd.DataFrame({
        "k": np.array([100, 101, 102, 103], dtype=np.int64),
        "x": np.array([1, 2, 3, 4], dtype=np.int64)}))
    session.register_table("sa_t4", pd.DataFrame({
        "m": np.array([1, 2, 3, 4], dtype=np.int64)}))
    session.register_table("sa_t2", pd.DataFrame({
        "j": np.array([1, 2], dtype=np.int64), "tag": ["a", "b"]}))
    session.register_table("sa_probe", pd.DataFrame({
        "k": np.arange(0, 200, dtype=np.int64),
        "v": np.arange(0, 200, dtype=np.int64)}))
    return session


def _semi_query(session):
    """Build side passes through an equi-join against selective sa_t2:
    the creation descent can inherit the tag='a' narrowing."""
    build = session.table("sa_t3").join(
        session.table("sa_t2").filter(col("tag") == lit("a")),
        left_on=col("x"), right_on=col("j"))
    return session.table("sa_probe").join(
        build, left_on=col("k"), right_on=col("x"))


def _shadowed_query(session):
    """The descent must pass THROUGH a Project that aliases x onto the
    name k while the underlying sa_t3 keeps a same-named physical k:
    name-resolution alone would bind the semi to the wrong column."""
    inner = session.table("sa_t3").join(
        session.table("sa_t4"), left_on=col("x"), right_on=col("m"))
    shadow = inner.select(col("x").alias("k"), col("k").alias("orig"))
    build = shadow.join(
        session.table("sa_t2").filter(col("tag") == lit("a")),
        left_on=col("k"), right_on=col("j"))
    return session.table("sa_probe").join(
        build, left_on=col("k"), right_on=col("k"))


def test_semi_aware_synthesizes_creation_semi(semi_tables):
    plan = _semi_query(semi_tables)._qe().executed_plan
    assert _count_creation_semis(plan) >= 1, plan.tree_string()
    semi_tables.conf.set(SEMI_KEY, False)
    plan_off = _semi_query(semi_tables)._qe().executed_plan
    assert _count_creation_semis(plan_off) == 0, plan_off.tree_string()


def test_semi_aware_parity_on_off(semi_tables):
    on = _semi_query(semi_tables).to_pandas() \
        .sort_values("v").reset_index(drop=True)
    semi_tables.conf.set(SEMI_KEY, False)
    off = _semi_query(semi_tables).to_pandas() \
        .sort_values("v").reset_index(drop=True)
    pd.testing.assert_frame_equal(on, off)
    assert len(on) > 0  # non-vacuous: some probe rows survive


def test_semi_aware_skips_shadowing_project(semi_tables):
    """Regression: a Project aliasing a different expr onto a join-key
    name (while the relation keeps a same-named physical column) must
    NOT synthesize a semi — binding by name would build the filter
    from a non-superset and silently drop matching probe rows."""
    on = _shadowed_query(semi_tables).to_pandas() \
        .sort_values("v").reset_index(drop=True)
    plan = _shadowed_query(semi_tables)._qe().executed_plan
    # the outer probe filter must not carry an unsound creation semi:
    # the only sound semi here is the one over the benign inner join
    semi_tables.conf.set(SEMI_KEY, False)
    off = _shadowed_query(semi_tables).to_pandas() \
        .sort_values("v").reset_index(drop=True)
    pd.testing.assert_frame_equal(on, off)
    assert len(on) == 1, on  # probe k=1 matches build x=1/tag=a
