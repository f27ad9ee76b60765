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


def _selective_join(session, flag=0):
    d = session.table("rtf_dim").filter(col("flag") == lit(flag))
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


# -- a filter that hands on its survivors compacted (PR 38) -------------------

def _rf_nodes(plan):
    from spark_tpu.plan import physical as P
    found, seen = [], set()

    def walk(n):
        if id(n) in seen:
            return
        seen.add(id(n))
        if isinstance(n, P.RuntimeFilterExec):
            found.append(n)
        for c in n.children:
            walk(c)

    walk(plan)
    return found


def _set_cap(plan, cap):
    nodes = _rf_nodes(plan)
    assert len(nodes) == 1, plan.tree_string()
    nodes[0].out_cap = cap
    return nodes[0]


@pytest.fixture
def engaged(monkeypatch):
    """The capacity loop's rule for compacting a filter engages on a
    probe of the tests' size (the engine's own floor is a probe of
    65,536 slots: executor.FILTER_COMPACT_MIN_SLOTS)."""
    from spark_tpu.execution import executor
    monkeypatch.setattr(executor, "FILTER_COMPACT_MIN_SLOTS", 1024)


def _probe_and_build():
    """A probe of 5,000 rows in 8,192 slots, every third row out of its
    selection, with a nullable and a dictionary column; and the build
    side's 100 keys."""
    import jax.numpy as jnp
    import pyarrow as pa
    from spark_tpu.columnar import Batch
    rs = np.random.RandomState(11)
    n = 5000
    k = rs.randint(0, 1000, n).astype(np.int64)
    v = pa.array(np.arange(n, dtype=np.int64),
                 mask=(np.arange(n) % 7 == 0))
    s = pa.array([None if i % 11 == 0 else f"s{i % 13}"
                  for i in range(n)])
    d = pa.array(rs.randint(8000, 9000, n).astype(np.int32),
                 type=pa.date32())
    probe = Batch.from_arrow(pa.table({"k": k, "v": v, "s": s, "d": d}))
    iota = jnp.arange(probe.capacity)
    probe = probe.with_selection(probe.selection_mask() & (iota % 3 != 0))
    build = Batch.from_arrow(pa.table({
        "k2": np.arange(0, 1000, 10, dtype=np.int64)}))
    return probe, build


@pytest.mark.parametrize("cap", [512, 2048, 64, 8192])
def test_compacted_output_is_the_masked_output_on_its_live_rows(
        session, cap):
    """One filter, masked and compacted: on the live rows, in order,
    every column's data and validity are the same arrays, a dictionary
    is the same dictionary, and the selection is a prefix. A capacity
    under what was kept (64) raises the overflow flag and holds the
    first rows kept; one at or over the probe's (8,192) changes
    nothing."""
    from spark_tpu.expr import ColumnRef
    from spark_tpu.plan import physical as P
    probe, build = _probe_and_build()

    def run(out_cap):
        node = P.RuntimeFilterExec(None, None, ColumnRef("k"),
                                   ColumnRef("k2"), est_items=128,
                                   out_cap=out_cap)
        ctx = P.ExecContext(session.conf)
        return node.compute(ctx, [probe, build]), ctx

    masked, mctx = run(None)
    live = np.asarray(masked.selection_mask())
    kept = int(live.sum())
    assert 64 < kept <= 512 and masked.capacity == 8192
    assert mctx.host["rtf_slots_rf0"] == 8192 and not mctx.flags
    assert int(mctx.metrics["rtf_tested_rf0"]) \
        - int(mctx.metrics["rtf_pruned_rf0"]) == kept

    out, ctx = run(cap)
    for name in ("rtf_tested_rf0", "rtf_pruned_rf0"):
        assert int(ctx.metrics[name]) == int(mctx.metrics[name])
    if cap >= probe.capacity:
        assert out.capacity == probe.capacity and not ctx.flags
        assert (np.asarray(out.selection_mask()) == live).all()
        return
    assert out.capacity == cap == ctx.host["rtf_slots_rf0"]
    assert bool(ctx.flags["rtf_overflow_rf0"]) == (kept > cap)
    n = min(kept, cap)
    assert (np.asarray(out.selection_mask())
            == (np.arange(cap) < kept)).all()
    for name, want in masked.columns.items():
        got = out.columns[name]
        assert got.dtype == want.dtype
        assert got.dictionary is want.dictionary
        assert (np.asarray(got.data)[:n]
                == np.asarray(want.data)[live][:n]).all(), name
        assert (got.validity is None) == (want.validity is None), name
        if want.validity is not None:
            assert (np.asarray(got.validity)[:n]
                    == np.asarray(want.validity)[live][:n]).all(), name


def _sorted(df):
    return df.sort_values("v").reset_index(drop=True)


def test_a_capacity_set_too_small_overflows_and_replans(tables):
    """A compacted filter never drops a row silently: with a capacity
    of 8 set by hand on the node the first attempt raises
    `rtf_overflow_rf0`, the capacity loop grows the capacity to the
    bucket of what the filter kept and runs the stage again, and the
    answer is the masked plan's."""
    from spark_tpu.columnar import bucket_capacity
    want = _sorted(_selective_join(tables).to_pandas())
    dispatches = tables.metrics.counter("stage_dispatches")
    qe = _selective_join(tables)._qe()
    node = _set_cap(qe.executed_plan, 8)
    assert "cap=8, " in node.simple_string()
    before = dispatches.value
    batch, flags, _ = qe.execute_batch()
    assert dispatches.value - before == 2
    marks = [s for s in qe.spans.to_dicts() if s["name"] == "aqe_overflow"]
    assert [m["attrs"]["flags"] for m in marks] == [["rtf_overflow_rf0"]]
    assert not bool(flags["rtf_overflow_rf0"])
    m = qe.last_metrics
    kept = m["rtf_tested_rf0"] - m["rtf_pruned_rf0"]
    assert node.out_cap == bucket_capacity(kept) == m["rtf_slots_rf0"]
    assert not qe.fault_summary
    got = _sorted(batch.to_arrow().to_pandas())
    pd.testing.assert_frame_equal(got, want)


def test_the_capacity_is_learned_for_the_next_execution(tables, engaged):
    """The first execution answers from the masked stage and leaves
    `rtf:rf0` among the converged capacities; the second applies it and
    compiles the compacted stage, once; the third finds it. The
    filter's counts repeat exactly, its slots shrink, the answers are
    the same frame."""
    misses = tables.metrics.counter("compile_cache_misses")
    seen = []
    for _ in range(3):
        before = misses.value
        # a text of its own: the session's stage cache is the module's
        qe = _selective_join(tables, flag=1)._qe()
        batch, flags, _ = qe.execute_batch()
        assert not any(bool(v) for v in flags.values()), flags
        seen.append((misses.value - before, qe.last_metrics,
                     _sorted(batch.to_arrow().to_pandas()),
                     _rf_nodes(qe.executed_plan)[0].out_cap))
    assert [s[0] for s in seen] == [1, 1, 0]
    assert [s[3] for s in seen] == [None, 4096, 4096]
    assert [s[1]["rtf_slots_rf0"] for s in seen] == [32768, 4096, 4096]
    for key in ("rtf_tested_rf0", "rtf_pruned_rf0", "join_rows_j0"):
        assert len({s[1][key] for s in seen}) == 1, key
    for s in seen[1:]:
        pd.testing.assert_frame_equal(s[2], seen[0][2])
    saved = [caps for caps in tables._aqe_caps.values()
             if "rtf:rf0" in caps]
    assert saved and saved[-1]["rtf:rf0"] == 4096


def test_a_filter_that_keeps_most_of_its_probe_is_left_masked(
        tables, engaged):
    """The rule reads the filter's own counts: one that keeps more than
    a quarter of its probe's slots learns no capacity."""
    def half():
        d = tables.table("rtf_dim").filter(col("flag") < lit(5))
        return tables.table("rtf_fact").join(
            d, left_on=col("k"), right_on=col("k2"))

    misses = tables.metrics.counter("compile_cache_misses")
    half()._qe().execute_batch()
    after_first = misses.value
    qe = half()._qe()
    qe.execute_batch()
    assert misses.value == after_first
    m = qe.last_metrics
    assert m["rtf_pruned_rf0"] > 0 and m["rtf_slots_rf0"] == 32768
    assert _rf_nodes(qe.executed_plan)[0].out_cap is None


def test_under_a_mesh_the_filter_stays_masked(tables, engaged):
    """Under `mesh.size` 2 the counts are sums over the shards: nothing
    is learned, and a capacity on the node is not applied."""
    from spark_tpu.parallel.mesh import get_mesh
    tables.conf.set(MESH_KEY, 2)
    want = None
    for cap in (None, 512):
        for _ in range(2):
            qe = _selective_join(tables)._qe()
            node = _set_cap(qe.executed_plan, cap)
            batch, flags, _ = qe.execute_batch()
            assert "rtf_overflow_rf0" not in flags
            # every shard hands on its part of the probe's slots
            assert qe.last_metrics["rtf_slots_rf0"] == 32768
            assert node.out_cap == cap
            if cap is None:
                learned = tables._aqe_caps.get(
                    qe._aqe_cache_key(get_mesh(tables.conf)), {})
                assert not any(k.startswith("rtf:") for k in learned)
            got = _sorted(batch.to_arrow().to_pandas())
            if want is None:
                want = got
            pd.testing.assert_frame_equal(got, want)
    tables.conf.set(MESH_KEY, 0)
    pd.testing.assert_frame_equal(
        _sorted(_selective_join(tables).to_pandas()), want)


def test_the_capacity_shows_where_an_operator_looks(tables, engaged,
                                                    tmp_path):
    """`explain(runtime=True)`, the plan analyzer, the predictions and
    `history.runtime_filter_summary` show or accept the capacity."""
    from spark_tpu import history
    from spark_tpu.analysis.plan_analyzer import analyze_plan
    from spark_tpu.analysis.predictions import predict_plan
    log_dir = str(tmp_path / "events")
    tables.conf.set("spark_tpu.sql.eventLog.dir", log_dir)
    _selective_join(tables)._qe().execute_batch()  # learns
    qe = _selective_join(tables)._qe()
    qe.execute_batch()
    tables.conf.set("spark_tpu.sql.eventLog.dir", "")
    text = qe.explain(runtime=True)
    # 1,954 rows join and the blocked filter lets some 27 more through
    # (the classic one some 540: a bucket of 4,096)
    assert "cap=2048, " in text and "slots out: 2,048" in text

    def codes(plan):
        return [f.code for f in analyze_plan(plan, tables.conf)
                if f.detail and f.detail.get("kind")
                == "runtime_filter.out_cap"]

    assert codes(qe.executed_plan) == []
    odd = _selective_join(tables)._qe().executed_plan
    _set_cap(odd, 3000)
    assert codes(odd) == ["UNBUCKETED_CAPACITY"]
    # the join above the filter is graded against the filter's slots
    join = [p for p in predict_plan(qe.executed_plan, tables.conf)
            if p["kind"] == "join_rows"]
    assert [p["predicted"] for p in join] == [2048]
    summary = history.runtime_filter_summary(
        history.read_event_log(log_dir))
    assert summary["slots"].tolist() == [32768, 2048]
    assert summary["tested"].nunique() == 1
