"""Hash build/probe join kernel + double-buffered ingest tests.

Acceptance bar (ISSUE 7): byte-identical golden parity between
`join.kernelMode=hash` and `sort` across join types (including
many-to-many expansion, null keys, empty/skewed builds, mesh-sharded
probes, and injected `join_build` chaos), the AQE saturation fallback,
kernel-choice heuristics, the `JOIN_HASH_TABLE_PRESSURE` analyzer
finding, and the ingest prefetcher (parity on/off, one-chunk fault
replay via `rec_chunks_replayed`, stall/overlap counters).
"""

import numpy as np
import pandas as pd
import pytest

from spark_tpu.functions import col, lit
from spark_tpu.testing import faults
from spark_tpu.tpch import golden as G
from spark_tpu.tpch import queries as Q
from spark_tpu.tpch.datagen import write_parquet

MODE_KEY = "spark_tpu.sql.join.kernelMode"
LOAD_KEY = "spark_tpu.sql.join.hashLoadFactor"
MAX_PROBE_KEY = "spark_tpu.sql.join.hashMaxProbe"
MAX_SLOTS_KEY = "spark_tpu.sql.join.hashMaxTableSlots"
MIN_ROWS_KEY = "spark_tpu.sql.join.hashMinProbeRows"
RATIO_KEY = "spark_tpu.sql.join.hashProbeBuildRatio"
PREFETCH_KEY = "spark_tpu.sql.ingest.prefetch"
CHUNK_KEY = "spark_tpu.sql.execution.streamingChunkRows"
CACHE_KEY = "spark_tpu.sql.io.deviceCacheBytes"
BUDGET_KEY = "spark_tpu.sql.memory.deviceBudget"
MESH_KEY = "spark_tpu.sql.mesh.size"

SF = 0.002


# -- fixtures ----------------------------------------------------------------

@pytest.fixture
def tables(session):
    rs = np.random.RandomState(11)
    fact = pd.DataFrame({
        "k": rs.randint(0, 700, 20000).astype(np.int64),
        "v": np.arange(20000, dtype=np.int64)})
    # duplicate build keys: the many-to-many expansion path
    dim = pd.DataFrame({
        "k2": np.repeat(np.arange(500, dtype=np.int64), 2),
        "w": np.arange(1000, dtype=np.int64)})
    session.register_table("hj_fact", fact)
    session.register_table("hj_dim", dim)
    return session


@pytest.fixture(scope="session")
def tpch_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tpch_hash_join") / "sf_small")
    write_parquet(path, SF)
    return path


@pytest.fixture(scope="session")
def tpch_session(session, tpch_path):
    Q.register_tables(session, tpch_path)
    return session


def _join_df(session, how):
    return session.table("hj_fact").join(
        session.table("hj_dim"), left_on=col("k"), right_on=col("k2"),
        how=how)


def _both_kernels(session, df_fn):
    """Run `df_fn()` under kernelMode=sort then =hash (cold stage cache
    each time) and return both frames."""
    session.conf.set(MODE_KEY, "sort")
    sort_out = df_fn().to_pandas()
    session.conf.set(MODE_KEY, "hash")
    hash_out = df_fn().to_pandas()
    return sort_out, hash_out


def _hash_ran(qe) -> bool:
    return any(k.startswith("join_table_slots_")
               for k in qe.last_metrics)


# -- kernel-choice heuristics (resolve_kernel / table_slots) -----------------

def test_table_slots_power_of_two(session):
    from spark_tpu.execution import hash_join as HJ
    conf = session.conf
    slots = HJ.table_slots(8192, conf)  # loadFactor 0.5 default
    assert slots == 16384
    assert HJ.table_slots(16, conf) >= 32
    conf.set(MAX_SLOTS_KEY, 1024)
    assert HJ.table_slots(1 << 20, conf) == 1024  # clamped


def test_resolve_kernel_modes(session):
    from spark_tpu.execution import hash_join as HJ
    conf = session.conf
    big, small = 1 << 22, 1 << 10
    assert HJ.resolve_kernel(conf, big, small, None) == "hash"  # auto
    # below hashMinProbeRows: the sort path's probe sorts are cheap
    assert HJ.resolve_kernel(conf, small, small, None) == "sort"
    # near-square join: the table build doesn't amortize
    assert HJ.resolve_kernel(conf, big, big, None) == "sort"
    conf.set(MODE_KEY, "sort")
    assert HJ.resolve_kernel(conf, big, small, None) == "sort"
    conf.set(MODE_KEY, "hash")
    assert HJ.resolve_kernel(conf, small, small, None) == "hash"
    # a saturated previous attempt pins the join to sort
    assert HJ.resolve_kernel(conf, big, small, False) == "sort"
    # maxTableSlots clamp pushing load factor past 0.7: trace-time
    # fallback even under forced hash
    conf.set(MAX_SLOTS_KEY, 1024)
    assert HJ.resolve_kernel(conf, big, 1 << 12, None) == "sort"


def test_auto_keeps_the_sort_kernel_on_a_tpu(session, monkeypatch):
    """On a TPU a gather costs three times a sort's share of a row and
    the hash probe's loop runs as far as the data's longest cluster:
    `auto` keeps every join on the sort kernel there, whatever the
    capacities (Q3 at SF1 on a v5e: 1.4 s a request against 3.9-4.8 s,
    PERF.md, PR 37). A forced `hash` is still a forced hash."""
    import jax
    from spark_tpu.execution import hash_join as HJ
    conf = session.conf
    big, small = 1 << 22, 1 << 10
    assert HJ.kernel_choice(conf, big, small) == ("hash", "auto")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert HJ.kernel_choice(conf, big, small) == ("sort", "tpu")
    assert HJ.kernel_choice(conf, small, small) == ("sort", "tpu")
    conf.set(MODE_KEY, "hash")
    assert HJ.kernel_choice(conf, big, small) == ("hash", "forced")
    conf.set(MODE_KEY, "sort")
    assert HJ.kernel_choice(conf, big, small) == ("sort", "forced")


def test_auto_keeps_sort_on_small_joins(tables):
    """Default auto mode on test-sized joins stays on the sort kernel
    (tier-1 CPU runs never trace the hash path unasked)."""
    qe = _join_df(tables, "inner")._qe()
    qe.execute_batch()
    assert not _hash_ran(qe), qe.last_metrics


# -- kernel parity -----------------------------------------------------------

@pytest.mark.parametrize("how", ["inner", "left", "left_semi",
                                 "left_anti"])
def test_kernel_parity_join_matrix(tables, how):
    """Byte-identical output across kernels, duplicate build keys
    included (many-to-many prefix-sum expansion shared by both)."""
    sort_out, hash_out = _both_kernels(
        tables, lambda: _join_df(tables, how))
    pd.testing.assert_frame_equal(sort_out, hash_out)


def test_kernel_parity_null_keys(session):
    left = pd.DataFrame({
        "k": pd.array([1, None, 3, 4, None, 2], dtype="Int64"),
        "lv": np.arange(6, dtype=np.int64)})
    right = pd.DataFrame({
        "k2": pd.array([2, 3, None, 3], dtype="Int64"),
        "rv": np.arange(4, dtype=np.int64)})
    session.register_table("hj_nl", left)
    session.register_table("hj_nr", right)
    for how in ("inner", "left", "left_semi", "left_anti"):
        sort_out, hash_out = _both_kernels(
            session, lambda: session.table("hj_nl").join(
                session.table("hj_nr"), left_on=col("k"),
                right_on=col("k2"), how=how))
        pd.testing.assert_frame_equal(sort_out, hash_out)
    # anti keeps null-key probe rows; null build keys never match
    session.conf.set(MODE_KEY, "hash")
    anti = session.table("hj_nl").join(
        session.table("hj_nr"), left_on=col("k"), right_on=col("k2"),
        how="left_anti").to_pandas()
    assert set(anti["lv"]) == {0, 1, 3, 4}


def test_kernel_parity_float_keys(session):
    """Float keys hash by canonicalized bit pattern: +-0.0 join equal
    under both kernels."""
    left = pd.DataFrame({
        "k": np.array([0.0, -0.0, 1.5, 2.5, 3.25], dtype=np.float64),
        "lv": np.arange(5, dtype=np.int64)})
    right = pd.DataFrame({
        "k2": np.array([-0.0, 2.5, 99.0], dtype=np.float64),
        "rv": np.arange(3, dtype=np.int64)})
    session.register_table("hj_fl", left)
    session.register_table("hj_fr", right)
    sort_out, hash_out = _both_kernels(
        session, lambda: session.table("hj_fl").join(
            session.table("hj_fr"), left_on=col("k"),
            right_on=col("k2")))
    pd.testing.assert_frame_equal(sort_out, hash_out)
    assert set(hash_out["lv"]) == {0, 1, 3}  # both zeros matched


def test_kernel_parity_empty_build(tables):
    for how in ("inner", "left", "left_semi", "left_anti"):
        sort_out, hash_out = _both_kernels(
            tables, lambda: tables.table("hj_fact").join(
                tables.table("hj_dim").filter(col("w") > lit(10 ** 9)),
                left_on=col("k"), right_on=col("k2"), how=how))
        pd.testing.assert_frame_equal(sort_out, hash_out)


def test_kernel_parity_skewed_keys_near_capacity(session):
    """One hot build key (a long sorted run, not a probe cluster) plus
    a distinct-key population pushed near the table's load-factor
    ceiling."""
    rs = np.random.RandomState(3)
    hot = np.zeros(600, dtype=np.int64)
    cold = np.arange(1, 700, dtype=np.int64)
    build = pd.DataFrame({
        "k2": np.concatenate([hot, cold]),
        "w": np.arange(600 + 699, dtype=np.int64)})
    probe = pd.DataFrame({
        "k": rs.randint(0, 700, 30000).astype(np.int64),
        "v": np.arange(30000, dtype=np.int64)})
    session.register_table("hj_skp", probe)
    session.register_table("hj_skb", build)
    # 1299 build rows bucket past 2048: with maxSlots clamped to 2048
    # the 0.7 ceiling forces the trace-time sort fallback; with the
    # clamp lifted the hash kernel must agree with sort exactly
    for max_slots in (2048, 1 << 26):
        session.conf.set(MAX_SLOTS_KEY, max_slots)
        sort_out, hash_out = _both_kernels(
            session, lambda: session.table("hj_skp").join(
                session.table("hj_skb"), left_on=col("k"),
                right_on=col("k2")))
        pd.testing.assert_frame_equal(sort_out, hash_out)


def test_saturation_falls_back_via_aqe(tables):
    """hashMaxProbe=1 saturates the open table at build time (collision
    clusters outrun the bound): the join_hashsat flag re-jits the join
    on the sort kernel and results stay correct."""
    conf = tables.conf
    conf.set(MODE_KEY, "sort")
    expect = _join_df(tables, "inner").to_pandas()
    conf.set(MODE_KEY, "hash")
    conf.set(MAX_PROBE_KEY, 1)
    qe = _join_df(tables, "inner")._qe()
    got = qe.collect().to_pandas()
    pd.testing.assert_frame_equal(expect, got)
    # the AQE loop pinned this join to the sort kernel
    assert "hash_fallback" in qe.executed_plan.tree_string()


def test_hash_metrics_emitted(tables):
    tables.conf.set(MODE_KEY, "hash")
    qe = _join_df(tables, "inner")._qe()
    qe.execute_batch()
    slots = [v for k, v in qe.last_metrics.items()
             if k.startswith("join_table_slots_")]
    assert slots and all(s >= 16 and (s & (s - 1)) == 0 for s in slots)
    # host milliseconds of trace time, from the record kept beside the
    # stage-cache entry (ExecContext.host): no program holds them
    ms = {k: v for k, v in qe.last_metrics.items()
          if k.startswith(("join_build_ms_", "join_probe_ms_"))}
    assert {k.rsplit("_", 1)[0] for k in ms} == {"join_build_ms",
                                                 "join_probe_ms"}, ms
    assert all(isinstance(v, float) and v > 0 for v in ms.values()), ms
    host = qe.session._stage_host[qe._last_stage_key]
    assert all(round(host[k], 3) == v for k, v in ms.items())


# -- mesh --------------------------------------------------------------------

def test_kernel_parity_mesh_sharded_probe(tables):
    tables.conf.set(MESH_KEY, 8)
    sort_out, hash_out = _both_kernels(
        tables, lambda: _join_df(tables, "inner"))
    pd.testing.assert_frame_equal(sort_out, hash_out)


# -- chaos -------------------------------------------------------------------

def test_chaos_join_build_fault_under_hash(tables):
    tables.conf.set(MODE_KEY, "sort")
    expect = _join_df(tables, "inner").to_pandas()
    tables.conf.set(MODE_KEY, "hash")
    tables.conf.set("spark_tpu.execution.backoffMs", 1)
    # cold stage cache: the join_build seam fires at TRACE time, and
    # sibling tests already compiled this exact hash stage
    tables._stage_cache.clear()
    tables._aqe_caps.clear()
    faults.reset()
    with faults.inject(tables.conf,
                       "join_build:unavailable:1") as plan:
        got = _join_df(tables, "inner").to_pandas()
    assert ("join_build", 1, "unavailable") in plan.fired_log
    pd.testing.assert_frame_equal(expect, got)


# -- TPC-H golden parity -----------------------------------------------------

@pytest.mark.parametrize("qname", ["q1", "q3", "q5"])
def test_tpch_golden_parity_hash_vs_sort(tpch_session, tpch_path,
                                         qname):
    conf = tpch_session.conf
    conf.set(MODE_KEY, "sort")
    sort_out = G.normalize_decimals(
        Q.QUERIES[qname](tpch_session).to_pandas())
    G.compare(sort_out.reset_index(drop=True),
              G.GOLDEN[qname](tpch_path))
    conf.set(MODE_KEY, "hash")
    qe = Q.QUERIES[qname](tpch_session)._qe()
    hash_out = G.normalize_decimals(qe.collect().to_pandas())
    if qname != "q1":  # q1 has no joins
        assert _hash_ran(qe), qe.last_metrics
    pd.testing.assert_frame_equal(sort_out, hash_out)


# -- analyzer finding --------------------------------------------------------

def test_hash_table_pressure_finding(tables):
    from spark_tpu.analysis.plan_analyzer import analyze_plan
    conf = tables.conf
    qe = _join_df(tables, "inner")._qe()
    conf.set(MODE_KEY, "hash")
    conf.set(MAX_SLOTS_KEY, 512)  # dim caps past 0.7 * 512
    found = [f for f in analyze_plan(qe.executed_plan, conf)
             if f.code == "JOIN_HASH_TABLE_PRESSURE"]
    assert found and found[0].detail["fallback"] == "sort"
    assert found[0].severity == "warn"
    conf.set(MAX_SLOTS_KEY, 1 << 26)
    conf.set(BUDGET_KEY, 4096)  # table bytes exceed the HBM budget
    found = [f for f in analyze_plan(qe.executed_plan, conf)
             if f.code == "JOIN_HASH_TABLE_PRESSURE"]
    assert found and found[0].detail["table_bytes"] > 4096
    # clean conf: no pressure findings on the same plan
    conf.unset(BUDGET_KEY)
    conf.set(MODE_KEY, "sort")
    assert [f for f in analyze_plan(qe.executed_plan, conf)
            if f.code == "JOIN_HASH_TABLE_PRESSURE"] == []


# -- double-buffered ingest --------------------------------------------------

@pytest.fixture
def streaming_conf(tpch_session):
    conf = tpch_session.conf
    conf.set("spark_tpu.execution.backoffMs", 1)
    conf.set(CHUNK_KEY, 1024)
    conf.set(CACHE_KEY, 0)
    faults.reset()
    yield conf
    faults.reset()


def _golden(session, qname, tpch_path):
    got = G.normalize_decimals(
        Q.QUERIES[qname](session).to_pandas()).reset_index(drop=True)
    G.compare(got, G.GOLDEN[qname](tpch_path))
    return got


def test_prefetch_parity_on_off(tpch_session, tpch_path,
                                streaming_conf):
    chunks0 = tpch_session.metrics.counter("ingest_chunks").value
    on = _golden(tpch_session, "q1", tpch_path)
    # the pipeline ran and was counted (its waits: test_observability)
    assert tpch_session.metrics.counter("ingest_chunks").value > chunks0
    streaming_conf.set(PREFETCH_KEY, False)
    off = _golden(tpch_session, "q1", tpch_path)
    pd.testing.assert_frame_equal(on, off)


def test_prefetch_parity_spill_path(tpch_session, tpch_path,
                                    streaming_conf):
    streaming_conf.set(BUDGET_KEY, 1)  # force the partial-spill driver
    on = _golden(tpch_session, "q3", tpch_path)
    streaming_conf.set(PREFETCH_KEY, False)
    off = _golden(tpch_session, "q3", tpch_path)
    pd.testing.assert_frame_equal(on, off)


def test_prefetch_fault_replays_one_chunk(tpch_session, tpch_path,
                                          streaming_conf):
    """A transient fault at the prefetcher's host-decode seam replays
    exactly one chunk through the standard per-chunk retry path."""
    replayed0 = tpch_session.metrics.counter(
        "rec_chunks_replayed").value
    with faults.inject(streaming_conf,
                       "ingest_prefetch:unavailable:3") as plan:
        _golden(tpch_session, "q1", tpch_path)
    assert ("ingest_prefetch", 3, "unavailable") in plan.fired_log
    assert tpch_session.metrics.counter(
        "rec_chunks_replayed").value == replayed0 + 1


def test_prefetch_fatal_fault_propagates(tpch_session, streaming_conf):
    """A FATAL fault on the worker thread surfaces on the consumer —
    never a hang, never a truncated result."""
    with faults.inject(streaming_conf, "ingest_prefetch:fatal:2"):
        with pytest.raises(Exception, match="INTERNAL|fatal"):
            Q.QUERIES["q1"](tpch_session).to_pandas()


def test_prefetch_mesh_checkpoint_restore(tpch_session, tpch_path,
                                          streaming_conf):
    """Prefetcher + mesh checkpoint/restore compose: the restored
    stream skips checkpointed chunks through the prefetcher's
    skip_chunks cursor (PR-5 semantics unchanged)."""
    streaming_conf.set(MESH_KEY, 8)
    streaming_conf.set("spark_tpu.execution.checkpoint.everyChunks", 4)
    with faults.inject(streaming_conf, "mesh:unavailable:2"):
        _golden(tpch_session, "q1", tpch_path)


def test_table_slots_non_power_of_two_clamp(session):
    """A non-power-of-two hashMaxTableSlots must floor to a power of
    two: slot indexing masks with `& (slots - 1)`, so 6e6 nominal
    slots would leave ~half the table unreachable."""
    from spark_tpu.execution import hash_join as HJ
    session.conf.set(MAX_SLOTS_KEY, 6_000_000)
    slots = HJ.table_slots(1 << 23, session.conf)
    assert slots == 1 << 22, slots  # largest power of two <= 6e6
    assert slots & (slots - 1) == 0


def test_prefetch_worker_exits_on_abandonment(tpch_session, tpch_path,
                                              streaming_conf):
    """A chunk driver unwound mid-stream (fault escalation, replan)
    abandons its PrefetchChunkIterator without close(); the worker
    thread must exit via the abandonment finalizer instead of spinning
    forever holding a decoded chunk."""
    import gc
    import threading
    import time

    import os

    from spark_tpu.io.sources import ParquetSource, PrefetchChunkIterator

    def workers():
        return [t for t in threading.enumerate()
                if t.name == "spark-tpu-ingest-prefetch" and t.is_alive()]

    src = ParquetSource(os.path.join(tpch_path, "lineitem.parquet"),
                        "lineitem")
    chunks = PrefetchChunkIterator(
        src.load_chunks(None, (), 1024), streaming_conf)
    next(chunks)  # starts the worker; stream has many chunks left
    assert len(workers()) >= 1
    del chunks  # abandoned: no close(), as on an error unwind
    gc.collect()
    deadline = time.monotonic() + 5.0
    while workers() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not workers(), "prefetch worker leaked after abandonment"


@pytest.mark.parametrize("build_keys,want_rows", [
    ([float("nan"), 2.5, 9.0], 3),        # unique-build fast path
    ([float("nan"), float("nan"), 2.5], 5),  # dup NaN: m2m expansion
])
def test_kernel_parity_nan_keys(session, build_keys, want_rows):
    """Non-null NaN float keys (Parquet NaN is a VALUE, not null) join
    equal to NaN under BOTH kernels, matching pandas merge. Regression:
    the sort kernel's +inf sentinel broke the sorted order whenever the
    build had NaN keys plus padding (NaN probes never matched), and
    duplicate NaN build keys slipped past build_has_duplicates' `==`
    so the unique fast path dropped their extra matches."""
    import pyarrow as pa
    nan = float("nan")
    session.register_table("nan_p", pa.table({
        "k": pa.array([1.5, nan, nan, 2.5], type=pa.float64()),
        "v": pa.array([0, 1, 2, 3], type=pa.int64())}))
    session.register_table("nan_b", pa.table({
        "k2": pa.array(build_keys, type=pa.float64()),
        "w": pa.array([10, 20, 30], type=pa.int64())}))

    def run(mode):
        session.conf.set(MODE_KEY, mode)
        return (session.table("nan_p").join(
                    session.table("nan_b"),
                    left_on=col("k"), right_on=col("k2"))
                .to_pandas().sort_values(["v", "w"])
                .reset_index(drop=True))

    srt, hsh = run("sort"), run("hash")
    pd.testing.assert_frame_equal(srt, hsh)
    want = (session.table("nan_p").to_pandas()
            .merge(session.table("nan_b").to_pandas(),
                   left_on="k", right_on="k2")
            .sort_values(["v", "w"]).reset_index(drop=True))
    assert len(srt) == want_rows == len(want)
    pd.testing.assert_frame_equal(srt, want)


def test_kernel_parity_signed_zero_keys(session):
    """-0.0 and +0.0 join equal under both kernels (canonicalized
    before sort/search/hash), matching pandas merge."""
    import pyarrow as pa
    session.register_table("z_p", pa.table({
        "k": pa.array([-0.0, 0.0], type=pa.float64()),
        "v": pa.array([0, 1], type=pa.int64())}))
    session.register_table("z_b", pa.table({
        "k2": pa.array([0.0], type=pa.float64()),
        "w": pa.array([7], type=pa.int64())}))

    def run(mode):
        session.conf.set(MODE_KEY, mode)
        return (session.table("z_p").join(
                    session.table("z_b"),
                    left_on=col("k"), right_on=col("k2"))
                .to_pandas().sort_values("v").reset_index(drop=True))

    srt, hsh = run("sort"), run("hash")
    pd.testing.assert_frame_equal(srt, hsh)
    assert len(srt) == 2


def test_high_load_factor_without_clamp_keeps_hash(session):
    """Regression: the 0.7 fallback bound applies only when
    hashMaxTableSlots actually reduced the table. An unclamped table
    under a user-chosen hashLoadFactor in (0.7, 0.9] must keep the
    hash kernel (and emit no misleading clamp pressure finding)."""
    from spark_tpu.execution import hash_join as HJ
    session.conf.set(LOAD_KEY, 0.9)
    # bucket ~3000: want ceil(3000/0.9)=3334 -> 4096 slots, effective
    # load 0.73 > 0.7 but NOT clamped — the conf'd load factor rules
    assert HJ.table_slots(3000, session.conf) == 4096
    session.conf.set(MODE_KEY, "hash")
    assert HJ.kernel_choice(session.conf, 1 << 22, 3000) == \
        ("hash", "forced")
    session.conf.set(MODE_KEY, "auto")
    assert HJ.kernel_choice(session.conf, 1 << 22, 3000) == \
        ("hash", "auto")
    # the clamp case still falls back with reason 'clamp'
    session.conf.set(MAX_SLOTS_KEY, 2048)
    assert HJ.kernel_choice(session.conf, 1 << 22, 3000) == \
        ("sort", "clamp")
