"""Multi-chip SPMD parity: every query must produce identical results on
an 8-shard virtual CPU mesh and on a single chip (the `local-cluster`
analog of the reference's DistributedSuite, SURVEY.md section 4).

conftest.py forces 8 virtual CPU devices, so the collectives
(all_to_all / all_gather / psum) actually execute."""

import numpy as np
import pandas as pd
import pytest

from spark_tpu import functions as F
from spark_tpu.functions import col, lit

MESH_KEY = "spark_tpu.sql.mesh.size"


@pytest.fixture
def dist(session):
    """Flip the session into 8-shard mode for one test."""
    prev = session.conf.get(MESH_KEY)
    session.conf.set(MESH_KEY, 8)
    yield session
    session.conf.set(MESH_KEY, prev)


def _parity(session, build_df, sort_cols):
    """Run the same plan single-chip and distributed; compare as pandas."""
    session.conf.set(MESH_KEY, 0)
    want = (build_df().to_pandas().sort_values(sort_cols)
            .reset_index(drop=True))
    session.conf.set(MESH_KEY, 8)
    got = (build_df().to_pandas().sort_values(sort_cols)
           .reset_index(drop=True))
    session.conf.set(MESH_KEY, 0)
    assert len(got) == len(want), (got, want)
    for c in want.columns:
        g, w = got[c], want[c]
        if g.dtype.kind == "f" or w.dtype.kind == "f":
            assert np.allclose(g.fillna(np.nan), w.fillna(np.nan),
                               equal_nan=True), (c, got, want)
        else:
            assert g.fillna(-999).tolist() == w.fillna(-999).tolist(), \
                (c, got, want)


def test_distributed_groupby_direct(session):
    _parity(session,
            lambda: session.range(10_000)
            .group_by((col("id") % 97).alias("k"))
            .agg(F.count().alias("c"), F.sum(col("id")).alias("s")),
            ["k"])


def test_distributed_groupby_sort_path(session):
    pdf = pd.DataFrame({
        "k": np.random.RandomState(0).randint(0, 1000, 5000) * 1_000_003,
        "v": np.arange(5000, dtype=np.int64)})

    def build():
        df = session.create_dataframe(pdf)
        return df.group_by(col("k")).agg(
            F.sum(col("v")).alias("s"), F.count().alias("c"),
            F.min(col("v")).alias("mn"), F.max(col("v")).alias("mx"))

    _parity(session, build, ["k"])


def test_distributed_global_aggregate(session):
    _parity(session,
            lambda: session.range(12_345).agg(
                F.sum(col("id")).alias("s"), F.count().alias("c"),
                F.min(col("id")).alias("mn"), F.max(col("id")).alias("mx"),
                F.avg(col("id")).alias("a")),
            ["s"])


def test_distributed_join_shuffle(session):
    rs = np.random.RandomState(1)
    left = pd.DataFrame({"k": rs.randint(0, 500, 2000).astype(np.int64),
                         "lv": np.arange(2000, dtype=np.int64)})
    right = pd.DataFrame({"k": np.arange(500, dtype=np.int64),
                          "rv": np.arange(500, dtype=np.int64) * 10})

    def build():
        l = session.create_dataframe(left)
        r = session.create_dataframe(right)
        return l.join(r, on="k")

    _parity(session, build, ["lv"])


def test_distributed_join_many_to_many_outer(session):
    left = pd.DataFrame({"k": np.array([1, 2, 2, 3, 9], dtype=np.int64),
                         "lv": np.array([1, 2, 3, 4, 5], dtype=np.int64)})
    right = pd.DataFrame({"k": np.array([2, 2, 3, 7], dtype=np.int64),
                          "rv": np.array([20, 21, 30, 70], dtype=np.int64)})

    for how in ("inner", "left", "right", "outer"):
        def build():
            l = session.create_dataframe(left)
            r = session.create_dataframe(right)
            return l.join(r, on="k", how=how)

        _parity(session, build, ["lv", "rv"])


def test_distributed_string_join_broadcast(session):
    # small dim side -> planner picks the broadcast (all_gather) strategy
    fact = pd.DataFrame({
        "s": [f"key{i % 7}" for i in range(1000)],
        "v": np.arange(1000, dtype=np.int64)})
    dim = pd.DataFrame({"s": [f"key{i}" for i in range(7)],
                        "dv": np.arange(7, dtype=np.int64) * 100})

    def build():
        f = session.create_dataframe(fact)
        d = session.create_dataframe(dim)
        return f.join(d, on="s")

    _parity(session, build, ["v"])


def test_broadcast_strategy_planned(dist):
    fact = dist.create_dataframe(pd.DataFrame(
        {"k": np.arange(1000, dtype=np.int64) % 7,
         "v": np.arange(1000, dtype=np.int64)}), "fact")
    dim = dist.create_dataframe(pd.DataFrame(
        {"k": np.arange(7, dtype=np.int64),
         "dv": np.arange(7, dtype=np.int64)}), "dim")
    plan = fact.join(dim, on="k")._qe().executed_plan.tree_string()
    assert "strategy=broadcast" in plan
    assert "Replicated" in plan


def test_distributed_sort_global_order(session):
    rs = np.random.RandomState(2)
    pdf = pd.DataFrame({"x": rs.permutation(4000).astype(np.int64)})

    session.conf.set(MESH_KEY, 8)
    try:
        df = session.create_dataframe(pdf)
        out = df.sort(col("x").desc()).collect().column("x").to_pylist()
    finally:
        session.conf.set(MESH_KEY, 0)
    assert out == sorted(pdf["x"].tolist(), reverse=True)


def test_distributed_sort_limit(session):
    session.conf.set(MESH_KEY, 8)
    try:
        df = session.range(1000).sort(col("id").desc()).limit(5)
        assert df.collect().column("id").to_pylist() == [999, 998, 997, 996,
                                                         995]
    finally:
        session.conf.set(MESH_KEY, 0)


def test_distributed_string_groupby(session):
    pdf = pd.DataFrame({
        "s": [f"g{i % 13}" for i in range(3000)],
        "v": np.arange(3000, dtype=np.int64)})

    def build():
        return (session.create_dataframe(pdf)
                .group_by(col("s")).agg(F.sum(col("v")).alias("sv")))

    _parity(session, build, ["s"])


def test_distributed_join_copartition_subset_keys(session):
    # left side arrives hash-partitioned on a subset of the join keys:
    # the planner must still exchange BOTH sides on the full key list
    # (checking each child in isolation silently lost matches)
    rs = np.random.RandomState(3)
    base = pd.DataFrame({"a": rs.randint(0, 40, 600).astype(np.int64),
                         "b": rs.randint(0, 5, 600).astype(np.int64)})
    rdf_pd = pd.DataFrame({"a": np.arange(40, dtype=np.int64),
                           "b": np.arange(40, dtype=np.int64) % 5,
                           "rv": np.arange(40, dtype=np.int64)})

    prev = session.conf.get("spark_tpu.sql.autoBroadcastJoinThreshold")
    session.conf.set("spark_tpu.sql.autoBroadcastJoinThreshold", 0)
    try:
        def build():
            l = (session.create_dataframe(base)
                 .group_by(col("a")).agg(F.max(col("b")).alias("b")))
            r = session.create_dataframe(rdf_pd)
            return l.join(r, on=["a", "b"])

        _parity(session, build, ["a", "b"])
    finally:
        session.conf.set("spark_tpu.sql.autoBroadcastJoinThreshold", prev)


def test_distributed_full_outer_then_groupby(session):
    # full-outer output has NULL left keys scattered across shards: the
    # join must report UnknownPartitioning so the group-by re-exchanges
    left = pd.DataFrame({"k": np.array([1, 2, 3], dtype=np.int64),
                         "lv": np.array([1, 2, 3], dtype=np.int64)})
    right = pd.DataFrame({"k": np.array([3, 4, 5, 6], dtype=np.int64),
                          "rv": np.array([30, 40, 50, 60], dtype=np.int64)})

    def build():
        l = session.create_dataframe(left)
        r = session.create_dataframe(right)
        j = l.join(r, left_on=col("k"), right_on=col("k"), how="full")
        return j.group_by(col("k")).agg(F.count().alias("c"))

    _parity(session, build, ["k"])


def test_distributed_cross_join(session):
    def build():
        a = session.create_dataframe(pd.DataFrame(
            {"x": np.arange(20, dtype=np.int64)}))
        b = session.create_dataframe(pd.DataFrame(
            {"y": np.arange(7, dtype=np.int64)}))
        return a.cross_join(b)

    _parity(session, build, ["x", "y"])


def test_distributed_filter_project(session):
    _parity(session,
            lambda: session.range(5000)
            .filter((col("id") % 7) == lit(3))
            .select((col("id") * 2).alias("x")),
            ["x"])


def test_distributed_union(session):
    """Round-2 ADVICE high: UnionExec inherited SinglePartition and lost
    rows under a mesh (striped distinct per-shard output)."""
    a = pd.DataFrame({"k": np.arange(12, dtype=np.int64)})
    b = pd.DataFrame({"k": np.arange(100, 108, dtype=np.int64)})

    def build():
        return (session.create_dataframe(a, "ua")
                .union(session.create_dataframe(b, "ub")))

    _parity(session, build, ["k"])


def test_distributed_union_then_groupby(session):
    a = pd.DataFrame({"k": np.arange(20, dtype=np.int64) % 5})
    b = pd.DataFrame({"k": np.arange(20, dtype=np.int64) % 3})

    def build():
        return (session.create_dataframe(a, "uga")
                .union(session.create_dataframe(b, "ugb"))
                .group_by(col("k")).agg(F.count().alias("c")))

    _parity(session, build, ["k"])


def test_distributed_full_join_computed_key(session):
    """Round-2 ADVICE high: full-outer on a computed key fell back to a
    replicated build, duplicating unmatched build rows per shard."""
    left = pd.DataFrame({"x": np.arange(8, dtype=np.int64)})
    right = pd.DataFrame({"y": np.arange(4, 12, dtype=np.int64)})

    def build():
        return session.create_dataframe(left, "fl").join(
            session.create_dataframe(right, "fr"),
            left_on=col("x") + 0, right_on=col("y"), how="outer")

    _parity(session, build, ["x", "y"])


def test_distributed_skewed_exchange_retry(session):
    """Size-aware exchange: all rows hash to ONE destination shard, so the
    2x-uniform seed must overflow and the executor must re-jit with a
    bigger receive block (the exch_overflow stats loop)."""
    pdf = pd.DataFrame({"k": np.zeros(4000, dtype=np.int64),
                        "v": np.arange(4000, dtype=np.int64)})

    def build():
        return (session.create_dataframe(pdf, "skewed")
                .group_by(col("k"))
                .agg(F.sum(col("v")).alias("s"), F.count().alias("c")))

    _parity(session, build, ["k"])


def test_distributed_skewed_join_exchange(session):
    rs = np.random.RandomState(7)
    left = pd.DataFrame({"k": np.where(rs.rand(3000) < 0.9, 1,
                                       rs.randint(0, 50, 3000)).astype(np.int64),
                         "lv": np.arange(3000, dtype=np.int64)})
    right = pd.DataFrame({"k": np.arange(50, dtype=np.int64),
                          "rv": np.arange(50, dtype=np.int64) * 3})

    def build():
        # force the shuffle strategy (skewed probe side) by size: the big
        # left is the probe, small right under threshold broadcasts unless
        # we disable it
        prev = session.conf.get("spark_tpu.sql.autoBroadcastJoinThreshold")
        session.conf.set("spark_tpu.sql.autoBroadcastJoinThreshold", 0)
        try:
            df = session.create_dataframe(left, "skl").join(
                session.create_dataframe(right, "skr"), on="k")
        finally:
            session.conf.set("spark_tpu.sql.autoBroadcastJoinThreshold", prev)
        return df

    _parity(session, build, ["lv"])


def test_distributed_union_mixed_partitioning(session):
    """A replicated (SinglePartition) child of a union must be striped so
    the sharded concat holds exactly one copy (code-review finding)."""
    a = pd.DataFrame({"k": np.arange(6, dtype=np.int64)})
    b = pd.DataFrame({"k": np.arange(50, 70, dtype=np.int64)})

    def build():
        sorted_a = session.create_dataframe(a, "mua").sort(col("k"))
        return sorted_a.union(session.create_dataframe(b, "mub"))

    _parity(session, build, ["k"])


def test_distributed_range_sort(session):
    """Global sort = sampled range bounds + all_to_all + local sort —
    no full-dataset all_gather (round-2 weak #5)."""
    rs = np.random.RandomState(3)
    pdf = pd.DataFrame({"k": rs.randint(-1000, 1000, 5000).astype(np.int64),
                        "v": np.arange(5000, dtype=np.int64)})

    def build():
        return session.create_dataframe(pdf, "rsort").sort(
            col("k"), col("v"))

    session.conf.set(MESH_KEY, 8)
    try:
        got = build().to_pandas()
        plan = build()._qe().executed_plan.tree_string()
    finally:
        session.conf.set(MESH_KEY, 0)
    assert "RangePartitioning" in plan, plan
    want = pdf.sort_values(["k", "v"]).reset_index(drop=True)
    # exact ORDER matters here (not just set equality)
    assert got["k"].tolist() == want["k"].tolist()
    assert got["v"].tolist() == want["v"].tolist()


def test_distributed_sort_desc_limit(session):
    rs = np.random.RandomState(4)
    pdf = pd.DataFrame({"k": rs.randint(0, 10**9, 3000).astype(np.int64)})

    def build():
        return session.create_dataframe(pdf, "rsl").sort(
            col("k").desc()).limit(7)

    session.conf.set(MESH_KEY, 8)
    try:
        got = build().to_pandas()
    finally:
        session.conf.set(MESH_KEY, 0)
    want = pdf.sort_values("k", ascending=False).head(7)
    assert got["k"].tolist() == want["k"].tolist()


def test_distributed_sort_skewed_keys(session):
    """Heavily skewed sort keys overflow the sampled buckets and must be
    recovered by the exchange retry loop."""
    pdf = pd.DataFrame({"k": np.concatenate([
        np.zeros(2500, dtype=np.int64),
        np.arange(100, dtype=np.int64) + 1])})

    def build():
        return session.create_dataframe(pdf, "rskew").sort(col("k"))

    session.conf.set(MESH_KEY, 8)
    try:
        got = build().to_pandas()
    finally:
        session.conf.set(MESH_KEY, 0)
    assert got["k"].tolist() == sorted(pdf["k"].tolist())


def test_distributed_streaming_aggregate(session):
    """Chunked scan streaming under the mesh: per-shard accumulator
    tables carried across host-ingested chunks (round-2 weak #7 — mesh
    runs used to materialize whole scans)."""
    import spark_tpu.execution.streaming_agg as SA

    rs = np.random.RandomState(9)
    pdf = pd.DataFrame({"v": rs.randint(0, 10**6, 5000).astype(np.int64)})
    session.register_table("stream_t", pdf)
    calls = []
    orig = SA.stream_scan_aggregate_mesh

    def spy(agg, mesh, conf, cache=None, recovery=None):
        out = orig(agg, mesh, conf, cache, recovery)
        calls.append(out is not None)
        return out

    SA.stream_scan_aggregate_mesh = spy
    prev_chunk = session.conf.get("spark_tpu.sql.execution.streamingChunkRows")
    session.conf.set("spark_tpu.sql.execution.streamingChunkRows", 1024)
    # disable the device cache so the (tiny) scan doesn't go resident
    prev_cache = session.conf.get("spark_tpu.sql.io.deviceCacheBytes")
    session.conf.set("spark_tpu.sql.io.deviceCacheBytes", 0)
    try:
        def build():
            return (session.table("stream_t")
                    .group_by((col("v") % 37).alias("k"))
                    .agg(F.count().alias("c"), F.sum(col("v")).alias("s")))

        _parity(session, build, ["k"])
    finally:
        SA.stream_scan_aggregate_mesh = orig
        session.conf.set("spark_tpu.sql.execution.streamingChunkRows",
                         prev_chunk)
        session.conf.set("spark_tpu.sql.io.deviceCacheBytes", prev_cache)
    assert any(calls), "mesh streaming path never engaged"


#: (row layout, block slots as a function of rows a shard L and shards n)
EXCHANGE_CASES = {
    "uniform": lambda L, n: 2 * L // n,
    "dead_rows": lambda L, n: 2 * L // n,
    "overflow": lambda L, n: 2 * L // n,     # destination 1 takes 70%
    "empty_shard": lambda L, n: 2 * L // n,  # shard 0 sends nothing,
                                             # shard n-1 receives nothing
    "block_over_L": lambda L, n: 2 * L,
}


def _exchange_targets(layout, rs, n, L):
    """A target shard a row, n for a dead row, [n * L]."""
    tgt = rs.randint(0, n, n * L)
    if layout == "dead_rows" or layout == "block_over_L":
        tgt[rs.rand(n * L) < 0.3] = n
    elif layout == "overflow":
        tgt[rs.rand(n * L) < 0.7] = 1
    elif layout == "empty_shard":
        tgt = rs.randint(0, n - 1, n * L)
        tgt[:L] = n
        tgt[rs.rand(n * L) < 0.2] = n
    return tgt.astype(np.int32)


def _scatter_model(x, tgt, n, L, block, fill):
    """The exchange's received rows as its scatter form placed them: a
    source's rows bound for d, in row order, take d's block of `block`
    slots from its start (the rows past it dropped, the slots after them
    `fill`); shard d receives source s's block at slots s*block on."""
    out = np.full((n, n * block), fill, x.dtype)
    for s in range(n):
        xs, ts = x[s * L:(s + 1) * L], tgt[s * L:(s + 1) * L]
        for d in range(n):
            rows = np.flatnonzero(ts == d)[:block]
            out[d, s * block:s * block + len(rows)] = xs[rows]
    return out.reshape(-1)


@pytest.mark.parametrize("n", [4, 8])
@pytest.mark.parametrize("layout", sorted(EXCHANGE_CASES))
def test_the_exchange_sends_what_its_scatter_did(n, layout):
    """`_exchange_by_target` under `shard_map` on the CPU's virtual
    devices: the columns (int64, float64, int32, bool, a dictionary
    string; each with validity), the validity arrays, the live mask,
    `exch_max_*` and the overflow flag are, slot for slot, what the send
    side's scatter into [n, block] gave before it was cut into slices
    of the target-sorted rows."""
    import jax
    import jax.numpy as jnp
    import pyarrow as pa
    from jax.sharding import Mesh, PartitionSpec

    from spark_tpu import Conf
    from spark_tpu import types as T
    from spark_tpu.columnar import Batch, Column
    from spark_tpu.parallel.mesh import AXIS, shard_map
    from spark_tpu.parallel.shuffle import _exchange_by_target
    from spark_tpu.plan.physical import ExecContext
    L = 96
    block = EXCHANGE_CASES[layout](L, n)
    rs = np.random.RandomState(44 + n)
    tgt = _exchange_targets(layout, rs, n, L)
    words = pa.array(["ASIA", "EUROPE", "AFRICA"])
    cols = {"i64": (rs.randint(-10**12, 10**12, n * L), T.LONG, None),
            "f64": (rs.randn(n * L), T.DOUBLE, None),
            "i32": (rs.randint(-10**9, 10**9, n * L).astype(np.int32),
                    T.INT, None),
            "b": (rs.rand(n * L) < 0.5, T.BOOLEAN, None),
            "s": (rs.randint(0, 3, n * L).astype(np.int32), T.STRING, words)}
    valid = {name: rs.rand(n * L) < 0.8 for name in cols}

    def run(tgt, *arrays):
        ctx = ExecContext(Conf(), AXIS, n)
        batch = Batch({name: Column(arrays[2 * i], dtype, arrays[2 * i + 1],
                                    dictionary)
                       for i, (name, (_, dtype, dictionary))
                       in enumerate(cols.items())}, tgt < n)
        out = _exchange_by_target(batch, tgt, ctx, block, "t")
        return (tuple(c.data for c in out.columns.values()),
                tuple(c.validity for c in out.columns.values()),
                out.selection, ctx.metrics["exch_max_t"].reshape(1),
                ctx.flags["exch_overflow_t"].reshape(1))

    mesh = Mesh(np.array(jax.devices()[:n]), (AXIS,))
    args = [jnp.asarray(tgt)]
    for name, (data, _, _) in cols.items():
        args += [jnp.asarray(data), jnp.asarray(valid[name])]
    data, validity, live, max_count, overflow = jax.jit(shard_map(
        run, mesh=mesh, in_specs=PartitionSpec(AXIS),
        out_specs=PartitionSpec(AXIS), check_vma=False))(*args)

    for i, (name, (x, _, _)) in enumerate(cols.items()):
        got = np.asarray(data[i])
        assert got.dtype == x.dtype, name
        assert np.array_equal(got, _scatter_model(x, tgt, n, L, block, 0)), \
            name
        assert np.array_equal(np.asarray(validity[i]), _scatter_model(
            valid[name], tgt, n, L, block, False)), name
    assert np.array_equal(np.asarray(live), _scatter_model(
        tgt < n, tgt, n, L, block, False))
    per_shard = np.array([np.bincount(tgt[s * L:(s + 1) * L], minlength=n + 1)
                          [:n].max() for s in range(n)])
    assert np.array_equal(np.asarray(max_count), per_shard)
    assert np.array_equal(np.asarray(overflow), per_shard > block)
    assert overflow.any() == (layout == "overflow")
    if layout == "empty_shard":
        assert not np.asarray(live).reshape(n, -1)[n - 1].any()


@pytest.mark.parametrize("dtype,via", [
    ("uint8", "convert_element_type"),   # Bloom bits: widened to int32
    ("int32", None),                     # the library's all-reduce as is
    ("int64", "all_gather"),             # stats, runtime-filter bounds
    ("float64", "all_gather"),
])
def test_mesh_pmax_pmin_take_the_route_the_tpu_gets_right(dtype, via):
    """`parallel.mesh.pmax/pmin`, element-wise over four shards: right
    for every width, and by the route XLA:TPU handles — on the chip a
    uint8 `lax.pmax` lost Bloom bits (wrong Q3 under mesh.size=4) and a
    64-bit one does not compile (PR 22). The CPU computes all of them
    correctly either way, so the route is asserted from the jaxpr."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec

    from spark_tpu.parallel.mesh import AXIS, pmax, pmin, shard_map
    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), (AXIS,))
    rs = np.random.RandomState(3)
    x = rs.randint(0, 2, n * 64).astype(dtype) if dtype == "uint8" \
        else rs.randint(-(1 << 30), 1 << 30, n * 64).astype(dtype)
    both = shard_map(lambda v: (pmax(v, AXIS), pmin(v, AXIS)), mesh=mesh,
                     in_specs=PartitionSpec(AXIS),
                     out_specs=PartitionSpec(), check_vma=False)
    hi, lo = jax.jit(both)(jnp.asarray(x))
    assert hi.dtype == lo.dtype == np.dtype(dtype)
    assert np.array_equal(np.asarray(hi), x.reshape(n, -1).max(axis=0))
    assert np.array_equal(np.asarray(lo), x.reshape(n, -1).min(axis=0))
    text = str(jax.make_jaxpr(both)(jnp.asarray(x)))
    if via is None:
        assert "pmax" in text and "all_gather" not in text \
            and "convert_element_type" not in text, text
    else:
        assert via in text, text
        assert ("pmax" in text) == (via != "all_gather"), text


def test_a_filter_built_under_a_mesh_ors_staging_bytes_then_packs():
    """`build_runtime_filter` inside `shard_map`: each shard scatters
    its keys' bits into the one-bit-a-byte staging array, the engine's
    `pmax` ORs the shards' BYTES (widened to int32: a uint8 `lax.pmax`
    lost bits on the chip, PR 22; a max over packed words would be no
    OR at all) and the pack to 32-bit words comes after it. Every
    shard then holds the filter one device builds from all the keys."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec

    from spark_tpu import Conf
    from spark_tpu.columnar import Batch, Column
    from spark_tpu.execution.join import build_runtime_filter
    from spark_tpu.expr import ColumnRef
    from spark_tpu.parallel.mesh import AXIS, shard_map
    from spark_tpu.plan.physical import ExecContext
    from spark_tpu.sketch import BloomFilter
    from spark_tpu import types as T
    n, rows = 4, 4096
    mesh = Mesh(np.array(jax.devices()[:n]), (AXIS,))
    rs = np.random.RandomState(5)
    keys = jnp.asarray(rs.randint(0, 10**9, n * rows).astype(np.int64))
    live = jnp.asarray(rs.rand(n * rows) < 0.5)

    def build(k, sel, ctx):
        batch = Batch({"k": Column(k, T.LongType())}, selection=sel)
        filt = build_runtime_filter(batch, ColumnRef("k"), ctx,
                                    expected_items=n * rows)
        return filt.bloom.words, filt.lo, filt.hi

    sharded = shard_map(
        lambda k, sel: build(k, sel, ExecContext(Conf(), AXIS, n)),
        mesh=mesh, in_specs=PartitionSpec(AXIS),
        out_specs=PartitionSpec(), check_vma=False)
    words, lo, hi = jax.jit(sharded)(keys, live)
    want, want_lo, want_hi = build(keys, live, ExecContext(Conf()))
    np.testing.assert_array_equal(np.asarray(words), np.asarray(want))
    assert (int(lo), int(hi)) == (int(want_lo), int(want_hi))
    assert want.dtype == jnp.uint32 and want.shape[0] \
        == BloomFilter.sizing(n * rows)[0]
    # the route, from the jaxpr: one pmax over 32 widened bytes a word,
    # and the words' shifts and sum after it
    text = str(jax.make_jaxpr(sharded)(keys, live))
    staged = f"i32[{32 * want.shape[0]}] = pmax["
    assert text.count(staged) == 1, text
    after = text[text.index(staged):]
    assert "shift_left" in after and "reduce_sum" in after
    assert f"u32[{want.shape[0]}] = pmax" not in text


@pytest.mark.parametrize("kept", ["every row", "a quarter"])
def test_an_exchange_learns_its_block_only_where_it_halves(monkeypatch,
                                                           kept):
    """A shuffle join of full shards: each (source, destination) bucket
    takes a little over half the seeded block (2x a uniform spread), so
    no block would halve and none is learned; with a quarter of the
    probe's rows kept, the probe's exchange learns the block its
    fullest bucket fills and the next execution runs it. Either way the
    answers are the single device's."""
    from spark_tpu import Conf, SparkTpuSession
    from spark_tpu.columnar import bucket_capacity
    from spark_tpu.execution import executor
    monkeypatch.setattr(executor, "EXCHANGE_SHRINK_MIN_SLOTS", 1)
    n, rows = 4, 8192
    rs = np.random.RandomState(11)
    left = pd.DataFrame({"k": rs.randint(0, 1 << 20, rows).astype(np.int64),
                         "lv": np.arange(rows, dtype=np.int64)})
    right = pd.DataFrame({"k": left["k"].values[::2],
                          "rv": np.arange(rows // 2, dtype=np.int64)})
    session = SparkTpuSession(
        conf=Conf().set(MESH_KEY, n)
        .set("spark_tpu.sql.autoBroadcastJoinThreshold", 0)
        .set("spark_tpu.execution.meshFallback.enabled", False),
        register_active=False)
    session.register_table("l", session.create_dataframe(left, "l"))
    session.register_table("r", session.create_dataframe(right, "r"))
    where = "" if kept == "every row" else " WHERE l.lv % 4 = 0"
    text = f"SELECT l.lv, r.rv FROM l JOIN r ON l.k = r.k{where}"
    want = left.merge(right, on="k")
    if where:
        want = want[want["lv"] % 4 == 0]
    want = want[["lv", "rv"]].sort_values(["lv", "rv"]) \
        .reset_index(drop=True)

    def execute():
        qe = session.sql(text)._qe()
        got = qe.collect().to_pandas().sort_values(["lv", "rv"]) \
            .reset_index(drop=True)
        pd.testing.assert_frame_equal(got, want, check_dtype=False)
        dispatch = [s for s in qe.spans.spans if s.name == "dispatch"][-1]
        return qe, {t: int(b) for t, b in (
            kv.split("=") for kv in dispatch.attrs["exch_blocks"].split(","))}

    first, seeded = execute()
    assert len(seeded) == 2, seeded
    fills = {t: bucket_capacity(max(first.last_metrics[f"exch_max_{t}"], 8))
             for t in seeded}
    learned = {k[len("exch:"):]: v for caps in session._aqe_caps.values()
               for k, v in caps.items() if k.startswith("exch:")}
    assert learned == {t: fills[t] for t in seeded
                       if 2 * fills[t] <= seeded[t]}
    assert len(learned) == (0 if kept == "every row" else 1), \
        (seeded, fills)
    # a stage whose blocks are learned leaves the cache, else it stays
    assert (first._last_stage_key in session._stage_cache) \
        == (not learned)
    _second, blocks = execute()
    assert blocks == {**seeded, **learned}
