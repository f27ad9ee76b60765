"""Regression tests for the round-1 correctness findings (VERDICT.md
"What's weak" + ADVICE.md): dictionary-transform group-by, cross-dictionary
string joins/unions, multi-key packing, truncated %, decimal division,
USING-join column dedup, signed dense-domain group keys."""

import decimal

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_tpu import functions as F
from spark_tpu.functions import col, lit


def test_substring_groupby_merges_colliding_codes(session):
    # round-1 bug: substr() rewrote the dictionary but left codes distinct,
    # so "aa1"/"aa2"/"aa3" grouped as three separate "aa" groups
    pdf = pd.DataFrame({"s": ["aa1", "aa2", "bb1", "aa3"],
                        "v": np.array([1, 2, 3, 4], dtype=np.int64)})
    df = session.create_dataframe(pdf)
    out = (df.group_by(col("s").substr(1, 2).alias("p"))
           .agg(F.sum(col("v")).alias("sv"))
           .to_pandas().sort_values("p").reset_index(drop=True))
    assert list(out["p"]) == ["aa", "bb"]
    assert list(out["sv"]) == [7, 3]


def test_string_join_different_dictionaries(session):
    # left and right encode strings independently: code equality is
    # meaningless without unification (ADVICE high-severity)
    left = session.create_dataframe(pd.DataFrame({
        "k": ["apple", "banana", "cherry"],
        "lv": np.array([1, 2, 3], dtype=np.int64)}))
    right = session.create_dataframe(pd.DataFrame({
        "k": ["cherry", "apple"],  # reversed insertion order -> codes differ
        "rv": np.array([30, 10], dtype=np.int64)}))
    out = (left.join(right, on="k")
           .to_pandas().sort_values("k").reset_index(drop=True))
    assert list(out["k"]) == ["apple", "cherry"]
    assert list(out["lv"]) == [1, 3]
    assert list(out["rv"]) == [10, 30]


def test_union_string_dictionaries(session):
    a = session.create_dataframe(pd.DataFrame({"s": ["x", "y"]}))
    b = session.create_dataframe(pd.DataFrame({"s": ["z", "x"]}))
    out = sorted(a.union(b).collect().column("s").to_pylist())
    assert out == ["x", "x", "y", "z"]


def test_multi_key_join_wide_keys(session):
    # two int64 keys cannot pack into 64 bits: the hashed path must
    # re-verify true equality (round-1: silent 32-bit truncation collided)
    k1 = np.array([1 << 40, (1 << 40) + 1, 5], dtype=np.int64)
    k2 = np.array([7, 7, 8], dtype=np.int64)
    left = session.create_dataframe(pd.DataFrame(
        {"a": k1, "b": k2, "lv": np.array([1, 2, 3], dtype=np.int64)}))
    right = session.create_dataframe(pd.DataFrame(
        {"a": k1[:2], "b": k2[:2], "rv": np.array([10, 20], dtype=np.int64)}))
    out = (left.join(right, on=["a", "b"])
           .to_pandas().sort_values("lv").reset_index(drop=True))
    assert list(out["lv"]) == [1, 2]
    assert list(out["rv"]) == [10, 20]


def test_multi_key_join_colliding_low_words(session):
    # round-1 bug: keys masked to low 32 bits -> (2^33+5, x) joined (5, x)
    left = session.create_dataframe(pd.DataFrame({
        "a": np.array([(1 << 33) + 5], dtype=np.int64),
        "b": np.array([1], dtype=np.int64)}))
    right = session.create_dataframe(pd.DataFrame({
        "a": np.array([5], dtype=np.int64),
        "b": np.array([1], dtype=np.int64),
        "rv": np.array([99], dtype=np.int64)}))
    out = left.join(right, on=["a", "b"]).to_pandas()
    assert len(out) == 0


def test_division_by_zero_is_null(session):
    pdf = pd.DataFrame({"a": np.array([10.0, 20.0]),
                        "b": np.array([2.0, 0.0])})
    df = session.create_dataframe(pdf)
    out = df.select((col("a") / col("b")).alias("q")).to_pandas()
    assert out["q"][0] == 5.0
    assert pd.isna(out["q"][1])
    # integer % 0 is NULL too
    pdf2 = pd.DataFrame({"a": np.array([10, 20], dtype=np.int64),
                         "b": np.array([3, 0], dtype=np.int64)})
    out2 = (session.create_dataframe(pdf2)
            .select((col("a") % col("b")).alias("m")).to_pandas())
    assert out2["m"][0] == 1
    assert pd.isna(out2["m"][1])


def test_decimal_division_returns_decimal(session):
    t = pa.table({
        "a": pa.array([decimal.Decimal("10.00"), decimal.Decimal("1.00")],
                      type=pa.decimal128(10, 2)),
        "b": pa.array([decimal.Decimal("4.00"), decimal.Decimal("3.00")],
                      type=pa.decimal128(10, 2))})
    df = session.create_dataframe(t)
    qt = df.select((col("a") / col("b")).alias("q"))
    import spark_tpu.types as T
    assert isinstance(qt.schema.fields[0].dtype, T.DecimalType)
    out = qt.collect().column("q").to_pylist()
    assert out[0] == decimal.Decimal("2.5")
    assert abs(float(out[1]) - 1 / 3) < 1e-6


def test_using_join_drops_right_key(session):
    left = session.create_dataframe(pd.DataFrame({
        "k": np.array([1, 2], dtype=np.int64),
        "lv": np.array([1, 2], dtype=np.int64)}))
    right = session.create_dataframe(pd.DataFrame({
        "k": np.array([2], dtype=np.int64),
        "rv": np.array([20], dtype=np.int64)}))
    out = left.join(right, on="k")
    assert out.columns == ["k", "lv", "rv"]  # no k_r leak


def test_groupby_negative_mod_keys(session):
    # truncated % yields negative keys; dense-domain path must not merge
    # them into slot 0
    pdf = pd.DataFrame({"x": np.array([-7, -4, -1, 1, 4, 7], dtype=np.int64)})
    df = session.create_dataframe(pdf)
    out = (df.group_by((col("x") % lit(3)).alias("k"))
           .agg(F.count().alias("c"))
           .to_pandas().sort_values("k").reset_index(drop=True))
    assert list(out["k"]) == [-1, 1]
    assert list(out["c"]) == [3, 3]


def test_groupby_negative_bytes(session):
    t = pa.table({"b": pa.array([-128, -1, 0, 127, -1], type=pa.int8()),
                  "v": pa.array([1, 2, 3, 4, 5], type=pa.int64())})
    df = session.create_dataframe(t)
    out = (df.group_by(col("b")).agg(F.sum(col("v")).alias("s"))
           .to_pandas().sort_values("b").reset_index(drop=True))
    assert list(out["b"]) == [-128, -1, 0, 127]
    assert list(out["s"]) == [1, 7, 3, 4]


def test_final_merge_kernel_full_width():
    """Round-3 ADVICE high: the MXU kernel bounded per-row limbs by
    AccSpec.width in ALL modes, but final-mode contributions are partial
    accumulators (counts in the thousands with width=8) — counts came
    back mod 256. merge=True must force full 64-bit limbs."""
    import jax.numpy as jnp
    from spark_tpu.execution import aggregate as K
    from spark_tpu.expr import Vec
    from spark_tpu.expr_agg import AccSpec
    import spark_tpu.types as T

    n = 160  # > the kernel's small-input gate when matmul is forced
    keys = Vec(jnp.arange(n, dtype=jnp.int64) % 4, T.LONG, None, None)
    specs = [[AccSpec("count", np.dtype(np.int64), "sum", width=8)],
             [AccSpec("sum", np.dtype(np.int64), "sum", width=16)]]
    # partial counts of 1000 (> 2^8) and partial sums of 1<<40 (> 2^16)
    contribs = [[jnp.full((n,), 1000, jnp.int64)],
                [jnp.full((n,), 1 << 40, jnp.int64)]]
    domains = [(4, 0)]
    spans = [4]
    _, _, accs, _ = K.direct_aggregate(
        [keys], domains, spans, contribs, specs, None,
        kernel_mode="matmul", merge=True)
    assert np.asarray(accs[0][0]).tolist() == [1000 * 40] * 4
    assert np.asarray(accs[1][0]).tolist() == [(1 << 40) * 40] * 4


#: (groups, int row widths, float rows): both kernels, one and several
#: blocks of each gridded axis, raw-width and merge-mode limbs
_KERNEL_SHAPES = {
    "small_int_float": (6, [8, 64, 64], 2),
    "small_three_blocks_floats": (300, [8], 3),
    "factored_8_64": (65536, [8, 64], 0),
    "factored_merge_null_slot": (65537, [8, 64, 64], 0),
    "factored_split_a_axis": (1 << 20, [8], 0),
}


@pytest.mark.parametrize("shape", sorted(_KERNEL_SHAPES))
def test_dense_groupby_sums_exact(shape):
    """The Pallas kernels (interpret mode) against numpy over several
    row tiles and super-tiles: integer sums bit-exact mod 2^64 whatever
    the limb widths, float sums to the f32-tile tolerance, out-of-range
    rows dropped. PR 22 re-cut both kernels' accumulation and block
    sizes to what the v5e compiler accepts (tests/test_chip_compile.py
    compiles them); this holds the arithmetic still."""
    import jax
    from spark_tpu.execution.pallas_groupby import dense_groupby_sums
    domain, widths, n_float = _KERNEL_SHAPES[shape]
    n = 70_000  # 9 row tiles: two super-tiles, the second mostly padding
    rs = np.random.RandomState(7)
    idx = rs.randint(0, domain + 1, n).astype(np.int32)  # == domain: dropped
    ints = [rs.randint(0, 1 << w, n, dtype=np.int64) if w < 64
            else rs.randint(-(1 << 62), 1 << 62, n, dtype=np.int64)
            for w in widths]
    floats = [rs.randn(n) * 1e3 for _ in range(n_float)]
    got_i, got_f = jax.jit(
        lambda i, a, b: dense_groupby_sums(
            i, list(a), list(b), domain, interpret=True,
            int_widths=widths))(idx, tuple(ints), tuple(floats))

    def want(v):
        out = np.zeros(domain + 1, v.dtype)
        np.add.at(out, idx, v)
        return out[:domain]

    for v, g in zip(ints, got_i):
        assert np.array_equal(np.asarray(g), want(v)), shape
    for v, g in zip(floats, got_f):
        np.testing.assert_allclose(np.asarray(g), want(v), rtol=1e-5,
                                   atol=1e-2)


def test_two_phase_mesh_agg_forced_matmul(session):
    """End-to-end: a distributed two-phase aggregate with the Pallas
    kernel forced (interpret mode on CPU) must match the single-chip
    scatter result — >256 rows per group per shard so a width-bounded
    merge would truncate."""
    mesh_key = "spark_tpu.sql.mesh.size"
    kern_key = "spark_tpu.sql.aggregate.kernelMode"
    n = 40_000  # 5 groups -> 8000 rows/group, ~1000/group/shard
    build = lambda: (session.range(n)
                     .group_by((col("id") % 5).alias("k"))
                     .agg(F.count().alias("c"), F.sum(col("id")).alias("s")))
    want = build().to_pandas().sort_values("k").reset_index(drop=True)
    try:
        session.conf.set(mesh_key, 8)
        session.conf.set(kern_key, "matmul")
        got = build().to_pandas().sort_values("k").reset_index(drop=True)
    finally:
        session.conf.set(mesh_key, 0)
        session.conf.set(kern_key, "auto")
    assert got["c"].tolist() == want["c"].tolist() == [8000] * 5
    assert got["s"].tolist() == want["s"].tolist()


def test_prune_columns_preserves_join_renames(session):
    """Plan-level: pruning must not change join output names — the
    colliding left column that forced an `_r` suffix stays alive
    (code-review: chained `x`/`x_r` collisions included)."""
    import pandas as pd
    from spark_tpu.functions import col
    from spark_tpu.plan.logical import Join, Project, Scan
    from spark_tpu.plan.optimizer import PruneColumns

    left = pd.DataFrame({"k": [1, 2], "x": [10, 20], "x_r": [5, 6]})
    right = pd.DataFrame({"k": [1, 2], "x": [7, 8]})
    df = (session.create_dataframe(left, "pl")
          .join(session.create_dataframe(right, "pr"),
                left_on=col("k"), right_on=col("k")))
    # right `x` collides twice -> x_r_r
    assert "x_r_r" in df.plan.schema().names
    pruned = PruneColumns().apply(
        Project(df.plan, [col("x_r_r")]))
    # output name still resolves after pruning
    assert pruned.schema().names == ["x_r_r"]
    got = (session.create_dataframe(left, "pl2")
           .join(session.create_dataframe(right, "pr2"),
                 left_on=col("k"), right_on=col("k"))
           .select(col("x_r_r")).to_pandas())
    assert got["x_r_r"].tolist() == [7, 8]


def test_first_merge_does_not_fabricate_values():
    """Round-4 ADVICE high: First packs (pos<<33|isnull<<32|word) per
    32-bit word under independent min reduces; when two merged updates
    tie on in-chunk position, the two word accumulators of a 64-bit
    value could each pick a DIFFERENT row — e.g. merging (2<<32)|1 and
    (1<<32)|5 at the same position returned (1<<32)|1, a value present
    in no input row. Globally unique row bases must make one genuine
    row win all words."""
    import jax.numpy as jnp
    from spark_tpu.columnar import Batch, Column
    from spark_tpu.expr import ColumnRef
    from spark_tpu.expr_agg import First
    import spark_tpu.types as T

    v1, v2 = (2 << 32) | 1, (1 << 32) | 5
    f = First(ColumnRef("x"))

    def one_row(v):
        return Batch({"x": Column(jnp.asarray([v], jnp.int64), T.LONG)},
                     jnp.asarray([True]))

    schema = one_row(v1).schema()
    u1 = f.update(one_row(v1), None, row_base=0)
    u2 = f.update(one_row(v2), None, row_base=1)  # a later chunk
    merged = [np.minimum(np.asarray(a), np.asarray(b))
              for a, b in zip(u1[:-1], u2[:-1])]
    merged.append(np.asarray(u1[-1]) + np.asarray(u2[-1]))
    val, valid = f.finalize(merged, schema)
    assert bool(valid[0])
    assert int(val[0]) == v1  # the smaller global position, verbatim


def test_first_mesh_merge_picks_genuine_rows(session):
    """End-to-end on the 8-device mesh: the partial/final split merges
    per-shard First accumulators whose in-shard positions all restart at
    0 — without globally unique row bases the final min-merge combined
    shard 0's low word with shard 1's high word, returning 4294967297
    ((1<<32)|1), a value present in no input row."""
    mesh_key = "spark_tpu.sql.mesh.size"
    v1, v2 = (2 << 32) | 1, (1 << 32) | 5
    n = 4096
    x = np.full(n, v2, np.int64)
    x[:512] = v1  # shard 0 holds the v1 rows; shards 1..7 hold v2
    pdf = pd.DataFrame({"k": np.zeros(n, np.int64), "x": x})
    session.register_table("first_mesh", pdf)
    try:
        session.conf.set(mesh_key, 8)
        out = (session.table("first_mesh").group_by(col("k"))
               .agg(F.first(col("x")).alias("f"),
                    F.last(col("x")).alias("l"))
               .to_pandas())
    finally:
        session.conf.set(mesh_key, 0)
    assert int(out["f"][0]) in (v1, v2)
    assert int(out["l"][0]) in (v1, v2)
