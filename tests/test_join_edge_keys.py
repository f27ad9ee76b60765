"""The equi-join's edge keys against an independent pandas reference.

One kernel matches every equi-join (`execution/join.py`: the build
sorted by key, each probe key found by binary search). These cases hold
it to pandas' answer where keys are awkward: every join type over
duplicate build keys (the many-to-many expansion), NULL keys, float
keys with both zeros, NaN keys, an empty build, one hot build key
beside many cold ones, and the probe sharded over the mesh.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_tpu.functions import col, lit

MESH_KEY = "spark_tpu.sql.mesh.size"
HOWS = ("inner", "left", "left_semi", "left_anti")


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


def _join_df(session, how):
    return session.table("hj_fact").join(
        session.table("hj_dim"), left_on=col("k"), right_on=col("k2"),
        how=how)


def _reference(left, right, how, lk="k", rk="k2"):
    """pandas' answer for `left JOIN right ON left.lk = right.rk`. A
    NULL build key matches nothing (pandas' merge would pair NULLs); a
    NaN is a value and matches NaN, as pandas has it."""
    if right[rk].dtype == "Int64":
        right = right[right[rk].notna()]
    if how in ("inner", "left"):
        return left.merge(right, left_on=lk, right_on=rk, how=how)
    found = left[lk].isin(right[rk]) & left[lk].notna() \
        if left[lk].dtype == "Int64" else left[lk].isin(right[rk])
    return left[found if how == "left_semi" else ~found]


def _canon(df):
    """Row order and integer-vs-float dtypes set aside: both frames as
    float64 columns, sorted by every column."""
    out = df.astype("float64")
    return out.sort_values(list(out.columns)).reset_index(drop=True)


def _assert_matches(got, want):
    assert list(got.columns) == list(want.columns), (got.columns,
                                                     want.columns)
    pd.testing.assert_frame_equal(_canon(got), _canon(want))


# -- every join type, duplicate build keys -----------------------------------

@pytest.mark.parametrize("how", HOWS)
def test_kernel_parity_join_matrix(tables, how):
    """Duplicate build keys included: the many-to-many prefix-sum
    expansion for inner/left, the existence test for semi/anti."""
    got = _join_df(tables, how).to_pandas()
    want = _reference(tables.table("hj_fact").to_pandas(),
                      tables.table("hj_dim").to_pandas(), how)
    assert len(got) > 0
    _assert_matches(got, want)


def test_kernel_parity_null_keys(session):
    left = pd.DataFrame({
        "k": pd.array([1, None, 3, 4, None, 2], dtype="Int64"),
        "lv": np.arange(6, dtype=np.int64)})
    right = pd.DataFrame({
        "k2": pd.array([2, 3, None, 3], dtype="Int64"),
        "rv": np.arange(4, dtype=np.int64)})
    session.register_table("hj_nl", left)
    session.register_table("hj_nr", right)
    for how in HOWS:
        got = session.table("hj_nl").join(
            session.table("hj_nr"), left_on=col("k"),
            right_on=col("k2"), how=how).to_pandas()
        _assert_matches(got, _reference(left, right, how))
        if how == "left_anti":
            # anti keeps null-key probe rows; null build keys never match
            assert set(got["lv"]) == {0, 1, 3, 4}


def test_kernel_parity_float_keys(session):
    """Float keys: +0.0 and -0.0 join equal."""
    left = pd.DataFrame({
        "k": np.array([0.0, -0.0, 1.5, 2.5, 3.25], dtype=np.float64),
        "lv": np.arange(5, dtype=np.int64)})
    right = pd.DataFrame({
        "k2": np.array([-0.0, 2.5, 99.0], dtype=np.float64),
        "rv": np.arange(3, dtype=np.int64)})
    session.register_table("hj_fl", left)
    session.register_table("hj_fr", right)
    got = session.table("hj_fl").join(
        session.table("hj_fr"), left_on=col("k"),
        right_on=col("k2")).to_pandas()
    _assert_matches(got, _reference(left, right, "inner"))
    assert set(got["lv"]) == {0, 1, 3}  # both zeros matched


def test_kernel_parity_empty_build(tables):
    fact = tables.table("hj_fact").to_pandas()
    empty = tables.table("hj_dim").to_pandas().iloc[:0]
    for how in HOWS:
        got = tables.table("hj_fact").join(
            tables.table("hj_dim").filter(col("w") > lit(10 ** 9)),
            left_on=col("k"), right_on=col("k2"), how=how).to_pandas()
        _assert_matches(got, _reference(fact, empty, how))
        assert len(got) == (0 if how in ("inner", "left_semi")
                            else len(fact))


def test_kernel_parity_skewed_keys_near_capacity(session):
    """One hot build key (a sorted run of 600 rows) beside 699 distinct
    cold ones: the expansion's output is several times its probe."""
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
    got = session.table("hj_skp").join(
        session.table("hj_skb"), left_on=col("k"),
        right_on=col("k2")).to_pandas()
    want = _reference(probe, build, "inner")
    assert len(want) > len(probe)
    _assert_matches(got, want)


# -- mesh --------------------------------------------------------------------

def test_kernel_parity_mesh_sharded_probe(tables):
    tables.conf.set(MESH_KEY, 8)
    got = _join_df(tables, "inner").to_pandas()
    _assert_matches(got, _reference(tables.table("hj_fact").to_pandas(),
                                    tables.table("hj_dim").to_pandas(),
                                    "inner"))


# -- NaN and signed zero -----------------------------------------------------

@pytest.mark.parametrize("build_keys,want_rows", [
    ([float("nan"), 2.5, 9.0], 3),        # unique-build fast path
    ([float("nan"), float("nan"), 2.5], 5),  # dup NaN: m2m expansion
])
def test_kernel_parity_nan_keys(session, build_keys, want_rows):
    """Non-null NaN float keys (Parquet NaN is a VALUE, not null) join
    equal to NaN, matching pandas merge. Regression: the +inf sentinel
    broke the sorted order whenever the build had NaN keys plus padding
    (NaN probes never matched), and duplicate NaN build keys slipped
    past build_has_duplicates' `==` so the unique fast path dropped
    their extra matches."""
    nan = float("nan")
    session.register_table("nan_p", pa.table({
        "k": pa.array([1.5, nan, nan, 2.5], type=pa.float64()),
        "v": pa.array([0, 1, 2, 3], type=pa.int64())}))
    session.register_table("nan_b", pa.table({
        "k2": pa.array(build_keys, type=pa.float64()),
        "w": pa.array([10, 20, 30], type=pa.int64())}))
    got = (session.table("nan_p").join(
               session.table("nan_b"),
               left_on=col("k"), right_on=col("k2"))
           .to_pandas().sort_values(["v", "w"]).reset_index(drop=True))
    want = (session.table("nan_p").to_pandas()
            .merge(session.table("nan_b").to_pandas(),
                   left_on="k", right_on="k2")
            .sort_values(["v", "w"]).reset_index(drop=True))
    assert len(got) == want_rows == len(want)
    pd.testing.assert_frame_equal(got, want)


def test_kernel_parity_signed_zero_keys(session):
    """-0.0 and +0.0 join equal (canonicalized before the sort and the
    search), matching pandas merge."""
    session.register_table("z_p", pa.table({
        "k": pa.array([-0.0, 0.0], type=pa.float64()),
        "v": pa.array([0, 1], type=pa.int64())}))
    session.register_table("z_b", pa.table({
        "k2": pa.array([0.0], type=pa.float64()),
        "w": pa.array([7], type=pa.int64())}))
    got = (session.table("z_p").join(
               session.table("z_b"),
               left_on=col("k"), right_on=col("k2"))
           .to_pandas().sort_values("v").reset_index(drop=True))
    want = (session.table("z_p").to_pandas()
            .merge(session.table("z_b").to_pandas(),
                   left_on="k", right_on="k2")
            .sort_values("v").reset_index(drop=True))
    assert len(got) == 2
    pd.testing.assert_frame_equal(got, want)
