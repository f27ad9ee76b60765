"""The sorts a join's stage is made of, written so that XLA:TPU
compiles them in time (PR 37: Q3's first request did not answer within
300 s on the chip): every one an unstable sort whose last key is the
row's position, a sorted search by one sort, a Bloom filter set by one
scatter. Each must give what the form it replaced gave, array for
array."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_tpu.execution.join import search_sorted
from spark_tpu.execution.sort import sort_carrying_positions
from spark_tpu.sketch import BloomFilter, _mix64


def _keys(rs, dtype, n, lo=-5, hi=15):
    a = rs.integers(lo, hi, n).astype(dtype)
    if np.issubdtype(np.dtype(dtype), np.floating) and n > 3:
        a[rs.integers(0, n)] = np.nan
        a[rs.integers(0, n)] = np.inf
        a[rs.integers(0, n)] = -0.0
    return a


@pytest.mark.parametrize("dtype", ["int64", "int32", "float64"])
@pytest.mark.parametrize("side", ["left", "right"])
def test_search_sorted_is_searchsorted(dtype, side):
    rs = np.random.default_rng(7)
    for n, m in ((1, 1), (7, 40), (59, 89), (64, 5)) * 3:
        build = jnp.sort(jnp.asarray(_keys(rs, dtype, n)))
        query = jnp.asarray(_keys(rs, dtype, m, -8, 18))
        want = jnp.searchsorted(build, query, side=side, method="sort")
        got = search_sorted(build, query, side=side)
        assert got.dtype == jnp.int32 and got.shape == (m,)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtypes", [("int8", "int64"), ("int32",),
                                    ("int8", "float64", "int8", "int32")])
def test_sort_carrying_positions_is_the_stable_sort(dtypes):
    rs = np.random.default_rng(11)
    for n in (1, 2, 17, 256):
        keys = [jnp.asarray(_keys(rs, d, n, 0, 4)) for d in dtypes]
        want = jax.lax.sort(
            tuple(keys) + (jnp.arange(n, dtype=jnp.int32),),
            num_keys=len(keys), is_stable=True)
        got = sort_carrying_positions(keys)
        assert len(got) == len(keys) + 1
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_the_sorts_are_unstable_sorts_with_the_position_as_last_key():
    """What makes the compile short is in the lowered text: one sort,
    not stable, every operand a key."""
    text = jax.jit(lambda a, b: sort_carrying_positions((a, b))).lower(
        jnp.zeros((64,), jnp.int8), jnp.zeros((64,), jnp.int64)).as_text()
    assert text.count("stablehlo.sort") == 1
    assert "is_stable = false" in text
    text = jax.jit(search_sorted).lower(
        jnp.zeros((64,), jnp.int64), jnp.zeros((256,), jnp.int64)).as_text()
    assert text.count("stablehlo.sort") == 1  # jnp's method="sort": 2


@pytest.mark.parametrize("hashes_a_scatter", [None, 2])
@pytest.mark.parametrize("masked", [False, True])
def test_the_bloom_filter_sets_the_same_bits_by_one_scatter(
        masked, hashes_a_scatter, monkeypatch):
    """All k hashes by one scatter; a side too large for that (here: the
    bound cut to two hashes' indices) by as few as the bound allows."""
    from spark_tpu import sketch
    if hashes_a_scatter:
        monkeypatch.setattr(sketch, "_SCATTER_INDICES",
                            hashes_a_scatter * 4000)
    rs = np.random.default_rng(3)
    values = jnp.asarray(rs.integers(0, 10**9, 4000))
    mask = jnp.asarray(rs.random(4000) < 0.6) if masked else None
    bloom = BloomFilter.build(values, expected_items=4000, mask=mask)
    m, k = bloom.bits.shape[0], bloom.num_hashes
    want = np.zeros(m, np.uint8)
    for s in range(k):
        idx = np.asarray((_mix64(values.astype(jnp.int64), s)
                          % np.uint64(m)).astype(jnp.int32))
        want[idx[np.asarray(mask)] if masked else idx] = 1
    np.testing.assert_array_equal(np.asarray(bloom.bits), want)
    assert k > 2 and bool(bloom.might_contain(values)[
        np.asarray(mask) if masked else slice(None)].all())
    text = jax.jit(lambda v: BloomFilter.build(
        v, expected_items=4000).bits).lower(values).as_text()
    # was one a hash
    assert text.count('"stablehlo.scatter"') == -(-k // (hashes_a_scatter
                                                         or k))
