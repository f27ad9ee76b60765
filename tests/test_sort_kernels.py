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


def _blocked_reference(values, num_words, k, keep=None):
    """The blocked layout in numpy: ONE mix a key, its word from the
    hash's top bits, k bit positions from the 5-bit fields of its low
    bits, OR-ed into that one word."""
    h = np.asarray(_mix64(jnp.asarray(values, jnp.int64), 0))
    word = (h >> np.uint64(64 - (num_words.bit_length() - 1))).astype(
        np.int64)
    keep = slice(None) if keep is None else np.asarray(keep)
    words = np.zeros(num_words, np.uint32)
    for j in range(k):
        bit = ((h >> np.uint64(5 * j)) & np.uint64(31)).astype(np.uint32)
        np.bitwise_or.at(words, word[keep], np.uint32(1) << bit[keep])
    return words


def _scatters(text):
    return text.count('"stablehlo.scatter"')


@pytest.mark.parametrize("hashes_a_scatter", [None, 2])
@pytest.mark.parametrize("masked", [False, True])
def test_the_bloom_filter_sets_the_same_bits_by_one_scatter(
        masked, hashes_a_scatter, monkeypatch):
    """All k bits by one scatter; a side too large for that (here: the
    bound cut to two hashes' indices) by as few as the bound allows."""
    from spark_tpu import sketch
    if hashes_a_scatter:
        monkeypatch.setattr(sketch, "_SCATTER_INDICES",
                            hashes_a_scatter * 4000)
    rs = np.random.default_rng(3)
    values = jnp.asarray(rs.integers(0, 10**9, 4000))
    mask = jnp.asarray(rs.random(4000) < 0.6) if masked else None
    bloom = BloomFilter.build(values, expected_items=4000, mask=mask)
    nw, k = bloom.words.shape[0], bloom.num_hashes
    assert (nw, k) == BloomFilter.sizing(4000) and bloom.words.dtype \
        == jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(bloom.words), _blocked_reference(values, nw, k, mask))
    assert k > 2 and bool(bloom.might_contain(values)[
        np.asarray(mask) if masked else slice(None)].all())
    text = jax.jit(lambda v: BloomFilter.build(
        v, expected_items=4000).words).lower(values).as_text()
    # was one a hash
    assert _scatters(text) == -(-k // (hashes_a_scatter or k))


@pytest.mark.parametrize("fpp,k", [(0.2, 2), (0.03, 5), (0.001, 8)])
def test_a_probe_is_one_gather_and_no_remainder_whatever_k(fpp, k):
    """What the chip charges a probe for is in the lowered text: one
    gather of a word a key (the classic layout took k, each behind a
    64-bit remainder by a table size that was no power of two)."""
    values = jnp.arange(4000)
    bloom = BloomFilter.build(values, expected_items=4000, fpp=fpp)
    assert bloom.num_hashes == k
    text = jax.jit(lambda w, v: BloomFilter(w, k).might_contain(v)) \
        .lower(bloom.words, values).as_text()
    assert text.count('"stablehlo.gather"') == 1
    assert "remainder" not in text and "divide" not in text


@pytest.mark.parametrize("fpp,k", [(0.2, 2), (0.03, 5), (0.001, 8)])
def test_a_build_is_one_scatter_whatever_k(fpp, k):
    text = jax.jit(lambda v: BloomFilter.build(
        v, expected_items=4000, fpp=fpp).words).lower(
            jnp.arange(4000)).as_text()
    assert _scatters(text) == 1 and "remainder" not in text


@pytest.mark.parametrize("load", [1.0, 0.25])
@pytest.mark.parametrize("expected_items", [1000, 32768, 524288])
def test_never_a_false_negative_and_the_rate_is_under_fpp(
        expected_items, load):
    """The contract of the sizing: at the design load and at a quarter
    of it the measured false-positive rate is at or under `fpp`; a key
    that went in is always found, built with a mask or without."""
    fpp = 0.03
    rs = np.random.default_rng(expected_items)
    n = int(expected_items * load)
    keys = rs.choice(1 << 40, n + 200_000, replace=False)
    inside, outside = jnp.asarray(keys[:n]), jnp.asarray(keys[n:])
    bloom = BloomFilter.build(inside, expected_items=expected_items,
                              fpp=fpp)
    assert bool(bloom.might_contain(inside).all())
    assert float(bloom.might_contain(outside).mean()) <= fpp
    # the same keys beside 200,000 that a mask leaves out
    both = jnp.concatenate([inside, outside])
    mask = jnp.arange(both.shape[0]) < n
    masked = BloomFilter.build(both, expected_items=expected_items,
                               fpp=fpp, mask=mask)
    np.testing.assert_array_equal(np.asarray(masked.words),
                                  np.asarray(bloom.words))


@pytest.mark.parametrize("expected_items,fpp", [
    (1, 0.03), (8, 0.03), (1000, 0.03), (32768, 0.03), (524288, 0.03),
    (524288, 0.2), (524288, 0.001), (20_000_000, 0.001)])
def test_the_table_is_a_power_of_two_of_words_in_no_more_bytes(
        expected_items, fpp):
    """Four times the classic filter's bits, rounded up to a power of
    two of 32-bit words: between half and all of the bytes the
    one-bit-a-byte array of m took."""
    m = int(max(64, -expected_items * np.log(fpp) / (np.log(2) ** 2)))
    nw, k = BloomFilter.sizing(expected_items, fpp)
    assert nw & (nw - 1) == 0 and 1 <= k <= 8
    assert 4 * m <= 32 * nw < 8 * m + 64 and 4 * nw <= m


@pytest.mark.parametrize("masked", [False, True])
def test_a_filter_too_large_to_stage_at_once_is_built_by_slices(
        masked, monkeypatch):
    """The staging array is 32 bytes a word; past the bound (here cut to
    64 words' worth) the words are built a slice at a time, one scatter
    a slice, and are the unsliced build's."""
    from spark_tpu import sketch
    rs = np.random.default_rng(5)
    values = jnp.asarray(rs.integers(0, 10**9, 4000))
    mask = jnp.asarray(rs.random(4000) < 0.6) if masked else None
    whole = BloomFilter.build(values, expected_items=4000, mask=mask)
    monkeypatch.setattr(sketch, "_STAGING_BYTES", 64 * 32)
    sliced = BloomFilter.build(values, expected_items=4000, mask=mask)
    np.testing.assert_array_equal(np.asarray(sliced.words),
                                  np.asarray(whole.words))
    text = jax.jit(lambda v: BloomFilter.build(
        v, expected_items=4000).words).lower(values).as_text()
    assert _scatters(text) == whole.words.shape[0] // 64 > 1


def test_a_filter_of_more_words_than_one_hash_indexes_takes_a_second_mix():
    """k = 8 leaves 24 bits of the one hash for the word's index; a
    table of 2**26 words takes its index from a second mix, so the
    index and the bit positions stay independent."""
    from spark_tpu.sketch import _word_and_bits
    x = jnp.arange(1000, dtype=jnp.int64)
    word, bits = _word_and_bits(x, 1 << 26, 8)
    assert len(bits) == 8 and all(
        0 <= int(b.min()) and int(b.max()) < 32 for b in bits)
    np.testing.assert_array_equal(
        np.asarray(word), np.asarray(_mix64(x, 1)) >> np.uint64(64 - 26))
    word, _ = _word_and_bits(x, 1 << 24, 8)
    np.testing.assert_array_equal(
        np.asarray(word), np.asarray(_mix64(x, 0)) >> np.uint64(64 - 24))
