"""Probabilistic sketches on device: Bloom filter + Count-Min.

The reference ships JVM implementations (`common/sketch/BloomFilter.java`,
`CountMinSketch.java`) used by DataFrame stat functions and runtime join
filters. Here both are jnp bit/scatter kernels over device arrays: the
Bloom filter is register-blocked, a key's k bits all in one 32-bit word
of a power-of-two table, so a probe is one hash and ONE gather whatever
k is (on the chip a gather costs by the rows gathered, some 10 ns each,
not by the table: PERF.md, PR 40), and Count-Min is a [depth, width]
scatter-add table.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

_MIX_MUL = np.uint64(0xBF58476D1CE4E5B9)
_MIX_MUL2 = np.uint64(0x94D049BB133111EB)
#: the most indices one scatter of `BloomFilter.build` takes (256 MiB of
#: int32): up to it the k bits share a scatter, beyond it they go in
#: turns, one bit a scatter for a side of more rows than this
_SCATTER_INDICES = 1 << 26
#: the most bytes `BloomFilter.build` stages at once, one byte a bit
#: (32 a word): a filter of more than 8 Mi words (some 9 Mi expected
#: keys and more at the default fpp) is built a slice of its words at
#: a time
_STAGING_BYTES = 1 << 28
_WORD_BITS = 32
_FIELD_BITS = 5       # log2(_WORD_BITS): one bit position of a word


def _mix64(x, seed: int):
    salt = (seed * 0x9E3779B97F4A7C15 or 1) & 0xFFFFFFFFFFFFFFFF
    u = x.astype(jnp.uint64) ^ np.uint64(salt)
    u = (u ^ (u >> 30)) * _MIX_MUL
    u = (u ^ (u >> 27)) * _MIX_MUL2
    return u ^ (u >> 31)


def _word_and_bits(x, num_words: int, num_hashes: int):
    """A key's place in a blocked filter from ONE mix: its word from the
    hash's top bits (`num_words` is a power of two: a shift, no 64-bit
    remainder) and `num_hashes` bit positions from as many disjoint
    5-bit fields of its low bits (two fields may name one bit). A
    filter of more words than the fields leave index bits takes the
    index from a second mix."""
    h = _mix64(x, 0)
    index_bits = num_words.bit_length() - 1
    spare = h if index_bits + _FIELD_BITS * num_hashes <= 64 \
        else _mix64(x, 1)
    word = (spare >> np.uint64(64 - index_bits)).astype(jnp.int32)
    bits = [((h >> np.uint64(_FIELD_BITS * j))
             & np.uint64(_WORD_BITS - 1)).astype(jnp.int32)
            for j in range(num_hashes)]
    return word, bits


class BloomFilter:
    """Membership sketch over int64 values, register-blocked: `words` is
    uint32[nw], nw a power of two, and a key sets and tests k bits of
    ONE word.

    k and the classic filter's bit count m follow the reference's
    sizing (`BloomFilter.optimalNumOfBits`): m = -n ln(fpp) / ln(2)^2,
    k = m/n ln(2). A blocked filter at m bits has a worse rate than the
    classic one, and a gather's cost does not grow with the table, so
    the table takes four times the bits: nw is the power of two at or
    above 4 m / 32, between m/2 and m bytes (the classic layout here
    held one bit a byte: m bytes). `fpp` is then an upper bound: at the
    design load the measured rate is some twentieth of it."""

    def __init__(self, words, num_hashes: int):
        self.words = words        # uint32[nw]
        self.num_hashes = num_hashes

    @staticmethod
    def sizing(expected_items: int, fpp: float = 0.03):
        """(number of 32-bit words, number of hashes)."""
        m = int(max(64, -expected_items * np.log(fpp) / (np.log(2) ** 2)))
        k = int(max(1, round(m / max(1, expected_items) * np.log(2))))
        num_words = 1 << (-(-4 * m // _WORD_BITS) - 1).bit_length()
        return num_words, min(k, 8)

    @classmethod
    def build(cls, values, expected_items: Optional[int] = None,
              fpp: float = 0.03, mask=None,
              combine: Optional[Callable] = None) -> "BloomFilter":
        """`combine`, under a mesh, ORs the shards' staging bytes (one
        bit a byte, so a cross-shard max is the OR; the words are
        packed after it)."""
        n = int(values.shape[0])
        nw, k = cls.sizing(expected_items or n, fpp)
        word, bits = _word_and_bits(values.astype(jnp.int64), nw, k)
        # the k bits' indices laid end to end and set by ONE scatter,
        # not k in a row: XLA:TPU sorts a scatter's indices, and each
        # such sort is compiled by itself (Q3's two filters were 10 of
        # its stage's 24 sorts; PERF.md, PR 37). A scatter takes as
        # many of the k as keep its index array within
        # _SCATTER_INDICES, so a large creation side (its capacity, not
        # its estimate, is `n`) holds no more at once than it did bit
        # by bit. It lands in a staging array of one byte a bit, laid
        # out [bit, word] (a [word, bit] array's 32-wide minor
        # dimension is padded to the chip's 128 lanes when it is
        # packed: 200 MB more of temporaries for Q3's larger filter),
        # which is then packed to words; a filter whose staging would
        # pass _STAGING_BYTES is built a slice of its words at a time,
        # each slice's scatter dropping the other slices' indices
        per = max(1, _SCATTER_INDICES // max(n, 1))
        # a power of two, as nw is: the slices tile the words exactly
        slice_words = min(nw, 1 << max(
            0, (_STAGING_BYTES // _WORD_BITS).bit_length() - 1))
        shifts = jnp.arange(_WORD_BITS, dtype=jnp.uint32)[:, None]
        slices = []
        for first_word in range(0, nw, slice_words):
            local = word - first_word
            live = mask
            if slice_words < nw:
                inside = (local >= 0) & (local < slice_words)
                live = inside if mask is None else inside & mask
            staging = jnp.zeros((slice_words * _WORD_BITS,), jnp.uint8)
            for first in range(0, k, per):
                group = bits[first:first + per]
                idx = jnp.concatenate(
                    [b * slice_words + local for b in group])
                if live is not None:
                    idx = jnp.where(jnp.tile(live, len(group)), idx,
                                    staging.shape[0])
                staging = staging.at[idx].max(
                    jnp.ones_like(idx, jnp.uint8), mode="drop")
            if combine is not None:
                staging = combine(staging)
            slices.append(jnp.sum(
                staging.reshape(_WORD_BITS, slice_words).astype(jnp.uint32)
                << shifts, axis=0, dtype=jnp.uint32))
        return cls(jnp.concatenate(slices), k)

    def might_contain(self, values):
        """Vectorized membership probe: False is definite, True is
        probabilistic (the join-prefilter contract). One hash, one
        gather of a word a key."""
        word, bits = _word_and_bits(values.astype(jnp.int64),
                                    self.words.shape[0], self.num_hashes)
        want = jnp.zeros(values.shape, jnp.uint32)
        for b in bits:
            want = want | (jnp.uint32(1) << b.astype(jnp.uint32))
        return (jnp.take(self.words, word) & want) == want


class CountMinSketch:
    """Frequency sketch: [depth, width] counters, point query = min over
    rows (reference: CountMinSketch.java)."""

    def __init__(self, table, depth: int, width: int):
        self.table = table
        self.depth = depth
        self.width = width

    @staticmethod
    def sizing(eps: float = 0.001, confidence: float = 0.99):
        width = int(np.ceil(2.0 / eps))
        depth = int(np.ceil(-np.log(1.0 - confidence) / np.log(2.0)))
        return max(1, depth), max(16, width)

    @classmethod
    def build(cls, values, eps: float = 0.001, confidence: float = 0.99,
              mask=None) -> "CountMinSketch":
        depth, width = cls.sizing(eps, confidence)
        table = jnp.zeros((depth, width), jnp.int64)
        x = values.astype(jnp.int64)
        ones = jnp.ones(values.shape, jnp.int64)
        for d in range(depth):
            idx = (_mix64(x, d) % np.uint64(width)).astype(jnp.int32)
            if mask is not None:
                idx = jnp.where(mask, idx, width)
            table = table.at[d].set(
                table[d].at[idx].add(ones, mode="drop"))
        return cls(table, depth, width)

    def estimate(self, values):
        x = values.astype(jnp.int64)
        est = None
        for d in range(self.depth):
            idx = (_mix64(x, d) % np.uint64(self.width)).astype(jnp.int32)
            row = jnp.take(self.table[d], idx)
            est = row if est is None else jnp.minimum(est, row)
        return est
