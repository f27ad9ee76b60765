"""Probabilistic sketches on device: Bloom filter + Count-Min.

The reference ships JVM implementations (`common/sketch/BloomFilter.java`,
`CountMinSketch.java`) used by DataFrame stat functions and runtime join
filters. Here both are jnp bit/scatter kernels over device arrays: the
Bloom filter stores one bit per byte (scatter-max is the TPU-friendly
"bitwise or"; 8x the memory of a packed bitmap, all of it HBM-cheap),
and Count-Min is a [depth, width] scatter-add table.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_MIX_MUL = np.uint64(0xBF58476D1CE4E5B9)
_MIX_MUL2 = np.uint64(0x94D049BB133111EB)
#: the most indices one scatter of `BloomFilter.build` takes (256 MiB of
#: int32): up to it the k hashes share a scatter, beyond it they go in
#: turns, one hash a scatter for a side of more rows than this
_SCATTER_INDICES = 1 << 26


def _mix64(x, seed: int):
    salt = (seed * 0x9E3779B97F4A7C15 or 1) & 0xFFFFFFFFFFFFFFFF
    u = x.astype(jnp.uint64) ^ np.uint64(salt)
    u = (u ^ (u >> 30)) * _MIX_MUL
    u = (u ^ (u >> 27)) * _MIX_MUL2
    return u ^ (u >> 31)


class BloomFilter:
    """Membership sketch over int64 values.

    `num_bits` per expected item follows the reference's sizing
    (`BloomFilter.optimalNumOfBits`): m = -n ln(fpp) / ln(2)^2,
    k = m/n ln(2) hash functions."""

    def __init__(self, bits, num_hashes: int):
        self.bits = bits          # uint8[m], one logical bit per byte
        self.num_hashes = num_hashes

    @staticmethod
    def sizing(expected_items: int, fpp: float = 0.03):
        m = int(max(64, -expected_items * np.log(fpp) / (np.log(2) ** 2)))
        k = int(max(1, round(m / max(1, expected_items) * np.log(2))))
        return m, min(k, 8)

    @classmethod
    def build(cls, values, expected_items: Optional[int] = None,
              fpp: float = 0.03, mask=None) -> "BloomFilter":
        n = int(values.shape[0])
        m, k = cls.sizing(expected_items or n, fpp)
        x = values.astype(jnp.int64)
        # the k hashes' indices laid end to end and set by ONE scatter,
        # not k in a row: XLA:TPU sorts a scatter's indices, and each
        # such sort is compiled by itself (Q3's two filters were 10 of
        # its stage's 24 sorts; PERF.md, PR 37). The bits set are the
        # same. A scatter takes as many hashes as keep its index array
        # within _SCATTER_INDICES, so a large creation side (its
        # capacity, not its estimate, is `n`) holds no more at once
        # than it did hash by hash
        per = max(1, _SCATTER_INDICES // max(n, 1))
        groups = []
        for first in range(0, k, per):
            hashes = range(first, min(k, first + per))
            idx = jnp.concatenate([
                (_mix64(x, s) % np.uint64(m)).astype(jnp.int32)
                for s in hashes])
            if mask is not None:
                idx = jnp.where(jnp.tile(mask, len(hashes)), idx, m)
            groups.append(idx)
        bits = jnp.zeros((m,), jnp.uint8)
        for idx in groups:
            bits = bits.at[idx].max(jnp.ones_like(idx, jnp.uint8),
                                    mode="drop")
        return cls(bits, k)

    def might_contain(self, values):
        """Vectorized membership probe: False is definite, True is
        probabilistic (the join-prefilter contract)."""
        m = self.bits.shape[0]
        x = values.astype(jnp.int64)
        out = jnp.ones(values.shape, jnp.bool_)
        for s in range(self.num_hashes):
            idx = (_mix64(x, s) % np.uint64(m)).astype(jnp.int32)
            out = out & (jnp.take(self.bits, idx) > 0)
        return out


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
