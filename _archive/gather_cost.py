"""PR 40: what a Bloom probe costs on the chip, by its parts.

A `jnp.take` at 4 Mi and 1 Mi int32 indices from a uint8 and from a
uint32 table of 0.25 MiB and of 8 MiB; the hash arithmetic of one
classic probe turn (`_mix64(x, s) % m`) against the blocked filter's
(`_mix64(x, 0)`, the top bits, k 5-bit fields OR-ed into a mask); the
whole classic probe (k gathers of a byte) against the blocked one (one
gather of a word) at Q3's two sizes; and the build, one scatter into m
bytes against one scatter into 32 * nw staging bytes and a pack, by
both layouts of the staging array. Each jitted alone, warmed, then the
median of REPS calls that end in block_until_ready.

Run on the chip: `python3 _archive/gather_cost.py` (prints one JSON
line a measurement and writes them to chiprun_out/gather_cost.jsonl).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

import spark_tpu  # noqa: F401  (x64 on, the compile cache placed)
from spark_tpu.sketch import _mix64

REPS = 15
MI = 1 << 20
OUT = []


def timed(name, fn, *args, **note):
    f = jax.jit(fn)
    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    ts = []
    for _ in range(REPS):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append((time.perf_counter() - t) * 1e3)
    ts.sort()
    line = dict(name=name, ms_p50=round(ts[len(ts) // 2], 4),
                ms_min=round(ts[0], 4), **note)
    OUT.append(line)
    print(json.dumps(line), flush=True)
    return line


def classic_sizing(n, fpp=0.03):
    m = int(max(64, -n * np.log(fpp) / (np.log(2) ** 2)))
    k = int(max(1, round(m / max(1, n) * np.log(2))))
    return m, min(k, 8)


def blocked_words(m):
    return 1 << max(3, int(np.ceil(np.log2(4 * m / 32))))


def classic_probe(bits, k):
    m = bits.shape[0]

    def probe(x):
        out = jnp.ones(x.shape, jnp.bool_)
        for s in range(k):
            idx = (_mix64(x, s) % np.uint64(m)).astype(jnp.int32)
            out = out & (jnp.take(bits, idx) > 0)
        return out
    return probe


def block_and_fields(x, nw, k):
    h = _mix64(x, 0)
    block = (h >> np.uint64(64 - (nw.bit_length() - 1))).astype(jnp.int32)
    fields = [((h >> np.uint64(5 * j)) & np.uint64(31)).astype(jnp.uint32)
              for j in range(k)]
    return block, fields


def blocked_mask(fields):
    mask = jnp.zeros(fields[0].shape, jnp.uint32)
    for f in fields:
        mask = mask | (jnp.uint32(1) << f)
    return mask


def blocked_probe(words, k):
    nw = words.shape[0]

    def probe(x):
        block, fields = block_and_fields(x, nw, k)
        mask = blocked_mask(fields)
        return (jnp.take(words, block) & mask) == mask
    return probe


def classic_build(m, k):
    def build(x):
        idx = jnp.concatenate([
            (_mix64(x, s) % np.uint64(m)).astype(jnp.int32)
            for s in range(k)])
        return jnp.zeros((m,), jnp.uint8).at[idx].max(
            jnp.ones_like(idx, jnp.uint8), mode="drop")
    return build


def blocked_build(nw, k, planes):
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def build(x):
        block, fields = block_and_fields(x, nw, k)
        if planes:   # staging[bit, word]
            idx = jnp.concatenate([f.astype(jnp.int32) * nw + block
                                   for f in fields])
        else:        # staging[word, bit]
            idx = jnp.concatenate([block * 32 + f.astype(jnp.int32)
                                   for f in fields])
        staging = jnp.zeros((32 * nw,), jnp.uint8).at[idx].max(
            jnp.ones_like(idx, jnp.uint8), mode="drop")
        if planes:
            return jnp.sum(staging.reshape(32, nw).astype(jnp.uint32)
                           << shifts[:, None], axis=0, dtype=jnp.uint32)
        return jnp.sum(staging.reshape(nw, 32).astype(jnp.uint32)
                       << shifts[None, :], axis=1, dtype=jnp.uint32)
    return build


def main():
    dev = jax.devices()[0]
    print(json.dumps(dict(device=dev.device_kind, platform=dev.platform)),
          flush=True)
    small = "--small" in sys.argv   # the CPU rehearsal
    scale = 64 if small else 1
    rs = np.random.default_rng(40)

    # 1. the gather alone
    for n in (4 * MI // scale, MI // scale):
        for dtype in (jnp.uint8, jnp.uint32):
            for table_bytes in (MI // 4, 8 * MI):
                size = table_bytes // np.dtype(dtype).itemsize
                table = jnp.asarray(rs.integers(0, 255, size), dtype)
                idx = jnp.asarray(rs.integers(0, size, n), jnp.int32)
                timed("take", lambda t, i: jnp.take(t, i), table, idx,
                      rows=n, dtype=np.dtype(dtype).name,
                      table_bytes=table_bytes)

    # 2. the hash arithmetic alone, 4 Mi int64 keys
    n = 4 * MI // scale
    x = jnp.asarray(rs.integers(0, 6_000_000, n), jnp.int64)
    m, k = classic_sizing(524288)
    nw = blocked_words(m)
    timed("hash.classic_turn",
          lambda v: (_mix64(v, 3) % np.uint64(m)).astype(jnp.int32), x,
          rows=n, m=m)
    timed("hash.mix_and_mask",
          lambda v: (_mix64(v, 0) & np.uint64(nw - 1)).astype(jnp.int32),
          x, rows=n, nw=nw)
    timed("hash.mix_top_bits",
          lambda v: block_and_fields(v, nw, k)[0], x, rows=n, nw=nw)
    timed("hash.blocked_whole",
          lambda v: (lambda b, f: (b, blocked_mask(f)))(
              *block_and_fields(v, nw, k)), x, rows=n, nw=nw, k=k)

    # 3. the whole probe, classic against blocked, at Q3's two sizes
    for rows, est in ((4 * MI // scale, 524288), (MI // scale, 32768)):
        m, k = classic_sizing(est)
        nw = blocked_words(m)
        keys = jnp.asarray(rs.integers(0, 6_000_000, rows), jnp.int64)
        bits = jnp.asarray(rs.random(m) < 0.3, jnp.uint8)
        words = jnp.asarray(rs.integers(0, 1 << 32, nw), jnp.uint32)
        timed("probe.classic", classic_probe(bits, k), keys,
              rows=rows, est=est, m=m, k=k)
        timed("probe.blocked", blocked_probe(words, k), keys,
              rows=rows, est=est, nw=nw, k=k)

    # 4. the build: creation sides of 1 Mi and 256 Ki slots
    for rows, est in ((MI // scale, 524288), (MI // 4 // scale, 32768)):
        m, k = classic_sizing(est)
        nw = blocked_words(m)
        keys = jnp.asarray(rs.integers(0, 6_000_000, rows), jnp.int64)
        timed("build.classic", classic_build(m, k), keys,
              rows=rows, est=est, m=m, k=k)
        for planes in (False, True):
            timed("build.blocked." + ("planes" if planes else "words"),
                  blocked_build(nw, k, planes), keys,
                  rows=rows, est=est, nw=nw, k=k, staging_bytes=32 * nw)

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gather_cost.jsonl", "w") as f:
        for line in OUT:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
