"""What the hash exchange's send side costs on one chip, by its parts.

At the shapes of LINEITEM's exchange in Q5 at SF10 over four chips: 16 Mi
rows a chip, 15 M of them live and spread evenly over n = 4 targets, the
rest dead (target n), block 8 Mi. The send layout as it was (rows sorted
by target, bucket counts by a scatter-add, each column gathered into
target order and scattered into the [n * block] buffer, the live mask
likewise) against the engine's (`parallel/shuffle.py`: the bucket bounds
read off the sorted targets, each column gathered into target order and
cut into n contiguous slices, the live mask from the counts alone), for
an int64 column, a bool column, and LINEITEM's four int64 columns with
the mask. The `all_to_all` after it is the same in both and not here.
Each jitted alone, warmed, then the median of REPS calls that end in
block_until_ready. Each new line says whether its buffer is the old
one's, slot for slot.

Run on the chip: `python3 _archive/exchange_cost.py` (prints one JSON
line a measurement and writes them to chiprun_out/exchange_cost.jsonl);
`--small` is the CPU rehearsal at 1/64 of the rows.
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
from spark_tpu.parallel.shuffle import _cut_to_blocks, _sort_to_buckets

REPS = 5
MI = 1 << 20
OUT = []


def timed(name, fn, *args, **note):
    f = jax.jit(fn)
    out = jax.block_until_ready(f(*args))
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
    return out


def old_buckets(tgt, n, block):
    """The send layout's indices as the engine had them: row perm[r]
    goes to flat slot flat[r]; dead rows and rows past a full bucket to
    n * block, which the scatter drops."""
    L = tgt.shape[0]
    tgt_s, perm = jax.lax.sort(
        (tgt, jnp.arange(L, dtype=jnp.int32)), num_keys=1)
    counts = jnp.zeros((n + 1,), jnp.int32).at[tgt].add(
        jnp.ones((L,), jnp.int32), mode="drop")
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(L, dtype=jnp.int32) - jnp.take(starts,
                                                    jnp.clip(tgt_s, 0, n))
    flat = jnp.where((tgt_s < n) & (pos < block), tgt_s * block + pos,
                     n * block)
    return flat, perm, jnp.max(counts[:n])


def old_send(x, perm, flat, n, block):
    return jnp.zeros((n * block,), x.dtype).at[flat].set(
        jnp.take(x, perm), mode="drop")


def new_sent(counts, block):
    return jnp.arange(block, dtype=jnp.int32)[None, :] < counts[:, None]


def new_send(x, perm, bounds, counts, block):
    return _cut_to_blocks(x, perm, bounds, new_sent(counts, block)) \
        .reshape(-1)


def main():
    dev = jax.devices()[0]
    print(json.dumps(dict(device=dev.device_kind, platform=dev.platform)),
          flush=True)
    scale = 64 if "--small" in sys.argv else 1
    n, L, block = 4, 16 * MI // scale, 8 * MI // scale
    live_rows = 15_000_000 // scale
    rs = np.random.default_rng(44)
    tgt = rs.integers(0, n, L).astype(np.int32)
    tgt[live_rows:] = n
    tgt = jnp.asarray(tgt)
    shape = dict(rows=L, live=live_rows, n=n, block=block)

    # 1. the buckets: the sort and what is read off it
    flat, perm, old_max = timed(
        "buckets.old", lambda t: old_buckets(t, n, block), tgt, **shape)
    perm_new, bounds, counts, new_max = timed(
        "buckets.new", lambda t: _sort_to_buckets(t, n), tgt, **shape)
    assert int(old_max) == int(new_max) <= block

    # 2. one column's send buffer, and the gather both forms start with
    cols = {"int64": jnp.asarray(rs.integers(-2**62, 2**62, L), jnp.int64),
            "bool": jnp.asarray(rs.random(L) < 0.5)}
    for dtype, x in cols.items():
        timed("take." + dtype, lambda v, p: jnp.take(v, p), x, perm,
              dtype=dtype, **shape)
        want = timed("send.old." + dtype,
                     lambda v, p, f: old_send(v, p, f, n, block),
                     x, perm, flat, dtype=dtype, **shape)
        got = jax.jit(lambda v, p, b, c: new_send(v, p, b, c, block))(
            x, perm_new, bounds, counts)
        timed("send.new." + dtype,
              lambda v, p, b, c: new_send(v, p, b, c, block),
              x, perm_new, bounds, counts, dtype=dtype,
              same=bool((np.asarray(got) == np.asarray(want)).all()),
              **shape)

    # 3. LINEITEM's exchange: four int64 columns and the live mask
    four = [jnp.asarray(rs.integers(0, 2**40, L), jnp.int64)
            for _ in range(4)]
    sel = tgt < n
    timed("send4.old",
          lambda f, p, s, *xs: [old_send(x, p, f, n, block)
                                for x in xs + (s,)],
          flat, perm, sel, *four, columns=4, **shape)
    timed("send4.new",
          lambda b, c, p, *xs: [new_send(x, p, b, c, block) for x in xs]
          + [new_sent(c, block).reshape(-1)],
          bounds, counts, perm_new, *four, columns=4, **shape)
    write()


def write():
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/exchange_cost.jsonl", "w") as f:
        for line in OUT:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
