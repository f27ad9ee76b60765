"""PR 40, four chips: a runtime filter of Q3's `rf0` size built under
`shard_map` (each shard scatters its quarter of the creation side into
the one-bit-a-byte staging array, the engine's pmax ORs the widened
bytes, the words are packed after it) against the filter ONE device
builds from all the keys: the same words, the same kept probe rows, no
key that went in pruned. On the chip a uint8 `lax.pmax` lost bits
(PR 22); the CPU's virtual mesh computes either correctly.

`python3 _archive/mesh_filter_chip.py` (prints one JSON line)."""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import spark_tpu  # noqa: F401
from spark_tpu import Conf
from spark_tpu import types as T
from spark_tpu.columnar import Batch, Column
from spark_tpu.execution.join import (apply_runtime_filter,
                                      build_runtime_filter)
from spark_tpu.expr import ColumnRef
from spark_tpu.parallel.mesh import AXIS, shard_map
from spark_tpu.plan.physical import ExecContext

small = "--small" in sys.argv
n = 4
rows, probed, est = ((1 << 14, 1 << 16, 8192) if small
                     else (1 << 20, 1 << 22, 1 << 20))


def stage(ctx):
    def run(keys, live, probe):
        filt = build_runtime_filter(
            Batch({"k": Column(keys, T.LongType())}, selection=live),
            ColumnRef("k"), ctx, expected_items=est)
        keep = apply_runtime_filter(
            filt, Batch({"k": Column(probe, T.LongType())}), ColumnRef("k"))
        return filt.bloom.words, keep
    return run


devices = jax.devices()
assert len(devices) >= n, devices
mesh = Mesh(np.array(devices[:n]), (AXIS,))
rs = np.random.default_rng(40)
keys = rs.integers(1, 6_000_000, rows)
live = rs.random(rows) < 0.15
probe = rs.integers(1, 6_000_000, probed)
sharded = NamedSharding(mesh, PartitionSpec(AXIS))
on_mesh = jax.jit(shard_map(
    stage(ExecContext(Conf(), AXIS, n)), mesh=mesh,
    in_specs=PartitionSpec(AXIS),
    out_specs=(PartitionSpec(), PartitionSpec(AXIS)), check_vma=False))
on_one = jax.jit(stage(ExecContext(Conf())))
args = [jnp.asarray(a) for a in (keys, live, probe)]
t0 = time.perf_counter()
words_m, keep_m = jax.block_until_ready(
    on_mesh(*[jax.device_put(a, sharded) for a in args]))
t1 = time.perf_counter()
words_1, keep_1 = jax.block_until_ready(on_one(*args))
words_m, keep_m, words_1, keep_1 = (np.asarray(a) for a in (
    words_m, keep_m, words_1, keep_1))
member = np.isin(probe, keys[live])
line = dict(
    device=devices[0].device_kind, chips=n, words=int(words_1.shape[0]),
    bits_set_mesh=int(np.unpackbits(words_m.view(np.uint8)).sum()),
    bits_set_one=int(np.unpackbits(words_1.view(np.uint8)).sum()),
    words_equal=bool(np.array_equal(words_m, words_1)),
    keep_equal=bool(np.array_equal(keep_m, keep_1)),
    false_negatives=int((member & ~keep_m).sum()),
    members=int(member.sum()), kept=int(keep_m.sum()),
    mesh_first_call_s=round(t1 - t0, 1))
print(json.dumps(line), flush=True)
ok = line["words_equal"] and line["keep_equal"] \
    and line["false_negatives"] == 0 and line["bits_set_one"] > 0
sys.exit(0 if ok else 1)
