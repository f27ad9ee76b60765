"""The main path's Pallas kernels, compiled for a described TPU v5e.

Interpret mode accepts what the chip's compiler refuses: before ISSUE
22 the factored kernel asked for 24.75M of scoped VMEM against a 16M
limit and every interpret-mode test passed. These cases hand
`dense_groupby_sums(..., interpret=False)` the shapes the served path
really traces (recorded from CPU runs of TPC-H Q1/Q5/Q6 at SF1 and the
83.9M-row linear-keys aggregate) to the TPU compiler installed here,
for a chip that is described and not attached. Nothing runs: a compile
that passes is not a chip run.

The topology is described inside the module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports this file (on-chip-measurement guide, section 2).
"""

import jax
import jax.numpy as jnp
import pytest

from spark_tpu.execution.pallas_groupby import dense_groupby_sums


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent
    # cache but never read back without the chip: keep it off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


#: (rows, groups, int row widths, float rows)
SHAPES = {
    # README headline: count + 64-bit sum into 65,536 groups
    "factored_65536_groups_8_64": (1 << 20, 65536, [8, 64], 0),
    # what the linear-keys aggregate traces today: one 16 Mi-row chunk,
    # the count alone (sum(k) rewrites to k * count), a NULL-key slot
    "factored_linear_keys_chunk": (1 << 24, 65537, [8], 0),
    # merge mode (a final aggregate folding per-shard partials): the
    # occupancy row beside full 64-bit limbs for every partial
    # accumulator, aggregate.py's `64 if merge`
    "factored_merge_mode": (1 << 16, 65536, [8, 64, 64], 0),
    # TPC-H Q1 at SF1: 8 Mi-row capacity, 12 slots, seven decimal sums
    # beside their counts, and the occupancy row
    "small_q1_sf1": (1 << 23, 12, [64, 8] * 7 + [8], 0),
    # Q1-like with DOUBLE sums: int and float rows in one call
    "small_int_and_float_rows": (1 << 20, 6, [8, 64, 64], 2),
    "small_q5_sf1": (1 << 23, 26, [64, 8], 0),
    # Q5 under mesh.size=4: the per-shard partial, then the final merge
    # of the exchanged partial tables
    "small_q5_mesh4_partial": (1 << 22, 26, [64, 8], 0),
    "small_q5_mesh4_final_merge": (64, 26, [8, 64, 64], 0),
    "small_q6_sf1": (1 << 17, 1, [64, 8], 0),
    # bench's 100-group count; `id % 100` traces a 200-slot domain
    "small_count_100_groups": (1 << 24, 100, [8], 0),
    "small_count_200_slots": (1 << 24, 200, [8], 0),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_compiles_for_v5e(one_chip, shape):
    n, domain, widths, n_float = SHAPES[shape]

    def sums(idx, ints, floats):
        return dense_groupby_sums(idx, list(ints), list(floats), domain,
                                  interpret=False, int_widths=widths)

    def spec(dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    compiled = jax.jit(sums).lower(
        spec(jnp.int32), tuple(spec(jnp.int64) for _ in widths),
        tuple(spec(jnp.float64) for _ in range(n_float))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", ["int64", "float64", "uint8"])
def test_mesh_pmax_pmin_compile_for_a_v5e_mesh(topo, dtype):
    """XLA:TPU all-reduces 64-bit operands by Sum only and gets 8-bit
    max/min wrong; the engine's cross-shard max/min of int64 stats and
    runtime-filter bounds and of uint8 Bloom bits (`parallel/mesh.py`)
    must lower for a four-chip mesh by their detours."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from spark_tpu.parallel.mesh import AXIS, pmax, pmin, shard_map
    mesh = Mesh(np.array(topo.devices[:4]), (AXIS,))
    bounds = shard_map(
        lambda x: (pmax(x, AXIS), pmin(x.min(), AXIS)), mesh=mesh,
        in_specs=PartitionSpec(AXIS), out_specs=PartitionSpec(),
        check_vma=False)
    x = jax.ShapeDtypeStruct(
        (1 << 20,), jnp.dtype(dtype),
        sharding=NamedSharding(mesh, PartitionSpec(AXIS)))
    # the refusal came from lower/compile: returning is the proof
    assert jax.jit(bounds).lower(x).compile() is not None


@pytest.mark.parametrize("sent", ["all_to_all", "all_gather", "psum",
                                  "pmax_narrow"])
def test_the_exchanges_collectives_keep_names_their_reader_knows(topo, sent):
    """`benchmark/layer_metrics/exchange_ms.py` finds a mesh stage's
    collectives in the device trace by the names of their HLO
    instructions, and XLA:TPU names one after its opcode or after the
    JAX primitive it came from (`all-gather.10`, `all_to_all.15`,
    `pmax.6`). What `parallel/shuffle.py` and `parallel/mesh.py` send
    (a hash exchange's `all_to_all` of 64-bit columns,
    `all_gather_batch`, the statistics' `psum`, a widened `pmax`) is
    compiled here for a four-chip v5e mesh, and every instruction whose
    opcode is a collective must bear a name the reader counts."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmark.layer_metrics.exchange_ms import is_collective
    from spark_tpu.parallel.mesh import AXIS, pmax, shard_map
    n, block = 4, 1 << 15
    send = {
        "all_to_all": lambda x: jax.lax.all_to_all(
            x.reshape(n, block), AXIS, 0, 0).reshape(n * block),
        "all_gather": lambda x: jax.lax.all_gather(x, AXIS).reshape(-1),
        "psum": lambda x: jax.lax.psum(x, AXIS),
        "pmax_narrow": lambda x: pmax(x.astype(jnp.uint8), AXIS)
        .astype(jnp.int64),
    }[sent]
    mesh = Mesh(np.array(topo.devices[:n]), (AXIS,))
    x = jax.ShapeDtypeStruct(
        (n * n * block,), jnp.int64,
        sharding=NamedSharding(mesh, PartitionSpec(AXIS)))
    text = jax.jit(shard_map(
        send, mesh=mesh, in_specs=PartitionSpec(AXIS),
        out_specs=PartitionSpec(AXIS), check_vma=False)).lower(x) \
        .compile().as_text()
    sent_as = re.findall(
        r"%([\w.-]+) = [^=]*? (?:all-to-all|all-reduce|all-gather|"
        r"collective-permute|reduce-scatter)(?:-start|-done)?\(", text)
    assert sent_as, text[:2000]
    assert all(is_collective(name) for name in sent_as), sent_as
    assert not is_collective("fusion.71") and not is_collective("copy.1")


def test_a_mesh_built_filter_is_one_widened_all_reduce_and_one_gather(topo):
    """A runtime filter built and probed under `shard_map`, at the sizes
    of Q3's `rf1` (32,768 expected keys, ORDERS' 1 Mi slots probed),
    compiled for a four-chip v5e mesh: the shards' staging bytes meet
    in ONE all-reduce over 32 widened bytes a word that bears a name
    `exchange_ms` counts, the words are packed after it, and a probed
    key costs one gather of a 32-bit word."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmark.layer_metrics.exchange_ms import is_collective
    from spark_tpu import Conf
    from spark_tpu import types as T
    from spark_tpu.columnar import Batch, Column
    from spark_tpu.execution.join import (apply_runtime_filter,
                                          build_runtime_filter)
    from spark_tpu.expr import ColumnRef
    from spark_tpu.parallel.mesh import AXIS, shard_map
    from spark_tpu.plan.physical import ExecContext
    from spark_tpu.sketch import BloomFilter
    n, est = 4, 32768
    nw, _ = BloomFilter.sizing(est)

    def stage(keys, live, probed):
        filt = build_runtime_filter(
            Batch({"k": Column(keys, T.LongType())}, selection=live),
            ColumnRef("k"), ExecContext(Conf(), AXIS, n),
            expected_items=est)
        return apply_runtime_filter(
            filt, Batch({"k": Column(probed, T.LongType())}),
            ColumnRef("k"))

    mesh = Mesh(np.array(topo.devices[:n]), (AXIS,))

    def spec(rows, dtype):
        return jax.ShapeDtypeStruct(
            (rows,), dtype,
            sharding=NamedSharding(mesh, PartitionSpec(AXIS)))

    text = jax.jit(shard_map(
        stage, mesh=mesh, in_specs=PartitionSpec(AXIS),
        out_specs=PartitionSpec(AXIS), check_vma=False)).lower(
            spec(1 << 18, jnp.int64), spec(1 << 18, jnp.bool_),
            spec(1 << 20, jnp.int64)).compile().as_text()
    staged = re.findall(
        r"%([\w.-]+) = s32\[" + str(32 * nw) + r"\]\S* all-reduce\(", text)
    assert len(staged) == 1 and is_collective(staged[0]), staged
    assert not re.search(r"= u8\[\d+\]\S* all-reduce\(", text)
    assert len(re.findall(r" gather\(", text)) == 1
    assert re.search(r"= u32\[262144\]\S* gather\(", text)


def _sorts_of(compiled_text):
    """The names of the instructions whose opcode is `sort`."""
    import re
    return re.findall(r"%([\w.-]+) = [^=]*? sort\(", compiled_text)


@pytest.mark.parametrize("kernel", ["join_build", "order_by_limit"])
def test_the_engines_sorts_keep_a_name_their_reader_knows(one_chip, kernel):
    """`benchmark/layer_metrics/sort_ms.py` finds a stage's sorts in
    the device trace by the names of their HLO instructions (`sort.12`).
    A join's build side (`execution/join.py::build_sorted`: validity,
    then a 64-bit key, the permutation carried) and Q3's `ORDER BY
    revenue DESC, o_orderdate` (`execution/sort.py::sort_permutation`:
    a 64-bit and a 32-bit key) are compiled here for a described v5e,
    and every instruction whose opcode is `sort` must bear a name the
    reader counts. At 1,024 rows, because XLA:TPU takes 75-100 s for
    each of them at 32,768 (sandbox, PR 37), and Q3's whole stage four to
    five minutes: `test_q3s_stage_compiles_for_v5e` below, marked slow."""
    from benchmark.layer_metrics.sort_ms import is_sort
    from spark_tpu import types as T
    from spark_tpu.columnar import Batch, Column
    from spark_tpu.execution import join as join_kernels
    from spark_tpu.execution import sort as sort_kernels
    from spark_tpu.expr import Vec
    from spark_tpu.functions import col
    n = 1 << 10

    def spec(dtype):
        return jax.ShapeDtypeStruct((n,), jnp.dtype(dtype), sharding=one_chip)

    if kernel == "join_build":
        def program(keys, sel):
            return join_kernels.build_sorted(Vec(keys, T.LONG), sel)

        args = (spec("int64"), spec("bool"))
    else:
        def program(revenue, date, sel):
            batch = Batch({"revenue": Column(revenue, T.LONG),
                           "o_orderdate": Column(date, T.DATE)}, sel)
            return sort_kernels.sort_permutation(
                batch, [col("revenue").desc(), col("o_orderdate").asc()])

        args = (spec("int64"), spec("int32"), spec("bool"))
    sorts = _sorts_of(jax.jit(program).lower(*args).compile().as_text())
    assert sorts
    assert all(is_sort(name) for name in sorts), sorts
    assert not is_sort("fusion.71") and not is_sort("copy.1")


@pytest.mark.slow
def test_q3s_stage_compiles_for_v5e(one_chip, tmp_path):
    """TPC-H Q3's whole stage at SF0.01, as the served path traces it
    (`spark_tpu/testing/stage_lowering.py`), through the TPU compiler
    for a described v5e: 24 instructions named `sort.<n>` in 321 s as
    the tree stood, 4 minutes since the sorts carry their positions
    (sandbox, PR 37), every one a name `sort_ms` counts."""
    import os

    from benchmark.datagen import customer, lineitem, orders
    from benchmark.layer_metrics.sort_ms import is_sort
    from spark_tpu import Conf
    from spark_tpu.io.sources import ParquetSource
    from spark_tpu.session import SparkTpuSession
    from spark_tpu.testing.stage_lowering import lower_stage
    from spark_tpu.tpch.sql_queries import Q3
    session = SparkTpuSession(conf=Conf(), register_active=False)
    for name, gen, parts in (("lineitem", lineitem, 2), ("orders", orders, 2),
                             ("customer", customer, 1)):
        d = str(tmp_path / name)
        os.makedirs(d)
        for part in range(parts):
            gen.write_part(0.01, 2147483659, parts, part, d)
        session.register_table(name, ParquetSource(d, name))
    text = lower_stage(session.sql(Q3)._qe(), one_chip).compile().as_text()
    sorts = _sorts_of(text)
    assert len(sorts) >= 5 and all(is_sort(name) for name in sorts), sorts
