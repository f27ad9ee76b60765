"""A scan held where it is used: under `spark_tpu.sql.mesh.size=4` the
request of the benchmark's cell `tpch-sf10-mesh4.q1q15max` (Q1 then
`q15max` over `POST /sql`) with the engine's own cache budget, on four
of the CPU's virtual devices at SF0.01. Both scans pass the residency
verdict, which holds a shard's part of the estimate against a chip's
budget; each is loaded once, its rows dealt evenly and in order over
the four shards on the host and every array put sharded over the data
axis; every later dispatch finds its rows in place.
`tests/test_mesh_served.py` has the same request with Q1 streaming."""

import json
import os
import sys

import numpy as np
import pyarrow as pa
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.datagen import lineitem as datagen  # noqa: E402
from benchmark.harness import compare  # noqa: E402
from benchmark.harness.entries import _columns  # noqa: E402
from benchmark.reference import q1 as ref_q1  # noqa: E402
from benchmark.reference import q15max as ref_q15max  # noqa: E402

import test_resident_served as resident  # noqa: E402
from test_mesh_served import _grown, no_fault_counter_moves  # noqa: E402

SF, PARTS, SEED = 0.01, 3, 2147483659
SHARDS = 4
#: under the table's 60 k rows, so both scans are asked stream or hold
CHUNK_ROWS = 1 << 14
QUERIES = ("q1", "q15max")
MESH_KEY = "spark_tpu.sql.mesh.size"
GUARD = "jax_transfer_guard_device_to_device"


class Served(resident.Served):
    QUERIES = QUERIES

    def __init__(self, directory, mesh_size, **conf):
        super().__init__(directory, **{
            MESH_KEY: mesh_size,
            "spark_tpu.sql.execution.streamingChunkRows": CHUNK_ROWS,
            **conf})

    def entries(self):
        """The table's entries laid over the four devices (the cache
        is the process's: the other services' lie beside them)."""
        from spark_tpu.io.device_cache import CACHE
        return {k: CACHE._entries[k][0] for k in self.cache_entries()
                if k[3] == tuple(range(SHARDS))}

    def dealt(self):
        """The live rows a shard, as the cache keeps them by entry."""
        from spark_tpu.io.device_cache import CACHE
        return {k: CACHE.dealt(k) for k in self.entries()}


@pytest.fixture(scope="module")
def directory(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("lineitem"))
    for part in range(PARTS):
        datagen.write_part(SF, SEED, PARTS, part, d)
    return d


@pytest.fixture(scope="module")
def mesh(directory):
    from spark_tpu.io.device_cache import CACHE
    s = Served(directory, SHARDS)
    s.sharded_loads = [CACHE.sharded_loads]
    s.first = s.request()  # loads both scans over the mesh, compiles
    s.sharded_loads.append(CACHE.sharded_loads)
    yield s
    s.svc.stop()


@pytest.fixture(scope="module")
def single(directory):
    s = Served(directory, 0)
    s.first = s.request()
    yield s
    s.svc.stop()


def _data_mesh(n):
    import jax
    from jax.sharding import Mesh
    from spark_tpu.parallel.mesh import AXIS
    return Mesh(np.array(jax.devices()[:n]), (AXIS,))


def _arrays(batch):
    for name, col in batch.columns.items():
        yield name, col.data
        if col.validity is not None:
            yield name + ".validity", col.validity
    yield "selection", batch.selection


def test_both_scans_are_held_and_no_request_streams(mesh):
    before = mesh.counters()
    mesh.request()
    after = mesh.counters()
    assert _grown(before, after, "scans_resident") == 2
    assert _grown(before, after, "scans_streamed") == 0
    assert _grown(before, after, "ingest_chunks") == 0
    assert _grown(before, after, "device_cache_hits") == 2
    assert _grown(before, after, "device_cache_misses") == 0
    for answer in mesh.first:
        names = {s["name"] for s in mesh.timeline(answer)["spans"]}
        assert "streaming" not in names and "chunk.launch" not in names


def test_a_scan_is_placed_over_the_mesh_once(mesh):
    from spark_tpu.io.device_cache import CACHE
    at_start, after_first = mesh.sharded_loads
    assert after_first - at_start == 2
    mesh.request()
    assert CACHE.sharded_loads == after_first
    # the cache's state reaches /metrics as gauges at every query end
    assert mesh.counters()["spark_tpu_device_cache_sharded_loads"] \
        == after_first


def test_every_array_of_an_entry_lies_over_the_data_axis(mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    from spark_tpu.parallel.mesh import AXIS
    want = NamedSharding(_data_mesh(SHARDS), PartitionSpec(AXIS))
    entries = mesh.entries()
    assert len(entries) == 2
    for key, batch in entries.items():
        assert key[3] == tuple(range(SHARDS))
        local = batch.capacity // SHARDS
        assert local * SHARDS == batch.capacity
        for name, x in _arrays(batch):
            assert x.sharding.is_equivalent_to(want, x.ndim), name
            assert [s.data.shape for s in x.addressable_shards] \
                == [(local,)] * SHARDS, name


def test_the_rows_are_dealt_evenly_and_lie_at_each_stripes_front(mesh):
    kept = {}
    dealt = mesh.dealt()
    for key, batch in mesh.entries().items():
        rows = dealt[key]
        assert len(rows) == SHARDS and max(rows) - min(rows) <= 1
        local = batch.capacity // SHARDS
        mask = np.asarray(batch.selection).reshape(SHARDS, local)
        for shard, count in enumerate(rows):
            assert mask[shard, :count].all() and not mask[shard, count:].any()
        kept[len(key[1])] = sum(rows)
    # Q1's seven columns under its date filter, `q15max`'s four under its
    tables = {"lineitem": mesh.directory}
    assert kept[7] == sum(
        ref_q1.compute({}, tables, None)["table"]["count_order"])
    assert 0 < kept[4] < kept[7]


def test_the_ingest_span_of_a_sharded_scan_carries_mesh(mesh, single):
    for served, want in ((mesh, SHARDS), (single, None)):
        for answer in served.first:
            got = [s["attrs"].get("mesh")
                   for s in served.timeline(answer)["spans"]
                   if s["name"] == "ingest"]
            assert got == [want], (want, got)


def test_a_warm_request_moves_no_input_between_devices(mesh, directory):
    """The guard is the process's, so that it holds on the service's
    threads. It does bite: with the cache off Q1 streams, its chunks
    land on the default device and the step cuts them from there."""
    import urllib.error
    import jax
    was = getattr(jax.config, GUARD)
    other = Served(directory, SHARDS,
                   **{"spark_tpu.sql.io.deviceCacheBytes": 0})
    try:
        other.request()
        jax.config.update(GUARD, "disallow")
        answers = mesh.request()
        with pytest.raises(urllib.error.HTTPError):
            other.request()
    finally:
        jax.config.update(GUARD, was)
        other.svc.stop()
    assert [a["rows"] for a in answers] == [a["rows"] for a in mesh.first]


def test_the_answers_are_one_devices_byte_for_byte_and_exact(mesh, single):
    tables = {"lineitem": mesh.directory}
    references = {"q1": ref_q1.compute({}, tables, None),
                  "q15max": ref_q15max.compute({}, tables, None)}
    warm = mesh.request()
    for over_mesh, alone in zip(warm, single.request()):
        assert over_mesh["columns"] == alone["columns"]
        assert json.dumps(over_mesh["rows"]) == json.dumps(alone["rows"])
    requests = [{"queries": [
        {"query": name, "status": a["status"],
         "answer": _columns(a["columns"], a["rows"])}
        for name, a in zip(QUERIES, answers)]}
        for answers in (mesh.first, warm)]
    verdict = compare.judge(requests, references, {}, {})
    assert verdict["correct"], verdict
    assert all(n["value"] == 0 for n in verdict["numbers"].values())


def test_no_fault_counter_moves(mesh):
    no_fault_counter_moves(mesh)


def test_the_shards_rows_count_the_held_scans_once_a_use(mesh):
    """`shard_rows_max` / `shard_rows_total` take each held scan's
    rows as the host dealt them, beside the exchanges' routed rows."""
    dealt = list(mesh.dealt().values())
    before = mesh.counters()
    routed = sum(v for answer in mesh.request()
                 for k, v in mesh.timeline(answer)["metrics"].items()
                 if k.startswith("exch_rows_"))
    after = mesh.counters()
    assert _grown(before, after, "shard_rows_total") \
        == routed + sum(sum(rows) for rows in dealt)
    assert _grown(before, after, "shard_rows_max") \
        >= sum(max(rows) for rows in dealt)


# -- the deal, on a table with nulls, strings and decimals -----------------


def _table(rows):
    from decimal import Decimal
    rng = np.random.default_rng(rows)
    ints = rng.integers(0, 1000, rows)
    return pa.table({
        "i": pa.array(ints, mask=ints % 7 == 0),
        "s": pa.chunked_array(
            [pa.array([f"v{v % 5}" for v in ints[:rows // 3]],
                      type=pa.string()),
             pa.array([None if v % 11 == 0 else f"w{v % 3}"
                       for v in ints[rows // 3:]], type=pa.string())]),
        "d": pa.array([Decimal(int(v)) / 100 for v in ints],
                      type=pa.decimal128(12, 2)),
        "day": pa.array(ints.astype(np.int32), type=pa.date32()),
        "b": pa.array(ints % 2 == 0, mask=ints % 13 == 0)})


@pytest.mark.parametrize("shards, rows", [(4, 1000), (4, 1001), (4, 3),
                                          (3, 1000), (2, 8), (4, 0)])
def test_a_dealt_table_is_the_table_in_order(shards, rows):
    from spark_tpu.columnar import Batch, ShardedPlacement
    from spark_tpu.parallel.mesh import AXIS
    table = _table(rows)
    placement = ShardedPlacement(_data_mesh(shards), AXIS)
    assert placement.dealt is None
    dealt = Batch.from_arrow(table, placement=placement)
    assert dealt.capacity % shards == 0
    base, extra = divmod(rows, shards)
    assert placement.dealt == tuple(
        base + (i < extra) for i in range(shards))
    for name, x in _arrays(dealt):
        assert x.sharding.is_equivalent_to(placement.sharding, 1), name
    # egress compacts by the mask, shard after shard: the table's order
    assert dealt.to_arrow().equals(Batch.from_arrow(table).to_arrow())
    # what lies under no live row is zero, as a padded row is
    local = dealt.capacity // shards
    for name, x in _arrays(dealt):
        stripes = np.asarray(x).reshape(shards, local)
        for shard, count in enumerate(placement.dealt):
            assert not stripes[shard, count:].any(), (name, shard)


# -- the verdict ----------------------------------------------------------

BUDGET = 1 << 20
HALF = BUDGET // 2


class _Leaf:
    """A scan of one int32 column, so that `estimated_scan_bytes` is
    8 bytes a row."""

    required_columns = None
    pushed_filters = ()

    def __init__(self, rows, token=("verdict",)):
        from spark_tpu import types as T

        class Source:
            def cache_token(self):
                return token

            def estimated_rows(self):
                return rows

        class Field:
            dtype = T.IntegerType()
            nullable = False

        class Schema:
            fields = [Field()]

        self.source = Source()
        self._schema = Schema()

    def schema(self):
        return self._schema


@pytest.mark.parametrize("est, budget, token, one, four", [
    (HALF - 8, BUDGET, ("t",), True, True),
    (HALF, BUDGET, ("t",), True, True),
    (HALF + 8, BUDGET, ("t",), False, True),       # a shard: 131,074 B
    (2 * HALF, BUDGET, ("t",), False, True),
    (SHARDS * HALF, BUDGET, ("t",), False, True),  # a shard: HALF
    (SHARDS * HALF + 8, BUDGET, ("t",), False, False),
    (16 * HALF, BUDGET, ("t",), False, False),
    (None, BUDGET, ("t",), False, False),          # no estimate
    (8, BUDGET, None, False, False),               # uncacheable source
    (8, 0, ("t",), False, False),                  # the cache is off
])
def test_the_verdict_holds_a_shards_part_against_a_chips_budget(
        est, budget, token, one, four):
    from spark_tpu import Conf
    from spark_tpu.execution.streaming_agg import _resident_verdict
    from spark_tpu.io.device_cache import (CACHE_BYTES_KEY,
                                           estimated_scan_bytes)
    leaf = _Leaf(None if est is None else est // 8, token)
    assert estimated_scan_bytes(leaf) == est
    conf = Conf().set(CACHE_BYTES_KEY, budget)
    # with no mesh the rule is the one it was: the whole estimate
    # against half the budget
    was = budget > 0 and token is not None and est is not None \
        and est <= budget // 2
    assert one == was
    assert _resident_verdict(leaf, conf, None) is one
    assert _resident_verdict(leaf, conf, _data_mesh(SHARDS)) is four


@pytest.mark.parametrize("gate", ["pool", "query_budget"])
def test_the_lease_and_the_query_budget_weigh_a_chips_share(gate):
    """The arbiter's pool and `memory.deviceBudget` are a chip's, like
    the cache's budget and its count of an entry: under a mesh the
    estimate's share is what is leased and weighed, so a table that
    needs a quarter of the pool on each chip is not refused as if one
    chip had to hold it whole. With no mesh both weigh the whole."""
    from spark_tpu import Conf
    from spark_tpu.io.device_cache import CACHE_BYTES_KEY
    from spark_tpu.service import arbiter as A
    from spark_tpu.service.arbiter import admit_scan_resident
    pool = BUDGET
    est = 2 * pool                       # a shard: half the pool
    mesh = _data_mesh(SHARDS)
    conf = Conf().set(CACHE_BYTES_KEY, 16 * pool)
    if gate == "query_budget":
        conf.set(A.DEVICE_BUDGET_KEY, pool)
        assert admit_scan_resident(conf, _Leaf(est // 8), None) is False
        assert admit_scan_resident(conf, _Leaf(est // 8), mesh) is True
        assert admit_scan_resident(
            conf, _Leaf(SHARDS * pool // 8 + 1), mesh) is False
        return
    arb = A.DeviceResourceArbiter(pool)
    A.install_arbiter(arb)
    try:
        for laid, want, leased in ((None, False, 0),
                                   (mesh, True, est // SHARDS)):
            token = A.enter_query(f"lease-{want}")
            try:
                leaf = _Leaf(est // 8, ("lease", want))
                assert admit_scan_resident(conf, leaf, laid) is want
                assert arb.leased_bytes == leased
            finally:
                A.exit_query(token)
            assert arb.leased_bytes == 0
    finally:
        A.install_arbiter(None)


def test_a_scan_with_a_list_column_is_laid_over_no_mesh():
    from spark_tpu import types as T
    from spark_tpu.io.device_cache import scan_cache_key, scan_mesh
    leaf = _Leaf(10)
    mesh = _data_mesh(SHARDS)
    assert scan_mesh(leaf, mesh) is mesh
    assert scan_cache_key(leaf, mesh)[3] == tuple(range(SHARDS))
    assert scan_cache_key(leaf)[3] is None
    leaf._schema.fields[0].dtype = T.ArrayType(T.IntegerType())
    assert scan_mesh(leaf, mesh) is None
    assert scan_cache_key(leaf, scan_mesh(leaf, mesh)) \
        == scan_cache_key(leaf)


# -- another mesh, or none, after a sharded load ---------------------------


@pytest.fixture()
def own_session(tmp_path):
    from spark_tpu import Conf
    from spark_tpu.io.sources import ParquetSource
    from spark_tpu.session import SparkTpuSession
    from spark_tpu.testing import faults
    session = SparkTpuSession(conf=Conf().set(MESH_KEY, SHARDS),
                              register_active=False)
    directory = str(tmp_path)  # a table no other test's entries are of
    datagen.write_part(SF, SEED + 1, 1, 0, directory)
    session.source = ParquetSource(directory, "lineitem")
    session.register_table("lineitem", session.source)
    faults.reset()
    yield session
    faults.reset()


def _pricing(session):
    from spark_tpu import functions as F
    from spark_tpu.functions import col
    qe = session.table("lineitem").group_by("l_returnflag", "l_linestatus") \
        .agg(F.sum(col("l_quantity")).alias("q"),
             F.first(col("l_shipdate")).alias("first_day"),
             F.count(col("l_tax")).alias("n")) \
        .sort("l_returnflag", "l_linestatus")._qe()
    return qe.collect().to_pandas(), qe


def _devices(session):
    from spark_tpu.io.device_cache import CACHE
    token = session.source.cache_token()
    return sorted((k[3] for k in CACHE._entries if k[0] == token),
                  key=repr)


@pytest.mark.parametrize("replan", ["fallback", "smaller_gang"])
def test_a_replan_under_another_mesh_misses_and_answers_the_same(
        own_session, replan):
    """The `mesh` seam fails the stage's compile: with gang restarts
    off the ladder falls back to one device, which must not be handed
    arrays laid out for four; a decommissioned device leaves a gang of
    three, which takes its own copy too (and a capacity that three
    divide)."""
    from spark_tpu.io.device_cache import CACHE
    from spark_tpu.testing import faults
    conf = own_session.conf
    want, qe = _pricing(own_session)
    assert not qe.fault_summary
    assert _devices(own_session) == [tuple(range(SHARDS))]
    loads, misses = CACHE.sharded_loads, CACHE.misses
    own_session._stage_cache.clear()
    if replan == "fallback":
        conf.set("spark_tpu.execution.meshRestart.enabled", False)
        with faults.inject(conf, "mesh:fatal:1") as plan:
            got, qe = _pricing(own_session)
            assert plan.fired_log == [("mesh", 1, "fatal")]
        assert qe.fault_summary.get("mesh_fallback") == 1
        assert _devices(own_session) == [tuple(range(SHARDS)), None]
        assert CACHE.sharded_loads == loads
    else:
        # of the CPU's eight devices, three are left to mesh over
        conf.set("spark_tpu.sql.mesh.excludeDevices", "3,4,5,6,7")
        got, qe = _pricing(own_session)
        assert not qe.fault_summary
        gang = tuple(range(SHARDS - 1))
        assert _devices(own_session) == [gang, tuple(range(SHARDS))]
        assert CACHE.sharded_loads == loads + 1
        key = next(k for k in CACHE._entries
                   if k[0] == own_session.source.cache_token()
                   and k[3] == gang)
        batch = CACHE._entries[key][0]
        assert batch.capacity % 3 == 0 and len(CACHE.dealt(key)) == 3
    assert CACHE.misses == misses + 1
    assert got.equals(want)


# -- one program for every order the rows bring a column's strings in -----


@pytest.mark.parametrize("mesh_size", [0, SHARDS])
def test_the_stage_is_one_program_whatever_order_the_strings_come_in(
        mesh_size):
    """A stage's program holds tables made from a string column's
    dictionary (a sort's ranks). A table held whole over a mesh
    carries its dictionary sorted, so two data sets of the same values
    compile to the same text and the persistent compile cache finds
    Q1's stage again under another seed's data, as it found the
    streamed scan's programs. Since PR 37 a table held on one device
    does too: Q3's stage had five texts, one a place of `BUILDING` in
    `c_mktsegment`'s order of first appearance."""
    import jax
    from spark_tpu import Conf, functions as F
    from spark_tpu.functions import col
    from spark_tpu.io.device_cache import load_scan, scan_mesh
    from spark_tpu.parallel.mesh import get_mesh, stage_token
    from spark_tpu.session import SparkTpuSession
    texts, answers = [], []
    for flags in (["R", "A", "N", "A"], ["N", "R", "A", "N"]):
        session = SparkTpuSession(conf=Conf().set(MESH_KEY, mesh_size),
                                  register_active=False)
        from spark_tpu.io.sources import ArrowTableSource
        session.register_table("t", ArrowTableSource("t", pa.table(
            {"flag": flags * 25, "v": list(range(100))})))
        qe = session.table("t").group_by("flag") \
            .agg(F.count(col("v")).alias("n")).sort("flag")._qe()
        root = qe.executed_plan
        scans = []
        qe._collect_scans(root, scans)
        mesh = get_mesh(session.conf)
        batches = [load_scan(s, session.conf, scan_mesh(s, mesh))[0]
                   for s in scans]
        assert batches[0].columns["flag"].dictionary.to_pylist() \
            == ["A", "N", "R"]
        args = (batches,) if mesh is None else (batches, stage_token(mesh))
        texts.append(jax.jit(qe._build_stage_fn(root, mesh))
                     .lower(*args).as_text())
        answers.append(qe.collect().to_pandas())
    assert texts[0] == texts[1]
    assert answers[0]["flag"].tolist() == ["A", "N", "R"]
    assert answers[0]["n"].tolist() == [50, 25, 25]
    assert answers[1]["n"].tolist() == [25, 50, 25]
