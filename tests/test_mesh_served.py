"""The served path across chips, as the benchmark's cell
`tpch-sf10-mesh4.q1q15max` drives it: one Parquet `lineitem` registered
with a `SqlService` under `spark_tpu.sql.mesh.size=4`, a request of Q1
then `q15max` (the maximum of Q15's revenue view) over `POST /sql`. On
four of the CPU's virtual devices at SF0.01, with the chunk and the
cache budget cut so that Q1 streams over the mesh in four chunks, as a
scan does that exceeds the chips' caches (`tests/test_mesh_resident.py`
has the same request with both scans held, as SF10 is on four chips):
the answers against the benchmark's plain
references and against the same service under `mesh.size=0`, the spans
the mesh stream leaves, and the process counters of what a mesh adds
(`mesh_stage_dispatches`, `exchange_rows`, `exchange_bytes`,
`shard_rows_max`, `shard_rows_total`), whose counts repeat exactly."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.datagen import lineitem as datagen  # noqa: E402
from benchmark.harness import compare  # noqa: E402
from benchmark.harness.entries import _columns  # noqa: E402
from benchmark.reference import q1 as ref_q1  # noqa: E402
from benchmark.reference import q15max as ref_q15max  # noqa: E402

import test_resident_served as resident  # noqa: E402

SF, PARTS, SEED = 0.01, 3, 2147483659
SHARDS = 4
#: 60 k rows in chunks of 16 Ki: four chunks, the last one partial
CHUNK_ROWS = 1 << 14
#: a chip's budget, and a scan is held where a shard's part of its
#: estimate is within half of it: of Q1's 9.96 MB a shard of four would
#: hold 2.49 MB, over the 2 MiB, so Q1 streams over the mesh as it does
#: on one device; `q15max`'s 1.4 MB a shard is within it, and on one
#: device its 5.8 MB is not
CACHE_BYTES = 4 << 20

QUERIES = ("q1", "q15max")
MESH_COUNTERS = ("mesh_stage_dispatches", "exchange_rows", "exchange_bytes",
                 "shard_rows_max", "shard_rows_total")
#: what `chunk_stream.drive` and the ingest pipeline leave under any
#: carry, the mesh's included; the benchmark's readers go by these names
STREAM_SPANS = ("chunk.wait", "chunk.decode", "chunk.put", "chunk.launch",
                "stream.drain")


class Served(resident.Served):
    """`tests/test_resident_served.py`'s service and client, under
    `mesh.size`, sending the four-chip cell's request."""

    QUERIES = QUERIES

    def __init__(self, directory, mesh_size, **conf):
        super().__init__(directory, **{
            "spark_tpu.sql.mesh.size": mesh_size,
            "spark_tpu.sql.execution.streamingChunkRows": CHUNK_ROWS,
            "spark_tpu.sql.io.deviceCacheBytes": CACHE_BYTES, **conf})


def _grown(before, after, name):
    name = "spark_tpu_" + name
    return after.get(name, 0) - before.get(name, 0)


@pytest.fixture(scope="module")
def directory(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("lineitem"))
    for part in range(PARTS):
        datagen.write_part(SF, SEED, PARTS, part, d)
    return d


@pytest.fixture(scope="module")
def mesh(directory):
    s = Served(directory, SHARDS)
    s.first = s.request()  # compiles the mesh stream and both stages
    yield s
    s.svc.stop()


@pytest.fixture(scope="module")
def single(directory):
    s = Served(directory, 0)
    s.first = s.request()
    yield s
    s.svc.stop()


@pytest.fixture(scope="module")
def references(directory):
    tables = {"lineitem": directory}
    return {"q1": ref_q1.compute({}, tables, None),
            "q15max": ref_q15max.compute({}, tables, None)}


@pytest.mark.parametrize("which", ["mesh", "single"])
def test_q1_then_q15max_agree_with_the_references(which, references,
                                                  request):
    served = request.getfixturevalue(which)
    requests = [{"queries": [
        {"query": name, "status": a["status"],
         "answer": _columns(a["columns"], a["rows"])}
        for name, a in zip(QUERIES, answers)]}
        for answers in (served.first, served.request())]
    verdict = compare.judge(requests, references, {}, {})
    assert verdict["correct"], verdict
    assert all(n["value"] == 0 for n in verdict["numbers"].values())
    assert len(requests[0]["queries"][0]["answer"]["count_order"]) == 4
    assert list(requests[0]["queries"][1]["answer"]) == ["one", "max_revenue"]


def test_four_shards_answer_as_one_device_does_byte_for_byte(mesh, single):
    for over_mesh, alone in zip(mesh.request(), single.request()):
        assert over_mesh["columns"] == alone["columns"]
        assert json.dumps(over_mesh["rows"]) == json.dumps(alone["rows"])


@pytest.mark.parametrize("name", STREAM_SPANS)
def test_the_mesh_stream_keeps_the_chunk_drivers_span_names(mesh, name):
    """Q1 streams over the mesh under the one chunk driver, so the
    spans the benchmark's ingest and chunk readers go by are there."""
    spans = [s for s in mesh.timeline(mesh.first[0])["spans"]
             if s["name"] == name]
    assert spans, name
    if name == "chunk.launch":
        assert [s["attrs"]["chunk"] for s in spans] == [0, 1, 2, 3]
    if name == "stream.drain":
        assert len(spans) == 1


def test_q1_streams_on_every_request_and_q15max_is_held(mesh):
    before = mesh.counters()
    mesh.request()
    after = mesh.counters()
    # Q1's scan fails the residency estimate even at a quarter a
    # shard; q15max's was laid over the four shards at the first
    # request and is found in the cache since
    assert _grown(before, after, "scans_streamed") == 1
    assert _grown(before, after, "scans_resident") == 1
    assert _grown(before, after, "ingest_chunks") == 4
    assert _grown(before, after, "device_cache_hits") >= 1
    assert _grown(before, after, "device_cache_misses") == 0


@pytest.mark.parametrize("which, want", [("mesh", [SHARDS]), ("single", [None])])
def test_the_dispatch_span_of_a_mesh_stage_carries_mesh(which, want, request):
    served = request.getfixturevalue(which)
    for answer in served.first:
        got = [s["attrs"].get("mesh")
               for s in served.timeline(answer)["spans"]
               if s["name"] == "dispatch"]
        assert got == want, (which, got)


@pytest.mark.parametrize("name", MESH_COUNTERS)
def test_the_mesh_counters_are_registered_and_served(mesh, name):
    from spark_tpu.observability.metrics import is_registered_metric
    assert is_registered_metric(name)
    assert mesh.counters()["spark_tpu_" + name] > 0


def test_the_mesh_counters_grow_by_what_three_requests_add(mesh, references):
    """Every stage of the request runs under the mesh; an exchange's
    routed rows and bytes are the stage's own `exch_rows_*` /
    `exch_bytes_*`; the shards' rows are those the exchanges routed,
    those Q1's stream folded, which are the rows Q1's pushed-down
    date filter keeps, and those of `q15max`'s held scan as the host
    dealt them over the shards, which are the rows its filter keeps."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    folded = sum(references["q1"]["table"]["count_order"])
    held = sum(int(ref_q15max.keep(
        pq.read_table(path, columns=["l_shipdate"])["l_shipdate"]
        .cast(pa.int32()).to_numpy()).sum())
        for path in ref_q1.part_files(mesh.directory))
    before = mesh.counters()
    want = {"rows": 0, "bytes": 0}
    for _ in range(3):
        for answer in mesh.request():
            metrics = mesh.timeline(answer)["metrics"]
            for k, v in metrics.items():
                if k.startswith("exch_rows_"):
                    want["rows"] += v
                elif k.startswith("exch_bytes_"):
                    want["bytes"] += v
    after = mesh.counters()
    assert want["rows"] > 0 and want["bytes"] > 0
    assert _grown(before, after, "exchange_rows") == want["rows"]
    assert _grown(before, after, "exchange_bytes") == want["bytes"]
    dispatches = _grown(before, after, "stage_dispatches")
    assert dispatches >= 3 * len(QUERIES) and dispatches % 3 == 0
    assert _grown(before, after, "mesh_stage_dispatches") == dispatches
    total = _grown(before, after, "shard_rows_total")
    assert total == want["rows"] + 3 * (folded + held)
    fullest = _grown(before, after, "shard_rows_max")
    # the fullest shard holds at least an even share, and the last
    # chunk is partial, so it holds more
    assert total < SHARDS * fullest <= SHARDS * total


def no_fault_counter_moves(mesh):
    """A request of `mesh` (a `Served` under a mesh) answers with no
    recovery of any kind."""
    before = mesh.counters()
    answers = mesh.request()
    after = mesh.counters()
    moved = {k: after[k] - before.get(k, 0) for k in after
             if k.startswith("spark_tpu_fault_")
             or k == "spark_tpu_queries_failed"}
    assert not any(moved.values()), moved
    assert _grown(before, after, "mesh_restart_attempts") == 0
    for answer in answers:
        status = json.loads(mesh.get(f"/queries/{answer['query_id']}"))
        assert status["status"] == "ok"
        assert not status.get("fault_events")
        assert not status.get("fault_summary")
        assert "mesh_fallback" not in mesh.timeline(answer)["metrics"]


def test_no_fault_counter_moves(mesh):
    no_fault_counter_moves(mesh)


def test_one_device_moves_no_mesh_counter(mesh, single):
    """Both services count into the process's one registry: a request
    of the single-device one leaves the mesh's counters where they
    were and still counts its dispatches."""
    before = single.counters()
    single.request()
    after = single.counters()
    assert _grown(before, after, "stage_dispatches") >= len(QUERIES)
    for name in MESH_COUNTERS:
        assert _grown(before, after, name) == 0, name


def test_the_counters_do_not_wait_for_shard_spans(directory, mesh):
    """`observability.shardSpans` gates the flight recorder's shard
    records, not the counters: with it off they grow all the same."""
    assert mesh.timeline(mesh.first[0])["shards"]  # a service observes
    served = Served(directory, SHARDS, **{
        "spark_tpu.sql.observability.shardSpans": "off"})
    try:
        before = served.counters()
        shards = [served.timeline(answer).get("shards")
                  for answer in served.request()]
        after = served.counters()
    finally:
        served.svc.stop()
    for name in MESH_COUNTERS:
        assert _grown(before, after, name) > 0, name
    assert not any(shards), shards
