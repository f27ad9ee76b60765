"""The chunk driver's contract, held for all four entry points at once.

`execution/chunk_stream.py::drive` is the one host loop of every
out-of-HBM scan; the direct aggregate, the spill aggregate, external
collect and the mesh aggregate differ by their carry alone. What each
driver's own file proves (parity with goldens, retry counts,
checkpoints) stays there; here are the cases no file held for all four:
the consumer's span sequence, the prefetch worker's end on every exit,
the join-overflow retry of one chunk, a resume from a cursor, and the
stage-cache keys with their donation.
"""

import threading

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_tpu.execution.chunk_stream as CS
import spark_tpu.execution.streaming_agg as SA
from spark_tpu import functions as F
from spark_tpu.execution.recovery import RecoveryContext
from spark_tpu.functions import col
from spark_tpu.observability.spans import SpanRecorder, use_recorder
from spark_tpu.plan import physical as P
from spark_tpu.testing import faults

CHUNK_KEY = "spark_tpu.sql.execution.streamingChunkRows"
CACHE_KEY = "spark_tpu.sql.io.deviceCacheBytes"
BUDGET_KEY = "spark_tpu.sql.memory.deviceBudget"
MESH_KEY = "spark_tpu.sql.mesh.size"
DOMAIN_KEY = "spark_tpu.sql.aggregate.maxDirectDomain"
EVERY_KEY = "spark_tpu.execution.checkpoint.everyChunks"

CHUNK = 1024
ROWS = 6000
N_CHUNKS = -(-ROWS // CHUNK)
WORKER = "spark-tpu-ingest-prefetch"
FAN = 4

#: driver -> (conf that routes the query to it, stage-cache key prefix)
DRIVERS = {
    "direct": ({}, "stream_scan:"),
    "spill": ({BUDGET_KEY: 1, DOMAIN_KEY: 1}, "stream_spill:"),
    "external": ({BUDGET_KEY: 1}, "ext_collect:"),
    "mesh": ({MESH_KEY: 8}, "stream_mesh:"),
}
JOINED = ["direct", "spill", "external"]  # the mesh streams join-free


@pytest.fixture(scope="module")
def data(session, tmp_path_factory):
    rs = np.random.RandomState(32)
    fact = pd.DataFrame({
        "fk": rs.randint(0, 50, ROWS).astype(np.int64),
        "v": rs.randint(0, 1000, ROWS).astype(np.int64)})
    path = str(tmp_path_factory.mktemp("chunk_stream") / "fact.parquet")
    pq.write_table(pa.Table.from_pandas(fact), path, row_group_size=1500)
    dim = pd.DataFrame({"fk": np.arange(50, dtype=np.int64),
                        "g": np.arange(50, dtype=np.int64) % 7})
    session.register_table("cs_dim", dim)
    # every fact row matches FAN dim rows: a chunk's join output is FAN
    # times the chunk capacity the stream seeds the join with
    many = pd.DataFrame({"fk": np.tile(dim.fk.to_numpy(), FAN),
                         "g": np.arange(50 * FAN, dtype=np.int64) % 7})
    session.register_table("cs_many", many)
    return path, fact, dim, many


@pytest.fixture(autouse=True)
def _streaming(session):
    """Small chunks, nothing resident, cold caches; the conftest guard
    restores the conf."""
    from spark_tpu.io.device_cache import CACHE
    session.conf.set(CHUNK_KEY, CHUNK)
    session.conf.set(CACHE_KEY, 0)
    session.conf.set("spark_tpu.execution.backoffMs", 1)
    session._stage_cache.clear()
    session._aqe_caps.clear()
    CACHE.clear()
    faults.reset()
    yield
    faults.reset()


def _query(session, driver, path, dim="cs_dim"):
    fact = session.read_parquet(path, "cs_fact")
    if driver == "mesh":
        return fact.group_by((col("v") % 37).alias("k")) \
            .agg(F.sum(col("v")).alias("s"), F.count().alias("c"))
    joined = fact.join(session.table(dim), on="fk")
    if driver == "external":
        return joined.order_by(col("v").desc(), col("fk"), col("g")) \
            .limit(7)
    return joined.group_by(F.pmod(col("g"), 7).alias("k")) \
        .agg(F.sum(col("v")).alias("s"), F.count().alias("c"))


def _expected(driver, fact, dim):
    if driver == "mesh":
        out = fact.assign(k=fact.v % 37).groupby("k").agg(
            s=("v", "sum"), c=("v", "size")).reset_index()
    elif driver == "external":
        return fact.merge(dim, on="fk").sort_values(
            ["v", "fk", "g"], ascending=[False, True, True]).head(7) \
            .reset_index(drop=True)[["fk", "v", "g"]]
    else:
        j = fact.merge(dim, on="fk")
        out = j.assign(k=j.g % 7).groupby("k").agg(
            s=("v", "sum"), c=("v", "size")).reset_index()
    return out.astype(np.int64)


def _route(session, driver):
    for k, v in DRIVERS[driver][0].items():
        session.conf.set(k, v)


def _run(session, driver, path, route=True, dim="cs_dim"):
    if route:
        _route(session, driver)
    qe = _query(session, driver, path, dim)._qe()
    got = qe.collect().to_pandas()
    if driver != "external":
        got = got.sort_values("k").reset_index(drop=True).astype(np.int64)
    return got, qe


def _consumer_spans(spans):
    return [(s.name, s.attrs.get("chunk"), s.tid)
            for s in sorted(spans, key=lambda s: s.id)
            if s.name in ("chunk.launch", "stream.drain")]


def _launches_then_drain(qe, cursors):
    """The consumer launched exactly `cursors`, in order, then drained
    once."""
    return [(n, c) for n, c, _ in _consumer_spans(qe.spans.spans)] == \
        [("chunk.launch", i) for i in cursors] + [("stream.drain", None)]


def _nodes(plan, cls):
    found = []

    def walk(n):
        if isinstance(n, cls):
            found.append(n)
        for c in n.children:
            walk(c)

    walk(plan)
    return found


def _workers():
    return [t for t in threading.enumerate() if t.name == WORKER]


def _keys(session, driver):
    return [k for k in session._stage_cache
            if isinstance(k, str) and k.startswith(DRIVERS[driver][1])]


# -- (a) the consumer's spans ------------------------------------------------

@pytest.mark.parametrize("driver", list(DRIVERS))
def test_launch_per_chunk_then_one_drain(session, data, driver):
    path, fact, dim, _ = data
    got, qe = _run(session, driver, path)
    pd.testing.assert_frame_equal(got, _expected(driver, fact, dim),
                                  check_dtype=False)
    assert _launches_then_drain(qe, range(N_CHUNKS))
    # one thread launches and drains: the one the phase span stands on
    (phase,) = [s for s in qe.spans.spans
                if s.name in ("streaming", "external")]
    assert {tid for _, _, tid in _consumer_spans(qe.spans.spans)} \
        == {phase.tid}
    assert _workers() == []


# -- (b) no ingest worker outlives its stream --------------------------------

@pytest.mark.parametrize("driver", list(DRIVERS))
def test_worker_joined_after_a_fault_in_the_second_chunk(session, data,
                                                         driver):
    path, _, _, _ = data
    session.conf.set("spark_tpu.execution.chunkRetry.enabled", False)
    session.conf.set("spark_tpu.execution.maxRetries", 0)
    session.conf.set("spark_tpu.execution.meshFallback.enabled", False)
    session.conf.set("spark_tpu.execution.meshRestart.enabled", False)
    with faults.inject(session.conf, "stream_chunk:fatal:2") as plan:
        with pytest.raises(Exception, match="injected"):
            _run(session, driver, path)
    assert ("stream_chunk", 2, "fatal") in plan.fired_log
    assert _workers() == []


@pytest.mark.parametrize("driver", ["direct", "mesh"])
def test_worker_joined_when_the_carry_declines(session, data, driver,
                                               monkeypatch):
    """A group key with no static domain: `prepare_direct` declines on
    the first chunk, the driver answers None, the whole-input path
    takes the query — and the stream it opened is closed."""
    path, fact, _, _ = data
    name = "stream_scan_aggregate" + ("_mesh" if driver == "mesh" else "")
    orig = getattr(SA, name)
    answers = []

    def spy(*args, **kwargs):
        out = orig(*args, **kwargs)
        answers.append((out is None, len(_workers())))
        return out

    monkeypatch.setattr(SA, name, spy)
    _route(session, driver)
    got = session.read_parquet(path, "cs_fact").group_by(col("v")) \
        .agg(F.count().alias("c")).to_pandas()
    assert answers == [(True, 0)]
    assert int(got.c.sum()) == len(fact) and len(got) == fact.v.nunique()


def test_worker_joined_after_the_early_limit_stop(session, data):
    path, _, _, _ = data
    session.conf.set(BUDGET_KEY, 1)
    qe = session.read_parquet(path, "cs_fact").limit(CHUNK + 1)._qe()
    assert qe.collect().num_rows == CHUNK + 1
    assert _launches_then_drain(qe, [0, 1])
    assert _workers() == []


# -- (c) a join overflow re-runs the SAME chunk ------------------------------

@pytest.mark.parametrize("driver", JOINED)
def test_join_overflow_reruns_the_same_chunk(session, data, driver,
                                             monkeypatch):
    """A REAL overflow: chunk 0's join output is FAN times the capacity
    the stream seeded. The chunk's program must be traced again under
    the grown plan (a program kept from before the growth answers with
    the same flag for ever), and the same chunk run again."""
    path, fact, _, many = data
    real = CS.apply_join_overflow
    seen = []

    def spy(flags, metrics, joins):
        raised = sorted(k.split("_")[1] for k, v in flags.items() if v)
        grew = real(flags, metrics, joins)
        seen.append((grew, raised, joins[0].out_cap))
        return grew

    monkeypatch.setattr(CS, "apply_join_overflow", spy)
    got, qe = _run(session, driver, path, dim="cs_many")
    # chunk 0 ran three times under ONE launch: the build side is not
    # unique (a re-jit), its output overflows (a re-jit), it fits; every
    # later chunk ran once, in the last program
    assert seen == [(True, ["nonunique"], CHUNK),
                    (True, ["overflow"], FAN * CHUNK)] \
        + [(False, [], FAN * CHUNK)] * N_CHUNKS
    assert _launches_then_drain(qe, range(N_CHUNKS))
    # one program per plan the chunk was tried under, each under its key
    assert len(_keys(session, driver)) == 3
    pd.testing.assert_frame_equal(got, _expected(driver, fact, many),
                                  check_dtype=False)
    # ... and the whole-input path agrees
    for k in (CHUNK_KEY, CACHE_KEY, BUDGET_KEY, DOMAIN_KEY):
        session.conf.unset(k)
    whole, qe = _run(session, driver, path, route=False, dim="cs_many")
    assert _consumer_spans(qe.spans.spans) == []
    pd.testing.assert_frame_equal(got, whole, check_dtype=False)


# -- (d) a resume folds exactly the chunks from the cursor on ----------------

def _aggregate(df, mode):
    return [n for n in _nodes(df._qe().executed_plan, P.HashAggregateExec)
            if n.mode == mode][0]


def _launched(rec):
    return [s.attrs["chunk"] for s in sorted(rec.spans, key=lambda s: s.id)
            if s.name == "chunk.launch"]


@pytest.mark.parametrize("cursor", [0, 4, N_CHUNKS])
def test_spill_resumes_from_the_cursor(session, data, cursor):
    """The seed partials first, then one partial per chunk from the
    cursor on; at the end of the stream the seed alone IS the answer."""
    path, fact, dim, _ = data
    _route(session, "spill")
    agg = _aggregate(_query(session, "spill", path), "complete")
    chain, leaf = SA.find_streamable_chain(agg)
    seed = pa.table({"k": pa.array([-1], pa.int64())})

    def resume(skip):
        rec = SpanRecorder(0)
        with use_recorder(rec):
            out = SA.stream_scan_aggregate_spill(
                agg, chain, leaf, session.conf, session._stage_cache,
                RecoveryContext(), skip_chunks=skip, seed_partials=[seed])
        assert _workers() == []
        return out, _launched(rec)

    (table, partial), launched = resume(cursor)
    assert launched == list(range(cursor, N_CHUNKS))
    assert partial.mode == "partial"
    rows = table.to_pandas()
    assert rows.k.iloc[0] == -1 and (rows.k.iloc[1:] >= 0).all()
    if cursor == N_CHUNKS:
        assert len(rows) == 1
        return
    tail = fact.iloc[cursor * CHUNK:].merge(dim, on="fk")
    want = tail.groupby(tail.g % 7).v.agg(["sum", "size"])
    got = rows.iloc[1:].groupby("k").sum()
    (sums,) = [c for c in got.columns if c.endswith("_sum")]
    (counts,) = [c for c in got.columns if c.endswith("_count")]
    assert got[sums].astype(np.int64).tolist() == want["sum"].tolist()
    assert got[counts].astype(np.int64).tolist() == want["size"].tolist()


def test_spill_cursor_past_the_end_matches_nothing(session, data):
    path, _, _, _ = data
    _route(session, "spill")
    agg = _aggregate(_query(session, "spill", path), "complete")
    assert SA.stream_scan_aggregate_spill(
        agg, *SA.find_streamable_chain(agg), session.conf,
        session._stage_cache, RecoveryContext(),
        skip_chunks=N_CHUNKS + 1, seed_partials=[]) is None
    assert _workers() == []


def test_mesh_resumes_from_its_checkpoint(session, data, monkeypatch):
    import time
    from spark_tpu.parallel.mesh import get_mesh
    path, _, _, _ = data
    concat, merged_at = pa.concat_tables, []

    def timed_concat(*args, **kwargs):
        merged_at.append(time.perf_counter())
        return concat(*args, **kwargs)

    monkeypatch.setattr(pa, "concat_tables", timed_concat)
    _route(session, "mesh")
    session.conf.set(EVERY_KEY, 4)
    agg = _aggregate(_query(session, "mesh", path), "partial")
    mesh = get_mesh(session.conf)
    recovery = RecoveryContext()

    def stream():
        rec = SpanRecorder(0)
        with use_recorder(rec):
            out = SA.stream_scan_aggregate_mesh(
                agg, mesh, session.conf, session._stage_cache, recovery)
        rows = out.to_arrow().to_pandas()
        key = [c for c in rows.columns if c == "k"][0]
        (drain,) = [s for s in rec.spans if s.name == "stream.drain"]
        return _launched(rec), rows.groupby(key).sum().sort_index(), drain

    launched, whole, _ = stream()
    assert launched == list(range(N_CHUNKS))
    (ck,) = recovery.checkpoints.values()
    assert ck.cursor == 4  # saved at 4; 8 is past the end
    del merged_at[:]
    launched, resumed, drain = stream()
    assert launched == [4, 5]
    pd.testing.assert_frame_equal(resumed, whole, check_dtype=False)
    # the drain is the wait for the device alone: the seed checkpoint's
    # rows are merged on the host once it has closed
    (merged,) = merged_at
    assert merged > drain.t1
    assert _workers() == []


# -- the stage-cache keys and the donation -----------------------------------

@pytest.mark.parametrize("driver", list(DRIVERS))
def test_stage_cache_key_and_donation(session, data, driver):
    """`<prefix><describe()>:<chunk_rows>[:<n>]<conf suffix>`, byte for
    byte: a chip machine's compile cache and every warm session find
    the chunk program under it. Tables are donated only where no join
    can ask for the chunk again."""
    path, _, _, _ = data
    _, qe = _run(session, driver, path)
    (key,) = _keys(session, driver)
    node = qe.executed_plan if driver == "external" else _aggregate(
        _query(session, driver, path),
        "partial" if driver == "mesh" else "complete")
    for j in _nodes(node, P.JoinExec):  # the chunk capacity the driver
        j.out_cap = CHUNK  # seeded (spill puts the plan's own back)
    tail = f":{CHUNK}" + (":8" if driver == "mesh" else "") \
        + SA.conf_compile_suffix(session.conf)
    assert key == DRIVERS[driver][1] + node.describe() + tail
    program = session._stage_cache[key]
    step = program[1] if isinstance(program, tuple) else program
    assert step._jit_info.donate_argnums == \
        ((0,) if driver == "mesh" else ())


def test_join_free_direct_stream_donates_its_tables(session, data):
    path, fact, _, _ = data
    got = session.read_parquet(path, "cs_fact") \
        .group_by((col("v") % 37).alias("k")) \
        .agg(F.sum(col("v")).alias("s")).to_pandas()
    assert int(got.s.sum()) == int(fact.v.sum())
    (key,) = _keys(session, "direct")
    _prep, step = session._stage_cache[key]
    assert step._jit_info.donate_argnums == (0,)
    assert list(step._jit_info.fun_signature.parameters) == [
        "tables", "b", "row_base"]
