"""Concurrent multi-session SQL service suite (spark_tpu/service/).

Covers the acceptance surface: two sessions with conflicting conf
overlays running TPC-H Q1/Q3 concurrently with golden parity over ONE
shared arbiter pool and ONE compiled-stage cache; admission-queue
rejection at queueDepth with structured errors + listener-bus events;
arbiter lease exhaustion degrading through the spill/OOM machinery
instead of crashing; and the HTTP endpoints end to end."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from spark_tpu import Conf
from spark_tpu.observability.metrics import parse_prometheus_text
from spark_tpu.service.admission import (AdmissionController,
                                         AdmissionRejected,
                                         AdmissionTimeout)
from spark_tpu.service.arbiter import (DeviceResourceArbiter, ResultCache,
                                       get_arbiter, install_arbiter)
from spark_tpu.service.server import SqlService
from spark_tpu.tpch import golden as G
from spark_tpu.tpch import queries as Q
from spark_tpu.tpch import sql_queries as SQLQ
from spark_tpu.tpch.datagen import write_parquet

SF = 0.002
CHUNK_KEY = "spark_tpu.sql.execution.streamingChunkRows"
HBM_KEY = "spark_tpu.service.hbmBudget"
PORT_KEY = "spark_tpu.service.port"
MAXC_KEY = "spark_tpu.service.maxConcurrent"
DEPTH_KEY = "spark_tpu.service.queueDepth"
QT_KEY = "spark_tpu.service.queueTimeoutMs"
CACHE_BYTES_KEY = "spark_tpu.sql.io.deviceCacheBytes"


@pytest.fixture(scope="module")
def tpch_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tpch_service") / "sf_small")
    write_parquet(path, SF)
    return path


@pytest.fixture()
def service(tpch_path):
    """A fresh service per test (ephemeral port, TPC-H tables on every
    pooled session), torn down with the arbiter uninstalled."""
    def make(**conf_overrides):
        conf = Conf()
        conf.set(PORT_KEY, 0)
        for k, v in conf_overrides.items():
            conf.set(k, v)
        svc = SqlService(
            conf, init_session=lambda s: Q.register_tables(s, tpch_path))
        made.append(svc)
        return svc

    made = []
    yield make
    for svc in made:
        svc.stop()
    install_arbiter(None)


def _golden(name, path):
    want = G.GOLDEN[name](path)
    return want.reset_index(drop=True)


def _check(name, got_df, path):
    want = _golden(name, path)
    got = G.normalize_decimals(got_df)[list(want.columns)]
    G.compare(got.reset_index(drop=True), want)


# ---------------------------------------------------------------------------
# Admission controller (unit)
# ---------------------------------------------------------------------------


def test_admission_rejects_at_queue_depth():
    ctl = AdmissionController(max_concurrent=1, queue_depth=1,
                              queue_timeout_ms=0)
    ctl.acquire("a")  # takes the only slot
    release_b = threading.Event()
    queued = threading.Event()
    got_slot = []

    def queued_query():
        queued.set()
        with ctl.slot("b"):
            got_slot.append("b")
            release_b.wait(5)

    t = threading.Thread(target=queued_query, daemon=True)
    t.start()
    queued.wait(5)
    for _ in range(100):  # wait until b is actually parked in the queue
        if ctl.stats()["queued"] == 1:
            break
        time.sleep(0.01)
    assert ctl.stats()["queued"] == 1
    # queue full: the third submission is rejected with the structured
    # error, not queued
    with pytest.raises(AdmissionRejected) as exc:
        ctl.acquire("c")
    err = exc.value.to_dict()
    assert err["error"] == "ADMISSION_REJECTED"
    assert err["queue_depth"] == 1 and err["max_concurrent"] == 1
    ctl.release()  # a frees -> b runs
    release_b.set()
    t.join(5)
    assert got_slot == ["b"]
    assert ctl.stats() == {"running": 0, "queued": 0,
                           "max_concurrent": 1, "queue_depth": 1}


def test_admission_queue_timeout():
    ctl = AdmissionController(max_concurrent=1, queue_depth=4,
                              queue_timeout_ms=30)
    ctl.acquire("a")
    with pytest.raises(AdmissionTimeout) as exc:
        ctl.acquire("b")
    assert exc.value.to_dict()["error"] == "ADMISSION_TIMEOUT"
    ctl.release()
    # slot free again: acquire succeeds immediately
    ctl.acquire("c")
    ctl.release()


# ---------------------------------------------------------------------------
# Arbiter (unit)
# ---------------------------------------------------------------------------


def test_arbiter_lease_grant_deny_release():
    arb = DeviceResourceArbiter(1000)
    from spark_tpu.service.arbiter import _Owner
    a, b = _Owner("a"), _Owner("b")
    assert arb.try_acquire(a, "scan1", 600)
    assert arb.try_acquire(a, "scan1", 600)  # idempotent per key
    assert arb.leased_bytes == 600
    assert not arb.try_acquire(b, "scan2", 600)  # pool exhausted
    # denial memoized: even after a releases, b's verdict is stable
    arb.release(a)
    assert not arb.try_acquire(b, "scan2", 600)
    arb.release(b)  # clears the denial memo
    assert arb.try_acquire(b, "scan2", 600)
    arb.release(b)
    assert arb.leased_bytes == 0


def test_arbiter_evicts_storage_under_lease_pressure(session):
    """UnifiedMemoryManager discipline: lease pressure evicts the
    device table cache (storage pool) before denying execution."""
    from spark_tpu.io.device_cache import CACHE
    from spark_tpu.service.arbiter import _Owner
    import numpy as np
    # park a real device batch in the cache so it has evictable bytes
    df = session.create_dataframe(
        {"x": np.arange(4096, dtype=np.int64)}, name="arb_evict_t")
    session.conf.set(CACHE_BYTES_KEY, 1 << 30)
    df.collect()
    # the create_dataframe scan is uncacheable (no load_chunks isn't
    # required; ArrowTableSource has a token) — ensure something cached
    if CACHE.nbytes == 0:
        pytest.skip("scan did not cache; nothing to evict")
    cached = CACHE.nbytes
    arb = DeviceResourceArbiter(cached + 100)
    owner = _Owner("q")
    # pool nearly full of storage: the lease only fits after eviction
    assert arb.try_acquire(owner, "s", cached + 50)
    assert CACHE.nbytes < cached  # storage was evicted
    arb.release(owner)


def test_result_cache_lru_bound():
    import pyarrow as pa
    rc = ResultCache(max_bytes=1)  # tiny: every insert evicts
    t = pa.table({"a": list(range(1000))})
    rc["fp1"] = t
    assert "fp1" not in rc and len(rc) == 0  # over-bound: rejected
    rc2 = ResultCache(max_bytes=t.nbytes * 2 + 100)
    rc2["fp1"] = t
    rc2["fp2"] = t
    assert "fp1" in rc2 and "fp2" in rc2
    rc2["fp3"] = t  # past the bound: LRU (fp1) evicted
    assert "fp1" not in rc2
    assert rc2.get("fp3") is t and rc2.pop("fp3") is t
    assert "fp3" not in rc2


# ---------------------------------------------------------------------------
# Concurrency: two sessions, conflicting overlays, golden parity
# ---------------------------------------------------------------------------


def test_concurrent_sessions_conflicting_conf_parity(service, tpch_path):
    """Two pooled sessions with conflicting overlays run Q1 and Q3
    concurrently, repeatedly, sharing ONE arbiter HBM pool, ONE stage
    cache and ONE metrics registry — both must hold golden parity and
    keep their own conf."""
    svc = service(**{HBM_KEY: 8 << 30})
    svc.start()  # installs the shared arbiter pool
    # conflicting overlays: a streams Q1 in small chunks, b stays
    # whole-input with a different estimatedGroups seed
    a_conf = {CHUNK_KEY: 2048,
              "spark_tpu.sql.caseSensitive": "false"}
    b_conf = {"spark_tpu.sql.aggregate.estimatedGroups": 1 << 10,
              "spark_tpu.sql.caseSensitive": "true"}
    errors = []
    results = {}

    def run(name, sql, sess, conf, rounds=3):
        try:
            for _ in range(rounds):
                record, table = svc.submit(sql, session=sess, conf=conf)
                assert record["status"] == "ok"
            results[name] = table.to_pandas()
        except Exception as e:  # noqa: BLE001 — surfaced via errors
            errors.append((name, e))

    t1 = threading.Thread(target=run,
                          args=("q1", SQLQ.Q1, "sess_a", a_conf))
    t3 = threading.Thread(target=run,
                          args=("q3", SQLQ.Q3, "sess_b", b_conf))
    t1.start(); t3.start()
    t1.join(300); t3.join(300)
    assert not errors, errors
    _check("q1", results["q1"], tpch_path)
    _check("q3", results["q3"], tpch_path)
    # overlays stayed per-session (no cross-stomp)
    sessions = svc.pool.sessions()
    assert int(sessions["sess_a"].conf.get(CHUNK_KEY)) == 2048
    assert int(sessions["sess_b"].conf.get(
        "spark_tpu.sql.aggregate.estimatedGroups")) == 1 << 10
    assert bool(sessions["sess_b"].conf.get(
        "spark_tpu.sql.caseSensitive")) is True
    assert bool(sessions["sess_a"].conf.get(
        "spark_tpu.sql.caseSensitive")) is False
    # both sessions share ONE compiled-stage cache object and drained
    # their leases from the ONE arbiter pool
    assert sessions["sess_a"]._stage_cache is sessions["sess_b"]._stage_cache
    assert get_arbiter() is svc.arbiter
    assert svc.arbiter.leased_bytes == 0


def test_shared_compile_cache_hit_across_sessions(service, tpch_path):
    """The second session's identical query hits the sessions-shared
    compiled-stage cache (the bucket-aligned stage keys from PR 4 make
    the keys identical across sessions over the same Parquet)."""
    svc = service()
    _, t_a = svc.submit(SQLQ.Q1, session="alpha")
    hits_before = svc.metrics.counter("compile_cache_hits").value
    _, t_b = svc.submit(SQLQ.Q1, session="beta")
    hits_after = svc.metrics.counter("compile_cache_hits").value
    assert hits_after > hits_before, (hits_before, hits_after)
    _check("q1", t_b.to_pandas(), tpch_path)
    # parity across sessions too
    _check("q1", t_a.to_pandas(), tpch_path)


def test_arbiter_lease_exhaustion_degrades_not_crashes(service,
                                                      tpch_path):
    """A starved shared pool routes queries down the spill/streaming
    paths (the UnifiedMemoryManager + OOM-ladder integration): parity
    holds, `arbiter_lease_denied` counts, nothing crashes, and the
    pool drains back to zero leases afterwards."""
    from spark_tpu.io.device_cache import CACHE
    CACHE.clear()  # cold: a warm cached scan is admitted as storage
    svc = service(**{HBM_KEY: 4096})  # 4KB: nothing fits resident
    svc.start()  # installs the arbiter
    assert get_arbiter() is svc.arbiter
    record, table = svc.submit(SQLQ.Q1, session="starved")
    assert record["status"] == "ok"
    _check("q1", table.to_pandas(), tpch_path)
    assert svc.metrics.counter("arbiter_lease_denied").value > 0
    assert svc.arbiter.leased_bytes == 0  # all leases released


def test_arbiter_large_pool_grants_and_releases(service, tpch_path):
    """With a roomy pool the same query stays resident: leases are
    granted and fully released at query end."""
    from spark_tpu.io.device_cache import CACHE
    CACHE.clear()  # cold: a warm cached scan is admitted without a lease
    svc = service(**{HBM_KEY: 8 << 30})
    svc.start()
    record, table = svc.submit(SQLQ.Q1, session="roomy")
    assert record["status"] == "ok"
    _check("q1", table.to_pandas(), tpch_path)
    assert svc.metrics.counter("arbiter_lease_granted").value > 0
    assert svc.arbiter.leased_bytes == 0


def test_arbiter_credits_warm_cached_scan(service, tpch_path):
    """A scan already resident in the device table cache is admitted
    as STORAGE (headroom already subtracts its bytes): re-leasing it
    would double-count and evict the very table the query reuses."""
    from spark_tpu.io.device_cache import CACHE
    CACHE.clear()
    svc = service(**{HBM_KEY: 64 << 20})
    svc.start()
    svc.submit(SQLQ.Q1, session="warm")  # cold: leases + fills cache
    assert CACHE.nbytes > 0
    denied0 = svc.metrics.counter("arbiter_lease_denied").value
    hits0 = CACHE.hits
    record, table = svc.submit(SQLQ.Q1, session="warm")
    assert record["status"] == "ok"
    assert CACHE.hits > hits0  # served from the warm cache...
    # ...with no lease denial (and so no self-eviction re-ingest)
    assert svc.metrics.counter("arbiter_lease_denied").value == denied0
    _check("q1", table.to_pandas(), tpch_path)


# ---------------------------------------------------------------------------
# HTTP endpoints
# ---------------------------------------------------------------------------


def _post_sql(port, payload, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/sql",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.load(resp)


def _get_json(port, path, timeout=30):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as resp:
        return resp.status, json.load(resp)


def test_http_sql_roundtrip_parity_and_status(service, tpch_path):
    import pandas as pd
    svc = service().start()
    port = svc.port
    status, resp = _post_sql(port, {"sql": SQLQ.Q1})
    assert status == 200 and resp["status"] == "ok"
    got = pd.DataFrame(resp["rows"], columns=resp["columns"])
    _check("q1", got, tpch_path)
    # status record from the listener bus
    status, rec = _get_json(port, f"/queries/{resp['query_id']}")
    assert status == 200 and rec["status"] == "ok"
    assert rec["engine_query_id"] >= 1
    assert rec["phase_times_s"]  # on_query_end fed the record
    assert any(e["action"] == "admitted" for e in rec["events"])
    # metrics exposition parses and shows the service counters
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as m:
        text = m.read().decode()
    parsed = parse_prometheus_text(text)
    assert parsed["spark_tpu_service_queries_submitted"] >= 1
    assert parsed["spark_tpu_queries_total"] >= 1
    # health
    status, h = _get_json(port, "/healthz")
    assert status == 200 and h["status"] == "ok" and h["sessions"] >= 1


def test_http_arrow_format(service, tpch_path):
    import pyarrow as pa
    svc = service().start()
    req = urllib.request.Request(
        f"http://127.0.0.1:{svc.port}/sql",
        data=json.dumps({"sql": SQLQ.Q1, "format": "arrow"}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"] == \
            "application/vnd.apache.arrow.stream"
        qid = resp.headers["X-Query-Id"]
        table = pa.ipc.open_stream(resp.read()).read_all()
    assert qid.startswith("q-")
    _check("q1", table.to_pandas(), tpch_path)


def test_http_bad_request_and_sql_error(service):
    svc = service().start()
    port = svc.port
    # malformed body
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/sql", data=b"not json",
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=30)
    assert exc.value.code == 400
    # user errors (parse/analysis) surface structured as 400, not 500
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post_sql(port, {"sql": "select nope from missing_table"})
    assert exc.value.code == 400
    body = json.load(exc.value)
    assert body["error"] == "INVALID_SQL"
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post_sql(port, {"sql": "SELEKT 1"})
    assert exc.value.code == 400
    assert json.load(exc.value)["error"] == "INVALID_SQL"
    # 404s
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get_json(port, "/queries/q-99999")
    assert exc.value.code == 404


def test_http_admission_rejection_structured(service, tpch_path):
    """maxConcurrent=1, queueDepth=0: while one slow query holds the
    slot, a second HTTP submission gets a structured 429 + a rejected
    ServiceEvent on the bus + the counter at /metrics."""
    svc = service(**{MAXC_KEY: 1, DEPTH_KEY: 0, QT_KEY: 100}).start()
    port = svc.port
    events = []

    from spark_tpu.observability import QueryListener

    class Sub(QueryListener):
        def on_service(self, event):
            events.append((event.action, event.query_id))

    svc.bus.register(Sub())
    # hold the only slot directly via the admission controller (a
    # deterministic stand-in for a long-running query)
    svc.admission.acquire("holder")
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post_sql(port, {"sql": SQLQ.Q1})
        assert exc.value.code == 429
        body = json.load(exc.value)
        assert body["error"] == "ADMISSION_REJECTED"
        assert body["queue_depth"] == 0
        assert body["query_id"].startswith("q-")
    finally:
        svc.admission.release()
    assert ("rejected", body["query_id"]) in events
    _, parsed = None, parse_prometheus_text(svc.metrics_text())
    assert parsed["spark_tpu_service_rejected"] >= 1
    # the rejected record is poll-visible with the structured error
    _, rec = _get_json(port, f"/queries/{body['query_id']}")
    assert rec["status"] == "rejected"
    assert rec["error"]["error"] == "ADMISSION_REJECTED"
    # and the service still works once the slot frees
    status, resp = _post_sql(port, {"sql": SQLQ.Q1})
    assert status == 200 and resp["status"] == "ok"


def test_http_query_listing_timeline_and_plan(service, tpch_path):
    """The live history API: GET /queries lists a completed Q1,
    /queries/<id>/timeline serves phase spans + stage peak-HBM +
    per-shard rows as JSON, /queries/<id>/plan serves the runtime
    tree — no JSONL scraping."""
    svc = service(**{"spark_tpu.sql.observability.xlaCost": "on"})
    svc.start()
    port = svc.port
    _, resp = _post_sql(port, {"sql": SQLQ.Q1})
    qid = resp["query_id"]
    _post_sql(port, {"sql": "select count(*) as n from lineitem"})
    status, listing = _get_json(port, "/queries")
    assert status == 200 and listing["total"] >= 2
    assert listing["queries"][0]["submitted_ts"] >= \
        listing["queries"][-1]["submitted_ts"]  # newest first
    assert any(q["id"] == qid and q["status"] == "ok"
               for q in listing["queries"])
    # pagination: limit=1 pages with next_offset
    _, page = _get_json(port, "/queries?limit=1")
    assert len(page["queries"]) == 1 and page["next_offset"] == 1
    _, page2 = _get_json(port, "/queries?limit=1&offset=1")
    assert page2["queries"][0]["id"] != page["queries"][0]["id"]
    # filters
    _, only_ok = _get_json(port, "/queries?status=ok&session=default")
    assert only_ok["total"] >= 2
    # timeline: spans + stage HBM + shards list (empty on single chip)
    _, tl = _get_json(port, f"/queries/{qid}/timeline")
    assert tl["engine_query_id"] >= 1
    assert any(s["name"] == "dispatch" for s in tl["spans"]), tl["spans"]
    assert any(s.get("peak_hbm_bytes") for s in tl["stages"]), tl
    assert isinstance(tl["shards"], list)
    assert tl["phase_times_s"].get("execution") is not None
    # plan: runtime-annotated physical tree + the submitted SQL
    _, pl = _get_json(port, f"/queries/{qid}/plan")
    assert "HashAggregateExec" in pl["physical"], pl
    assert "rows out" in pl["physical"]  # runtime annotations present
    assert pl["sql"].lstrip().lower().startswith("select")
    # unknown ids 404 on both detail endpoints
    for suffix in ("timeline", "plan"):
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get_json(port, f"/queries/q-99999/{suffix}")
        assert exc.value.code == 404


def test_timeline_holds_queue_and_egress(service):
    """The request's wait for its session and slot (`queue`, at or
    after the recorder's origin, which is the request's first instant
    and no longer the query's construction) and the pull of the rows
    (`egress`, after the engine's end event) both reach the timeline,
    with the tree's ids; a query that fails is amended the same way."""
    svc = service()
    svc.start()
    _, resp = _post_sql(svc.port, {"sql": SQLQ.Q1})
    _, tl = _get_json(svc.port, f"/queries/{resp['query_id']}/timeline")
    by_name = {s["name"]: s for s in tl["spans"]}
    assert {"queue", "dispatch", "dispatch.launch", "dispatch.sync",
            "egress"} <= set(by_name), sorted(by_name)
    assert tl["spans_dropped"] == 0
    assert by_name["queue"]["t0_ms"] >= 0 <= by_name["queue"]["dur_ms"]
    assert by_name["queue"]["t0_ms"] + by_name["queue"]["dur_ms"] \
        <= by_name["parse"]["t0_ms"]
    assert by_name["egress"]["t0_ms"] >= by_name["dispatch"]["t0_ms"] \
        + by_name["dispatch"]["dur_ms"]
    assert by_name["dispatch.sync"]["parent"] == by_name["dispatch"]["id"]
    assert len({s["tid"] for s in tl["spans"]}) == 1  # resident: one thread
    # a store that no longer holds the query is left alone
    svc.history.amend("q-unknown", spans=[])
    assert svc.history.get("q-unknown") is None


def _written_timeline(port, rid, timeout_s=20.0):
    """The timeline once the handler has copied the spans for the last
    time, after `http.write`: the client holds its answer a moment
    before that."""
    deadline = time.monotonic() + timeout_s
    while True:
        _, tl = _get_json(port, f"/queries/{rid}/timeline")
        if tl.get("request_ms") is not None \
                and tl["status"] not in ("submitted", "running"):
            return tl
        assert time.monotonic() < deadline, tl
        time.sleep(0.02)


def _post_any(port, payload):
    """(status, headers, body bytes) of a `POST /sql`, error or not."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/sql", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, resp.headers, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.headers, e.read()


#: the front end's spans, on the handler's thread, once a request
FRONT_SPANS = ["http.accept", "http.read", "queue", "parse"]
ANSWER_SPANS = ["encode", "http.write"]
#: once a query that reached the engine (`stage.lookup` once a
#: dispatch attempt, which is once here)
ENGINE_SPANS = ["query.begin", "replan.key", "predict", "stream.verdict",
                "stage.lookup", "dispatch", "plan.fingerprint",
                "stage_event", "end_event", "finish"]
SLOW_STREAM = {CHUNK_KEY: 512, "spark_tpu.sql.memory.deviceBudget": 1,
               "spark_tpu.faults.inject": "stream_chunk:slow:2:20000"}
GROUPED = ("select l_returnflag, sum(l_quantity) as s from lineitem "
           "group by l_returnflag")


@pytest.mark.parametrize(
    "case", ["json", "arrow", "async", "parse_error", "cancelled"])
def test_request_spans_frame_the_request(service, monkeypatch, case):
    """From the accept to the last byte written a request stands under
    leaf spans of one recorder, born with the request: every `t0_ms`
    at or after 0, each of the front end's names once, `http.write`
    ending last and `request_ms` with it, no span left open; also for
    a request that fails in `parse` and for one that is cancelled.
    Counts and structure only, no duration."""
    from spark_tpu.service import server as server_mod
    made = []

    class Tracked(server_mod.SpanRecorder):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            made.append(self)

    monkeypatch.setattr(server_mod, "SpanRecorder", Tracked)
    svc = service()
    svc.start()
    port = svc.port
    if case == "json":
        status, _, body = _post_any(port, {"sql": SQLQ.Q1})
        rid = json.loads(body)["query_id"]
    elif case == "arrow":
        status, headers, body = _post_any(
            port, {"sql": SQLQ.Q1, "format": "arrow"})
        rid = headers["X-Query-Id"]
    elif case == "async":
        status, _, body = _post_any(port, {"sql": SQLQ.Q1, "mode": "async"})
        rid = json.loads(body)["query_id"]
    elif case == "parse_error":
        status, _, body = _post_any(port, {"sql": "select from where"})
        (rid,) = [q["id"] for q in svc.query_listing()["queries"]]
    else:
        answer = []
        t = threading.Thread(target=lambda: answer.append(_post_any(
            port, {"sql": GROUPED, "conf": SLOW_STREAM})), daemon=True)
        t.start()
        deadline = time.monotonic() + 60
        while not svc.query_listing(status="running")["queries"]:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        (rid,) = [q["id"] for q in svc.query_listing()["queries"]]
        time.sleep(0.3)  # into the chunk loop's slow sleep
        svc.cancel_query(rid)
        t.join(30)
        ((status, _, body),) = answer
    assert status == {"json": 200, "arrow": 200, "async": 202,
                      "parse_error": 400, "cancelled": 409}[case], body
    tl = _written_timeline(port, rid)
    spans = tl["spans"]
    assert tl["spans_dropped"] == 0
    assert all(s["t0_ms"] >= 0 and s["dur_ms"] >= 0 for s in spans), spans
    count = {}
    for s in spans:
        count[s["name"]] = count.get(s["name"], 0) + 1
    by_name = {s["name"]: s for s in spans}
    for name in FRONT_SPANS + ANSWER_SPANS:
        assert count.get(name) == 1, (name, count)
    # in order, on the handler's thread, each a leaf
    starts = [by_name[n]["t0_ms"] for n in FRONT_SPANS]
    assert starts == sorted(starts)
    assert by_name["http.accept"]["t0_ms"] == 0
    assert by_name["http.read"]["attrs"]["bytes"] > 0
    assert by_name["encode"]["attrs"]["bytes"] == len(body)
    front_tid = by_name["http.read"]["tid"]
    for name in FRONT_SPANS[:2] + ANSWER_SPANS:
        assert by_name[name]["tid"] == front_tid, name
        assert by_name[name]["parent"] is None
        assert not any(s["parent"] == by_name[name]["id"] for s in spans)
    write_end = by_name["http.write"]["t0_ms"] + by_name["http.write"]["dur_ms"]
    assert by_name["encode"]["t0_ms"] + by_name["encode"]["dur_ms"] \
        <= by_name["http.write"]["t0_ms"]
    assert write_end == pytest.approx(tl["request_ms"], abs=2e-3)
    if case != "async":  # the 202 is written while the query runs
        assert write_end == max(s["t0_ms"] + s["dur_ms"] for s in spans)
        assert len({s["tid"] for s in spans}) == (
            1 if case != "cancelled" else 2)  # its prefetch worker
    # no root: no span holds the others
    assert not any(s["t0_ms"] <= by_name["http.read"]["t0_ms"]
                   and s["t0_ms"] + s["dur_ms"] >= write_end for s in spans)
    if case == "parse_error":
        assert sorted(count) == sorted(
            FRONT_SPANS + ["finish"] + ANSWER_SPANS)
        assert by_name["parse"]["attrs"]["error"] == "ParseError"
    elif case == "cancelled":
        assert count["cancelled"] == count["end_event"] == 1
        assert count["stream.open"] == count["prefetch.start"] == 1
        assert count["query.begin"] == count["finish"] == 1
        assert tl["status"] == "cancelled"
    else:
        for name in ENGINE_SPANS:
            assert count.get(name) == 1, (name, count)
        # an answer's rows; the 202 of an async submission has none
        assert by_name["encode"]["attrs"].get("rows") == (
            None if case == "async" else 4)
        assert tl["status"] == "ok"
    # one recorder a request, the query's own, and nothing left open
    (rec,) = made
    assert rec.request_id == rid
    assert rec.open_spans() == {}
    assert rec.query_id == (None if case == "parse_error"
                            else tl["engine_query_id"])


def test_sync_and_async_submission_give_the_same_names(service):
    """Embedded, with no HTTP around them: `submit` and `submit_async`
    make the request's recorder at their entry and leave the same
    names, `queue` at or after the origin."""
    svc = service()
    svc.submit(SQLQ.Q6)  # loads the scan: the two below find it held
    record, _ = svc.submit(SQLQ.Q6)
    queued = svc.submit_async(SQLQ.Q6)
    deadline = time.monotonic() + 60
    while svc.query_snapshot(queued["id"])["status"] in (
            "submitted", "running"):
        assert time.monotonic() < deadline
        time.sleep(0.02)
    names = []
    for rid in (record["id"], queued["id"]):
        tl = svc.query_timeline(rid)
        assert tl["status"] == "ok" and tl["request_ms"] is None
        assert all(s["t0_ms"] >= 0 for s in tl["spans"])
        names.append(sorted(s["name"] for s in tl["spans"]))
    assert names[0] == names[1]
    assert {"queue", "parse", "query.begin", "stage.lookup", "end_event",
            "egress", "finish"} <= set(names[0])
    assert not {"http.accept", "http.read", "encode",
                "http.write"} & set(names[0])


def test_history_store_bounded(service):
    from spark_tpu.service.query_history import QueryHistoryStore
    store = QueryHistoryStore(max_entries=2)
    for i in range(4):
        store.put(f"q-{i}", {"engine_query_id": i})
    assert len(store) == 2
    assert store.get("q-0") is None and store.get("q-3") is not None


def test_concurrent_queries_scrape_and_rotation(service, tpch_path,
                                                tmp_path):
    """Satellite: pooled sessions running parallel queries while
    /metrics is scraped and the event log rotates (tiny maxBytes) —
    the Prometheus text must stay parseable on every scrape and the
    rotated event log must replay with zero corrupt lines."""
    from spark_tpu.service.query_history import QueryHistoryStore  # noqa: F401
    ev_dir = str(tmp_path / "ev")
    svc = service(**{
        "spark_tpu.sql.eventLog.dir": ev_dir,
        "spark_tpu.sql.eventLog.maxBytes": 512,
        "spark_tpu.sql.metrics.sink": "prometheus",
        "spark_tpu.sql.metrics.dir": str(tmp_path / "m"),
    }).start()
    port = svc.port
    n_sessions, n_rounds = 3, 3
    errors = []
    done = threading.Event()

    def run(sess):
        try:
            for _ in range(n_rounds):
                record, _ = svc.submit(
                    "select count(*) as n from lineitem", session=sess)
                assert record["status"] == "ok"
        except Exception as e:  # noqa: BLE001 — surfaced via errors
            errors.append((sess, e))

    def scrape():
        try:
            while not done.is_set():
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=30) as m:
                    parsed = parse_prometheus_text(m.read().decode())
                assert isinstance(parsed, dict)
                time.sleep(0.01)
        except Exception as e:  # noqa: BLE001
            errors.append(("scrape", e))

    threads = [threading.Thread(target=run, args=(f"s{i}",))
               for i in range(n_sessions)]
    scraper = threading.Thread(target=scrape)
    scraper.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    done.set()
    scraper.join(60)
    assert not errors, errors
    # rotated log replays completely: one parseable line per query,
    # schema-valid throughout (read_event_log raises on corrupt JSON)
    import os as _os
    from spark_tpu import history as H
    files = _os.listdir(ev_dir)
    assert len(files) > n_sessions, files  # rotation actually rolled
    events = H.read_event_log(ev_dir)
    assert len(events) == n_sessions * n_rounds
    assert (events["status"] == "ok").all()
    assert (events["schema_version"] == 7).all()
    # the versioned-schema validator agrees line by line
    import importlib.util
    root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "events_tool", _os.path.join(root, "scripts", "events_tool.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.validate([ev_dir]) == []


def test_http_async_submission(service):
    svc = service().start()
    status, resp = _post_sql(svc.port, {"sql": SQLQ.Q1, "mode": "async"})
    assert status == 202
    qid = resp["query_id"]
    for _ in range(600):
        _, rec = _get_json(svc.port, f"/queries/{qid}")
        if rec["status"] in ("ok", "error"):
            break
        time.sleep(0.1)
    assert rec["status"] == "ok" and rec["row_count"] >= 1


def test_session_pool_bound(service):
    from spark_tpu.service.pool import PoolExhausted
    svc = service(**{"spark_tpu.service.maxSessions": 1})
    svc.submit("select l_orderkey from lineitem limit 1", session="only")
    with pytest.raises(PoolExhausted):
        svc.submit("select l_orderkey from lineitem limit 1",
                   session="another")


def test_active_session_contextvar_isolated(service):
    """Pooled sessions never clobber the process-global active session
    (the builder singleton other code in the process relies on)."""
    from spark_tpu import SparkTpuSession
    before = SparkTpuSession._active
    svc = service()
    svc.submit("select count(*) as n from lineitem")
    assert SparkTpuSession._active is before


def test_session_busy_sheds_with_structured_timeout(service):
    """A second request for a session already running a query must not
    burn an execution slot waiting — it sheds with a structured 503
    after queueTimeoutMs while OTHER sessions keep executing."""
    svc = service(**{QT_KEY: 150})
    entry = svc.pool.get_or_create("busy")
    entry.lock.acquire()  # stand-in for a long-running query
    try:
        with pytest.raises(AdmissionTimeout) as exc:
            svc.submit("select count(*) as n from lineitem",
                       session="busy")
        assert exc.value.to_dict()["session"] == "busy"
        # an idle session is unaffected (no slot was consumed)
        record, _ = svc.submit("select count(*) as n from lineitem",
                               session="idle")
        assert record["status"] == "ok"
    finally:
        entry.lock.release()


def test_async_submissions_bounded(service):
    """An async burst past maxConcurrent + queueDepth rejects at the
    front door (429-shaped) instead of spawning unbounded threads."""
    svc = service(**{MAXC_KEY: 1, DEPTH_KEY: 0, QT_KEY: 100})
    svc.admission.acquire("holder")  # pin the only slot
    # park the first worker at the session lease too: with warm caches
    # session init is fast enough that the worker could reach the
    # queue_depth=0 admission rejection (freeing its in-flight slot)
    # before the second submission's bound check runs
    entry = svc.pool.get_or_create("default")
    entry.lock.acquire()
    try:
        first = svc.submit_async(
            "select count(*) as n from lineitem")  # occupies the bound
        with pytest.raises(AdmissionRejected) as exc:
            svc.submit_async("select count(*) as n from lineitem")
        body = exc.value.to_dict()
        assert body["error"] == "ADMISSION_REJECTED"
        assert body["bound"] == 1
    finally:
        # slot first, lease second: the worker waking from the lease
        # must find the slot free (queue_depth=0 would otherwise
        # reject it in the gap between the two releases)
        svc.admission.release()
        entry.lock.release()
    for _ in range(200):
        if first["status"] in ("ok", "error", "queue_timeout"):
            break
        time.sleep(0.05)
    assert first["status"] in ("ok", "queue_timeout")


def test_pinned_cache_entries_survive_lease_pressure():
    """evict_bytes skips entries pinned by running queries: their HBM
    would not actually be freed (the query's reference keeps it live),
    so crediting their bytes would overcommit the pool."""
    from spark_tpu.io.device_cache import DeviceTableCache

    class _B:  # minimal Batch stand-in for batch_nbytes
        def __init__(self, n):
            import numpy as np

            class _C:
                def __init__(self):
                    self.data = np.zeros(n, dtype="u1")
                    self.validity = None
            self.columns = {"c": _C()}
            self.selection = None

    cache = DeviceTableCache()
    cache.put(("pinned",), _B(1000), budget=1 << 20)
    cache.put(("loose",), _B(500), budget=1 << 20)
    assert cache.pin(("pinned",))
    freed = cache.evict_bytes(10_000)
    assert freed == 500  # only the unpinned entry went
    assert cache.contains(("pinned",))
    cache.unpin(("pinned",))
    assert cache.evict_bytes(10_000) == 1000  # now reclaimable
    assert not cache.pin(("missing",))  # absent key: caller leases


def _stand_in_batch(n):
    """Minimal Batch stand-in for batch_nbytes."""
    import numpy as np

    class _C:
        def __init__(self):
            self.data = np.zeros(n, dtype="u1")
            self.validity = None

    class _B:
        def __init__(self):
            self.columns = {"c": _C()}
            self.selection = None
    return _B()


def test_put_eviction_skips_pinned_entries():
    """put's budget eviction must honor pins like evict_bytes does:
    evicting an entry a running query was admitted against frees no
    HBM (its reference stays live) while zeroing the storage bytes it
    is accounted under — phantom headroom for the next admission."""
    from spark_tpu.io.device_cache import DeviceTableCache
    cache = DeviceTableCache()
    cache.put(("pinned",), _stand_in_batch(1000), budget=2000)
    assert cache.pin(("pinned",))
    cache.put(("loose",), _stand_in_batch(800), budget=2000)
    # over budget: the pinned entry is older (LRU victim) but must
    # survive; the loose one goes instead
    cache.put(("new",), _stand_in_batch(900), budget=2000)
    assert cache.contains(("pinned",))
    assert not cache.contains(("loose",))
    assert cache.contains(("new",))
    # everything else pinned: the just-inserted entry itself survives
    assert cache.pin(("new",))
    cache.put(("last",), _stand_in_batch(1000), budget=2000)
    assert cache.contains(("last",)) and cache.contains(("new",))
    cache.unpin(("pinned",))
    cache.unpin(("new",))
    cache.evict_bytes(1 << 30)


def test_lease_kept_when_cache_put_rejected():
    """convert_lease_to_pin must NOT drop the lease when the entry
    never landed in the device cache (put rejected it): the batch is
    live on device but absent from CACHE.nbytes, so dropping the lease
    would credit phantom headroom."""
    from spark_tpu.io.device_cache import CACHE
    from spark_tpu.service.arbiter import _Owner
    arb = DeviceResourceArbiter(10_000)
    owner = _Owner("q")
    key = ("svc-test-lease-kept",)
    assert arb.try_acquire(owner, key, 4000)
    # key is NOT in the cache: pin fails, lease must be retained
    arb.convert_lease_to_pin(owner, key)
    assert arb.leased_bytes == 4000
    # once the entry genuinely lands in storage, conversion proceeds
    CACHE.put(key, _stand_in_batch(100), budget=1 << 20)
    try:
        arb.convert_lease_to_pin(owner, key)
        assert arb.leased_bytes == 0
    finally:
        arb.release(owner)  # unpins
        CACHE.evict_bytes(200)


def test_prefer_resident_takes_no_lease_for_streaming_scan():
    """_prefer_resident runs its cheap disqualifiers BEFORE consulting
    the arbiter: a scan that will stream anyway (uncacheable source)
    must not hold an est-sized lease from the shared pool to query
    end."""
    from spark_tpu import types as T
    from spark_tpu.execution.streaming_agg import _prefer_resident
    from spark_tpu.service import arbiter as A

    class _Src:
        def cache_token(self):
            return None  # uncacheable: the scan streams

        def estimated_rows(self):
            return 1_000_000

    class _Field:
        dtype = T.IntegerType()
        nullable = False

    class _Schema:
        fields = [_Field()]

    class _Leaf:
        source = _Src()
        required_columns = None
        pushed_filters = ()

        def schema(self):
            return _Schema()

    arb = DeviceResourceArbiter(1 << 30)
    install_arbiter(arb)
    try:
        conf = Conf()
        conf.set(CACHE_BYTES_KEY, 1 << 30)
        token = A.enter_query("stream-test")
        try:
            assert _prefer_resident(_Leaf(), conf, None) is False
            assert arb.leased_bytes == 0  # no est-sized lease parked
        finally:
            A.exit_query(token)
    finally:
        install_arbiter(None)


def test_standalone_session_result_cache_unbounded(session):
    """Standalone sessions keep the pre-service unbounded result cache
    unless resultCacheBytes is explicitly set — a cache()-marked table
    larger than a default bound must not silently recompute."""
    from spark_tpu.service.arbiter import RESULT_CACHE_BYTES_KEY
    from spark_tpu.session import SparkTpuSession
    assert session._data_cache.max_bytes == 0
    conf = Conf()
    conf.set(RESULT_CACHE_BYTES_KEY, 1234)
    bounded = SparkTpuSession(conf=conf, register_active=False)
    assert bounded._data_cache.max_bytes == 1234


_ARENAS = """
import sys, threading
sys.path.insert(0, {repo!r})
from spark_tpu.service import server

HEAP = server._ARENA_HEAP_BYTES


def whole_heaps():
    # heap-sized, heap-aligned blocks that lie whole inside one
    # readable and writable mapping (a neighbour may be merged in)
    n = 0
    for line in open("/proc/self/maps"):
        span, perms = line.split()[:2]
        lo, hi = (int(x, 16) for x in span.split("-"))
        if perms.startswith("rw"):
            n += max(0, hi // HEAP - -(-lo // HEAP))
    return n


def from_a_new_thread(out, looked, done):
    keep = [bytearray(100_000) for _ in range(50)]
    out.append(whole_heaps())
    looked.set()
    done.wait(60)  # alive, so that its arena is no one else's


seen, done = [], threading.Event()
for tuned in (False, True):
    if tuned:
        server._open_thread_arenas_whole()
    looked = threading.Event()
    threading.Thread(target=from_a_new_thread,
                     args=(seen, looked, done)).start()
    assert looked.wait(60)
done.set()
print("SEEN", *seen)
"""


def test_a_started_service_opens_a_threads_arena_whole():
    """A served query runs on a handler's thread, whose glibc arena
    grows by one `mprotect` a few pages; `SqlService.start` pads the
    growth so that a new 64 MiB heap is readable and writable whole
    (PR 38: where that call is dear, a stage's executable took 9 s to
    come out of the compile cache on such a thread and 2 s on the
    main one). In a process of its own: the setting is the process's."""
    import os
    import subprocess
    import sys
    if not os.path.exists("/proc/self/maps"):
        pytest.skip("no /proc/self/maps to read the heaps from")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _ARENAS.format(repo=repo)],
        capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items()
             if not k.startswith("MALLOC_")})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("SEEN")]
    before, after = (int(x) for x in line[-1].split()[1:])
    assert after > before, (before, after)
