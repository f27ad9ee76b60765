"""Query lifecycle control suite (execution/lifecycle.py + service/).

Covers the acceptance surface: cooperative cancellation at every
engine boundary (the cancel-point chaos matrix: cancellation delivered
at the nth boundary x {single-chip chunked, mesh, streaming,
service-async}, each cell proving structured error + no thread leak +
arbiter drained + byte-identical immediate re-run), end-to-end
deadlines (armed through retry backoff, admission queue and arbiter
lease waits; deadline < stageTimeout stops the recovery ladder), the
DELETE /queries/<id> endpoint (cancel-during-queue, idempotency,
cancel-after-finish 409, structured 404), and the per-session quotas
(admission maxConcurrent starvation + arbiter hbmShare)."""

import json
import threading
import time
import types
import urllib.error
import urllib.request

import pytest

from spark_tpu import Conf
from spark_tpu.execution import lifecycle
from spark_tpu.execution.failures import FailureClass, classify
from spark_tpu.service.arbiter import (DeviceResourceArbiter, _Owner,
                                       install_arbiter)
from spark_tpu.service.server import SqlService
from spark_tpu.testing import faults
from spark_tpu.testing.lockwatch import LockWatch
from spark_tpu.tpch import queries as Q
from spark_tpu.tpch.datagen import write_parquet

SF = 0.002
CHUNK_KEY = "spark_tpu.sql.execution.streamingChunkRows"
BUDGET_KEY = "spark_tpu.sql.memory.deviceBudget"
MESH_KEY = "spark_tpu.sql.mesh.size"
BACKOFF_KEY = "spark_tpu.execution.backoffMs"
DEADLINE_KEY = "spark_tpu.execution.queryDeadlineMs"
STAGE_TIMEOUT_KEY = "spark_tpu.execution.stageTimeoutMs"
INJECT_KEY = "spark_tpu.faults.inject"
PORT_KEY = "spark_tpu.service.port"
MAXC_KEY = "spark_tpu.service.maxConcurrent"
QT_KEY = "spark_tpu.service.queueTimeoutMs"
SESSION_MAXC_KEY = "spark_tpu.service.session.maxConcurrent"


@pytest.fixture(scope="module")
def tpch_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tpch_lifecycle") / "sf_small")
    write_parquet(path, SF)
    return path


@pytest.fixture()
def tpch_session(session, tpch_path):
    Q.register_tables(session, tpch_path)
    return session


@pytest.fixture()
def service(tpch_path):
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


def _assert_no_prefetch_leak():
    LockWatch().assert_no_thread_leak(timeout_s=10.0)


def _cancel_when_registered(session, qid, timeout_s=30.0):
    """Poll until the execution registers its token, then cancel."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if lifecycle.cancel(session.app_id, qid):
            return True
        time.sleep(0.002)
    return False


def _run_in_thread(qe):
    out = {}

    def run():
        try:
            out["table"] = qe.collect()
        except Exception as e:  # noqa: BLE001 — asserted by callers
            out["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, out


# ---------------------------------------------------------------------------
# CancelToken / classification (unit)
# ---------------------------------------------------------------------------


def test_token_cancel_and_deadline_classify_cancelled():
    tok = lifecycle.CancelToken()
    tok.cancel()
    with pytest.raises(lifecycle.QueryCancelledError) as exc:
        tok.check("chunk")
    assert "chunk" in str(exc.value)
    assert classify(exc.value) is FailureClass.CANCELLED

    tok2 = lifecycle.CancelToken(deadline_ms=1)
    time.sleep(0.01)
    with pytest.raises(lifecycle.QueryDeadlineError) as exc2:
        tok2.check()
    assert classify(exc2.value) is FailureClass.CANCELLED


def test_token_wait_wakes_on_cross_thread_cancel():
    tok = lifecycle.CancelToken()
    threading.Timer(0.05, tok.cancel).start()
    t0 = time.perf_counter()
    with pytest.raises(lifecycle.QueryCancelledError):
        tok.wait(30.0)
    assert time.perf_counter() - t0 < 5.0  # not the 30s sleep


def test_session_cancel_unknown_query_returns_false(session):
    assert session.cancel(999999) is False


# ---------------------------------------------------------------------------
# Cancel during retry backoff: returns within ~a tick, not backoffMs
# ---------------------------------------------------------------------------


def test_cancel_during_backoff_returns_promptly(tpch_session):
    s = tpch_session
    # one transient fault, then a HUGE backoff: min first-retry sleep
    # is backoffMs * 2^0 * 0.5 = 15s — the cancel must not wait it out
    s.conf.set(BACKOFF_KEY, 30000.0)
    with faults.inject(s.conf, "stage_run:unavailable:1"):
        qe = Q.q1(s)._qe()
        t, out = _run_in_thread(qe)
        assert _cancel_when_registered(s, qe.query_id)
        t0 = time.perf_counter()
        t.join(10)
        assert not t.is_alive()
        assert time.perf_counter() - t0 < 10
    assert isinstance(out.get("error"), lifecycle.QueryCancelledError)
    # the cancel action landed in fault_summary (history FAULT_ACTIONS)
    assert qe.fault_summary.get("cancel") == 1
    # and the Chrome-trace instant span
    assert any(sp.name == "cancelled" for sp in qe.spans.spans)


# ---------------------------------------------------------------------------
# Deadline interplay: deadline < stageTimeout stops the ladder
# ---------------------------------------------------------------------------


def test_deadline_beats_stage_timeout_and_stops_ladder(tpch_session):
    s = tpch_session
    s.conf.set(STAGE_TIMEOUT_KEY, 500)
    s.conf.set(DEADLINE_KEY, 350.0)
    try:
        # a 5s slow fault at the pre-dispatch seam: the interruptible
        # sleep is capped by the 350ms budget and raises the DEADLINE
        # error — never StageTimeoutError, never a retry
        with faults.inject(s.conf, "stage_run:slow:1:5000"):
            qe = Q.q1(s)._qe()
            t0 = time.perf_counter()
            with pytest.raises(lifecycle.QueryDeadlineError):
                qe.collect()
            assert time.perf_counter() - t0 < 4.0
        assert "stage_timeout" not in qe.fault_summary
        assert "transient_retry" not in qe.fault_summary
        assert qe.fault_summary.get("cancel") == 1
        assert s.metrics.counter("query_deadline_exceeded").value >= 1
    finally:
        s.conf.set(DEADLINE_KEY, 0.0)
        s.conf.set(STAGE_TIMEOUT_KEY, 0)


def test_deadline_fires_inside_retry_backoff(tpch_session):
    s = tpch_session
    s.conf.set(BACKOFF_KEY, 60000.0)
    s.conf.set(DEADLINE_KEY, 400.0)
    try:
        with faults.inject(s.conf, "stage_run:unavailable:1"):
            qe = Q.q1(s)._qe()
            t0 = time.perf_counter()
            with pytest.raises(lifecycle.QueryDeadlineError):
                qe.collect()
            # the 30s+ backoff sleep was cut at the deadline budget
            assert time.perf_counter() - t0 < 5.0
    finally:
        s.conf.set(DEADLINE_KEY, 0.0)


# ---------------------------------------------------------------------------
# Arbiter: lease-wait deadline + per-session hbmShare quota (unit)
# ---------------------------------------------------------------------------


def test_lease_wait_respects_deadline_token():
    arb = DeviceResourceArbiter(1000)
    o1 = _Owner("s1:q1")
    assert arb.try_acquire(o1, "k1", 1000)
    ctx = lifecycle.install(lifecycle.CancelToken(deadline_ms=200))
    try:
        t0 = time.perf_counter()
        with pytest.raises(lifecycle.QueryDeadlineError):
            arb.try_acquire(_Owner("s2:q1"), "k2", 500, wait_ms=30000)
        assert time.perf_counter() - t0 < 5.0  # not the 30s wait
    finally:
        lifecycle.uninstall(ctx)
    arb.release(o1)
    assert arb.stats()["leased_bytes"] == 0


def test_lease_wait_wakes_on_cancel():
    arb = DeviceResourceArbiter(1000)
    o1 = _Owner("s1:q1")
    assert arb.try_acquire(o1, "k1", 1000)
    tok = lifecycle.CancelToken()
    ctx = lifecycle.install(tok)
    try:
        threading.Timer(0.1, tok.cancel).start()
        t0 = time.perf_counter()
        with pytest.raises(lifecycle.QueryCancelledError):
            arb.try_acquire(_Owner("s2:q1"), "k2", 500, wait_ms=30000)
        assert time.perf_counter() - t0 < 5.0
    finally:
        lifecycle.uninstall(ctx)


def test_hbm_share_caps_one_session_group():
    from spark_tpu.observability import MetricsRegistry
    m = MetricsRegistry()
    arb = DeviceResourceArbiter(1000, metrics=m)
    greedy1, greedy2 = _Owner("greedy:q1"), _Owner("greedy:q2")
    other = _Owner("other:q1")
    # share 0.25 => 250-byte cap per session group
    assert arb.try_acquire(greedy1, "k1", 200, share=0.25)
    assert not arb.try_acquire(greedy2, "k2", 100, share=0.25)
    assert m.counter("session_quota_rejections").value == 1
    # the other session still leases within ITS OWN share — greedy's
    # denial never consumed the pool
    assert arb.try_acquire(other, "k3", 200, share=0.25)
    # denial memoized per (owner, key): a later identical ask is a
    # stable verdict, not a flip-flop
    assert not arb.try_acquire(greedy2, "k2", 100, share=0.25)
    arb.release(greedy1)
    arb.release(other)
    assert arb.stats()["leased_bytes"] == 0


# ---------------------------------------------------------------------------
# Post-cancel byte parity on Q3 (engine level)
# ---------------------------------------------------------------------------


def test_post_cancel_rerun_byte_parity_q3(tpch_session):
    s = tpch_session
    s.conf.set(CHUNK_KEY, 1024)
    s.conf.set(BUDGET_KEY, 1)  # force the chunked spill path
    baseline = Q.q3(s)._qe().collect()

    qe = Q.q3(s)._qe()
    t, out = _run_in_thread(qe)
    assert _cancel_when_registered(s, qe.query_id)
    t.join(30)
    assert not t.is_alive()
    # fast queries may finish before the cancel lands — the contract
    # under test is the CANCELLED path, so only assert when it took
    if "error" in out:
        assert isinstance(out["error"], lifecycle.QueryCancelledError)
    _assert_no_prefetch_leak()
    again = Q.q3(s)._qe().collect()
    assert again.equals(baseline)  # byte-identical Arrow tables


# ---------------------------------------------------------------------------
# Cancel-point chaos matrix: cancellation delivered at the nth
# cooperative boundary x execution shape. Every cell must terminate
# with the structured error, leak no worker thread, drain the arbiter
# (when installed) and leave the engine able to reproduce the
# uninterrupted result byte-identically.
# ---------------------------------------------------------------------------


def _matrix_sweep(s, make_qe, baseline, max_n=48):
    """Sweep cancel_point:cancel:n until a run completes without the
    rule firing (n outran the query's boundary count). Returns the
    number of cancelled cells (must be >= 1)."""
    cancelled_cells = 0
    n = 1
    while n <= max_n:
        with faults.inject(s.conf, f"cancel_point:cancel:{n}") as plan:
            qe = make_qe()
            try:
                table = qe.collect()
                fired = any(site == "cancel_point"
                            for site, _, _ in plan.fired_log)
                if not fired:
                    break  # past the last boundary: sweep complete
                # the rule fired on the FINAL boundary of a run whose
                # work was already done — still a clean completion
                assert table.equals(baseline)
            except lifecycle.QueryCancelledError:
                cancelled_cells += 1
                _assert_no_prefetch_leak()
                from spark_tpu.service.arbiter import get_arbiter
                arb = get_arbiter()
                if arb is not None:
                    assert arb.stats()["leased_bytes"] == 0
                    assert arb.stats()["owners"] == 0
        # immediate identical re-run, chaos disarmed: byte parity
        again = make_qe().collect()
        assert again.equals(baseline)
        # dense early (scan/compile/attempt boundaries), sparser into
        # the chunk run to bound the sweep's wall clock
        n += 1 if n < 8 else 4
    assert cancelled_cells >= 1
    return cancelled_cells


def test_cancel_matrix_single_chip_chunked(tpch_session):
    s = tpch_session
    s.conf.set(CHUNK_KEY, 1024)
    s.conf.set(BUDGET_KEY, 1)  # chunked spill path: chunk boundaries
    baseline = Q.q1(s)._qe().collect()
    cells = _matrix_sweep(s, lambda: Q.q1(s)._qe(), baseline)
    assert cells >= 2  # at least pre-stream + chunk boundaries


def test_cancel_matrix_mesh(tpch_session):
    s = tpch_session
    s.conf.set(MESH_KEY, 8)
    s.conf.set(CHUNK_KEY, 1024)
    try:
        baseline = Q.q1(s)._qe().collect()
        cells = _matrix_sweep(s, lambda: Q.q1(s)._qe(), baseline,
                              max_n=32)
        assert cells >= 1
    finally:
        s.conf.set(MESH_KEY, 0)


def test_cancel_matrix_streaming_trigger(session, tmp_path):
    import numpy as np
    import pandas as pd
    from spark_tpu import functions as F
    from spark_tpu.functions import col
    from spark_tpu.streaming import MemoryStream
    s = session
    schema = pd.DataFrame({"k": pd.Series([], dtype=np.int64),
                           "v": pd.Series([], dtype=np.int64)})
    stream = MemoryStream(s, schema)
    q = (stream.to_df()
         .group_by(F.pmod(col("k"), 4).alias("g"))
         .agg(F.sum(col("v")).alias("total"))
         .write_stream(str(tmp_path / "ck")))
    stream.add_data(pd.DataFrame({"k": [0, 1, 1], "v": [1, 2, 3]}))
    # cancellation at the trigger boundary: nothing of the batch
    # commits, and a later drain is exactly-once
    with faults.inject(s.conf, "cancel_point:cancel:1"):
        ctx = lifecycle.install(lifecycle.CancelToken())
        try:
            with pytest.raises(lifecycle.QueryCancelledError):
                q.process_available()
        finally:
            lifecycle.uninstall(ctx)
    assert q.latest() is None  # no batch committed
    q.process_available()  # disarmed: drains exactly-once
    out = q.latest().set_index("g")
    assert out.loc[0, "total"] == 1 and out.loc[1, "total"] == 5


# ---------------------------------------------------------------------------
# Service: DELETE /queries/<id> end to end
# ---------------------------------------------------------------------------


def _post_sql(port, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/sql",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _http(port, method, path):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method)
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _poll_terminal(svc, rid, timeout_s=60.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        rec = svc.query_snapshot(rid)
        if rec and rec.get("status") not in ("submitted", "running"):
            return rec
        time.sleep(0.02)
    raise AssertionError(f"query {rid} never reached a terminal "
                         f"status: {svc.query_snapshot(rid)}")


def test_delete_running_query_bounded_latency(service):
    svc = service()
    svc.start()
    port = svc.port
    # chunked Q1 with a 10s interruptible slow fault mid-stream: the
    # uninterrupted run is >= 10s, so a < 3s cancel proves the DELETE
    # landed at a boundary (and the slow sleep woke on cancellation)
    status, body = _post_sql(port, {
        "sql": "select l_returnflag, sum(l_quantity) as s from "
               "lineitem group by l_returnflag",
        "mode": "async",
        "conf": {CHUNK_KEY: 512, BUDGET_KEY: 1,
                 INJECT_KEY: "stream_chunk:slow:2:10000"}})
    assert status == 202
    rid = body["query_id"]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        rec = svc.query_snapshot(rid)
        if rec.get("status") == "running":
            break
        time.sleep(0.01)
    time.sleep(0.2)  # let it get into the chunk loop / slow sleep
    t0 = time.perf_counter()
    code, resp = _http(port, "DELETE", f"/queries/{rid}")
    assert code == 200 and resp["status"] == "cancel_requested"
    rec = _poll_terminal(svc, rid, timeout_s=15)
    latency = time.perf_counter() - t0
    assert rec["status"] == "cancelled", rec
    assert rec["error"]["error"] == "QUERY_CANCELLED"
    assert latency < 3.0, f"cancel took {latency:.2f}s"
    _assert_no_prefetch_leak()
    assert svc.arbiter.stats()["leased_bytes"] == 0
    # cancelled status flows into the listing filter
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/queries?status=cancelled") as r:
        listing = json.loads(r.read())
    assert any(q["id"] == rid for q in listing["queries"])
    # cancel-after-finish: 409, structured
    code, resp = _http(port, "DELETE", f"/queries/{rid}")
    assert code == 409 and resp["error"] == "QUERY_FINISHED"
    # immediate clean re-run of the same query: parity with a direct
    # engine run (chaos disarmed via fresh conf override)
    status, body = _post_sql(port, {
        "sql": "select l_returnflag, sum(l_quantity) as s from "
               "lineitem group by l_returnflag",
        "conf": {INJECT_KEY: "", BUDGET_KEY: 0}})
    assert status == 200 and body["row_count"] >= 1


def test_delete_queued_async_never_executes(service):
    svc = service(**{MAXC_KEY: 1, QT_KEY: 60000})
    svc.start()
    port = svc.port
    # occupy the single slot with a slow query on session "a"
    status, body = _post_sql(port, {
        "sql": "select count(*) as n from lineitem",
        "session": "a", "mode": "async",
        "conf": {"spark_tpu.faults.inject": "stage_run:slow:1:2500"}})
    assert status == 202
    rid_a = body["query_id"]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if svc.query_snapshot(rid_a).get("status") == "running":
            break
        time.sleep(0.01)
    # a DIFFERENT session queues behind the slot
    status, body = _post_sql(port, {
        "sql": "select count(*) as n from orders",
        "session": "b", "mode": "async"})
    assert status == 202
    rid_b = body["query_id"]
    time.sleep(0.2)  # parked in the admission queue
    code, resp = _http(port, "DELETE", f"/queries/{rid_b}")
    assert code == 200
    rec_b = _poll_terminal(svc, rid_b, timeout_s=10)
    assert rec_b["status"] == "cancelled"
    assert "started_ts" not in rec_b  # never executed
    assert svc.metrics.counter("query_cancelled").value >= 1
    # slot math intact: the running query finishes, and a fresh
    # submission still admits + executes
    _poll_terminal(svc, rid_a, timeout_s=30)
    status, body = _post_sql(port, {
        "sql": "select count(*) as n from orders", "session": "b"})
    assert status == 200
    stats = svc.admission.stats()
    assert stats["running"] == 0 and stats["queued"] == 0


def test_delete_unknown_and_double_delete(service):
    svc = service()
    svc.start()
    port = svc.port
    # structured 404, same error shape as the admission bodies
    code, resp = _http(port, "DELETE", "/queries/q-999")
    assert code == 404
    assert resp["error"] == "NOT_FOUND" and "message" in resp
    assert resp["query_id"] == "q-999"
    # GET of an unknown id: structured too
    code, resp = _http(port, "GET", "/queries/q-999")
    assert code == 404 and resp["error"] == "NOT_FOUND"
    assert resp["query_id"] == "q-999"
    # double-DELETE while running is idempotent (two 200s)
    status, body = _post_sql(port, {
        "sql": "select count(*) as n from lineitem", "mode": "async",
        "conf": {"spark_tpu.faults.inject": "stage_run:slow:1:2577"}})
    rid = body["query_id"]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if svc.query_snapshot(rid).get("status") == "running":
            break
        time.sleep(0.01)
    code1, resp1 = _http(port, "DELETE", f"/queries/{rid}")
    code2, resp2 = _http(port, "DELETE", f"/queries/{rid}")
    assert code1 == 200
    assert code2 in (200, 409)  # 409 only if it already stopped
    rec = _poll_terminal(svc, rid, timeout_s=15)
    assert rec["status"] == "cancelled"


def test_service_deadline_in_admission_queue(service):
    svc = service(**{MAXC_KEY: 1, QT_KEY: 60000})
    svc.start()
    port = svc.port
    status, body = _post_sql(port, {
        "sql": "select count(*) as n from lineitem",
        "session": "a", "mode": "async",
        "conf": {"spark_tpu.faults.inject": "stage_run:slow:1:2654"}})
    rid_a = body["query_id"]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if svc.query_snapshot(rid_a).get("status") == "running":
            break
        time.sleep(0.01)
    # queued request with a 400ms end-to-end deadline: it must fail
    # with the DEADLINE error from inside the queue wait — not wait
    # out the 60s admission timeout
    t0 = time.perf_counter()
    status, resp = _post_sql(port, {
        "sql": "select count(*) as n from orders", "session": "b",
        "conf": {DEADLINE_KEY: 400.0}})
    assert status == 504, resp
    assert resp["error"] == "QUERY_DEADLINE_EXCEEDED"
    assert time.perf_counter() - t0 < 10.0
    _poll_terminal(svc, rid_a, timeout_s=30)


def test_session_quota_starvation(service):
    svc = service(**{SESSION_MAXC_KEY: 1, QT_KEY: 60000,
                     MAXC_KEY: 4})
    svc.start()
    port = svc.port
    # greedy session's first request occupies its quota slot
    status, body = _post_sql(port, {
        "sql": "select count(*) as n from lineitem",
        "session": "greedy", "mode": "async",
        "conf": {"spark_tpu.faults.inject": "stage_run:slow:1:2731"}})
    assert status == 202
    rid_1 = body["query_id"]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if svc.query_snapshot(rid_1).get("status") == "running":
            break
        time.sleep(0.01)
    # greedy's second request 429s with the structured quota error
    status, resp = _post_sql(port, {
        "sql": "select count(*) as n from orders",
        "session": "greedy"})
    assert status == 429, resp
    assert resp["error"] == "SESSION_QUOTA_EXCEEDED"
    assert svc.metrics.counter("session_quota_rejections").value >= 1
    # another session proceeds untouched
    status, body = _post_sql(port, {
        "sql": "select count(*) as n from orders", "session": "other"})
    assert status == 200
    # greedy frees its slot -> admitted again
    _poll_terminal(svc, rid_1, timeout_s=30)
    status, body = _post_sql(port, {
        "sql": "select count(*) as n from orders", "session": "greedy"})
    assert status == 200
    assert svc.session_quota.stats()["sessions_in_flight"] == {}


def test_cancel_matrix_service_async(service):
    """The service-async shape of the cancel matrix: cancellation via
    the cancel_point seam inside a service-run query — structured
    record status, drained arbiter, clean re-run parity over HTTP."""
    svc = service(**{"spark_tpu.service.hbmBudget": 1 << 30})
    svc.start()
    port = svc.port
    sql = ("select l_returnflag, sum(l_quantity) as s from lineitem "
           "group by l_returnflag")
    status, base = _post_sql(port, {
        "sql": sql, "conf": {CHUNK_KEY: 512}})
    assert status == 200
    cancelled = 0
    for n in (1, 2, 4, 7, 11):
        status, body = _post_sql(port, {
            "sql": sql, "mode": "async",
            "conf": {CHUNK_KEY: 512,
                     INJECT_KEY: f"cancel_point:cancel:{n}"}})
        assert status == 202
        rec = _poll_terminal(svc, body["query_id"], timeout_s=60)
        if rec["status"] == "cancelled":
            cancelled += 1
            assert rec["error"]["error"] == "QUERY_CANCELLED"
            assert svc.arbiter.stats()["leased_bytes"] == 0
            assert svc.arbiter.stats()["owners"] == 0
            _assert_no_prefetch_leak()
        else:
            assert rec["status"] == "ok"
        # immediate clean re-run, chaos disarmed: same rows
        status, again = _post_sql(port, {
            "sql": sql, "conf": {CHUNK_KEY: 512, INJECT_KEY: ""}})
        assert status == 200
        assert again["rows"] == base["rows"]
    assert cancelled >= 1
    # lifecycle counters visible on /metrics
    from spark_tpu.observability.metrics import parse_prometheus_text
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics") as r:
        metrics = parse_prometheus_text(r.read().decode())
    assert metrics.get("spark_tpu_query_cancelled", 0) >= 1


# ---------------------------------------------------------------------------
# Dispatched-stage sync (executor._sync_dispatched): the dispatch gap
# ---------------------------------------------------------------------------


class _FakeDeviceArray:
    """Stand-in for a dispatched jax.Array. The 'device' finishes
    `ready_after_s` from now, or when the test sets `ends` (a stage
    that never ends by itself: the test sets it in its `finally`, so
    that no waiter thread outlives the test). is_ready() flips then
    and block_until_ready() returns then; __array__ lets
    jax.device_get materialize it. `fails` names the methods that
    raise a device error once the stage has ended."""

    def __init__(self, ready_after_s=0.0, ends=None, fails=()):
        import numpy as np
        self._value = np.zeros(2, dtype=np.int64)
        self.ready_ts = time.monotonic() + ready_after_s
        self._ends = ends
        self._fails = fails

    def is_ready(self):
        return time.monotonic() >= self.ready_ts or (
            self._ends is not None and self._ends.is_set())

    def block_until_ready(self):
        left = self.ready_ts - time.monotonic()
        if left > 0:
            if self._ends is not None:
                self._ends.wait(left)
            else:
                time.sleep(left)
        if "block_until_ready" in self._fails:
            raise RuntimeError("device error in block_until_ready")
        return self

    def __array__(self, dtype=None):
        if "__array__" in self._fails:
            raise RuntimeError("device error in __array__")
        return self._value


def _poll_conf(poll_ms):
    from spark_tpu.execution.executor import DISPATCH_POLL_KEY
    return Conf().set(DISPATCH_POLL_KEY, poll_ms)


def _assert_no_sync_waiter_leak():
    from spark_tpu.execution.executor import SYNC_WAITER_THREAD
    LockWatch().assert_no_thread_leak(prefix=SYNC_WAITER_THREAD)


def _SyncSpan():
    """What `_sync_dispatched` needs of the caller's span."""
    return types.SimpleNamespace(attrs={})


def test_dispatch_poll_cancel_lands_mid_stage():
    """Regression for the dispatch gap: with a never-ready output, a
    cancel must land within ~one poll tick instead of blocking in
    jax.device_get until the device finishes the stage."""
    from spark_tpu.execution.executor import _sync_dispatched
    conf = _poll_conf(20)
    tok = lifecycle.CancelToken()
    ctx = lifecycle.install(tok)
    ends = threading.Event()
    try:
        timer = threading.Timer(0.15, tok.cancel)
        timer.start()
        t0 = time.monotonic()
        with pytest.raises(lifecycle.QueryCancelledError):
            _sync_dispatched(
                {"flags": _FakeDeviceArray(3600, ends)}, conf)
        elapsed = time.monotonic() - t0
        assert elapsed < 2.0, f"cancel took {elapsed:.2f}s (gap back?)"
        timer.cancel()
    finally:
        ends.set()
        lifecycle.uninstall(ctx)
    _assert_no_sync_waiter_leak()


def test_dispatch_poll_deadline_lands_mid_stage():
    from spark_tpu.execution.executor import _sync_dispatched
    conf = _poll_conf(20)
    tok = lifecycle.CancelToken(deadline_ms=150)
    ctx = lifecycle.install(tok)
    ends = threading.Event()
    try:
        t0 = time.monotonic()
        with pytest.raises(lifecycle.QueryDeadlineError):
            _sync_dispatched([_FakeDeviceArray(3600, ends)], conf)
        assert time.monotonic() - t0 < 2.0
    finally:
        ends.set()
        lifecycle.uninstall(ctx)
    _assert_no_sync_waiter_leak()


def test_dispatch_poll_returns_when_ready():
    """The wait ends on readiness and returns device_get's result;
    arrays without is_ready (host values) never stall it."""
    from spark_tpu.execution.executor import _sync_dispatched
    import numpy as np
    conf = _poll_conf(20)
    tok = lifecycle.CancelToken()
    ctx = lifecycle.install(tok)
    try:
        out = _sync_dispatched(
            {"a": _FakeDeviceArray(ready_after_s=0.1), "b": 7}, conf)
        assert np.array_equal(out["a"], np.zeros(2, dtype=np.int64))
        assert out["b"] == 7
    finally:
        lifecycle.uninstall(ctx)
    _assert_no_sync_waiter_leak()


def test_dispatch_poll_disabled_blocks_straight_through():
    """dispatchPollMs=0 (and no token) short-circuits to the plain
    blocking device_get — the pre-existing fast path."""
    from spark_tpu.execution.executor import _sync_dispatched
    import numpy as np
    out = _sync_dispatched([_FakeDeviceArray()], _poll_conf(0))
    assert np.array_equal(out[0], np.zeros(2, dtype=np.int64))


def _median_lateness_ms(stage_ms, conf, n=7):
    """Median over `n` syncs of how long after its stage ended
    `_sync_dispatched` returned."""
    import statistics
    from spark_tpu.execution.executor import _sync_dispatched
    late = []
    for _ in range(n):
        arr = _FakeDeviceArray(ready_after_s=stage_ms / 1e3)
        span = _SyncSpan()
        _sync_dispatched([arr], conf, span)
        late.append((time.monotonic() - arr.ready_ts) * 1e3)
        assert span.attrs["waited"] == 1
        # a slice is 25 ms: none passes in 10 ms, 2 in 60, 7-8 in 200
        # (fewer where a loaded host wakes the slices late)
        slices = stage_ms // 25
        assert max(0, slices - 2) <= span.attrs["ticks"] <= slices, \
            span.attrs
    return statistics.median(late)


@pytest.mark.parametrize("stage_ms,parent_tick_ms",
                         [(10, 8), (60, 25), (200, 25)])
def test_dispatch_sync_found_ready_when_ready(stage_ms, parent_tick_ms):
    """The sync waits for the device and not for a clock: it returns a
    thread's wake-up after the stage ends, and how late does not grow
    with the stage. `parent_tick_ms` is the tick that the ramp this
    replaced (1, 2, 4, 8, 16, 25, 25.. ms) was sleeping when a stage
    of that length ended; it found the stage half of it late on
    average and up to all of it."""
    conf = _poll_conf(25)
    ctx = lifecycle.install(lifecycle.CancelToken())
    try:
        late = _median_lateness_ms(stage_ms, conf)
        short = late if stage_ms == 10 else _median_lateness_ms(10, conf)
    finally:
        lifecycle.uninstall(ctx)
    assert late < parent_tick_ms / 3, (late, stage_ms)
    assert late - short < 3.0, (late, short, stage_ms)
    _assert_no_sync_waiter_leak()


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_dispatch_sync_stop_lands_within_a_slice(how):
    """A cancel and a blown deadline 50 ms into a stage that never
    ends raise their structured errors from the sync within
    dispatchPollMs (plus the host's slack), and `ticks` counted the
    slices slept through until then."""
    from spark_tpu.execution.executor import _sync_dispatched
    poll_ms, at_ms, slack_ms = 25, 50, 100
    tok = lifecycle.CancelToken(
        deadline_ms=at_ms if how == "deadline" else None)
    error = (lifecycle.QueryDeadlineError if how == "deadline"
             else lifecycle.QueryCancelledError)
    ctx = lifecycle.install(tok)
    ends = threading.Event()
    span = _SyncSpan()
    timer = threading.Timer(at_ms / 1e3, tok.cancel)
    try:
        if how == "cancel":
            timer.start()
        t0 = time.monotonic()
        with pytest.raises(error):
            _sync_dispatched([_FakeDeviceArray(3600, ends)],
                             _poll_conf(poll_ms), span)
        late_ms = (time.monotonic() - t0) * 1e3 - at_ms
    finally:
        timer.cancel()
        ends.set()
        lifecycle.uninstall(ctx)
    assert -1.0 <= late_ms < poll_ms + slack_ms, late_ms
    # the slices before the stop: at 25 ms, and at 50 where the
    # slice's end wins the race with the stop
    assert span.attrs["waited"] == 1
    assert 1 <= span.attrs["ticks"] <= 2 + slack_ms // poll_ms, span.attrs
    _assert_no_sync_waiter_leak()


@pytest.mark.parametrize("fails", [("block_until_ready", "__array__"),
                                   ("__array__",)])
def test_dispatch_sync_device_error_on_query_thread(fails):
    """The waiter thread only waits: what its block_until_ready raises
    is swallowed there, and the device's error reaches the caller from
    `_sync_dispatched` itself, out of the pull on the query's thread
    (where `dispatch.sync` and _execute_recover see it)."""
    from spark_tpu.execution.executor import _sync_dispatched
    ctx = lifecycle.install(lifecycle.CancelToken())
    try:
        with pytest.raises(RuntimeError, match="error in __array__"):
            _sync_dispatched(
                [_FakeDeviceArray(ready_after_s=0.03, fails=fails)],
                _poll_conf(25))
    finally:
        lifecycle.uninstall(ctx)
    _assert_no_sync_waiter_leak()


def test_dispatch_sync_ready_stage_starts_nothing(monkeypatch):
    """A stage that is ready at the call is pulled at once: no waiter
    thread, no wait and no tick counted."""
    from spark_tpu.execution import executor
    import numpy as np

    def no_thread(*a, **kw):
        raise AssertionError("a ready stage started a waiter thread")
    ctx = lifecycle.install(lifecycle.CancelToken())
    monkeypatch.setattr(executor.threading, "Thread", no_thread)
    span = _SyncSpan()
    try:
        out = executor._sync_dispatched(
            {"a": _FakeDeviceArray(), "b": 7}, _poll_conf(25), span)
    finally:
        lifecycle.uninstall(ctx)
    assert np.array_equal(out["a"], np.zeros(2, dtype=np.int64))
    assert span.attrs == {"ticks": 0, "waited": 0}


@pytest.mark.parametrize("first", ["cancel", "wait"])
def test_cancel_token_wakes_a_wait_parked_elsewhere(first):
    """`wait(on=event)`: a cancel ends a wait that is parked on the
    caller's event, whichever of the two came first, and the wait
    raises; it returns True where someone else set the event, False
    where the slice passed."""
    tok = lifecycle.CancelToken()
    ev = threading.Event()
    if first == "cancel":
        tok.cancel()
    else:
        assert tok.wait(0.001, on=ev) is False
        threading.Timer(0.02, tok.cancel).start()
    t0 = time.monotonic()
    with pytest.raises(lifecycle.QueryCancelledError):
        tok.wait(5.0, on=ev)
    assert time.monotonic() - t0 < 2.0
    assert tok._wakers == []
    other = lifecycle.CancelToken(deadline_ms=60_000)
    done = threading.Event()
    threading.Timer(0.02, done.set).start()
    assert other.wait(5.0, on=done) is True


def test_dispatch_gap_regression_slow_stage_cancel(service):
    """End-to-end: a slow-stage fault holds the dispatched stage on
    device; DELETE /queries/<id> during the stall must cancel the
    query promptly (structured QUERY_CANCELLED) instead of waiting
    out the stage."""
    svc = service().start()
    rec = svc.submit_async(
        "SELECT l_orderkey FROM lineitem LIMIT 4",
        conf={INJECT_KEY: "stage_run:slow:1:5000"})
    qid = rec["id"]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        r = svc.get_query(qid)
        if r and r["status"] == "running":
            break
        time.sleep(0.01)
    assert svc.cancel_query(qid), "cancel not delivered"
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        r = svc.get_query(qid)
        if r["status"] not in ("submitted", "running"):
            break
        time.sleep(0.02)
    assert r["status"] in ("cancelled", "ok"), r
    if r["status"] == "cancelled":
        assert r["error"]["error"] == "QUERY_CANCELLED"
    _assert_no_prefetch_leak()
