"""Chaos matrix for the crash-only serving fleet (service/fleet.py).

kill -9 mid-query x {sync, async, streaming-trigger} x {affinity
re-home, flap-breaker quarantine, SIGTERM drain}, plus the worker
lifecycle satellites: /healthz liveness/readiness split, signal-safe
idempotent stop, drain shedding. Every fleet cell asserts structured
errors (WORKER_LOST / FLEET_UNAVAILABLE / FLEET_DRAINING) or byte
parity, zero orphaned worker processes, zero leaked fleet threads,
and the fleet back at full strength after recovery.

Workers are REAL subprocesses (python -m spark_tpu.service.fleet
--worker); the supervisor runs in-process so tests can reach its ring
and worker table directly. Session init ships as a tmp-dir module on
PYTHONPATH (subprocesses can't inherit lambdas)."""

import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import pandas as pd
import pytest

from spark_tpu import Conf
from spark_tpu.execution import lifecycle
from spark_tpu.observability.metrics import parse_prometheus_text
from spark_tpu.service.admission import ServiceDraining
from spark_tpu.service.fleet import (FleetSupervisor, _is_read,
                                     _merge_prometheus)
from spark_tpu.service.server import SqlService
from spark_tpu.testing.lockwatch import LockWatch
from spark_tpu.tpch import golden as G
from spark_tpu.tpch import queries as Q
from spark_tpu.tpch import sql_queries as SQLQ
from spark_tpu.tpch.datagen import write_parquet

SF = 0.002
WORKERS_KEY = "spark_tpu.service.fleet.workers"
RESTART_MAX_KEY = "spark_tpu.service.fleet.restartMaxPerWindow"
RESTART_WINDOW_KEY = "spark_tpu.service.fleet.restartWindowMs"
RESTART_BACKOFF_KEY = "spark_tpu.service.fleet.restartBackoffMs"
DRAIN_TIMEOUT_KEY = "spark_tpu.service.fleet.drainTimeoutMs"
HEALTH_INTERVAL_KEY = "spark_tpu.service.fleet.healthIntervalMs"
FLEET_DIR_KEY = "spark_tpu.service.fleet.dir"
INIT_KEY = "spark_tpu.service.fleet.init"
PORT_KEY = "spark_tpu.service.port"
WAREHOUSE_KEY = "spark_tpu.sql.warehouse.dir"
CC_ENABLED_KEY = "spark_tpu.sql.compileCache.enabled"
CC_DIR_KEY = "spark_tpu.sql.compileCache.dir"
CC_WARM_KEY = "spark_tpu.sql.compileCache.warmStart"
INJECT_KEY = "spark_tpu.faults.inject"

TPCH_INIT_SRC = """\
import spark_tpu.tpch.queries as Q
PATH = {path!r}
def init(session):
    Q.register_tables(session, PATH)
"""

STREAM_INIT_SRC = """\
import tempfile
import numpy as np
import pandas as pd
from spark_tpu.streaming import MemoryStream
def init(session):
    src = MemoryStream(session, pd.DataFrame(
        {"k": pd.Series([], dtype=np.int64),
         "v": pd.Series([], dtype=np.int64)}))
    ck = tempfile.mkdtemp(prefix="fleet-stream-ck-")
    q = src.to_df().write_stream(ck, output_mode="append")
    q.start(trigger_ms=200)
"""


# -- HTTP helpers -----------------------------------------------------------


def _req(port, method, path, body=None, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"}, method=method)
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, dict(r.headers), json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read() or b"{}")


def _post_sql(port, sql, session="default", conf=None, mode=None,
              timeout=120):
    body = {"sql": sql, "session": session}
    if conf:
        body["conf"] = conf
    if mode:
        body["mode"] = mode
    return _req(port, "POST", "/sql", body, timeout=timeout)


def _assert_pid_dead(pid, timeout_s=15.0):
    """The crash-only invariant: killed/stopped workers are REAPED —
    no zombie, no orphan."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return
        time.sleep(0.05)
    raise AssertionError(f"worker pid {pid} still alive (orphan)")


def _wait(cond, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


def _running_on_fleet(port, qid):
    st, _, listing = _req(port, "GET", "/queries")
    return st == 200 and any(
        q.get("id") == qid and q.get("status") == "running"
        for q in listing.get("queries", []))


# -- fixtures ---------------------------------------------------------------


@pytest.fixture(scope="module")
def tpch_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tpch_fleet") / "sf_small")
    write_parquet(path, SF)
    return path


@pytest.fixture(scope="module")
def init_dir(tmp_path_factory, tpch_path):
    """Tmp dir on the workers' PYTHONPATH holding the init modules."""
    d = tmp_path_factory.mktemp("fleet_init")
    (d / "fleet_tpch_init.py").write_text(
        TPCH_INIT_SRC.format(path=tpch_path))
    (d / "fleet_stream_init.py").write_text(STREAM_INIT_SRC)
    prev = os.environ.get("PYTHONPATH")
    os.environ["PYTHONPATH"] = str(d) + (
        os.pathsep + prev if prev else "")
    yield str(d)
    if prev is None:
        os.environ.pop("PYTHONPATH", None)
    else:
        os.environ["PYTHONPATH"] = prev


def _fleet_conf(tmp_path_factory, workers, init_spec, **overrides):
    cache = tmp_path_factory.mktemp("fleet_cc")
    conf = (Conf()
            .set(PORT_KEY, 0)
            .set(WORKERS_KEY, workers)
            .set(HEALTH_INTERVAL_KEY, 100)
            .set(RESTART_BACKOFF_KEY, 100)
            .set(RESTART_MAX_KEY, 5)
            .set(RESTART_WINDOW_KEY, 60000)
            .set(DRAIN_TIMEOUT_KEY, 30000)
            .set(FLEET_DIR_KEY, str(tmp_path_factory.mktemp("fleet")))
            .set(WAREHOUSE_KEY,
                 str(tmp_path_factory.mktemp("fleet_wh")))
            .set(CC_ENABLED_KEY, True)
            .set(CC_DIR_KEY, str(cache))
            .set(CC_WARM_KEY, True))
    if init_spec:
        conf.set(INIT_KEY, init_spec)
    for k, v in overrides.items():
        conf.set(k, v)
    return conf


@pytest.fixture(scope="module")
def fleet(tmp_path_factory, init_dir):
    """One 2-worker fleet shared by the routing/failover cells; each
    kill cell restores full strength before finishing, and teardown
    asserts zero orphans + zero leaked fleet threads."""
    conf = _fleet_conf(tmp_path_factory, 2,
                       "fleet_tpch_init:init")
    sup = FleetSupervisor(conf).start()
    assert sup.wait_ready(180), sup.fleet_health()
    yield sup
    pids = sup.worker_pids()
    prefix = sup.thread_prefix
    sup.stop()
    for pid in pids:
        _assert_pid_dead(pid)
    LockWatch().assert_no_thread_leak(prefix, timeout_s=15)


# -- routing + parity -------------------------------------------------------


def test_router_parity_and_introspection(fleet, tpch_path):
    st, hdrs, resp = _post_sql(fleet.port, SQLQ.Q1, session="alpha")
    assert st == 200 and resp["status"] == "ok", resp
    # session affinity: the router picked the session's ring-home
    assert int(hdrs["X-Fleet-Worker"]) == fleet._route("alpha")[0]
    got = pd.DataFrame(resp["rows"], columns=resp["columns"])
    want = G.GOLDEN["q1"](tpch_path).reset_index(drop=True)
    G.compare(G.normalize_decimals(got)[list(want.columns)]
              .reset_index(drop=True), want)
    # same session routes to the same worker; the generation-prefixed
    # id routes GET /queries/<id> back to the owner without a table
    qid = resp["query_id"]
    assert qid.startswith(f"q-w{hdrs['X-Fleet-Worker']}g")
    st, hdrs2, rec = _req(fleet.port, "GET", f"/queries/{qid}")
    assert st == 200 and rec["status"] == "ok"
    assert hdrs2["X-Fleet-Worker"] == hdrs["X-Fleet-Worker"]
    # merged listing sees it; fleet health + metrics agree
    st, _, listing = _req(fleet.port, "GET", "/queries")
    assert st == 200 and any(q["id"] == qid
                             for q in listing["queries"])
    st, _, health = _req(fleet.port, "GET", "/healthz")
    assert st == 200 and health["workers_ready"] == 2
    prom = parse_prometheus_text(urllib.request.urlopen(
        f"http://127.0.0.1:{fleet.port}/metrics",
        timeout=30).read().decode())
    assert prom.get("spark_tpu_fleet_requests_proxied", 0) >= 1
    # a stale generation 503s structurally instead of 404-ing
    st, _, err = _req(fleet.port, "GET", "/queries/q-w0g999-1")
    assert st == 503 and err["error"] == "WORKER_LOST"


def test_metrics_fanout_merges_worker_series(fleet):
    """GET /metrics on the router merges the supervisor's fleet_*
    counters with every live worker's metrics, each worker's series
    tagged worker="<idx>" — one scrape covers the whole fleet and
    stays valid exposition (parseable, one # TYPE line per family)."""
    st, hdrs, resp = _post_sql(fleet.port, "SHOW TABLES",
                               session="metrics-fanout")
    assert st == 200, resp
    widx = hdrs["X-Fleet-Worker"]
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{fleet.port}/metrics",
        timeout=30).read().decode()
    prom = parse_prometheus_text(text)  # merged doc parses cleanly
    # supervisor's own series stay unlabeled...
    assert prom.get("spark_tpu_fleet_requests_proxied", 0) >= 1
    # ...and the worker that served the query shows up labeled
    assert prom.get(
        f'spark_tpu_service_admitted{{worker="{widx}"}}', 0) >= 1
    # TYPE lines dedup across sources: one per family name
    families = [ln.split()[2] for ln in text.splitlines()
                if ln.startswith("# TYPE ")]
    assert len(families) == len(set(families)), families


def test_sync_kill9_failover_parity_and_rehome(fleet, tpch_path):
    """Sync cell: kill -9 the session's home worker mid-query. The
    idempotent read retries ONCE on the re-homed worker with golden
    parity, and the session's DURABLE catalog state (a CTAS table in
    the shared warehouse dir) survives the crash — the re-homed
    worker reads the same bytes the dead worker wrote."""
    home = fleet._route("alpha")[0]
    pid = fleet._workers[home].snapshot()["pid"]
    # durable session state, written through the home worker
    st, _, resp = _post_sql(
        fleet.port,
        "CREATE TABLE fleet_scratch AS "
        "SELECT l_orderkey FROM lineitem LIMIT 1", session="alpha")
    assert st == 200, resp
    st, _, before = _post_sql(
        fleet.port, "SELECT l_orderkey FROM fleet_scratch",
        session="alpha")
    assert st == 200, before

    results = []

    def run():
        results.append(_post_sql(
            fleet.port, SQLQ.Q1, session="alpha",
            conf={INJECT_KEY: "stage_run:slow:1:2500"}))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    # kill once the query is observably in flight on the worker
    _wait(lambda: any(
        q.get("status") == "running" and q.get("session") == "alpha"
        for q in _req(fleet.port, "GET", "/queries")[2].get(
            "queries", [])), 30, "query in flight")
    os.kill(pid, signal.SIGKILL)
    t.join(120)
    assert results, "query thread wedged"
    st, hdrs, resp = results[0]
    assert st == 200 and resp["status"] == "ok", resp
    assert hdrs.get("X-Fleet-Failover") == "1"
    assert int(hdrs["X-Fleet-Worker"]) != home
    got = pd.DataFrame(resp["rows"], columns=resp["columns"])
    want = G.GOLDEN["q1"](tpch_path).reset_index(drop=True)
    G.compare(G.normalize_decimals(got)[list(want.columns)]
              .reset_index(drop=True), want)
    # durable state re-homed with the session: the new worker serves
    # the table the dead worker created, byte-for-byte
    st, _, after = _post_sql(
        fleet.port, "SELECT l_orderkey FROM fleet_scratch",
        session="alpha")
    assert st == 200 and after["rows"] == before["rows"], after
    # fleet back at full strength; the killed pid was reaped
    assert fleet.wait_ready(180), fleet.fleet_health()
    _assert_pid_dead(pid)
    respawn = fleet._workers[home].snapshot()
    assert respawn["generation"] >= 2
    # and the respawned worker serves Q1 from the shared persistent
    # compile cache (warm-started from it at spawn), not a recompile
    st, _, resp = _post_sql(respawn["port"], SQLQ.Q1, session="alpha2")
    assert st == 200, resp
    prom = parse_prometheus_text(urllib.request.urlopen(
        f"http://127.0.0.1:{respawn['port']}/metrics",
        timeout=30).read().decode())
    assert prom.get("spark_tpu_compile_cache_warm_entries", 0) >= 1, prom
    assert prom.get("spark_tpu_compile_cache_hits", 0) >= 1, prom
    assert prom.get("spark_tpu_compile_cache_misses", 0) == 0, prom
    assert prom.get("spark_tpu_compile_cache_disk_misses", 0) == 0, prom


def test_async_kill9_worker_lost_structured(fleet):
    """Async cell: the submitted query's record dies with its worker.
    GET/DELETE on its id answer 503 WORKER_LOST (broken worker first,
    stale generation after the respawn) — never a 404, never a hang."""
    session = "beta"
    st, hdrs, resp = _post_sql(
        fleet.port, SQLQ.Q1, session=session, mode="async",
        conf={INJECT_KEY: "stage_run:slow:1:3000"})
    assert st == 202, resp
    qid = resp["query_id"]
    owner = int(hdrs["X-Fleet-Worker"])
    pid = fleet._workers[owner].snapshot()["pid"]
    _wait(lambda: _running_on_fleet(fleet.port, qid), 30,
          "async query running")
    os.kill(pid, signal.SIGKILL)
    st, _, err = _req(fleet.port, "GET", f"/queries/{qid}")
    assert st == 503 and err["error"] == "WORKER_LOST", err
    assert err["query_id"] == qid and err["worker"] == owner
    st, _, err = _req(fleet.port, "DELETE", f"/queries/{qid}")
    assert st == 503 and err["error"] == "WORKER_LOST", err
    # after the respawn the generation moved on: still WORKER_LOST
    assert fleet.wait_ready(180), fleet.fleet_health()
    st, _, err = _req(fleet.port, "GET", f"/queries/{qid}")
    assert st == 503 and err["error"] == "WORKER_LOST", err
    _assert_pid_dead(pid)


# -- streaming-trigger cell -------------------------------------------------


def test_streaming_trigger_kill9_rehome(tmp_path_factory, init_dir):
    """Streaming cell: a worker with a live supervised trigger loop is
    kill -9'd. The loop is in-memory worker state — it vanishes from
    the merged listing, the fleet sheds structurally while down, and
    the respawned worker's session init starts a FRESH loop."""
    conf = _fleet_conf(tmp_path_factory, 1, "fleet_stream_init:init")
    sup = FleetSupervisor(conf).start()
    try:
        assert sup.wait_ready(180), sup.fleet_health()
        st, _, resp = _post_sql(sup.port, "SHOW TABLES",
                                session="gamma")
        assert st == 200, resp
        _wait(lambda: _req(sup.port, "GET", "/queries")[2].get(
            "streams"), 30, "live trigger loop in merged listing")
        pid = sup._workers[0].snapshot()["pid"]
        os.kill(pid, signal.SIGKILL)
        # single worker down: structured shed, streams gone
        st, _, err = _post_sql(sup.port, "SHOW TABLES",
                               session="gamma")
        assert st == 503, err
        assert err["error"] in ("WORKER_LOST", "FLEET_UNAVAILABLE")
        _assert_pid_dead(pid)
        # crash-only recovery: respawn, re-init, fresh loop
        assert sup.wait_ready(180), sup.fleet_health()
        st, _, resp = _post_sql(sup.port, "SHOW TABLES",
                                session="gamma")
        assert st == 200, resp
        _wait(lambda: _req(sup.port, "GET", "/queries")[2].get(
            "streams"), 30, "respawned trigger loop")
        assert sup._workers[0].snapshot()["generation"] >= 2
    finally:
        pids = sup.worker_pids()
        prefix = sup.thread_prefix
        sup.stop()
        for p in pids:
            _assert_pid_dead(p)
        LockWatch().assert_no_thread_leak(prefix, timeout_s=15)


# -- flap breaker -----------------------------------------------------------


def test_flap_breaker_quarantine_and_shed(tmp_path_factory, init_dir):
    """A deterministic boot failure (unimportable init module) crashes
    the worker every spawn: after restartMaxPerWindow crashes inside
    the window the breaker QUARANTINES the slot instead of respawn-
    storming, traffic sheds with structured 503s, and every death
    left a flight bundle."""
    conf = _fleet_conf(tmp_path_factory, 1,
                       "fleet_no_such_module_xyz:init",
                       **{RESTART_MAX_KEY: 2,
                          RESTART_BACKOFF_KEY: 50})
    sup = FleetSupervisor(conf).start()
    try:
        _wait(lambda: sup._workers[0].snapshot()["state"]
              == "quarantined", 120, "flap-breaker quarantine")
        st, _, err = _post_sql(sup.port, "SHOW TABLES")
        assert st == 503 and err["error"] == "FLEET_UNAVAILABLE", err
        st, _, health = _req(sup.port, "GET", "/healthz")
        assert st == 503 and health["status"] == "degraded"
        prom = parse_prometheus_text(urllib.request.urlopen(
            f"http://127.0.0.1:{sup.port}/metrics",
            timeout=30).read().decode())
        assert prom.get("spark_tpu_fleet_worker_lost", 0) >= 2
        assert prom.get("spark_tpu_fleet_quarantined", 0) >= 1
        bundles_dir = os.path.join(
            str(conf.get(FLEET_DIR_KEY)), "bundles")
        bundles = sorted(os.listdir(bundles_dir))
        assert len(bundles) >= 2, bundles
        manifest = json.load(open(os.path.join(
            bundles_dir, bundles[0], "MANIFEST.json")))
        assert manifest["worker"] == 0 and manifest["reason"]
        stderr_txt = open(os.path.join(
            bundles_dir, bundles[0], "stderr.txt")).read()
        assert "fleet_no_such_module_xyz" in stderr_txt
    finally:
        prefix = sup.thread_prefix
        sup.stop()
        LockWatch().assert_no_thread_leak(prefix, timeout_s=15)


# -- drain ------------------------------------------------------------------


def test_drain_finishes_inflight_sheds_new(tmp_path_factory,
                                           init_dir):
    """Drain cell: shutdown() mid-query stops admitting (structured
    FLEET_DRAINING), lets the in-flight query finish with its result
    intact, SIGTERMs the worker through its own drain path (exit 0),
    and leaves zero orphans and zero fleet threads."""
    conf = _fleet_conf(tmp_path_factory, 1, "fleet_tpch_init:init")
    sup = FleetSupervisor(conf).start()
    stopped = False
    try:
        assert sup.wait_ready(180), sup.fleet_health()
        pid = sup._workers[0].snapshot()["pid"]
        results, shut = [], []

        def run():
            results.append(_post_sql(
                sup.port, SQLQ.Q1, session="delta",
                conf={INJECT_KEY: "stage_run:slow:1:2000"}))

        t = threading.Thread(target=run, daemon=True)
        t.start()
        _wait(lambda: any(
            q.get("status") == "running"
            for q in _req(sup.port, "GET", "/queries")[2].get(
                "queries", [])), 60, "query in flight")
        ts = threading.Thread(target=lambda: shut.append(
            sup.shutdown()), daemon=True)
        ts.start()
        # draining: the front door sheds IMMEDIATELY and structurally
        _wait(lambda: _post_sql(sup.port, "SHOW TABLES",
                                timeout=10)[2].get("error")
              == "FLEET_DRAINING", 10, "drain shed")
        t.join(120)
        ts.join(120)
        assert not ts.is_alive() and shut == [True], shut
        st, _, resp = results[0]
        # zero dropped in-flight: the query that was running when the
        # drain began completed normally
        assert st == 200 and resp["status"] == "ok", resp
        _assert_pid_dead(pid)
        assert sup.wait_for_shutdown(1)
        stopped = True
        LockWatch().assert_no_thread_leak(sup.thread_prefix,
                                          timeout_s=15)
    finally:
        if not stopped:
            sup.stop()


# -- worker lifecycle satellites (in-process SqlService) --------------------


@pytest.fixture()
def svc_conf(tmp_path):
    def make(**overrides):
        conf = Conf().set(PORT_KEY, 0)
        for k, v in overrides.items():
            conf.set(k, v)
        return conf
    return make


def _warm_gate(monkeypatch):
    """Replace the warm-start replay with an Event-gated stub so tests
    can hold a service in live-but-not-ready deterministically."""
    from spark_tpu.execution import compile_cache as CC
    gate = threading.Event()

    def slow_warm(stage_cache, conf, metrics):
        gate.wait(10)
        return 0

    monkeypatch.setattr(CC, "warm_start", slow_warm)
    return gate


def test_healthz_liveness_readiness_split(svc_conf, tmp_path,
                                          monkeypatch):
    gate = _warm_gate(monkeypatch)
    conf = svc_conf(**{CC_ENABLED_KEY: True,
                       CC_DIR_KEY: str(tmp_path / "cc"),
                       CC_WARM_KEY: True})
    svc = SqlService(conf).start()
    try:
        # live-but-NOT-ready while the manifest replays
        st, _, live = _req(svc.port, "GET", "/healthz/live")
        assert st == 200 and live["live"] and not live["ready"]
        st, _, ready = _req(svc.port, "GET", "/healthz/ready")
        assert st == 503 and ready["error"] == "NOT_READY", ready
        st, _, health = _req(svc.port, "GET", "/healthz")
        assert st == 200 and health["ready"] is False
        gate.set()
        _wait(lambda: _req(svc.port, "GET",
                           "/healthz/ready")[0] == 200, 15,
              "readiness flip after warm start")
    finally:
        gate.set()
        svc.stop()


def test_stop_idempotent_and_concurrent(svc_conf):
    """Double-stop / stop-racing-shutdown never deadlocks: every
    caller returns inside the bounded joins."""
    svc = SqlService(svc_conf()).start()
    threads = [threading.Thread(target=svc.stop, daemon=True)
               for _ in range(2)]
    threads.append(threading.Thread(target=svc.shutdown, daemon=True))
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads), "stop deadlocked"
    svc.stop()  # and once more, after the fact
    assert svc.wait_for_shutdown(1)


def test_stop_during_warm_start_no_deadlock(svc_conf, tmp_path,
                                            monkeypatch):
    gate = _warm_gate(monkeypatch)
    conf = svc_conf(**{CC_ENABLED_KEY: True,
                       CC_DIR_KEY: str(tmp_path / "cc"),
                       CC_WARM_KEY: True})
    svc = SqlService(conf).start()
    t0 = time.monotonic()
    stopper = threading.Thread(target=svc.stop, daemon=True)
    stopper.start()
    time.sleep(0.2)
    gate.set()  # replay finishes under a concurrent stop
    stopper.join(45)
    assert not stopper.is_alive(), "stop wedged on the warm thread"
    assert time.monotonic() - t0 < 40
    assert svc.ready  # the finally-set readiness flag still flipped


def test_sigterm_runs_drain_path(svc_conf):
    """SIGTERM lands in the installed handler, drains and stops the
    service from a normal thread, and unblocks wait_for_shutdown —
    what a fleet worker does when its supervisor terminates it."""
    saved = {s: signal.getsignal(s)
             for s in (signal.SIGTERM, signal.SIGINT)}
    svc = SqlService(svc_conf()).start()
    try:
        svc.install_signal_handlers()
        os.kill(os.getpid(), signal.SIGTERM)
        assert svc.wait_for_shutdown(30), "handler never fired"
        _wait(lambda: svc._stopped, 30, "signal-driven stop")
        with pytest.raises(ServiceDraining):
            svc.submit("SHOW TABLES")
    finally:
        for s, h in saved.items():
            signal.signal(s, h)
        svc.stop()


def test_sigterm_drains_inflight_async_query(svc_conf, tpch_path):
    """Regression: the SIGTERM handler must NOT set the shutdown
    event itself — a worker main parked on wait_for_shutdown() would
    wake, call stop() and exit while an in-flight ASYNC query (which
    the router's in-flight count never sees) was still running,
    silently skipping the bounded-drain guarantee. The event may only
    fire once drain+stop completed, with the async query finished
    inside drainTimeoutMs."""
    saved = {s: signal.getsignal(s)
             for s in (signal.SIGTERM, signal.SIGINT)}
    conf = svc_conf(**{DRAIN_TIMEOUT_KEY: 30000})
    svc = SqlService(
        conf, init_session=lambda s: Q.register_tables(s, tpch_path))
    svc.start()
    try:
        svc.install_signal_handlers()
        st, _, resp = _post_sql(
            svc.port, "select count(*) as n from lineitem",
            mode="async",
            conf={INJECT_KEY: "stage_run:slow:1:2500"})
        assert st == 202, resp
        rid = resp["query_id"]
        _wait(lambda: svc.query_snapshot(rid).get("status")
              == "running", 60, "async query in flight")
        t0 = time.monotonic()
        os.kill(os.getpid(), signal.SIGTERM)
        # parked exactly like _worker_main: waking implies the drain
        # already ran and stop() tore the service down
        assert svc.wait_for_shutdown(60), "drain+stop never finished"
        assert (time.monotonic() - t0) * 1e3 <= 30000
        assert svc._stopped, "event fired before stop() completed"
        rec = svc.query_snapshot(rid)
        assert rec["status"] == "ok", (
            f"in-flight async query dropped by early exit: {rec}")
        with svc._async_lock:
            assert svc._async_inflight == 0
    finally:
        for s, h in saved.items():
            signal.signal(s, h)
        svc.stop()


def test_drain_sheds_structured_and_is_idempotent(svc_conf):
    svc = SqlService(svc_conf()).start()
    try:
        assert svc.drain(timeout_ms=2000) is True
        assert svc.drain(timeout_ms=2000) is True  # idempotent
        with pytest.raises(ServiceDraining) as exc:
            svc.submit("SHOW TABLES")
        err = exc.value.to_dict()
        assert err["error"] == "SERVICE_DRAINING"
        assert exc.value.http_status == 503
    finally:
        svc.stop()


# -- exposition merge (unit) ------------------------------------------------


def test_merge_prometheus_labels_and_dedups():
    sup = "# TYPE spark_tpu_fleet_x counter\nspark_tpu_fleet_x 2\n"
    w0 = ("# TYPE spark_tpu_service_admitted counter\n"
          "spark_tpu_service_admitted 3\n"
          "# TYPE spark_tpu_h histogram\n"
          'spark_tpu_h_bucket{le="1"} 1\n')
    w1 = ("# TYPE spark_tpu_service_admitted counter\n"
          "spark_tpu_service_admitted 5\n")
    text = _merge_prometheus([(None, sup), ("0", w0), ("1", w1)])
    prom = parse_prometheus_text(text)
    assert prom["spark_tpu_fleet_x"] == 2
    # same family from two workers: one TYPE line, two labeled series
    assert prom['spark_tpu_service_admitted{worker="0"}'] == 3
    assert prom['spark_tpu_service_admitted{worker="1"}'] == 5
    # worker label lands FIRST inside an existing label set
    assert prom['spark_tpu_h_bucket{worker="0",le="1"}'] == 1
    families = [ln.split()[2] for ln in text.splitlines()
                if ln.startswith("# TYPE ")]
    assert len(families) == len(set(families)), families


# -- read classifier --------------------------------------------------------


def test_is_read_classifier():
    assert _is_read("SELECT 1 FROM t")
    assert _is_read("  -- comment\n  select x from t")
    assert _is_read("WITH c AS (SELECT 1 FROM t) SELECT * FROM c")
    assert _is_read("SHOW TABLES")
    assert _is_read("DESCRIBE t")
    assert not _is_read("CREATE TABLE t AS SELECT 1 FROM s")
    assert not _is_read("INSERT INTO t VALUES (1)")
    assert not _is_read("DROP TABLE t")
    assert not _is_read("")
    assert not _is_read("-- only a comment")
