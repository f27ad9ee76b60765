"""Status store, latency histograms/SLO, and the flight recorder:
Histogram metric semantics + Prometheus round-trip, the listener-fed
status store (fold-in, ring bounds, heartbeat lifecycle), latency/SLO
burn accounting at query end, crash-time flight-recorder bundles
(injected fatal + on-demand), live `/status` + `/status/timeseries`
under a concurrent service with lockwatch, and the offline replay
views (history.status_summary, events_tool stats)."""

import glob
import json
import os
import threading

import pandas as pd
import pytest

from spark_tpu import Conf, history
from spark_tpu import functions as F
from spark_tpu.functions import col
from spark_tpu.observability.flight_recorder import FlightRecorder
from spark_tpu.observability.metrics import (Histogram, MetricsRegistry,
                                             parse_prometheus_text,
                                             prometheus_text)
from spark_tpu.observability.status_store import StatusStore
from spark_tpu.testing import faults
from spark_tpu.testing.lockwatch import LockWatch

EVENT_KEY = "spark_tpu.sql.eventLog.dir"
SLO_KEY = "spark_tpu.service.slo.latencyMs"
STATUS_RING_KEY = "spark_tpu.sql.status.ringSize"
HEARTBEAT_KEY = "spark_tpu.sql.status.heartbeatMs"
STATUS_ON_KEY = "spark_tpu.sql.status.enabled"
FR_ON_KEY = "spark_tpu.sql.flightRecorder.enabled"
FR_DIR_KEY = "spark_tpu.sql.flightRecorder.dir"
FR_RING_KEY = "spark_tpu.sql.flightRecorder.ringSize"


def _fresh_agg(session, n):
    """A plan unlikely to be stage-cached already (n varies per test)."""
    return (session.range(n)
            .group_by((col("id") % 7).alias("k"))
            .agg(F.sum(col("id")).alias("s")))


# -- Histogram metric type ---------------------------------------------------

def test_histogram_counts_sum_and_percentiles():
    h = Histogram()
    for v in (1.0, 2.0, 4.0, 8.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(115.0)
    assert snap["min"] == 1.0 and snap["max"] == 100.0
    # one slot per bound + overflow, totals preserved
    assert len(snap["counts"]) == len(snap["bounds"]) + 1
    assert sum(snap["counts"]) == 5
    p = h.percentiles()
    assert p["p50"] <= p["p95"] <= p["p99"]
    # quantile estimates are clamped to the observed range
    assert snap["min"] <= p["p50"] and p["p99"] <= snap["max"]


def test_histogram_empty_and_overflow():
    h = Histogram()
    assert h.quantile(0.99) == 0.0
    assert h.snapshot()["min"] == 0.0
    big = Histogram.DEFAULT_BOUNDS[-1] * 4  # beyond the last bound
    h.observe(big)
    snap = h.snapshot()
    assert snap["counts"][-1] == 1  # overflow bucket
    assert h.quantile(0.99) == big  # clamped to max_v, not a bound


def test_histogram_concurrent_observe():
    h = Histogram()

    def hammer():
        for i in range(500):
            h.observe(float(i % 32) + 0.5)

    ts = [threading.Thread(target=hammer) for _ in range(4)]
    [t.start() for t in ts]
    [t.join(30) for t in ts]
    snap = h.snapshot()
    assert snap["count"] == 2000
    assert sum(snap["counts"]) == 2000


# -- Prometheus exposition round-trip ----------------------------------------

def test_prometheus_histogram_round_trip():
    reg = MetricsRegistry()
    reg.counter("status_heartbeats").inc(3)
    reg.gauge("status_queries_inflight").set(2)
    h = reg.histogram("status_latency_ms")
    for v in (0.5, 3.0, 3.0, 900.0):
        h.observe(v)
    text = prometheus_text(reg.snapshot())
    parsed = parse_prometheus_text(text)  # every line must round-trip
    assert parsed["spark_tpu_status_heartbeats"] == 3
    assert parsed["spark_tpu_status_queries_inflight"] == 2
    assert parsed["spark_tpu_status_latency_ms_count"] == 4
    assert parsed["spark_tpu_status_latency_ms_sum"] == \
        pytest.approx(906.5)
    buckets = {k: v for k, v in parsed.items()
               if k.startswith("spark_tpu_status_latency_ms_bucket")}
    assert buckets['spark_tpu_status_latency_ms_bucket{le="+Inf"}'] == 4
    # cumulative and monotone in bound order
    assert buckets['spark_tpu_status_latency_ms_bucket{le="0.5"}'] == 1
    assert buckets['spark_tpu_status_latency_ms_bucket{le="4"}'] == 3
    ordered = [buckets[f'spark_tpu_status_latency_ms_bucket{{le="{b:g}"}}']
               for b in Histogram.DEFAULT_BOUNDS]
    assert ordered == sorted(ordered)


def test_prometheus_timer_summary_round_trip():
    reg = MetricsRegistry()
    t = reg.timer("udf_exec_ms")
    t.observe(0.25)
    t.observe(0.75)
    parsed = parse_prometheus_text(prometheus_text(reg.snapshot()))
    assert parsed["spark_tpu_udf_exec_ms_seconds_count"] == 2
    assert parsed["spark_tpu_udf_exec_ms_seconds_sum"] == \
        pytest.approx(1.0)
    # legacy pair still present for existing scrapers
    assert parsed["spark_tpu_udf_exec_ms_count"] == 2
    assert parsed["spark_tpu_udf_exec_ms_seconds_total"] == \
        pytest.approx(1.0)


# -- StatusStore: fold-in, rings, heartbeat lifecycle ------------------------

def _fresh_store(providers=None, ring=4, enabled=True):
    conf = Conf()
    conf.set(STATUS_RING_KEY, ring)
    if not enabled:
        conf.set(STATUS_ON_KEY, False)
    return StatusStore(conf, MetricsRegistry(), providers), conf


def test_status_store_listener_fold_in(session):
    store = StatusStore(session.conf, session.metrics)
    feed = store.bind(session, "t0")
    try:
        _fresh_agg(session, 771771).to_pandas()
    finally:
        session.remove_listener(feed)
    snap = store.snapshot()
    assert snap["enabled"] is True
    assert snap["queries_total"] >= 1
    assert snap["statuses"].get("ok", 0) >= 1
    assert snap["queries_inflight"]["t0"] == 0
    assert snap["sessions"]["t0"]["ok"] >= 1
    # per-phase cumulative seconds folded from the end event
    assert snap["phase_seconds"], snap
    assert session.metrics.gauge("status_queries_inflight").value == 0


def test_status_store_ring_capacity_bound():
    ticks = {"n": 0}

    def prov():
        ticks["n"] += 1
        return {"depth": ticks["n"], "skipped": "text"}

    store, _ = _fresh_store({"q": prov}, ring=4)
    for _ in range(11):
        store.sample()
    ts = store.timeseries()
    assert ts["heartbeats"] == 11
    assert ts["ring_capacity"] == 4
    pts = ts["series"]["q_depth"]
    assert len(pts) == 4  # bounded: 11 samples, ring keeps the last 4
    assert [v for _, v in pts] == [8.0, 9.0, 10.0, 11.0]
    assert "q_skipped" not in ts["series"]  # non-numeric leaves dropped
    # names/limit filters
    ts2 = store.timeseries(names=["q_depth"], limit=2)
    assert list(ts2["series"]) == ["q_depth"]
    assert len(ts2["series"]["q_depth"]) == 2


def test_status_store_provider_failure_isolated():
    def bad():
        raise RuntimeError("provider down")

    store, _ = _fresh_store({"bad": bad, "ok": lambda: {"x": 1}})
    vals = store.sample()
    assert vals["ok_x"] == 1.0  # the healthy provider still sampled
    snap = store.snapshot()
    assert "error" in snap["providers"]["bad"]
    assert snap["providers"]["ok"] == {"x": 1}


def test_status_store_heartbeat_joins_on_stop():
    store, conf = _fresh_store({"p": lambda: {"v": 1}})
    conf.set(HEARTBEAT_KEY, 20)
    store.start()
    try:
        assert any(t.name == "spark-tpu-status-heartbeat"
                   for t in threading.enumerate())
        deadline = 200
        while store.snapshot()["heartbeats"] < 2 and deadline:
            threading.Event().wait(0.02)
            deadline -= 1
        assert store.snapshot()["heartbeats"] >= 2
    finally:
        store.stop()
    LockWatch().assert_no_thread_leak(
        prefix="spark-tpu-status-heartbeat", timeout_s=5.0)
    store.stop()  # idempotent


def test_status_store_disabled_is_inert():
    store, _ = _fresh_store(enabled=False)
    store.start()
    assert store._thread is None  # no heartbeat thread spawned
    assert store.snapshot()["enabled"] is False


# -- latency histograms + SLO burn at query end ------------------------------

def test_latency_histograms_and_slo_burn(session, tmp_path):
    m = session.metrics
    lat0 = m.histogram("status_latency_ms").snapshot()["count"]
    slo0 = m.counter("slo_queries_total").value
    burn0 = m.counter("slo_burned_total").value
    session.conf.set(EVENT_KEY, str(tmp_path / "ev"))
    session.conf.set(SLO_KEY, 1)  # 1 ms target: a fresh agg burns it
    try:
        _fresh_agg(session, 772772).to_pandas()
    finally:
        session.conf.set(EVENT_KEY, "")
        session.conf.set(SLO_KEY, 0)
    assert m.histogram("status_latency_ms").snapshot()["count"] > lat0
    # per-phase and per-class histograms fed alongside
    names = m.histogram_names()
    assert any(n.startswith("status_phase_ms_") for n in names), names
    assert any(n.startswith("status_class_ms_") for n in names), names
    assert m.counter("slo_queries_total").value > slo0
    assert m.counter("slo_burned_total").value > burn0
    assert m.counter("slo_burn_ms_total").value >= 1


def test_slo_disabled_by_default(session, tmp_path):
    slo0 = session.metrics.counter("slo_queries_total").value
    session.conf.set(EVENT_KEY, str(tmp_path / "ev"))
    try:
        _fresh_agg(session, 773773).to_pandas()
    finally:
        session.conf.set(EVENT_KEY, "")
    # no target set -> latency histograms still fill, burn counters idle
    assert session.metrics.counter("slo_queries_total").value == slo0


# -- flight recorder ---------------------------------------------------------

def test_flightrec_installed_and_rings_fill(session, tmp_path):
    rec = FlightRecorder.of(session)
    assert rec is not None  # installed by default on every session
    session.conf.set(EVENT_KEY, str(tmp_path / "ev"))  # events on
    try:
        _fresh_agg(session, 774774).to_pandas()
    finally:
        session.conf.set(EVENT_KEY, "")
    with rec._lock:
        kinds = {r["kind"] for r in rec._rings["query"]}
    assert {"start", "end"} <= kinds


def test_flightrec_streaming_ring_holds_the_batch(session, tmp_path):
    """A streaming batch lands in the `streaming` ring, its own kind
    (snapshot / delta / stateless) beside the record's `kind`."""
    import warnings

    import numpy as np

    from spark_tpu.streaming import MemoryStream
    rec = FlightRecorder.of(session)
    schema = pd.DataFrame({"k": pd.Series([], dtype=np.int64),
                           "v": pd.Series([], dtype=np.int64)})
    src = MemoryStream(session, schema)
    q = (src.to_df().group_by(F.pmod(col("k"), 4).alias("g"))
         .agg(F.sum(col("v")).alias("s"))
         .write_stream(str(tmp_path / "ck")))
    src.add_data(pd.DataFrame({"k": np.arange(8, dtype=np.int64),
                               "v": np.ones(8, dtype=np.int64)}))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a listener that raised warns
        q.process_available()
    with rec._lock:
        batches = [r for r in rec._rings.get("streaming", ())
                   if r["kind"] == "batch"]
    assert len(batches) == 1, batches
    assert batches[0]["batch_id"] == 0 and batches[0]["rows_in"] == 8
    assert batches[0]["batch_kind"] == "snapshot"


def test_flightrec_bundle_on_injected_fatal(session, tmp_path):
    session.conf.set(EVENT_KEY, str(tmp_path / "ev"))
    session.conf.set(FR_DIR_KEY, str(tmp_path / "fr"))
    try:
        _fresh_agg(session, 775001).to_pandas()  # a healthy query first
        with faults.inject(session.conf, "stage_run:fatal:1"):
            with pytest.raises(faults.FaultInjected):
                _fresh_agg(session, 775775).to_pandas()
    finally:
        session.conf.set(EVENT_KEY, "")
        session.conf.set(FR_DIR_KEY, "")
    bundles = glob.glob(str(tmp_path / "fr" / "bundle-*"))
    assert len(bundles) == 1, bundles
    b = bundles[0]
    manifest = json.load(open(os.path.join(b, "MANIFEST.json")))
    assert manifest["bundle_version"] == 1
    assert manifest["reason"] == "fatal"
    assert "FaultInjected" in manifest["error"]
    assert manifest["extra"]["plan"]
    for fname in manifest["files"]:
        assert os.path.exists(os.path.join(b, fname)), fname
    rings = [json.loads(line)
             for line in open(os.path.join(b, "rings.jsonl"))]
    assert {"query", "stage"} <= {r["subsystem"] for r in rings}
    spans = json.load(open(os.path.join(b, "spans.json")))
    assert any(spans["spans"].values())  # the healthy query's spans
    conf_snap = json.load(open(os.path.join(b, "conf.json")))
    assert FR_DIR_KEY in conf_snap["explicitly_set"]
    assert conf_snap["effective"][FR_ON_KEY] is True
    threads_txt = open(os.path.join(b, "threads.txt")).read()
    assert "MainThread" in threads_txt
    tail = [json.loads(line) for line in
            open(os.path.join(b, "eventlog_tail.jsonl"))]
    assert tail and all("schema_version" in e for e in tail)
    metrics_snap = json.load(open(os.path.join(b, "metrics.json")))
    assert "counters" in metrics_snap


def test_flightrec_results_identical_on_vs_off(session, tmp_path):
    session.conf.set(FR_DIR_KEY, str(tmp_path / "fr"))
    try:
        on = _fresh_agg(session, 776776).to_pandas()
        session.conf.set(FR_ON_KEY, False)
        off = _fresh_agg(session, 776776).to_pandas()
    finally:
        session.conf.set(FR_ON_KEY, True)
        session.conf.set(FR_DIR_KEY, "")
    pd.testing.assert_frame_equal(on, off)  # byte-identical
    # a healthy run never dumps a bundle on its own
    assert glob.glob(str(tmp_path / "fr" / "bundle-*")) == []


def test_flightrec_disabled_dump_returns_none(session):
    rec = FlightRecorder.of(session)
    session.conf.set(FR_ON_KEY, False)
    try:
        assert rec.dump("test") is None
    finally:
        session.conf.set(FR_ON_KEY, True)


def test_flightrec_on_demand_dump(session, tmp_path):
    rec = FlightRecorder.of(session)
    session.conf.set(FR_DIR_KEY, str(tmp_path / "fr"))
    try:
        path = rec.dump("on_demand", extra={"who": "test"})
    finally:
        session.conf.set(FR_DIR_KEY, "")
    assert path and os.path.isdir(path)
    manifest = json.load(open(os.path.join(path, "MANIFEST.json")))
    assert manifest["reason"] == "on_demand"
    assert manifest["extra"] == {"who": "test"}
    assert manifest["error"] is None
    assert session.metrics.counter("flightrec_bundles").value >= 1


def test_flightrec_ring_bounded(session, tmp_path):
    session.conf.set(FR_RING_KEY, 8)
    session.conf.set(EVENT_KEY, str(tmp_path / "ev"))
    rec = FlightRecorder(session)  # fresh: rings created at cap 8
    session.add_listener(rec)
    try:
        for i in range(6):  # 6 starts + 6 ends = 12 records > 8
            _fresh_agg(session, 777100 + i).to_pandas()
    finally:
        session.remove_listener(rec)
        session.conf.set(EVENT_KEY, "")
        session.conf.set(FR_RING_KEY, 256)
    with rec._lock:
        assert len(rec._rings["query"]) == 8  # bounded, newest kept
        assert all(len(d) <= 8 for d in rec._rings.values())


# -- live service: /status, /status/timeseries, /debug/bundle ----------------

@pytest.fixture(scope="module")
def status_tpch_path(tmp_path_factory):
    from spark_tpu.tpch.datagen import write_parquet
    path = str(tmp_path_factory.mktemp("tpch_status") / "sf")
    write_parquet(path, 0.001)
    return path


def test_status_under_concurrent_service(status_tpch_path, tmp_path):
    import urllib.request

    from spark_tpu.service.arbiter import install_arbiter
    from spark_tpu.service.server import SqlService
    from spark_tpu.tpch import queries as Q
    from spark_tpu.tpch import sql_queries as SQLQ

    sessions = ["s1", "s2", "s3"]
    conf = Conf()
    conf.set("spark_tpu.service.port", 0)
    conf.set("spark_tpu.service.hbmBudget", 1 << 30)
    conf.set(HEARTBEAT_KEY, 25)
    conf.set(FR_DIR_KEY, str(tmp_path / "fr"))
    svc = SqlService(
        conf, init_session=lambda s: Q.register_tables(
            s, status_tpch_path)).start()
    watch = LockWatch()
    scrapes = []
    try:
        for name in sessions:  # warm the pool, then watch it
            svc.submit(SQLQ.Q1, session=name)
        watch.install_service(svc)

        results, errors = [], []
        stop_scrape = threading.Event()

        def run_queries(name):
            try:
                for _ in range(2):
                    results.append(svc.submit(SQLQ.Q1, session=name)[1])
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append((name, repr(e)))

        def scrape():
            while not stop_scrape.is_set():
                st = json.load(urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.port}/status", timeout=30))
                ts = json.load(urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.port}/status/timeseries"
                    f"?limit=5", timeout=30))
                scrapes.append((st, ts))
                threading.Event().wait(0.02)

        scraper = threading.Thread(target=scrape, daemon=True)
        scraper.start()
        threads = [threading.Thread(target=run_queries, args=(n,))
                   for n in sessions]
        [t.start() for t in threads]
        [t.join(300) for t in threads]
        stop_scrape.set()
        scraper.join(30)
        assert not any(t.is_alive() for t in threads), "query wedged"
        assert errors == [], errors
        assert len(results) == 6

        st = svc.status_store.snapshot()
        assert st["queries_total"] >= 9  # 3 warm + 6 concurrent
        assert st["statuses"].get("ok", 0) >= 9
        assert st["queries_inflight_total"] == 0
        assert set(sessions) <= set(st["sessions"])
        lat = st["latency"]["e2e_ms"]
        assert lat["count"] >= 9 and lat["p50"] <= lat["p95"]
        for prov in ("admission", "quota", "arbiter", "pool", "udf"):
            assert prov in st["providers"], st["providers"]
        # every live scrape parsed; rings bounded on every series
        assert scrapes, "scraper never ran"
        for st_s, ts_s in scrapes:
            assert st_s["enabled"] is True
            for pts in ts_s["series"].values():
                assert len(pts) <= 5  # limit honored
        # the heartbeat actually sampled while queries ran
        ts_all = svc.status_store.timeseries()
        assert ts_all["heartbeats"] >= 1
        for pts in ts_all["series"].values():
            assert len(pts) <= ts_all["ring_capacity"]

        # on-demand bundle over HTTP, one per pooled session
        db = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{svc.port}/debug/bundle", timeout=60))
        assert len(db["bundles"]) >= len(sessions)
        for entry in db["bundles"]:
            manifest = json.load(open(os.path.join(
                entry["path"], "MANIFEST.json")))
            assert manifest["reason"] == "on_demand"

        watch.assert_order_consistent()
    finally:
        watch.uninstall()
        svc.stop()
        install_arbiter(None)
    # stop() joined the heartbeat: no status thread may survive
    LockWatch().assert_no_thread_leak(
        prefix="spark-tpu-status-heartbeat", timeout_s=5.0)


def test_status_timeseries_bad_limit_is_400(status_tpch_path):
    import urllib.error
    import urllib.request

    from spark_tpu.service.arbiter import install_arbiter
    from spark_tpu.service.server import SqlService
    from spark_tpu.tpch import queries as Q

    conf = Conf()
    conf.set("spark_tpu.service.port", 0)
    svc = SqlService(
        conf, init_session=lambda s: Q.register_tables(
            s, status_tpch_path)).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{svc.port}/status/timeseries"
                f"?limit=bogus", timeout=30)
        assert ei.value.code == 400
    finally:
        svc.stop()
        install_arbiter(None)


# -- offline replay: history.status_summary + events_tool stats --------------

def _events_tool():
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "events_tool", os.path.join(root, "scripts", "events_tool.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_history_status_summary_and_stats(session, tmp_path):
    log_dir = str(tmp_path / "ev")
    session.conf.set(EVENT_KEY, log_dir)
    try:
        _fresh_agg(session, 778778).to_pandas()
        _fresh_agg(session, 779779).to_pandas()
    finally:
        session.conf.set(EVENT_KEY, "")
    events = history.read_event_log(log_dir)
    summ = history.status_summary(events)
    assert len(summ) == 1  # one app
    row = summ.iloc[0]
    assert row["queries"] == 2 and row["n_ok"] == 2
    assert row["p50_ms"] <= row["p95_ms"] <= row["p99_ms"]
    assert row["p99_ms"] > 0
    assert any(c.startswith("total_") for c in summ.columns)

    tool = _events_tool()
    lines = tool.stats([log_dir])
    text = "\n".join(lines)
    assert "records: 2" in text
    assert "ok=2" in text
    assert "schema versions: v7=2" in text
    assert "time span:" in text
    assert tool.main(["stats", log_dir]) == 0
    # empty target still prints a sane summary
    empty = tmp_path / "none"
    empty.mkdir()
    assert "records: 0" in "\n".join(tool.stats([str(empty)]))
