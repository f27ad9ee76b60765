"""Observability layer: listener bus ordering (incl. under faults),
span/Chrome-trace validity, XLA cost accounting, metrics sinks,
event-log hardening + rotation, history replay views, and golden
parity with every observability conf enabled."""

import json
import os

import numpy as np
import pandas as pd
import pytest

from spark_tpu import functions as F
from spark_tpu import history
from spark_tpu.functions import col
from spark_tpu.observability import QueryListener
from spark_tpu.observability.metrics import parse_prometheus
from spark_tpu.observability.sinks import json_default
from spark_tpu.testing import faults
from spark_tpu.tpch import golden as G
from spark_tpu.tpch import queries as Q
from spark_tpu.tpch.datagen import write_parquet

EVENT_KEY = "spark_tpu.sql.eventLog.dir"
TRACE_KEY = "spark_tpu.sql.trace.dir"
SINK_KEY = "spark_tpu.sql.metrics.sink"
MDIR_KEY = "spark_tpu.sql.metrics.dir"
MAXB_KEY = "spark_tpu.sql.eventLog.maxBytes"
COST_KEY = "spark_tpu.sql.observability.xlaCost"


class Recorder(QueryListener):
    """Collects (callback, event) tuples for ordering assertions."""

    def __init__(self):
        self.calls = []

    def on_query_start(self, e):
        self.calls.append(("start", e))

    def on_stage_compiled(self, e):
        self.calls.append(("compiled", e))

    def on_stage_completed(self, e):
        self.calls.append(("completed", e))

    def on_fault(self, e):
        self.calls.append(("fault", e))

    def on_query_end(self, e):
        self.calls.append(("end", e))

    def names(self):
        return [c[0] for c in self.calls]


def _fresh_agg(session, n=777):
    """A plan unlikely to be stage-cached already (n varies per test)."""
    return (session.range(n)
            .group_by((col("id") % 5).alias("k"))
            .agg(F.sum(col("id")).alias("s")))


# -- listener bus ------------------------------------------------------------

def test_listener_callback_ordering(session):
    rec = Recorder()
    session.add_listener(rec)
    try:
        _fresh_agg(session, 771).to_pandas()
    finally:
        session.remove_listener(rec)
    names = rec.names()
    assert names[0] == "start" and names[-1] == "end"
    assert "completed" in names
    if "compiled" in names:  # cold stage cache: compile precedes run
        assert names.index("compiled") < names.index("completed")
    end = rec.calls[-1][1]
    assert end.status == "ok"
    assert end.query_id == rec.calls[0][1].query_id
    assert end.event["metrics"], end.event


def test_listener_ordering_under_faults(session):
    session.conf.set("spark_tpu.execution.backoffMs", 1)
    rec = Recorder()
    session.add_listener(rec)
    try:
        with faults.inject(session.conf, "stage_run:unavailable:1"):
            got = _fresh_agg(session, 772).to_pandas()
    finally:
        session.remove_listener(rec)
    assert got["s"].sum() == sum(range(772))
    names = rec.names()
    # retry: fault posted between start and end, completion still last
    assert "fault" in names
    assert rec.calls[names.index("fault")][1].action == "transient_retry"
    assert names.index("fault") < names.index("end")
    assert names[-1] == "end" and rec.calls[-1][1].status == "ok"
    # the transient retry dropped the compiled entry: a second compile
    # event lands AFTER the fault
    compiles = [i for i, n in enumerate(names) if n == "compiled"]
    assert compiles and compiles[-1] > names.index("fault")


def test_listener_failure_isolated(session):
    class Bad(QueryListener):
        def on_query_end(self, e):
            raise RuntimeError("listener bug")

    bad = Bad()
    session.add_listener(bad)
    try:
        with pytest.warns(UserWarning, match="listener bug"):
            out = session.range(50).to_pandas()
    finally:
        session.remove_listener(bad)
    assert len(out) == 50
    assert session.listeners.dropped >= 1


def test_failed_query_posts_error_end(session):
    rec = Recorder()
    session.add_listener(rec)
    try:
        with faults.inject(session.conf, "stage_run:fatal:1"):
            with pytest.raises(Exception, match="INTERNAL"):
                _fresh_agg(session, 773).to_pandas()
    finally:
        session.remove_listener(rec)
    assert rec.names()[-1] == "end"
    end = rec.calls[-1][1]
    assert end.status == "error"
    assert "INTERNAL" in end.event["error"]


# -- spans / chrome trace ----------------------------------------------------

def test_chrome_trace_valid(session, tmp_path):
    trace_dir = str(tmp_path / "traces")
    session.conf.set(TRACE_KEY, trace_dir)
    try:
        _fresh_agg(session, 774).to_pandas()
    finally:
        session.conf.set(TRACE_KEY, "")
    files = [f for f in os.listdir(trace_dir)
             if f.endswith(".trace.json")]
    assert files, os.listdir(trace_dir)
    with open(os.path.join(trace_dir, files[-1])) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    assert events
    names = {e["name"] for e in events}
    # the lifecycle phases are all present as spans
    assert {"analysis", "optimize", "plan", "ingest",
            "dispatch"} <= names, names
    for e in events:
        assert e["ph"] in ("X", "i")
        assert isinstance(e["ts"], (int, float))
        assert e["tid"] >= 1  # query id
        if e["ph"] == "X":
            assert e["dur"] >= 0


def test_spans_in_event_log(session, tmp_path):
    log_dir = str(tmp_path / "ev")
    session.conf.set(EVENT_KEY, log_dir)
    try:
        _fresh_agg(session, 775).to_pandas()
    finally:
        session.conf.set(EVENT_KEY, "")
    events = history.read_event_log(log_dir)
    spans = history.stage_summary(events)
    assert {"analysis", "dispatch"} <= set(spans["span"])
    assert (spans["dur_ms"] >= 0).all()


# -- XLA cost accounting -----------------------------------------------------

def test_stage_cost_captured_in_event_log(session, tmp_path):
    log_dir = str(tmp_path / "ev")
    session.conf.set(EVENT_KEY, log_dir)
    try:
        qe = _fresh_agg(session, 776)._qe()
        qe.execute_batch()
    finally:
        session.conf.set(EVENT_KEY, "")
    assert qe.stage_costs, "cost capture should be on with eventLog set"
    info = next(iter(qe.stage_costs.values()))
    assert info.get("flops", 0) > 0
    assert info.get("peak_hbm_bytes", 0) > 0
    events = history.read_event_log(log_dir)
    comp = history.compile_summary(events)
    assert len(comp) >= 1 and comp["flops"].notna().any()
    hbm = history.hbm_summary(events)
    assert len(hbm) >= 1
    assert hbm.iloc[-1]["peak_hbm_bytes"] > 0
    # runtime explain surfaces the same accounting
    text = qe.explain(runtime=True)
    assert "Stage cost (XLA)" in text and "peak HBM" in text


def test_cost_capture_off_by_default(session):
    qe = _fresh_agg(session, 778)._qe()
    qe.execute_batch()
    assert not qe.stage_costs  # no observability output configured


def test_oom_diagnostic_cites_measured_hbm(session, tmp_path):
    from spark_tpu.execution.failures import StageOOMError
    session.conf.set("spark_tpu.execution.backoffMs", 1)
    session.conf.set(EVENT_KEY, str(tmp_path / "ev"))
    try:
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with faults.inject(session.conf,
                               "stage_run:resource_exhausted:1,"
                               "stage_run:resource_exhausted:2,"
                               "stage_run:resource_exhausted:3"):
                with pytest.raises(StageOOMError) as exc:
                    _fresh_agg(session, 779).to_pandas()
    finally:
        session.conf.set(EVENT_KEY, "")
    msg = str(exc.value)
    assert "measured peak HBM demand" in msg, msg
    assert "temps=" in msg


# -- metrics registry + sinks ------------------------------------------------

def test_prometheus_sink_scrape_parses(session, tmp_path):
    mdir = str(tmp_path / "metrics")
    session.conf.set(SINK_KEY, "prometheus")
    session.conf.set(MDIR_KEY, mdir)
    try:
        _fresh_agg(session, 780).to_pandas()
    finally:
        session.conf.set(SINK_KEY, "")
    prom = parse_prometheus(os.path.join(mdir, "metrics.prom"))
    assert prom["spark_tpu_queries_total"] >= 1
    assert "spark_tpu_query_execution_count" in prom
    assert any(k.startswith("spark_tpu_compile_cache_") for k in prom)
    assert any(k.startswith("spark_tpu_device_cache_") for k in prom)


def test_jsonl_sink_appends_snapshots(session, tmp_path):
    mdir = str(tmp_path / "metrics")
    session.conf.set(SINK_KEY, "jsonl")
    session.conf.set(MDIR_KEY, mdir)
    try:
        _fresh_agg(session, 781).to_pandas()
        _fresh_agg(session, 782).to_pandas()
    finally:
        session.conf.set(SINK_KEY, "")
    lines = [json.loads(ln) for ln in
             open(os.path.join(mdir, "metrics.jsonl"))]
    assert len(lines) >= 2
    assert lines[-1]["counters"]["queries_total"] \
        > lines[0]["counters"]["queries_total"] - 1
    assert "ts" in lines[-1]


def test_sink_validator_rejects_unknown(session):
    with pytest.raises(ValueError):
        session.conf.set(SINK_KEY, "statsd")


def test_metrics_lint_clean():
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "metrics_lint", os.path.join(root, "scripts", "metrics_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.run() == []


def test_unregistered_metric_name_rejected(session):
    from spark_tpu.config import Conf
    from spark_tpu.plan.physical import ExecContext
    ctx = ExecContext(Conf())
    with pytest.raises(ValueError, match="unregistered metric"):
        ctx.add_metric("made_up_metric", 1)
    ctx.add_metric("rows_op1", 1)  # registered prefix passes


# -- event-log hardening + rotation ------------------------------------------

def test_json_default_encoder():
    import jax.numpy as jnp
    assert json_default(np.int64(7)) == 7
    assert json_default(np.float32(0.5)) == 0.5
    assert json_default(np.array([1, 2])) == [1, 2]
    assert json_default(jnp.asarray(3)) == 3
    assert json_default({"b", "a"}) == ["a", "b"]
    # end-to-end: numpy scalars inside an event dict serialize
    s = json.dumps({"v": np.int64(5), "w": np.float64(1.5)},
                   default=json_default)
    assert json.loads(s) == {"v": 5, "w": 1.5}


def test_event_log_schema_and_unique_filename(session, tmp_path):
    log_dir = str(tmp_path / "ev")
    session.conf.set(EVENT_KEY, log_dir)
    try:
        _fresh_agg(session, 783).to_pandas()
    finally:
        session.conf.set(EVENT_KEY, "")
    files = os.listdir(log_dir)
    assert len(files) == 1
    # session-unique name: app-<pid>-<token>.jsonl, not bare pid
    assert files[0] == f"app-{session.app_id}.jsonl"
    assert files[0] != f"app-{os.getpid()}.jsonl"
    line = json.loads(open(os.path.join(log_dir, files[0])).read()
                      .splitlines()[-1])
    assert line["schema_version"] == 7
    assert line["status"] == "ok"
    assert line["query_id"] >= 1


def test_event_log_rotation_and_replay_order(session, tmp_path):
    log_dir = str(tmp_path / "ev")
    session.conf.set(EVENT_KEY, log_dir)
    session.conf.set(MAXB_KEY, 1)  # every write rolls the previous file
    session.conf.set(COST_KEY, "off")  # keep lines small + fast
    try:
        for i in range(4):
            session.range(100 + i).agg(
                F.sum(col("id")).alias("s")).to_pandas()
    finally:
        session.conf.set(EVENT_KEY, "")
        session.conf.set(MAXB_KEY, 0)
        session.conf.set(COST_KEY, "auto")
    names = sorted(os.listdir(log_dir))
    rolled = [n for n in names if n.count(".") == 2]
    assert len(rolled) == 3, names  # 4 writes -> 3 rolls + live file
    events = history.read_event_log(log_dir)
    assert len(events) == 4
    # replay order == write order (rolled files first, in N order)
    assert events["ts"].is_monotonic_increasing
    # per-app filter sees rolled files too
    assert len(history.read_event_log(log_dir, app=session.app_id)) == 4


def test_event_log_write_failure_warns_not_raises(session, tmp_path):
    bad = tmp_path / "afile"
    bad.write_text("x")
    session.conf.set(EVENT_KEY, str(bad))
    try:
        with pytest.warns(UserWarning, match="event log write failed"):
            out = session.range(5).to_pandas()
    finally:
        session.conf.set(EVENT_KEY, "")
    assert len(out) == 5


# -- runtime tree annotations ------------------------------------------------

def test_runtime_tree_join_annotations(session):
    left = pd.DataFrame({"k": np.arange(50, dtype=np.int64),
                         "v": np.arange(50, dtype=np.int64)})
    right = pd.DataFrame({"k": np.arange(0, 50, 5, dtype=np.int64),
                          "w": np.arange(10, dtype=np.int64)})
    session.register_table("obs_l", left)
    session.register_table("obs_r", right)
    df = session.table("obs_l").join(session.table("obs_r"), on="k")
    qe = df._qe()
    qe.execute_batch()
    text = qe.explain(runtime=True)
    assert "join rows: 10" in text, text
    assert "cap" in text  # capacity rides along with the actual


# -- history: compare_runs ---------------------------------------------------

def _synthetic_events(tmp_path, name, execution_s):
    log_dir = tmp_path / name
    log_dir.mkdir()
    lines = [{"schema_version": 2, "query_id": i + 1, "ts": 100.0 + i,
              "status": "ok", "plan": "(AggExec (ScanExec t))",
              "phase_times_s": {"execution": execution_s},
              "metrics": {"rows_op1": 1000 * (i + 1)},
              "stages": [{"key_hash": "abc", "flops": 5000,
                          "peak_hbm_bytes": 4096,
                          "argument_bytes": 2048, "temp_bytes": 1024,
                          "output_bytes": 1024}]}
             for i in range(2)]
    with open(log_dir / "app-1-synthetic.jsonl", "w") as f:
        for ln in lines:
            f.write(json.dumps(ln) + "\n")
    return str(log_dir)


def test_hbm_summary_on_synthetic_log(tmp_path):
    events = history.read_event_log(
        _synthetic_events(tmp_path, "a", 0.5))
    hbm = history.hbm_summary(events)
    assert len(hbm) == 2
    row = hbm.iloc[0]
    assert row["peak_hbm_bytes"] == 4096
    assert row["peak_stage"] == "abc"
    assert row["capacity_bytes"] is None  # CPU logs no capacity


def test_compare_runs_on_synthetic_logs(tmp_path):
    base = history.read_event_log(_synthetic_events(tmp_path, "a", 2.0))
    other = history.read_event_log(_synthetic_events(tmp_path, "b", 1.0))
    cmp = history.compare_runs(base, other)
    assert len(cmp) >= 1
    row = cmp[cmp["column"] == "phase_execution_s"].iloc[0]
    assert row["base"] == 2.0 and row["other"] == 1.0
    assert row["delta"] == -1.0 and row["ratio"] == 0.5


# -- per-shard telemetry + straggler detection -------------------------------

MESH_KEY = "spark_tpu.sql.mesh.size"
CHUNK_KEY = "spark_tpu.sql.execution.streamingChunkRows"
CACHE_KEY = "spark_tpu.sql.io.deviceCacheBytes"
SHARD_SPANS_KEY = "spark_tpu.sql.observability.shardSpans"


def _mesh_stream_qe(session, n_rows=5000, chunk=1024, name="shard_obs_t"):
    """A mesh streamed-aggregate execution with per-shard spans on."""
    pdf = pd.DataFrame({"v": np.arange(n_rows, dtype=np.int64)})
    session.register_table(name, pdf)
    session.conf.set(CHUNK_KEY, chunk)
    session.conf.set(CACHE_KEY, 0)
    session.conf.set(SHARD_SPANS_KEY, "on")
    session.conf.set(MESH_KEY, 8)
    qe = (session.table(name)
          .group_by((col("v") % 13).alias("k"))
          .agg(F.sum(col("v")).alias("s")))._qe()
    return qe, pdf


def test_shard_telemetry_mesh_stream(session, tmp_path):
    log_dir = str(tmp_path / "ev")
    session.conf.set(EVENT_KEY, log_dir)
    try:
        qe, pdf = _mesh_stream_qe(session)
        qe.execute_batch()
    finally:
        session.conf.set(EVENT_KEY, "")
        session.conf.set(MESH_KEY, 0)
    comp = [r for r in qe.spans.shard_records if r["phase"] == "compute"]
    assert {r["shard"] for r in comp} == set(range(8))
    assert max(r["chunk"] for r in comp) >= 2  # genuinely chunked
    # per-shard row counts tile the scan exactly (psum-free coverage)
    assert sum(r["rows"] for r in comp) == len(pdf)
    assert all(r["bytes"] == r["rows"] * 8 for r in comp)
    ingest = [r for r in qe.spans.shard_records
              if r["phase"] == "ingest"]
    assert ingest and all(r["shard"] is None for r in ingest)
    # exchange transfer vectors rode the metrics channel into records
    transfer = [r for r in qe.spans.shard_records
                if r["phase"] == "transfer"]
    assert transfer and all(
        r["source"].startswith("exchange:") for r in transfer)
    # ...and the [n]-vector metrics never leak into scalar last_metrics
    assert not any(k.startswith("shard_") for k in qe.last_metrics)
    # event log: schema v3 `shards` replayed by the history views
    events = history.read_event_log(log_dir)
    assert events.iloc[-1]["schema_version"] == 7
    ss = history.shard_summary(events)
    assert len(ss) == len(qe.spans.shard_records)
    rep = history.straggler_report(events)
    assert not rep.empty and not rep["flagged"].any()


def test_straggler_monitor_flags_slow_shard(session):
    """Chaos: a `slow` fault on exactly one shard's telemetry window
    (shard 5, every chunk) must flag exactly that shard — on_straggler
    event + straggler_flagged counter — with result parity."""
    from spark_tpu.observability import QueryListener, StragglerMonitor

    straggler_events = []

    class Sub(QueryListener):
        def on_straggler(self, e):
            straggler_events.append(e)

    sub = Sub()
    session.add_listener(sub)
    session.conf.set("spark_tpu.sql.straggler.minChunks", 3)
    session.conf.set("spark_tpu.sql.straggler.factor", 4.0)
    flagged_before = session.metrics.counter("straggler_flagged").value
    # 5 chunks x 8 shards; shard 5's window is hit c*8 + 5 + 1
    rules = ",".join(f"shard_chunk:slow:{c * 8 + 6}:60" for c in range(5))
    try:
        with faults.inject(session.conf, rules) as fp:
            qe, pdf = _mesh_stream_qe(session, name="straggler_t")
            batch, _, _ = qe.execute_batch()
            got = batch.to_arrow().to_pandas()
    finally:
        session.remove_listener(sub)
        session.conf.set(MESH_KEY, 0)
    assert fp.fired_log, "shard_chunk seam never fired — test is vacuous"
    # parity: the slow shard perturbed nothing but its wait
    want = pdf.assign(k=pdf.v % 13).groupby("k")["v"].sum()
    res = got.set_index("k")["s"].sort_index()
    assert (res == want).all()
    mon = StragglerMonitor.of(session)
    assert mon is not None
    assert mon.report().get(qe.query_id) == {5}, mon.report()
    assert session.metrics.counter("straggler_flagged").value \
        == flagged_before + 1
    assert len(straggler_events) == 1
    ev = straggler_events[0]
    assert ev.shard == 5 and ev.query_id == qe.query_id
    assert ev.median_ms > ev.baseline_ms


def test_straggler_monitor_state_self_bounded(session):
    """With shardSpans=on and NO observability output, on_query_end
    never fires — the monitor's live maps must self-bound instead of
    leaking one entry per mesh query (code-review finding)."""
    from spark_tpu.observability import StragglerMonitor
    from spark_tpu.observability.listener import ShardChunkEvent
    from spark_tpu.observability import straggler as S
    mon = StragglerMonitor.of(session)
    assert mon is not None
    for qid in range(1000, 1000 + S._LIVE_BOUND + 5):
        mon.on_shard_records(ShardChunkEvent(
            query_id=qid, ts=0.0, chunk=0,
            records=[{"shard": 0, "host": 0, "phase": "compute",
                      "wait_ms": 0.1},
                     {"shard": 1, "host": 0, "phase": "compute",
                      "wait_ms": 0.1}]))
    assert len(mon._waits) <= S._LIVE_BOUND
    assert 1000 not in mon._waits  # oldest evicted
    assert 1000 + S._LIVE_BOUND + 4 in mon._waits  # newest retained


def test_shard_telemetry_retry_discards_failed_attempt(session):
    """A ChunkRetrier replay re-dispatches the SAME chunk index: the
    failed attempt's buffered array must be discarded, not flushed —
    duplicate (shard, chunk) records would double-count row totals
    and skew straggler medians (code-review finding)."""
    import time as _t

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from spark_tpu.observability.spans import (ShardStreamTelemetry,
                                               SpanRecorder)
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    rec = SpanRecorder(1)
    telem = ShardStreamTelemetry(rec, mesh, query_id=1)
    # sharded like the driver's real output: one piece per mesh device
    arr = jax.device_put(jnp.ones((8,), jnp.int64),
                         NamedSharding(mesh, PartitionSpec("data")))
    telem.chunk_dispatched(0, arr, 8, _t.perf_counter())
    telem.chunk_dispatched(0, arr, 8, _t.perf_counter())  # retry, same ci
    telem.chunk_dispatched(1, arr, 8, _t.perf_counter())
    telem.finish()
    comp = [r for r in rec.shard_records if r["phase"] == "compute"]
    assert len(comp) == 16  # 2 chunks x 8 shards: retry deduped
    per_chunk = {(r["chunk"], r["shard"]) for r in comp}
    assert len(per_chunk) == len(comp)  # no duplicate (chunk, shard)


def test_straggler_min_chunks_above_window_still_detects(session):
    """minChunks above the default rolling WINDOW must widen the
    window, not silently disable detection (code-review finding)."""
    from spark_tpu.observability import StragglerMonitor
    from spark_tpu.observability import straggler as S
    from spark_tpu.observability.listener import ShardChunkEvent
    mon = StragglerMonitor.of(session)
    min_chunks = S.WINDOW + 8
    session.conf.set("spark_tpu.sql.straggler.minChunks", min_chunks)
    session.conf.set("spark_tpu.sql.straggler.factor", 3.0)
    qid = 7777
    for c in range(min_chunks + 2):
        mon.on_shard_records(ShardChunkEvent(
            query_id=qid, ts=0.0, chunk=c,
            records=[{"shard": s, "host": 0, "phase": "compute",
                      "wait_ms": 50.0 if s == 2 else 0.1}
                     for s in range(4)]))
    assert mon.flagged(qid) == {2}, mon.flagged(qid)


def test_shard_telemetry_off_by_default(session):
    """No observability output + shardSpans=auto: the mesh stream must
    record nothing (zero flight-recorder tax on bare runs)."""
    pdf = pd.DataFrame({"v": np.arange(4000, dtype=np.int64)})
    session.register_table("shard_off_t", pdf)
    session.conf.set(CHUNK_KEY, 1024)
    session.conf.set(CACHE_KEY, 0)
    session.conf.set(MESH_KEY, 8)
    try:
        qe = (session.table("shard_off_t")
              .group_by((col("v") % 7).alias("k"))
              .agg(F.sum(col("v")).alias("s")))._qe()
        qe.execute_batch()
    finally:
        session.conf.set(MESH_KEY, 0)
    assert qe.spans.shard_records == []


def test_shard_records_bounded(session):
    session.conf.set(
        "spark_tpu.sql.observability.maxShardRecords", 10)
    try:
        qe, _ = _mesh_stream_qe(session, name="shard_bound_t")
        qe.execute_batch()
    finally:
        session.conf.set(MESH_KEY, 0)
    assert len(qe.spans.shard_records) == 10
    assert qe.spans.shard_dropped > 0  # truncation counted, not silent


# -- analyzer self-grading (predictions) -------------------------------------

def test_prediction_report_and_grading(session, tmp_path):
    log_dir = str(tmp_path / "ev")
    session.conf.set(EVENT_KEY, log_dir)
    try:
        left = pd.DataFrame({"k": np.arange(200, dtype=np.int64) % 50,
                             "v": np.arange(200, dtype=np.int64)})
        right = pd.DataFrame({"k": np.arange(50, dtype=np.int64),
                              "w": np.arange(50, dtype=np.int64)})
        session.register_table("pred_l", left)
        session.register_table("pred_r", right)
        qe = (session.table("pred_l")
              .join(session.table("pred_r"), on="k")
              .group_by(col("k")).agg(F.sum(col("v")).alias("s")))._qe()
        qe.execute_batch()
    finally:
        session.conf.set(EVENT_KEY, "")
    assert qe.plan_predictions, "no predictions harvested from the plan"
    kinds = {p["kind"] for p in qe.plan_predictions}
    assert "join_rows" in kinds and "agg_groups" in kinds
    graded = history.grade_predictions(qe.plan_predictions,
                                       qe.last_metrics)
    assert graded, (qe.plan_predictions, qe.last_metrics)
    assert all(g["grade"] in ("hit", "over", "under") for g in graded)
    jr = [g for g in graded if g["kind"] == "join_rows"]
    assert jr and jr[0]["observed"] == 200  # fk join: one match per row
    # replayed from the event log, the report grades the same rows
    events = history.read_event_log(log_dir)
    rep = history.prediction_report(events)
    assert len(rep) >= len(graded)
    assert set(rep["grade"]) <= {"hit", "over", "under"}


# -- events_tool (schema validation + tail) ----------------------------------

def _events_tool():
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "events_tool", os.path.join(root, "scripts", "events_tool.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_events_tool_validate_and_tail(session, tmp_path):
    log_dir = str(tmp_path / "ev")
    session.conf.set(EVENT_KEY, log_dir)
    try:
        _fresh_agg(session, 784).to_pandas()
    finally:
        session.conf.set(EVENT_KEY, "")
    tool = _events_tool()
    assert tool.validate([log_dir]) == []
    assert tool.main(["validate", log_dir]) == 0
    lines = tool.tail([log_dir], n=5)
    assert lines and "ok" in lines[-1]
    # a corrupt line and a schema violation both fail loudly
    path = os.path.join(log_dir, os.listdir(log_dir)[0])
    with open(path, "a") as f:
        f.write("not json\n")
        f.write(json.dumps({"schema_version": 2, "query_id": 1,
                            "ts": 1.0, "status": "ok", "plan": "p",
                            "shards": []}) + "\n")  # v3 field in v2
    problems = tool.validate([log_dir])
    assert len(problems) == 2, problems
    assert tool.main(["validate", log_dir]) == 1
    # old-version lines (v2, no shards) still validate
    ok2 = {"schema_version": 2, "query_id": 1, "ts": 1.0,
           "status": "ok", "plan": "p",
           "phase_times_s": {"execution": 0.1}}
    p2 = tmp_path / "old" / "app-1-old.jsonl"
    p2.parent.mkdir()
    p2.write_text(json.dumps(ok2) + "\n")
    assert tool.validate([str(tmp_path / "old")]) == []


# -- golden parity with everything on ----------------------------------------

@pytest.fixture(scope="module")
def obs_tpch_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tpch_obs") / "sf")
    write_parquet(path, 0.002)
    return path


@pytest.mark.parametrize("qname", ["q1", "q3"])
def test_golden_parity_all_observability_on(session, obs_tpch_path,
                                            tmp_path, qname):
    """Tracing/metrics/cost capture must not perturb results."""
    Q.register_tables(session, obs_tpch_path)
    session.conf.set(EVENT_KEY, str(tmp_path / "ev"))
    session.conf.set(TRACE_KEY, str(tmp_path / "tr"))
    session.conf.set(SINK_KEY, "jsonl,prometheus")
    session.conf.set(MDIR_KEY, str(tmp_path / "m"))
    session.conf.set(COST_KEY, "on")
    try:
        got = G.normalize_decimals(
            Q.QUERIES[qname](session)._qe().collect().to_pandas())
    finally:
        session.conf.set(EVENT_KEY, "")
        session.conf.set(TRACE_KEY, "")
        session.conf.set(SINK_KEY, "")
        session.conf.set(COST_KEY, "auto")
    G.compare(got.reset_index(drop=True),
              G.GOLDEN[qname](obs_tpch_path))
    # and all three artifact families exist
    assert os.listdir(str(tmp_path / "ev"))
    assert os.listdir(str(tmp_path / "tr"))
    assert os.path.exists(str(tmp_path / "m" / "metrics.prom"))


# -- the span tree of a streamed scan (both threads) --------------------------

PREFETCH_KEY = "spark_tpu.sql.ingest.prefetch"
PROFILE_KEY = "spark_tpu.sql.profile.dir"
STREAM_ROWS, STREAM_CHUNK = 5000, 1024
#: per chunk, on the consumer's thread and under `streaming`
CONSUMER_CHUNK_SPANS = ("chunk.to_device", "chunk.launch")
#: per chunk, on whichever thread makes the chunk's host half (the
#: chunks here are too small for a thread a column)
HOST_CHUNK_SPANS = ("chunk.decode", "chunk.unify", "chunk.convert")


@pytest.fixture(scope="module")
def stream_table(tmp_path_factory):
    """A Parquet table of a string, an int64 and a decimal column, in
    row groups that do not divide the chunk."""
    import decimal
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.default_rng(7)
    t = pa.table({
        "k": pa.array(rng.choice(["A", "B", "C"], STREAM_ROWS)),
        "v": pa.array(np.arange(STREAM_ROWS, dtype=np.int64)),
        "d": pa.array([decimal.Decimal(int(x)) / 100 for x in
                       rng.integers(0, 10000, STREAM_ROWS)],
                      pa.decimal128(12, 2))})
    path = str(tmp_path_factory.mktemp("stream_spans"))
    pq.write_table(t, os.path.join(path, "t.parquet"), row_group_size=700)
    return path


def _stream_qe(session, path, prefetch=True, name="stream_spans_t"):
    from spark_tpu.io.sources import ParquetSource
    session.register_table(name, ParquetSource(path, name))
    session.conf.set(CHUNK_KEY, STREAM_CHUNK)
    session.conf.set(CACHE_KEY, 0)
    session.conf.set(PREFETCH_KEY, prefetch)
    return (session.table(name).group_by(col("k"))
            .agg(F.sum(col("v")).alias("s"),
                 F.sum(col("d")).alias("d")))._qe()


def _ingest_counters(session):
    return {k: session.metrics.counter(k).value
            for k in ("ingest_stall_ms", "ingest_chunks", "ingest_rows",
                      "ingest_put_bytes", "scans_streamed",
                      "scans_resident")}


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch_on", "prefetch_off"])
def test_stream_span_tree(session, stream_table, prefetch):
    before = _ingest_counters(session)
    qe = _stream_qe(session, stream_table, prefetch)
    out = qe.collect().to_pandas()
    assert int(out["s"].sum()) == STREAM_ROWS * (STREAM_ROWS - 1) // 2
    grew = {k: v - before[k] for k, v in _ingest_counters(session).items()}
    spans = qe.spans.spans
    by_id = {s.id: s for s in spans}
    assert len(by_id) == len(spans) and qe.spans.dropped == 0
    assert qe.spans.open_spans() == {}
    n_chunks = -(-STREAM_ROWS // STREAM_CHUNK)
    (streaming,) = [s for s in spans if s.name == "streaming"]
    consumer = streaming.tid

    def named(name):
        return [s for s in spans if s.name == name]

    # exactly the tree: per chunk one of each and a convert per column
    # beside decode and unify, a put per column under each to_device,
    # one drain, and with prefetch a wait per chunk and one for the
    # end of the stream
    for name in CONSUMER_CHUNK_SPANS + HOST_CHUNK_SPANS:
        per_chunk = 3 if name == "chunk.convert" else 1
        assert len(named(name)) == per_chunk * n_chunks, name
        assert all(s.parent == streaming.id for s in named(name))
    assert len(named("stream.drain")) == 1
    # once a streamed scan: what `drive` does before it takes the first
    # chunk, and the chunk program found or built on it; leaves beside
    # the chunks' spans, the worker's still caused by `streaming`
    for name in ("stream.open", "stream.begin"):
        (s,) = named(name)
        assert s.parent == streaming.id and s.tid == consumer
        assert not any(c.parent == s.id for c in spans), name
    assert named("stream.open")[0].t1 <= min(
        s.t0 for s in named("chunk.to_device"))
    assert named("stream.open")[0].id < named("stream.begin")[0].id \
        < named("chunk.launch")[0].id
    assert len(named("chunk.wait")) == (n_chunks + 1 if prefetch else 0)
    # the worker's thread made and started, before the first wait
    assert len(named("prefetch.start")) == (1 if prefetch else 0)
    for s in named("prefetch.start"):
        assert s.parent == streaming.id and s.tid == consumer
        assert named("stream.open")[0].t1 <= s.t0 \
            and s.t1 <= min(w.t0 for w in named("chunk.wait"))
    for td in named("chunk.to_device"):
        kids = [s.name for s in spans if s.parent == td.id]
        assert kids == ["chunk.put"] * 3
    assert sorted(s.attrs["column"] for s in named("chunk.convert")) \
        == sorted(s.attrs["column"] for s in named("chunk.put"))
    assert {s.name for s in spans if s.name.startswith(
        ("chunk.", "stream."))} == {
        "chunk.decode", "chunk.unify", "chunk.to_device", "chunk.convert",
        "chunk.put", "chunk.launch", "stream.drain", "stream.open",
        "stream.begin"} | ({"chunk.wait"} if prefetch else set())
    assert [s.attrs["chunk"] for s in sorted(
        named("chunk.launch"), key=lambda s: s.id)] == list(range(n_chunks))

    # threads: the consumer runs all but decode, unify and convert,
    # which the prefetch worker runs when there is one
    host_tids = {s.tid for n in HOST_CHUNK_SPANS for s in named(n)}
    for s in spans:
        if s.name not in HOST_CHUNK_SPANS:
            assert s.tid == consumer, s
    if prefetch:
        assert len(host_tids) == 1 and consumer not in host_tids
    else:
        assert host_tids == {consumer}

    # every child lies inside its parent
    for s in spans:
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1, (s, p)

    # the counters at the same boundaries
    assert sum(s.attrs["rows"] for s in named("chunk.decode")) == STREAM_ROWS
    assert grew["ingest_chunks"] == n_chunks
    assert grew["ingest_rows"] == STREAM_ROWS
    assert grew["scans_streamed"] == 1 and grew["scans_resident"] == 0
    # string code 4 B, int64 8 B, decimal's unscaled int64 8 B
    assert grew["ingest_put_bytes"] == n_chunks * STREAM_CHUNK * (4 + 8 + 8)
    assert grew["ingest_put_bytes"] == sum(
        s.attrs["bytes"] for s in named("chunk.put"))
    # what was filled is what was put
    assert grew["ingest_put_bytes"] == sum(
        s.attrs["bytes"] for s in named("chunk.convert"))
    waited = sum(s.dur_ms for s in named("chunk.wait"))
    # one interval read twice, the span a few clock readings wider
    assert abs(waited - grew["ingest_stall_ms"]) <= 0.05 * (n_chunks + 1)
    if not prefetch:
        assert grew["ingest_stall_ms"] == 0

    # the rest of the request
    (dispatch,) = named("dispatch")
    assert sorted(s.name for s in spans if s.parent == dispatch.id) == [
        "dispatch.launch", "dispatch.sync"]
    assert len(named("egress")) == 1 and named("egress")[0].parent is None
    dicts = {d["id"]: d for d in qe.spans.to_dicts()}
    assert all({"id", "parent", "tid"} <= set(d) for d in dicts.values())
    assert dicts[dispatch.id]["parent"] is None


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch_on", "prefetch_off"])
def test_stream_counts_how_string_columns_arrived(session, stream_table,
                                                  prefetch):
    """A string column the Parquet reader hands over as the file's
    codes is unified by dictionary, an in-memory table's strings are
    hashed row by row: one counter each, on `/metrics`, and the
    `chunk.unify` span says what its time went to, a `chunk.convert`
    what it filled."""
    import pyarrow as pa
    from spark_tpu.io.sources import ArrowTableSource
    from spark_tpu.observability.metrics import (parse_prometheus_text,
                                                 prometheus_text)
    keys = ("ingest_dict_columns_read", "ingest_dict_columns_encoded")

    def counters():
        return [session.metrics.counter(k).value for k in keys]

    n_chunks = -(-STREAM_ROWS // STREAM_CHUNK)
    before = counters()
    qe = _stream_qe(session, stream_table, prefetch)
    qe.collect()
    assert [a - b for a, b in zip(counters(), before)] == [n_chunks, 0]
    unify = [s for s in qe.spans.spans if s.name == "chunk.unify"]
    assert len(unify) == n_chunks
    for s in unify:
        assert {"chunk", "rows", "dict_ms"} <= set(s.attrs)
        assert 0 < s.attrs["dict_ms"] <= s.dur_ms
    # no column is concatenated any more: each is filled where it is
    # put, a code 4 B, an int64 and a decimal's unscaled int64 8 B
    filled = {}
    for s in qe.spans.spans:
        if s.name == "chunk.convert":
            filled.setdefault(s.attrs["column"], set()).add(s.attrs["bytes"])
    assert filled == {"k": {4 * STREAM_CHUNK}, "v": {8 * STREAM_CHUNK},
                      "d": {8 * STREAM_CHUNK}}
    assert any(d["name"] == "chunk.unify" and "dict_ms" in d["attrs"]
               for d in qe.spans.to_dicts())

    rng = np.random.default_rng(11)
    session.register_table("stream_plain_strings_t", ArrowTableSource(
        "stream_plain_strings_t", pa.table({
            "k": pa.array(rng.choice(["A", "B", "C"], STREAM_ROWS)),
            "v": pa.array(np.arange(STREAM_ROWS, dtype=np.int64))})))
    before = counters()
    qe = (session.table("stream_plain_strings_t").group_by(col("k"))
          .agg(F.sum(col("v")).alias("s")))._qe()
    out = qe.collect().to_pandas()
    assert int(out["s"].sum()) == STREAM_ROWS * (STREAM_ROWS - 1) // 2
    assert any(s.name == "streaming" for s in qe.spans.spans)
    assert [a - b for a, b in zip(counters(), before)] == [0, n_chunks]

    # what `GET /metrics` renders (SqlService.metrics_text)
    served = parse_prometheus_text(
        prometheus_text(session.metrics.snapshot()))
    assert [served["spark_tpu_" + k] for k in keys] == counters()


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch_on", "prefetch_off"])
def test_stream_failure_leaves_no_span_open(session, stream_table, prefetch):
    """A stream that raises mid-chunk closes every span on both
    threads, and the next query's tree names none of its spans."""
    qe = _stream_qe(session, stream_table, prefetch)
    session.conf.set("spark_tpu.faults.inject", "stream_chunk:fatal:2")
    faults.reset()
    try:
        with pytest.raises(Exception):
            qe.collect()
    finally:
        session.conf.set("spark_tpu.faults.inject", "")
        faults.reset()
    assert qe.spans.open_spans() == {}
    failed = [s for s in qe.spans.spans if "error" in s.attrs]
    assert {"chunk.launch", "streaming"} <= {s.name for s in failed}
    again = _stream_qe(session, stream_table, prefetch)
    again.collect()
    ids = {s.id for s in again.spans.spans}
    assert all(s.parent is None or s.parent in ids
               for s in again.spans.spans)
    assert not any("error" in s.attrs for s in again.spans.spans)
    assert again.spans.open_spans() == {}


def _host_plane_names(trace_dir):
    import glob
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        # a line is a host thread; threads may share a name
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("spark_tpu."):
                    names.setdefault(e.name, set()).add(i)
    return names


def test_engine_spans_stand_in_profiler_trace(session, stream_table,
                                              tmp_path):
    """Under a profiler session the engine's spans are annotations on
    the trace's host plane, the worker's on a line of its own."""
    import jax
    qe = _stream_qe(session, stream_table, prefetch=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        qe.collect()
    finally:
        jax.profiler.stop_trace()
    names = _host_plane_names(str(tmp_path))
    assert {"spark_tpu.streaming", "spark_tpu.chunk.decode",
            "spark_tpu.chunk.put", "spark_tpu.egress"} <= set(names), names
    assert names["spark_tpu.chunk.decode"].isdisjoint(
        names["spark_tpu.streaming"])
    # the names PR 39 gave the rest of a query, on the query's line
    new = {"spark_tpu." + n for n in (
        "replan.key", "predict", "stream.open", "prefetch.start",
        "stream.begin", "stage.lookup", "plan.fingerprint")}
    assert new <= set(names), sorted(new - set(names))
    for name in new:
        assert names[name] == names["spark_tpu.streaming"], name


def test_profile_dir_holds_the_streamed_chunks(session, stream_table,
                                               tmp_path):
    """`spark_tpu.sql.profile.dir` traces the whole attempt, the
    streaming splice with its chunks included."""
    qe = _stream_qe(session, stream_table, prefetch=True)
    session.conf.set(PROFILE_KEY, str(tmp_path))
    try:
        qe.collect()
    finally:
        session.conf.set(PROFILE_KEY, "")
    names = _host_plane_names(str(tmp_path))
    assert {"spark_tpu.streaming", "spark_tpu.chunk.decode",
            "spark_tpu.chunk.launch", "spark_tpu.dispatch"} <= set(names)


def test_resident_scan_ingest_names_its_columns(session, stream_table):
    """A scan that loads whole leaves its columns' convert and put
    under the `ingest` phase."""
    from spark_tpu.io.sources import ParquetSource
    before = _ingest_counters(session)
    session.register_table("resident_spans_t",
                           ParquetSource(stream_table, "resident_spans_t"))
    session.conf.set(CHUNK_KEY, 1 << 20)
    qe = (session.table("resident_spans_t").group_by(col("k"))
          .agg(F.sum(col("v")).alias("s")))._qe()
    qe.collect()
    (ingest,) = [s for s in qe.spans.spans if s.name == "ingest"]
    kids = [s.name for s in qe.spans.spans if s.parent == ingest.id]
    assert sorted(kids) == ["chunk.convert"] * 2 + ["chunk.put"] * 2
    assert not any(s.name == "streaming" for s in qe.spans.spans)
    assert _ingest_counters(session)["ingest_chunks"] \
        == before["ingest_chunks"]


def test_range_stream_names_its_two_acts(session):
    """A streamed Range has no host loop: the fused chunk loop found
    or built, then its one launch, stand under `streaming` by the
    names a driven stream's acts have."""
    session.conf.set(CHUNK_KEY, 1024)
    qe = (session.range(10_000).group_by((col("id") % 7).alias("k"))
          .agg(F.sum(col("id")).alias("s")))._qe()
    out = qe.collect().to_pandas()
    assert int(out["s"].sum()) == 10_000 * 9_999 // 2
    (streaming,) = [s for s in qe.spans.spans if s.name == "streaming"]
    kids = sorted((s for s in qe.spans.spans if s.parent == streaming.id),
                  key=lambda s: s.id)
    assert [s.name for s in kids] == ["stream.begin", "chunk.launch"]
    assert not any(s.name == "stream.verdict" for s in qe.spans.spans)
    assert qe.spans.open_spans() == {}


def test_span_recorder_parent_thread_and_discard():
    import threading
    from spark_tpu.observability import SpanRecorder, to_chrome_trace
    rec = SpanRecorder(query_id=9, max_spans=8)
    with rec.span("outer", x=1) as outer:
        with rec.span("inner"):
            rec.mark("note")
        assert rec.current() == outer.id
        rec_cause = rec.current()

        def work():
            with rec.span("worker", parent=rec_cause) as w:
                with rec.span("worker.inner"):
                    pass
                w.attrs["rows"] = 3
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with rec.span("nothing") as nothing:
            rec.discard(nothing)
        with rec.span("kept") as kept:
            rec.record("handed_over", 1.0, 2.0)
            rec.discard(kept)  # a child names it: it stays
    by_name = {s.name: s for s in rec.spans}
    assert "nothing" not in by_name and rec.open_spans() == {}
    assert by_name["inner"].parent == outer.id
    assert by_name["note"].parent == by_name["inner"].id
    assert by_name["worker"].parent == outer.id
    assert by_name["worker"].tid != outer.tid
    assert by_name["worker"].attrs == {"rows": 3}
    assert by_name["worker.inner"].parent == by_name["worker"].id
    assert by_name["handed_over"].parent == by_name["kept"].id
    assert by_name["outer"].parent is None
    events = {e["name"]: e for e in to_chrome_trace(rec)["traceEvents"]}
    assert events["worker"]["tid"] == by_name["worker"].tid
    assert events["inner"]["args"]["parent"] == outer.id
    assert events["inner"]["args"]["query_id"] == 9
    with pytest.raises(ValueError):
        with rec.span("raises"):
            raise ValueError("boom")
    assert rec.spans[-1].attrs["error"] == "ValueError"
    for _ in range(4):
        rec.mark("overflow")
    assert len(rec.spans) == 8 and rec.dropped > 0


def test_span_recorder_many_threads_lose_nothing():
    """More threads than cores on one recorder, switching often: every
    span arrives once, with an id of its own and its own thread's
    parent, and no stack is left behind."""
    import sys
    import threading
    from spark_tpu.observability import SpanRecorder
    n_threads, n_spans = 32, 200
    rec = SpanRecorder(query_id=1, max_spans=10 ** 6)

    def work(k):
        for i in range(n_spans):
            with rec.span("outer", k=k) as outer:
                with rec.span("inner", k=k) as inner:
                    assert inner.parent == outer.id
                rec.record("handed", 0.0, 1.0, k=k)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert len(rec.spans) == 3 * n_threads * n_spans and rec.dropped == 0
    assert len({s.id for s in rec.spans}) == len(rec.spans)
    by_id = {s.id: s for s in rec.spans}
    for s in rec.spans:
        if s.name != "outer":
            assert by_id[s.parent].attrs["k"] == s.attrs["k"]
            assert by_id[s.parent].tid == s.tid
    assert rec.open_spans() == {}


def test_phase_times_reach_the_event_to_the_microsecond(session):
    qe = _fresh_agg(session, 771)._qe()
    qe.execute_batch()
    event = qe._build_event(None)
    for k, v in qe.phase_times.items():
        assert abs(event["phase_times_s"][k] - v) <= 5e-7, (k, v)
