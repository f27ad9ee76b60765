"""Unattended streaming: network source + supervised trigger loop +
host-spillable keyed state.

The robustness tier over test_streaming_durability.py: the crash
matrix here kills the stream at every NEW seam (`stream_net_connect`,
`stream_net_recv`, `trigger_tick`, `state_spill`) for stateless /
stateful / spilled-event-time queries against a live socket producer,
and proves a fresh query over the same checkpoint recovers
byte-identical output. The non-matrix tests pin the individual
guarantees: mid-batch socket kills reconnect with zero loss and zero
duplication, poison frames quarantine without wedging the stream, the
wall-clock trigger loop skips (never queues) missed ticks under an
injected clock, the restart supervisor's backoff ladder is
deterministic under injected sleep+rng, FATAL errors park the query in
structured FAILED status with zero orphan threads, spilled state is
output-identical to resident state, and the SQL service lists/stops
live loops."""

import json
import os
import socket
import threading
import time

import numpy as np
import pandas as pd
import pytest

from spark_tpu import functions as F
from spark_tpu.config import Conf
from spark_tpu.execution import lifecycle
from spark_tpu.functions import col
from spark_tpu.io.network_source import (MAX_RECONNECTS_KEY,
                                         FrameProducer)
from spark_tpu.streaming import (SPILL_BYTES_KEY, SPILL_PARTS_KEY,
                                 TRIGGER_BACKOFF_KEY,
                                 TRIGGER_MAX_RESTARTS_KEY, MemoryStream,
                                 get_live, live_queries, read_sink)
from spark_tpu.testing import faults
from spark_tpu.testing.lockwatch import LockWatch

SEAMS = ("stream_net_connect", "stream_net_recv", "trigger_tick",
         "state_spill")

#: "spilled" = the event-time/watermark shape with a 1-byte HBM budget
#: for resident keyed state, so EVERY batch runs through the
#: host-spill backend (execution/external.py SpillableKeyedState)
SHAPES = ("stateless", "stateful", "spilled")

TRIGGER_PREFIX = "spark-tpu-stream-trigger"


# -- harness ----------------------------------------------------------------


def _schema_df(shape):
    if shape == "spilled":
        return pd.DataFrame({"ts": [pd.Timestamp("2024-01-01")],
                             "v": [0.0]})
    return pd.DataFrame({"k": pd.Series([], dtype=np.int64),
                         "v": pd.Series([], dtype=np.int64)})


def _round_df(shape, i):
    if shape == "spilled":
        base = pd.Timestamp("2024-01-01") + pd.Timedelta(seconds=30 * i)
        return pd.DataFrame(
            {"ts": [base, base + pd.Timedelta(seconds=4)],
             "v": [float(i + 1), float(2 * i + 1)]})
    return pd.DataFrame(
        {"k": np.arange(6, dtype=np.int64) + i,
         "v": np.arange(6, dtype=np.int64) * (i + 1)})


def _plan(shape, src):
    df = src.to_df()
    if shape == "stateless":
        return df.filter(col("v") >= 0), "append"
    if shape == "stateful":
        return (df.group_by(F.pmod(col("k"), 5).alias("g"))
                .agg(F.sum(col("v")).alias("s"),
                     F.count().alias("c")), "complete")
    return (df.with_watermark("ts", "10 seconds")
            .group_by(F.window(col("ts"), "10 seconds").alias("w"))
            .agg(F.sum(col("v")).alias("s"),
                 F.count().alias("c")), "complete")


def _norm(shape, pdf):
    if pdf is None or not len(pdf):
        return pdf
    key = {"stateful": "g", "spilled": "w"}.get(shape)
    if key is not None and key in pdf.columns:
        return pdf.sort_values(key).reset_index(drop=True)
    return pdf.reset_index(drop=True)


def _join_loop(q, want_status, timeout_s=15.0):
    """Wait for the supervised loop to reach a terminal status."""
    deadline = time.monotonic() + timeout_s
    while q.status == "RUNNING" and time.monotonic() < deadline:
        time.sleep(0.01)
    assert q.status == want_status, (q.status, q.exception())


class _NetFeeder:
    """One (shape, sink) network-stream fixture: a live FrameProducer
    plus fresh queries over ONE persistent checkpoint. `hard_crash`
    closes the consumer socket the way a dead process would — the
    producer notices the FIN and frees its serve loop for the next
    (recovered) consumer's connection."""

    def __init__(self, session, shape, sink, base, tag):
        self.session = session
        self.shape = shape
        self.producer = FrameProducer()
        self.port = self.producer.start()
        self.ck = os.path.join(base, f"ck_{tag}")
        self.sink = (os.path.join(base, f"sink_{tag}")
                     if sink == "file" else None)
        self._n = 0

    def feed(self):
        self.producer.send(_round_df(self.shape, self._n))
        self._n += 1

    def query(self):
        src = self.session.network_stream(
            "127.0.0.1", self.port, _schema_df(self.shape))
        plan_df, mode = _plan(self.shape, src)
        return plan_df.write_stream(self.ck, output_mode=mode,
                                    sink_path=self.sink)

    @staticmethod
    def hard_crash(q):
        q.stream.close()

    def close(self):
        self.producer.close()


# -- the crash matrix -------------------------------------------------------


@pytest.mark.parametrize("sink", ["memory", "file"])
@pytest.mark.parametrize("shape", SHAPES)
def test_unattended_crash_matrix(session, tmp_path, shape, sink):
    if shape == "spilled":
        session.conf.set(SPILL_BYTES_KEY, 1)
        session.conf.set(SPILL_PARTS_KEY, 4)
    base = str(tmp_path)
    # uninterrupted baseline: 3 feed rounds, one query start to finish
    fb = _NetFeeder(session, shape, sink, base, "base")
    try:
        qb = fb.query()
        for _ in range(3):
            fb.feed()
            qb.process_available()
        want_concat = (pd.concat(qb.results(), ignore_index=True)
                       if shape == "stateless" else None)
        want_final = _norm(shape, qb.latest())
        want_sink = (_norm(shape, read_sink(fb.sink))
                     if sink == "file" else None)
        fb.hard_crash(qb)
    finally:
        fb.close()

    for seam in SEAMS:
        f = _NetFeeder(session, shape, sink, base, seam)
        try:
            q = f.query()
            f.feed()
            q.process_available()  # batch 0 commits clean
            f.feed()
            fired = False
            if seam == "trigger_tick":
                # the seam lives at the top of the supervised loop's
                # tick: a fatal there parks the query in FAILED — the
                # in-loop flavor of a hard crash
                with faults.inject(session.conf,
                                   "trigger_tick:fatal:1"):
                    q.start(trigger_ms=5)
                    _join_loop(q, "FAILED")
                fired = "FaultInjected" in (q.exception() or "")
                q.stop()
            else:
                if seam == "stream_net_connect":
                    # the seam only fires when a connect happens: kill
                    # the warm connection so batch 1 must reconnect
                    f.producer.kill_connection()
                with faults.inject(session.conf,
                                   f"{seam}:fatal:1") as fp:
                    try:
                        q.process_available()  # crash mid-batch-1
                    except faults.FaultInjected:
                        fired = True
            # state_spill only exists on the spilled shape; every
            # other (seam, shape) must actually crash or the cell is
            # vacuous
            expect_fire = not (seam == "state_spill"
                               and shape != "spilled")
            assert fired == expect_fire, (shape, sink, seam)
            survivors = dict(q._sink_results)
            f.hard_crash(q)
            del q  # the hard crash: the query object is GONE
            f.feed()
            q2 = f.query()  # fresh query over the same checkpoint
            q2.process_available()
            combined = dict(survivors)
            combined.update(q2._sink_results)
            cell = f"{shape}/{sink}/{seam}"
            try:
                if shape == "stateless":
                    got = pd.concat(
                        [combined[k] for k in sorted(combined)],
                        ignore_index=True)
                    pd.testing.assert_frame_equal(got, want_concat)
                else:
                    got_final = _norm(shape, combined[max(combined)])
                    pd.testing.assert_frame_equal(got_final, want_final)
                if sink == "file":
                    got_sink = _norm(shape, read_sink(f.sink))
                    pd.testing.assert_frame_equal(
                        got_sink.sort_values(list(got_sink.columns))
                        .reset_index(drop=True),
                        want_sink.sort_values(list(want_sink.columns))
                        .reset_index(drop=True))
            except AssertionError as e:
                raise AssertionError(
                    f"crash-matrix cell {cell}: {e}") from e
            f.hard_crash(q2)
        finally:
            f.close()
    LockWatch().assert_no_thread_leak(TRIGGER_PREFIX)


# -- network source: reconnect ladder ---------------------------------------


def test_socket_kill_mid_stream_zero_loss_zero_dup(session, tmp_path):
    """The headline acceptance: a connection killed mid-stream (both
    flavors — clean EOF at a frame boundary and a torn frame mid-
    payload) resumes at the durable offset via the handshake: every
    row arrives exactly once, one `streaming_reconnects` tick per
    re-established connection."""
    prod = FrameProducer()
    port = prod.start()
    try:
        src = session.network_stream("127.0.0.1", port,
                                     _schema_df("stateless"))
        q = (src.to_df().filter(col("v") >= 0)
             .write_stream(str(tmp_path / "ck"), output_mode="append",
                           sink_path=str(tmp_path / "sink")))
        prod.send(_round_df("stateless", 0))
        q.process_available()
        rc0 = session.metrics.counter("streaming_reconnects").value
        # clean kill: EOF at a frame boundary, frames pending
        prod.kill_connection()
        prod.send(_round_df("stateless", 1))
        q.process_available()
        assert session.metrics.counter(
            "streaming_reconnects").value == rc0 + 1
        # torn kill: half a frame on the wire -> stall -> reconnect ->
        # the SAME frame arrives whole (nothing durable was skipped,
        # nothing durable was resent)
        prod.kill_connection_midframe()
        prod.send(_round_df("stateless", 2))
        q.process_available()
        assert session.metrics.counter(
            "streaming_reconnects").value == rc0 + 2
        got = pd.concat(q.results(), ignore_index=True)
        want = pd.concat([_round_df("stateless", i) for i in range(3)],
                         ignore_index=True)
        pd.testing.assert_frame_equal(got, want)
        assert src.quarantined() == []
        got_sink = read_sink(str(tmp_path / "sink"))
        pd.testing.assert_frame_equal(
            got_sink.sort_values(["k", "v"]).reset_index(drop=True),
            want.sort_values(["k", "v"]).reset_index(drop=True))
        src.close()
    finally:
        prod.close()


def test_poison_frame_quarantined_stream_flows(session, tmp_path):
    """One undecodable frame cannot wedge the stream: it quarantines
    durably (seen-log entry + counter), later frames flow, and a fresh
    query over the checkpoint skips it without re-decoding or
    re-counting."""
    prod = FrameProducer()
    port = prod.start()
    ck = str(tmp_path / "ck")
    q0 = session.metrics.counter("streaming_frames_quarantined").value
    try:
        def build():
            src = session.network_stream("127.0.0.1", port,
                                         _schema_df("stateless"))
            return src, (src.to_df().filter(col("v") >= 0)
                         .write_stream(ck, output_mode="append"))

        src, q = build()
        prod.send(_round_df("stateless", 0))
        prod.send_poison()
        prod.send(_round_df("stateless", 1))
        with pytest.warns(UserWarning, match="poison network frame"):
            q.process_available()
        assert session.metrics.counter(
            "streaming_frames_quarantined").value == q0 + 1
        got = pd.concat(q.results(), ignore_index=True)
        want = pd.concat([_round_df("stateless", 0),
                          _round_df("stateless", 1)],
                         ignore_index=True)
        pd.testing.assert_frame_equal(got, want)
        quar = src.quarantined()
        assert len(quar) == 1 and quar[0]["index"] == 1
        src.close()
        del q
        src2, q2 = build()
        q2.process_available()  # drained: nothing new
        assert len(src2.quarantined()) == 1
        assert session.metrics.counter(
            "streaming_frames_quarantined").value == q0 + 1
        src2.close()
    finally:
        prod.close()


def test_reconnect_ladder_exhaustion_is_transient_shaped(session,
                                                         tmp_path):
    """A producer that never comes back exhausts the per-poll ladder
    with a TRANSIENT-classified error (the trigger supervisor's retry
    contract), not a raw socket error."""
    from spark_tpu.execution import failures
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()  # nothing listens here now
    session.conf.set(MAX_RECONNECTS_KEY, 1)
    session.conf.set(
        "spark_tpu.streaming.source.network.backoffMs", 1)
    src = session.network_stream("127.0.0.1", port,
                                 _schema_df("stateless"))
    q = (src.to_df().filter(col("v") >= 0)
         .write_stream(str(tmp_path / "ck"), output_mode="append"))
    with pytest.raises(ConnectionError,
                       match="connection attempt budget exhausted"):
        q.process_available()
    try:
        q.process_available()
    except ConnectionError as e:
        assert failures.classify(e) == failures.FailureClass.TRANSIENT


# -- supervised trigger loop ------------------------------------------------


def test_trigger_overrun_skips_never_queues(session, tmp_path):
    """Injected-clock pacing: a batch 2.5x slower than the interval
    SKIPS the missed ticks and re-anchors on the wall-clock grid —
    sleeps stay positive (no backlog of queued ticks is ever run
    back-to-back)."""
    src = MemoryStream(session, _schema_df("stateless"))
    q = (src.to_df().filter(col("v") >= 0)
         .write_stream(str(tmp_path / "ck"), output_mode="append"))

    class _Clk:
        t = 0.0

    clk = _Clk()
    waits = []

    def sleep_fn(s):
        waits.append(s)
        clk.t += s
        if len(waits) >= 5:
            raise lifecycle.QueryCancelledError("test: stop the loop")

    orig = q.process_available

    def slow():
        clk.t += 0.25  # the batch costs 2.5 trigger intervals
        return orig()

    q.process_available = slow
    q.start(trigger_ms=100.0, clock=lambda: clk.t, sleep=sleep_fn)
    q._loop_thread.join(timeout=10)
    assert not q._loop_thread.is_alive()
    assert q.status == "STOPPED" and q.exception() is None
    s = q.state()
    # each iteration: tick, overrun by 150ms -> skip 2, wait 50ms
    assert s["ticks"] == 5
    assert s["skipped_ticks"] == 10
    assert all(w == pytest.approx(0.05) for w in waits), waits
    assert all(w > 0 for w in waits)
    LockWatch().assert_no_thread_leak(TRIGGER_PREFIX)


def test_supervisor_backoff_deterministic_then_parks(session, tmp_path):
    """Transient tick failures climb ONE deterministic ladder under
    injected sleep+rng — delays double from trigger.backoffMs — and an
    exhausted ladder parks the query in FAILED with the error
    preserved and zero orphan threads."""
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    session.conf.set(MAX_RECONNECTS_KEY, 0)
    session.conf.set(
        "spark_tpu.streaming.source.network.backoffMs", 1)
    session.conf.set(TRIGGER_MAX_RESTARTS_KEY, 3)
    session.conf.set(TRIGGER_BACKOFF_KEY, 8)
    src = session.network_stream("127.0.0.1", port,
                                 _schema_df("stateless"))
    q = (src.to_df().filter(col("v") >= 0)
         .write_stream(str(tmp_path / "ck"), output_mode="append"))
    sleeps = []

    class _Rng:
        @staticmethod
        def random():
            return 1.0  # jitter factor pinned to 1.0

    q.start(trigger_ms=5, clock=lambda: 0.0,
            sleep=lambda s: sleeps.append(round(s * 1e3, 6)),
            rng=_Rng())
    _join_loop(q, "FAILED")
    q._loop_thread.join(timeout=10)
    assert sleeps == [8.0, 16.0, 32.0]  # backoffMs * 2^n, jitter = 1
    assert q.state()["restarts"] == 3
    assert "connection attempt budget exhausted" in q.exception()
    assert get_live(q._live_id) is None  # parked loops unregister
    q.stop()  # idempotent on a parked loop
    LockWatch().assert_no_thread_leak(TRIGGER_PREFIX)


def test_fatal_batch_parks_failed_zero_orphans(session, tmp_path):
    """A FATAL batch error (unbounded group domain) must NOT retry:
    the query parks immediately in structured FAILED status, restarts
    stay 0, and no trigger thread outlives the park."""
    src = MemoryStream(session, _schema_df("stateful"))
    q = (src.to_df().group_by(col("k").alias("g"))
         .agg(F.sum(col("v")).alias("s"))
         .write_stream(str(tmp_path / "ck")))
    src.add_data(_round_df("stateful", 0))
    q.start(trigger_ms=5)
    _join_loop(q, "FAILED")
    assert "ValueError" in q.exception()
    assert q.state()["restarts"] == 0
    assert get_live(q._live_id) is None
    q.stop()
    LockWatch().assert_no_thread_leak(TRIGGER_PREFIX)


def test_trigger_loop_runs_commits_and_stops_bounded(session, tmp_path):
    """Happy path end to end on the real clock: start() drives batches
    unattended, stop() joins bounded, is idempotent, and a stopped
    query's durable state serves a fresh manual-trigger query."""
    src = MemoryStream(session, _schema_df("stateful"))
    ck = str(tmp_path / "ck")

    def build():
        return (src.to_df()
                .group_by(F.pmod(col("k"), 5).alias("g"))
                .agg(F.sum(col("v")).alias("s")).write_stream(ck))

    q = build()
    src.add_data(_round_df("stateful", 0))
    q.start(trigger_ms=10)
    deadline = time.monotonic() + 15
    while q._committed_batch < 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    src.add_data(_round_df("stateful", 1))
    while q._committed_batch < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert q._committed_batch >= 1
    live_id = q._live_id
    assert any(r["id"] == live_id and r["status"] == "RUNNING"
               for r in live_queries())
    q.stop()
    assert q.status == "STOPPED"
    q.stop()  # idempotent
    assert all(r["id"] != live_id for r in live_queries())
    LockWatch().assert_no_thread_leak(TRIGGER_PREFIX)
    # durable state is live after the loop stopped: a fresh query
    # folds the next round onto it, landing on an uninterrupted
    # twin's totals
    src.add_data(_round_df("stateful", 2))
    q2 = build()
    q2.process_available()
    src3 = MemoryStream(session, _schema_df("stateful"))
    q3 = (src3.to_df().group_by(F.pmod(col("k"), 5).alias("g"))
          .agg(F.sum(col("v")).alias("s"))
          .write_stream(str(tmp_path / "ck3")))
    for i in range(3):
        src3.add_data(_round_df("stateful", i))
    q3.process_available()
    pd.testing.assert_frame_equal(
        q2.latest().sort_values("g").reset_index(drop=True),
        q3.latest().sort_values("g").reset_index(drop=True))


def test_deadline_caps_unattended_loop(session, tmp_path):
    """execution.queryDeadlineMs bounds an unattended stream end to
    end: the loop's lifecycle token expires mid-pacing-sleep and the
    query parks FAILED with the structured deadline error."""
    session.conf.set(lifecycle.DEADLINE_KEY, 150)
    src = MemoryStream(session, _schema_df("stateless"))
    q = (src.to_df().filter(col("v") >= 0)
         .write_stream(str(tmp_path / "ck"), output_mode="append"))
    q.start(trigger_ms=20)
    _join_loop(q, "FAILED")
    assert "QueryDeadlineError" in q.exception()
    LockWatch().assert_no_thread_leak(TRIGGER_PREFIX)


# -- host-spillable keyed state ---------------------------------------------


def test_spilled_state_output_parity_with_resident(session, tmp_path):
    """A 1-byte state budget reroutes the event-time path through the
    host-spill backend: the stream COMPLETES, output is byte-identical
    to a resident run, the spill counter ticks, and crash recovery is
    unchanged (fresh query over the spilled checkpoint lands on the
    same totals)."""
    # resident twin first (conf untouched)
    src_r = MemoryStream(session, _schema_df("spilled"))
    plan_r, mode = _plan("spilled", src_r)
    q_r = plan_r.write_stream(str(tmp_path / "ck_r"), output_mode=mode)
    for i in range(3):
        src_r.add_data(_round_df("spilled", i))
        q_r.process_available()
    want = _norm("spilled", q_r.latest())
    assert q_r._spill is None  # resident run never engaged

    session.conf.set(SPILL_BYTES_KEY, 1)
    session.conf.set(SPILL_PARTS_KEY, 4)
    sp0 = session.metrics.counter("streaming_spill_bytes").value
    src_s = MemoryStream(session, _schema_df("spilled"))
    ck = str(tmp_path / "ck_s")
    plan_s, _ = _plan("spilled", src_s)
    q_s = plan_s.write_stream(ck, output_mode=mode)
    for i in range(3):
        src_s.add_data(_round_df("spilled", i))
        q_s.process_available()
    assert q_s._spill is not None  # the budget engaged the backend
    assert session.metrics.counter(
        "streaming_spill_bytes").value > sp0
    spill_dir = os.path.join(ck, "state", "spill")
    assert [f for f in os.listdir(spill_dir)
            if f.endswith(".parquet")]
    pd.testing.assert_frame_equal(_norm("spilled", q_s.latest()), want)
    # crash recovery rides the SAME delta/snapshot store: a fresh
    # query over the spilled checkpoint folds the next round onto
    # identical state
    del q_s
    src_r.add_data(_round_df("spilled", 3))
    q_r.process_available()
    src_s.add_data(_round_df("spilled", 3))
    plan_s2, _ = _plan("spilled", src_s)
    q_s2 = plan_s2.write_stream(ck, output_mode=mode)
    q_s2.process_available()
    pd.testing.assert_frame_equal(
        _norm("spilled", q_s2.latest()),
        _norm("spilled", q_r.latest()))


# -- observability: v6 trigger record ---------------------------------------


def test_trigger_event_log_v6_summary_and_validator(session, tmp_path):
    """Supervised ticks that ran batches land a schema-v6 `trigger`
    record in the event log; streaming_summary folds them in beside
    the batch rows; events_tool validates v6 and rejects a pre-v6 line
    smuggling a trigger record."""
    from spark_tpu import history
    from spark_tpu.observability.listener import QueryListener

    class Recorded(QueryListener):
        """Released once a tick's trigger record is posted: the tick
        that committed a batch has finished with it."""

        def __init__(self):
            self.ticks = threading.Semaphore(0)

        def on_streaming_trigger(self, event):
            self.ticks.release()

    recorded = Recorded()
    ev_dir = str(tmp_path / "events")
    session.conf.set("spark_tpu.sql.eventLog.dir", ev_dir)
    session.add_listener(recorded)
    try:
        src = MemoryStream(session, _schema_df("stateful"))
        q = (src.to_df().group_by(F.pmod(col("k"), 5).alias("g"))
             .agg(F.sum(col("v")).alias("s"))
             .write_stream(str(tmp_path / "ck")))
        src.add_data(_round_df("stateful", 0))
        q.start(trigger_ms=10)
        # the bound only keeps a broken loop from hanging the suite
        assert recorded.ticks.acquire(timeout=600), q.state()
        src.add_data(_round_df("stateful", 1))
        assert recorded.ticks.acquire(timeout=600), q.state()
        q.stop()
    finally:
        session.remove_listener(recorded)
        session.conf.set("spark_tpu.sql.eventLog.dir", "")
    events = history.read_event_log(ev_dir)
    assert (events["schema_version"].dropna() == 7).all()
    ss = history.streaming_summary(events)
    trig = ss[ss["record"] == "trigger"]
    assert len(trig) >= 2, ss
    assert (trig["batches_run"] >= 1).all()
    assert (trig["restarts"] == 0).all()
    assert (trig["reconnects"] == 0).all()
    assert (trig["source"] == "memory").all()
    assert (trig["skew_ms"] >= 0).all()
    assert trig["tick"].is_monotonic_increasing
    assert len(ss[ss["record"] == "batch"]) >= 2
    # the versioned-schema validator accepts the v6 lines
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "events_tool", os.path.join(root, "scripts", "events_tool.py"))
    et = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(et)
    assert et.validate([ev_dir]) == []
    # a pre-v6 line smuggling a trigger record is rejected
    bad = {"schema_version": 5, "query_id": 1, "ts": 1.0,
           "status": "ok", "plan": "x", "trigger": {"tick": 1}}
    bad_path = os.path.join(ev_dir, "app-bad.jsonl")
    with open(bad_path, "w") as f:
        f.write(json.dumps(bad) + "\n")
    problems = et.validate([bad_path])
    assert any("v6 field 'trigger'" in p for p in problems), problems
    # and a malformed v6 trigger record is rejected
    bad2 = dict(bad, schema_version=6,
                trigger={"tick": "one", "skew_ms": 0.0,
                         "batches_run": 1, "restarts": 0,
                         "source": "memory", "reconnects": 0})
    with open(bad_path, "w") as f:
        f.write(json.dumps(bad2) + "\n")
    problems = et.validate([bad_path])
    assert any("malformed trigger record" in p for p in problems), \
        problems


# -- service visibility -----------------------------------------------------


def test_service_lists_and_stops_live_streams(session, tmp_path):
    """GET /queries folds live trigger loops in under `streams`;
    DELETE /queries/stream-<n> stops the loop bounded (zero orphan
    threads) and a second DELETE is a structured 404."""
    from spark_tpu.service.server import SqlService
    svc = SqlService(Conf())
    src = MemoryStream(session, _schema_df("stateless"))
    q = (src.to_df().filter(col("v") >= 0)
         .write_stream(str(tmp_path / "ck"), output_mode="append"))
    q.start(trigger_ms=20)
    try:
        live_id = q._live_id
        rows = [s for s in svc.query_listing()["streams"]
                if s["id"] == live_id]
        assert rows and rows[0]["status"] == "RUNNING"
        assert rows[0]["source"] == "memory"
        assert rows[0]["trigger_ms"] == 20.0
        status, body = svc.cancel_query(live_id)
        assert status == 200
        assert body["status"] == "stopped"
        assert body["query_status"] == "STOPPED"
        assert get_live(live_id) is None
        assert all(s["id"] != live_id
                   for s in svc.query_listing()["streams"])
        status2, body2 = svc.cancel_query(live_id)
        assert status2 == 404 and body2["error"] == "NOT_FOUND"
        LockWatch().assert_no_thread_leak(TRIGGER_PREFIX)
    finally:
        q.stop()
        svc.stop()
