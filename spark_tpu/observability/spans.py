"""Per-query spans: a tree over both threads, on two clocks.

Each QueryExecution owns one `SpanRecorder`; a served query adopts the
one its request was born with (`SqlService.submit(spans=)`,
`QueryExecution(spans=)`), whose origin is the request's first
instant: the connection's accept, not the query's construction. Its
spans are the front end's, on the handler's thread (`http.accept`,
`http.read`, the service's `queue`, `parse`; after the query
`finish`, `encode` and `http.write`), the lifecycle phases
(`query.begin`, `analysis`, `optimize`, `plan`, `replan.key`,
`analyze`, `predict`, `streaming` or for a query that streams nothing
`stream.verdict`, `external`, `ingest`, `analyze_jaxpr`,
`stage.lookup`, `compile`, `deserialize`, `dispatch` with
`dispatch.launch` / `dispatch.sync`, `stage_event`,
`plan.fingerprint`, `end_event`, `egress`), the chunk pipeline of a
streamed scan (`stream.open`, `prefetch.start`, `chunk.wait`,
`stream.begin`; `chunk.decode`, `chunk.unify` and one `chunk.convert`
per column on the thread that makes the chunk's host half, a large
chunk's columns on threads of their own; `chunk.to_device` with one
`chunk.put` per column; `chunk.launch`, `stream.drain`; a resident
load leaves `chunk.convert` / `chunk.put` per column under `ingest`)
and the marks (`aqe_replan`, `aqe_overflow`, `retry:<action>`,
`cancelled`). Names are fixed and carry no ordinal: the benchmark's
readers go by them (PERF.md section 3). No span holds the whole
request: the frame is the recorder's origin and the timeline's
`request_ms`.

A span knows the span that caused it (`parent`: the span open on the
same thread when it started, else the one handed over from the thread
that started the work) and its thread (`tid`), so a layer's self time
is its length less the union of its children on its own thread.

Clocks: `time.perf_counter` (cheap, monotonic) with a wall-clock
anchor captured at recorder creation, so export maps to epoch
microseconds: the Chrome trace-event "X" (complete-event) format,
loadable in Perfetto / chrome://tracing. A span opened with `span()`
also holds a `jax.profiler.TraceAnnotation("spark_tpu.<name>")` for
its interval: whenever a profiler session is on (the benchmark's
`--trace 1`, `spark_tpu.sql.profile.dir`) the same interval stands on
the trace's host plane, on the device events' clock. With no session
on the annotation is a flag test.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

#: prefix of every engine span in the profiler's trace
ANNOTATION_PREFIX = "spark_tpu."


def _native_id() -> int:
    """The calling thread's native id as `threading` noted it when the
    thread started: `threading.get_native_id()` asks the kernel every
    time, 5.8 us a call where the benchmark runs against 0.2 us for
    this (my chip runs, PR 39, PERF.md), and a span asked once, a
    record twice."""
    return threading.current_thread().native_id


@dataclass
class Span:
    name: str
    t0: float            # perf_counter seconds
    t1: float
    attrs: Dict = field(default_factory=dict)
    id: int = 0          # unique within the recorder, in order of start
    parent: Optional[int] = None  # id of the span that caused this one
    tid: int = 0         # native id of the thread it ran on

    @property
    def dur_ms(self) -> float:
        return (self.t1 - self.t0) * 1e3


class SpanRecorder:
    """Bounded span list for one QueryExecution. Spans are appended as
    they END; `id` orders them by start. Safe to use from the query's
    thread and its ingest-prefetch worker at once. `origin` (a
    `perf_counter` reading, now or earlier) is where `t0_ms` counts
    from: a served request's recorder is made before its query and
    starts at the request's first instant."""

    def __init__(self, query_id: Optional[int] = None,
                 max_spans: int = 1000, max_shard_records: int = 4096,
                 origin: Optional[float] = None):
        #: the engine's id of the query; None while a request's
        #: recorder has no query yet (`QueryExecution(spans=)` sets it)
        self.query_id = query_id
        #: the service's id of the request this recorder was born for
        self.request_id: Optional[str] = None
        self.max_spans = max_spans
        self.spans: List[Span] = []
        #: spans dropped past the bound (surfaced so truncation is
        #: visible, never silent)
        self.dropped = 0
        #: per-shard telemetry records (mesh runs): dicts with shard,
        #: host, chunk, phase, rows, bytes, t0_ms, dur_ms, wait_ms,
        #: source — the event log's `shards` field (schema v3)
        self.shard_records: List[Dict] = []
        self.max_shard_records = max_shard_records
        self.shard_dropped = 0
        now = time.perf_counter()
        self._anchor_perf = now if origin is None else origin
        self._anchor_wall = time.time() - (now - self._anchor_perf)
        self._lock = threading.Lock()
        self._ids = 0
        #: native thread id -> ids of the spans open on that thread,
        #: outermost first; a thread with none open has no entry
        self._open: Dict[int, List[int]] = {}
        self._discarded: set = set()

    def add_shard_records(self, records: List[Dict]) -> None:
        room = self.max_shard_records - len(self.shard_records)
        if room < len(records):
            self.shard_dropped += len(records) - max(room, 0)
            records = records[:max(room, 0)]
        self.shard_records.extend(records)

    def rel_ms(self, t_perf: float) -> float:
        """Perf-counter time as milliseconds since the recorder anchor
        (the shared origin of span t0_ms and shard-record t0_ms)."""
        return round((t_perf - self._anchor_perf) * 1e3, 3)

    def current(self) -> Optional[int]:
        """Id of the innermost span open on the calling thread: what a
        thread hands to the worker it starts, as the worker's cause."""
        stack = self._open.get(_native_id())
        return stack[-1] if stack else None

    def open_spans(self) -> Dict[int, List[int]]:
        """{thread: ids of its open spans}; empty between queries."""
        with self._lock:
            return {tid: list(ids) for tid, ids in self._open.items()}

    def _add_locked(self, span: Span) -> None:
        if len(self.spans) >= self.max_spans:
            self.dropped += 1
        else:
            self.spans.append(span)

    def record(self, name: str, t0: float, t1: Optional[float] = None,
               **attrs) -> None:
        """An interval handed over after the fact (no annotation in
        the profiler's trace); its parent is the span open on the
        calling thread."""
        tid = _native_id()
        with self._lock:
            self._ids += 1
            stack = self._open.get(tid)
            self._add_locked(Span(
                name, t0, t1 if t1 is not None else t0, attrs, self._ids,
                stack[-1] if stack else None, tid))

    @contextlib.contextmanager
    def span(self, name: str, parent: Optional[int] = None, **attrs):
        """Open a span on the calling thread for the body's interval;
        yields it, so the body can add attributes known only at the
        end. `parent` names the cause across threads and counts only
        where the calling thread has no span open. The span is closed
        and recorded however the body leaves (`error` names what it
        raised)."""
        tid = _native_id()
        with self._lock:
            self._ids += 1
            stack = self._open.setdefault(tid, [])
            sp = Span(name, 0.0, 0.0, attrs, self._ids,
                      stack[-1] if stack else parent, tid)
            stack.append(sp.id)
        try:
            with TraceAnnotation(ANNOTATION_PREFIX + name,
                                 query_id=self.query_id):
                sp.t0 = time.perf_counter()
                try:
                    yield sp
                except BaseException as e:
                    sp.attrs["error"] = type(e).__name__
                    raise
                finally:
                    sp.t1 = time.perf_counter()
        finally:
            with self._lock:
                stack.remove(sp.id)
                if not stack:
                    self._open.pop(tid, None)
                if sp.id not in self._discarded or any(
                        s.parent == sp.id for s in self.spans):
                    self._add_locked(sp)

    def discard(self, span: Span) -> None:
        """Leave an open span out when it ends, unless a child names
        it (a phase that turned out to have nothing to do)."""
        self._discarded.add(span.id)

    def mark(self, name: str, **attrs) -> None:
        """Zero-duration span (exported as a Chrome instant event)."""
        t = time.perf_counter()
        self.record(name, t, t, **attrs)

    def wall(self, t_perf: float) -> float:
        """Map a perf_counter time onto the epoch clock."""
        return self._anchor_wall + (t_perf - self._anchor_perf)

    def to_dicts(self) -> List[Dict]:
        """Event-log form: relative start + duration in milliseconds."""
        out = []
        for s in list(self.spans):
            d = {"name": s.name,
                 "t0_ms": round((s.t0 - self._anchor_perf) * 1e3, 3),
                 "dur_ms": round(s.dur_ms, 3),
                 "id": s.id, "parent": s.parent, "tid": s.tid}
            if s.attrs:
                d["attrs"] = s.attrs
            out.append(d)
        return out


def to_chrome_trace(recorder: SpanRecorder,
                    pid: Optional[int] = None) -> Dict:
    """Chrome trace-event JSON ({"traceEvents": [...]}) from a
    recorder's spans, one row per thread (`tid`); `query_id`, `id` and
    `parent` ride in `args`. Zero-duration spans export as instant
    events (ph "i"), the rest as complete events (ph "X")."""
    pid = pid if pid is not None else os.getpid()
    events = []
    for s in list(recorder.spans):
        ts_us = recorder.wall(s.t0) * 1e6
        ev = {"name": s.name, "cat": "spark_tpu", "pid": pid,
              "tid": s.tid, "ts": ts_us}
        dur_us = (s.t1 - s.t0) * 1e6
        if dur_us <= 0:
            ev["ph"] = "i"
            ev["s"] = "t"  # thread-scoped instant
        else:
            ev["ph"] = "X"
            ev["dur"] = dur_us
        ev["args"] = dict(s.attrs, query_id=recorder.query_id, id=s.id,
                          parent=s.parent)
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# The current query's recorder (columnar ingest, chunk drivers)
# ---------------------------------------------------------------------------

#: the executor installs the running execution's recorder here; code
#: below it that has no handle on the query (`columnar.py`, the chunk
#: drivers) opens its spans through `span()`. A thread the query
#: starts does not inherit it: the prefetch worker's spans go through
#: the recorder its iterator was bound to (`io/sources.py`).
_RECORDER: ContextVar[Optional[SpanRecorder]] = \
    ContextVar("spark_tpu_span_recorder", default=None)


def current_recorder() -> Optional[SpanRecorder]:
    return _RECORDER.get()


@contextlib.contextmanager
def use_recorder(recorder: Optional[SpanRecorder]):
    token = _RECORDER.set(recorder)
    try:
        yield recorder
    finally:
        _RECORDER.reset(token)


def span(name: str, **attrs):
    """A span on the current query's recorder; outside a query a
    context that does nothing and yields None."""
    rec = _RECORDER.get()
    if rec is None:
        return contextlib.nullcontext()
    return rec.span(name, **attrs)


# ---------------------------------------------------------------------------
# Per-shard telemetry (mesh chunk drivers)
# ---------------------------------------------------------------------------

#: the executor installs the current execution's telemetry here around
#: the streaming-materialization phase; the mesh chunk drivers read it
#: (the same context-threading pattern the arbiter's enter_query uses,
#: so driver signatures — which tests monkeypatch — stay unchanged)
_SHARD_TELEMETRY: ContextVar[Optional["ShardStreamTelemetry"]] = \
    ContextVar("spark_tpu_shard_telemetry", default=None)


def current_shard_telemetry() -> Optional["ShardStreamTelemetry"]:
    return _SHARD_TELEMETRY.get()


@contextlib.contextmanager
def use_shard_telemetry(telem: Optional["ShardStreamTelemetry"]):
    token = _SHARD_TELEMETRY.set(telem)
    try:
        yield telem
    finally:
        try:
            if telem is not None:
                telem.finish()
        finally:
            # the reset must survive a raising finish: a stale context
            # var would leak this query's telemetry into the next
            _SHARD_TELEMETRY.reset(token)


class ShardStreamTelemetry:
    """Per-shard/per-chunk flight recorder for the mesh chunk drivers.

    The hot path stays sync-free: each chunk dispatch hands over the
    step's per-shard live-row array (a device-resident [n] int64,
    sharded on the data axis — appending it costs no transfer), and the
    PREVIOUS chunk's buffer is flushed at the next chunk boundary,
    where the driver is already doing host work (Parquet decode of the
    next chunk). A flush walks the array's addressable shards in mesh
    order, timing the block-until-ready wait it pays on each — the
    per-shard completion profile: a straggling device inflates its own
    wait window while shards that kept up read back instantly — then
    pulls the row counts in one device_get and emits one record per
    (shard, chunk) plus a host-side ingest record. Records land on the
    SpanRecorder (event-log `shards`, schema v3) and are posted on the
    listener bus (`on_shard_records`) for the StragglerMonitor.

    The `shard_chunk` chaos seam fires once per (chunk, shard) inside
    the timed wait window, so an injected `slow` fault models exactly
    one straggling shard (hit ordinal = chunk * n_shards + shard + 1).
    """

    def __init__(self, recorder: SpanRecorder, mesh, query_id: int,
                 bus=None, source: str = "stream_mesh"):
        from ..parallel.mesh import shard_hosts
        self.recorder = recorder
        self.query_id = query_id
        self.bus = bus
        self.source = source
        self.hosts = shard_hosts(mesh)
        self.n = len(self.hosts)
        self._dev_pos = {d.id: i for i, d in enumerate(mesh.devices.flat)}
        #: (chunk, shard_rows device array, row_width, t_dispatch0)
        self._pending: Optional[tuple] = None

    # -- driver-facing hooks (hot path: no device sync) ---------------------

    def chunk_ingested(self, chunk: int, rows: int, nbytes: int,
                       t0: float, t1: float) -> None:
        """Host-side decode of one chunk (the ingest phase): recorded
        directly — it is already host wall-clock, nothing to flush."""
        import jax
        self.recorder.add_shard_records([{
            "shard": None, "host": int(jax.process_index()),
            "chunk": int(chunk), "phase": "ingest", "rows": int(rows),
            "bytes": int(nbytes), "t0_ms": self.recorder.rel_ms(t0),
            "dur_ms": round((t1 - t0) * 1e3, 3), "source": self.source}])

    def chunk_dispatched(self, chunk: int, shard_rows, row_width: int,
                         t_dispatch: float) -> None:
        """Buffer one chunk's per-shard live-row array (device-side;
        no sync) after flushing the previous chunk's buffer."""
        if self._pending is not None and self._pending[0] == int(chunk):
            # retried attempt of the SAME chunk (ChunkRetrier replay):
            # discard the failed attempt's buffer — flushing it would
            # emit duplicate (shard, chunk) records (double-counting
            # row totals, skewing straggler medians) off an array the
            # failed dispatch may have poisoned
            self._pending = None
        self._flush_pending()
        self._pending = (int(chunk), shard_rows, int(row_width),
                         t_dispatch)

    def finish(self) -> None:
        self._flush_pending()

    # -- flush (chunk boundary / stream end) --------------------------------

    def _shard_pieces(self, arr) -> List:
        """The array's addressable shards in mesh-axis order (None
        placeholders for shards this process cannot see — multi-host)."""
        pieces = [None] * self.n
        for s in getattr(arr, "addressable_shards", ()) or ():
            i = self._dev_pos.get(getattr(s.device, "id", None))
            if i is not None:
                pieces[i] = s.data
        return pieces

    def _flush_pending(self) -> None:
        """Flush the buffered chunk into records. The WHOLE flush is
        failure-isolated: an async device error surfacing through
        block_until_ready here must neither fail the query nor mask
        the stream's own exception (finish() runs on unwind paths) —
        the dispatch that owns the error re-raises it at the engine's
        own sync point, where the failure ladder classifies it. A
        raising fault injected at the shard_chunk seam is likewise
        swallowed: the seam models a SLOW shard, not a dead one."""
        if self._pending is None:
            return
        try:
            self._flush_pending_inner()
        except Exception as e:  # noqa: BLE001 — never fail the query
            import warnings
            warnings.warn(f"per-shard telemetry flush failed (records "
                          f"dropped): {type(e).__name__}: {e}")

    def _flush_pending_inner(self) -> None:
        import jax
        from ..testing import faults
        chunk, arr, row_width, t0 = self._pending
        self._pending = None
        pieces = self._shard_pieces(arr)
        waits = []
        for i in range(self.n):
            w0 = time.perf_counter()
            # chaos seam INSIDE the timed window: `slow` on hit
            # chunk*n + shard + 1 models that one shard straggling
            faults.fire("shard_chunk")
            if pieces[i] is not None:
                jax.block_until_ready(pieces[i])
            waits.append((time.perf_counter() - w0) * 1e3)
        t_done = time.perf_counter()
        # read each shard's count from its ADDRESSABLE piece — a
        # device_get of the global array raises on a multi-host mesh
        # (non-addressable devices). Shards owned by other processes
        # get no record HERE: every host runs this same driver and
        # records its own shards, so the fleet's logs union to full
        # coverage instead of each host fabricating remote waits.
        rows = [None if pieces[i] is None
                else int(jax.device_get(pieces[i]).reshape(-1)[0])
                for i in range(self.n)]
        records = [{
            "shard": i, "host": self.hosts[i], "chunk": chunk,
            "phase": "compute", "rows": rows[i],
            "bytes": rows[i] * row_width,
            "t0_ms": self.recorder.rel_ms(t0),
            "dur_ms": round((t_done - t0) * 1e3, 3),
            "wait_ms": round(waits[i], 3), "source": self.source,
        } for i in range(self.n) if rows[i] is not None]
        self.recorder.add_shard_records(records)
        if self.bus is not None:
            from .listener import ShardChunkEvent
            self.bus.post("on_shard_records", ShardChunkEvent(
                query_id=self.query_id, ts=time.time(), chunk=chunk,
                records=records))
