"""The two ways a client reaches the engine, behind one shape.

`http`: a `SqlService` on an ephemeral port in this process (one
process holds the chip), queries as `POST /sql`, JSON rows back; the
status record, the timeline and `/metrics` are read over HTTP as any
client would.

`dataframe`: a `SparkTpuSession` in this process, queries built by
`benchmark/queries/<builder>.py`, `collect()` to pandas, as upstream's
`AggregateBenchmark` drives a session.

Both give, per query, the same record: the client's clock around it,
the answer as {column: numpy array}, the engine's status, and (on
request) its phase times, spans and operator counters.
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request
from typing import Dict, List

import numpy as np

from . import spec

#: a request that has not answered by then counts as unanswered
REQUEST_TIMEOUT_S = 300.0


def parse_prometheus(text: str) -> Dict[str, float]:
    """Unlabelled samples of a Prometheus exposition: name -> value."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#") or "{" in line:
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            pass
    return out


def _columns(names: List[str], rows: List[Dict]) -> Dict[str, np.ndarray]:
    return {c: np.array([r[c] for r in rows]) for c in names}


class HttpEntry:
    kind = "http"

    def __init__(self, cell: spec.Cell, tables: Dict[str, str]):
        from spark_tpu import Conf
        from spark_tpu.io.sources import ParquetSource
        from spark_tpu.service.server import SqlService
        conf = Conf()
        conf.set("spark_tpu.service.port", 0)
        for k, v in cell.config.get("conf", {}).items():
            conf.set(k, v)

        def register(session):
            for name, path in tables.items():
                session.register_table(name, ParquetSource(path, name))

        self.svc = SqlService(conf, init_session=register).start()
        self.base = f"http://127.0.0.1:{self.svc.port}"

    def _get(self, path: str):
        with urllib.request.urlopen(self.base + path, timeout=60) as resp:
            return resp.read()

    def query(self, q: Dict) -> Dict:
        body = json.dumps({"sql": q["text"]}).encode()
        req = urllib.request.Request(
            self.base + "/sql", data=body,
            headers={"Content-Type": "application/json"})
        rec = {"query": q["name"], "status": "unanswered", "answer": None}
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(
                    req, timeout=REQUEST_TIMEOUT_S) as resp:
                payload = json.loads(resp.read())
        except urllib.error.HTTPError as e:
            rec["status"] = f"http_{e.code}"
            rec["error"] = e.read().decode(errors="replace")[:300]
            payload = None
        except (urllib.error.URLError, TimeoutError, OSError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
            payload = None
        rec["t_send"], rec["t_done"] = t0, time.perf_counter()
        rec["client_ms"] = (rec["t_done"] - t0) * 1e3
        if payload is not None:
            rec["status"] = payload.get("status")
            rec["id"] = payload.get("query_id")
            rec["elapsed_ms"] = payload.get("elapsed_ms")
            rec["answer"] = _columns(payload["columns"], payload["rows"])
        return rec

    def details(self, rec: Dict, timeline: bool) -> None:
        """Fill the record from the status record (and the timeline)."""
        if rec.get("id") is None:
            return
        try:
            status = json.loads(self._get(f"/queries/{rec['id']}"))
        except urllib.error.HTTPError:
            return  # aged out of the service's bounded query log
        rec["status"] = status.get("status")
        rec["fault_events"] = status.get("fault_events") or []
        rec["fault_summary"] = status.get("fault_summary") or {}
        rec["phase_times_s"] = status.get("phase_times_s")
        if timeline:
            tl = json.loads(self._get(f"/queries/{rec['id']}/timeline"))
            rec["phase_times_s"] = tl.get("phase_times_s") \
                or rec["phase_times_s"]
            # span starts are relative to the query's own recorder,
            # which starts within the request: anchor them at the send
            rec["spans"] = [
                {"name": s["name"],
                 "t0": rec["t_send"] + s["t0_ms"] / 1e3,
                 "t1": rec["t_send"] + (s["t0_ms"] + s["dur_ms"]) / 1e3}
                for s in tl.get("spans") or []]
            rec["op_metrics"] = tl.get("metrics") or {}

    def counters(self) -> Dict[str, float]:
        return parse_prometheus(self._get("/metrics").decode())

    def stop(self) -> None:
        self.svc.stop()


class DataFrameEntry:
    kind = "dataframe"

    def __init__(self, cell: spec.Cell, tables: Dict[str, str]):
        from spark_tpu import SparkTpuSession
        from spark_tpu.io.sources import ParquetSource
        self.config = cell.config
        self.spark = SparkTpuSession.builder().get_or_create()
        for k, v in cell.config.get("conf", {}).items():
            self.spark.conf.set(k, v)
        for name, path in tables.items():
            self.spark.register_table(name, ParquetSource(path, name))

    def query(self, q: Dict) -> Dict:
        build = spec.module("queries", q["builder"]).build
        rec = {"query": q["name"], "status": "unanswered", "answer": None}
        t0 = time.perf_counter()
        try:
            qe = build(self.spark, self.config)._qe()
            frame = qe.collect().to_pandas()
        except Exception as e:  # noqa: BLE001 — the request failed; counted
            rec["status"] = "error"
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
            qe = frame = None
        rec["t_send"], rec["t_done"] = t0, time.perf_counter()
        rec["client_ms"] = (rec["t_done"] - t0) * 1e3
        if frame is not None:
            rec["status"] = "ok"
            rec["answer"] = {c: frame[c].to_numpy() for c in frame.columns}
            # the engine's own record of the query, copied while it is
            # at hand (a few small dicts; the query object is let go)
            rec["fault_events"] = []
            rec["fault_summary"] = {
                k: v for k, v in qe.fault_summary.items()
                if isinstance(v, (int, float))}
            rec["phase_times_s"] = dict(qe.phase_times)
            rec["spans"] = [{"name": s.name, "t0": s.t0, "t1": s.t1}
                            for s in qe.spans.spans]
            rec["op_metrics"] = dict(qe.last_metrics)
        return rec

    def details(self, rec: Dict, timeline: bool) -> None:
        """Nothing to fetch: `query` already holds the record."""

    def counters(self) -> Dict[str, float]:
        from spark_tpu.observability.metrics import prometheus_text
        return parse_prometheus(prometheus_text(self.spark.metrics.snapshot()))

    def stop(self) -> None:
        self.spark = None


ENTRIES = {"http": HttpEntry, "dataframe": DataFrameEntry}
