"""The resident served path, as the benchmark's cell `tpch-sf1.q1q6`
drives it: one Parquet `lineitem` registered with a `SqlService`, a
request of Q1 then Q6 over `POST /sql`. On the CPU at SF0.01: the
answers against the benchmark's plain references, what the device-table
cache holds, and the three counters of the dispatch path
(`stage_dispatches`, `dispatch_sync_ticks`, `dispatch_sync_waits`),
whose counts repeat exactly where nothing depends on timing."""

import json
import os
import sys
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.datagen import lineitem as datagen  # noqa: E402
from benchmark.harness import compare  # noqa: E402
from benchmark.harness.entries import _columns, parse_prometheus  # noqa: E402
from benchmark.reference import q1 as ref_q1  # noqa: E402
from benchmark.reference import q6 as ref_q6  # noqa: E402

SF, PARTS, SEED = 0.01, 3, 2147483659

DISPATCHES = "spark_tpu_stage_dispatches"
TICKS = "spark_tpu_dispatch_sync_ticks"
WAITS = "spark_tpu_dispatch_sync_waits"


def _text(query):
    with open(os.path.join(REPO, "benchmark", "queries", query + ".sql")) as f:
        return f.read()


class Served:
    """A service over one table, and a client of it."""

    #: the request, as the cell's client sends it
    QUERIES = ("q1", "q6")

    def __init__(self, directory, **overrides):
        from spark_tpu import Conf
        from spark_tpu.io.sources import ParquetSource
        from spark_tpu.service.server import SqlService
        self.directory = directory
        self.source = ParquetSource(directory, "lineitem")
        conf = Conf().set("spark_tpu.service.port", 0)
        for key, value in overrides.items():
            conf.set(key, value)
        self.svc = SqlService(
            conf, init_session=lambda s: s.register_table(
                "lineitem", self.source)).start()
        self.base = f"http://127.0.0.1:{self.svc.port}"

    def get(self, path):
        with urllib.request.urlopen(self.base + path, timeout=60) as resp:
            return resp.read()

    def sql(self, text):
        req = urllib.request.Request(
            self.base + "/sql", data=json.dumps({"sql": text}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            payload = json.loads(resp.read())
        assert payload["status"] == "ok", payload
        return payload

    def request(self):
        return [self.sql(_text(q)) for q in self.QUERIES]

    def counters(self):
        return parse_prometheus(self.get("/metrics").decode())

    def cache_entries(self):
        from spark_tpu.io.device_cache import CACHE
        token = self.source.cache_token()
        return [k for k in CACHE._entries if k[0] == token]

    def sync_attrs(self, payload, attr):
        """The attribute `attr` of a served query's `dispatch.sync`
        spans, off its timeline."""
        return [s["attrs"][attr] for s in self.timeline(payload)["spans"]
                if s["name"] == "dispatch.sync"]

    def timeline(self, payload):
        return json.loads(
            self.get(f"/queries/{payload['query_id']}/timeline"))

    def sync_ticks(self, payload):
        return self.sync_attrs(payload, "ticks")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("lineitem"))
    for part in range(PARTS):
        datagen.write_part(SF, SEED, PARTS, part, d)
    s = Served(d)
    s.first = s.request()  # loads both scans, compiles both stages
    yield s
    s.svc.stop()


def test_q1_then_q6_agree_with_the_references(served):
    tables = {"lineitem": served.directory}
    references = {"q1": ref_q1.compute({}, tables, None),
                  "q6": ref_q6.compute({}, tables, None)}
    requests = []
    for answers in (served.first, served.request()):
        requests.append({"queries": [
            {"query": name, "status": a["status"],
             "answer": _columns(a["columns"], a["rows"])}
            for name, a in zip(("q1", "q6"), answers)]})
    verdict = compare.judge(requests, references, {}, {})
    assert verdict["correct"], verdict
    assert all(n["value"] == 0 for n in verdict["numbers"].values())
    assert len(requests[0]["queries"][0]["answer"]["count_order"]) == 4
    assert list(requests[0]["queries"][1]["answer"]) == ["one", "revenue"]


def test_the_filter_has_rows_to_cut(served):
    """Q6 keeps about 1.9% of the rows, so leaving its filter out, or
    any conjunct of it, reads wrong."""
    rows = datagen.rows(served.directory)
    assert 0.015 * rows < ref_q6.kept_rows(served.directory) < 0.023 * rows


def test_one_table_under_two_queries_is_two_cache_entries(served):
    keys = served.cache_entries()
    assert len(keys) == 2
    filters = {frozenset(k[1]): k[2] for k in keys}
    assert set(filters) == {frozenset(ref_q1.COLUMNS),
                            frozenset(ref_q6.COLUMNS)}
    q6_filters = filters[frozenset(ref_q6.COLUMNS)]
    # every conjunct of Q6's filter is pushed into its entry's scan
    assert len(q6_filters) == 5, q6_filters
    # the specification's own text of Q6, without the benchmark's key
    # column, reads the same entry
    from spark_tpu.tpch.sql_queries import Q6
    plain = served.sql(Q6)
    assert plain["rows"][0]["revenue"] == served.first[1]["rows"][0]["revenue"]
    assert len(served.cache_entries()) == 2


def test_every_later_scan_is_a_hit(served):
    before = served.counters()
    for n in (1, 2, 3):
        served.request()
        after = served.counters()
        assert after["spark_tpu_device_cache_hits"] \
            - before["spark_tpu_device_cache_hits"] == 2 * n
        assert after.get("spark_tpu_device_cache_misses", 0) \
            == before.get("spark_tpu_device_cache_misses", 0)
        assert after.get("spark_tpu_scans_streamed", 0) \
            == before.get("spark_tpu_scans_streamed", 0)
    assert len(served.cache_entries()) == 2


def test_stage_dispatches_grow_by_the_same_number_every_request(served):
    counts = [served.counters()[DISPATCHES]]
    for _ in range(4):
        served.request()
        counts.append(served.counters()[DISPATCHES])
    grown = {b - a for a, b in zip(counts, counts[1:])}
    assert grown == {2.0}, counts  # one whole-stage program a query


def test_sync_ticks_are_the_sum_of_the_spans_attributes(served):
    before = served.counters()[TICKS]
    ticks = []
    for _ in range(3):
        for answer in served.request():
            per_span = served.sync_ticks(answer)
            assert len(per_span) == 1, per_span
            ticks.extend(per_span)
    assert all(isinstance(t, int) and t >= 0 for t in ticks)
    assert served.counters()[TICKS] - before == sum(ticks)


def test_both_counters_are_on_metrics(served):
    text = served.get("/metrics").decode()
    for name in (DISPATCHES, TICKS):
        assert f"# TYPE {name} counter" in text, name
    from spark_tpu.observability.metrics import is_registered_metric
    assert is_registered_metric("stage_dispatches")
    assert is_registered_metric("dispatch_sync_ticks")


def test_sync_waits_are_counted_and_never_outgrow_the_dispatches(served):
    """`dispatch_sync_waits`: one for each sync that found its stage
    still running and started a waiter. Whether it did is timing, so
    the count is bounded and not fixed: at most one a dispatch, and
    the sum of the spans' `waited`."""
    from spark_tpu.observability.metrics import is_registered_metric
    assert is_registered_metric("dispatch_sync_waits")
    assert f"# TYPE {WAITS} counter" in served.get("/metrics").decode()
    before = served.counters()
    waited = []
    for _ in range(3):
        for answer in served.request():
            waited.extend(served.sync_attrs(answer, "waited"))
    after = served.counters()
    grown = after[WAITS] - before[WAITS]
    assert set(waited) <= {0, 1}, waited
    assert grown == sum(waited)
    assert 0 <= grown <= after[DISPATCHES] - before[DISPATCHES] == 6
    from spark_tpu.execution.executor import SYNC_WAITER_THREAD
    from spark_tpu.testing.lockwatch import LockWatch
    LockWatch().assert_no_thread_leak(prefix=SYNC_WAITER_THREAD)


def test_a_sync_that_never_polls_counts_no_tick(session, served):
    """Every execution installs a cancel token, so the branch of
    `_sync_dispatched` that blocks straight through is reached with
    `dispatchPollMs` 0, or with no token at all."""
    from spark_tpu.execution.executor import (DISPATCH_POLL_KEY,
                                              _sync_dispatched)
    from spark_tpu.io.sources import ParquetSource
    from spark_tpu.observability.spans import SpanRecorder
    session.conf.set(DISPATCH_POLL_KEY, 0)
    session.register_table("lineitem_unpolled",
                           ParquetSource(served.directory, "lineitem"))
    dispatches = session.metrics.counter("stage_dispatches")
    ticks = session.metrics.counter("dispatch_sync_ticks")
    d0, t0 = dispatches.value, ticks.value
    qe = session.sql(
        "select count(*) as n from lineitem_unpolled")._qe()
    assert qe.collect().to_pylist() == [
        {"n": datagen.rows(served.directory)}]
    assert dispatches.value == d0 + 1
    assert ticks.value == t0
    assert [s.attrs["ticks"] for s in qe.spans.spans
            if s.name == "dispatch.sync"] == [0]
    # no token: the plain blocking pull, whatever the conf says
    session.conf.set(DISPATCH_POLL_KEY, 25)
    rec = SpanRecorder(0)
    with rec.span("dispatch.sync") as sp:
        assert _sync_dispatched({"a": 7}, session.conf, sp) == {"a": 7}
    assert sp.attrs["ticks"] == 0
