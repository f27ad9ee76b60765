"""The first join as a deployment, as the benchmark's cell
`tpch-sf1-join.q3` drives it: CUSTOMER, ORDERS and LINEITEM as Parquet
registered with a `SqlService`, a request of TPC-H Q3 over `POST /sql`.
On the CPU at SF0.01: the answers against the benchmark's plain
reference, a compile of the stage on the first submission, one of the
stage whose runtime filters hand on their survivors compacted on the
second (PR 38) and none however often the query is sent after that,
three entries in the device-table cache and hits ever after, the join's
process counters, and a lowered text that is the same in every
process, with no reading of the host's clock in it."""

import hashlib
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.datagen import customer, lineitem, orders  # noqa: E402
from benchmark.harness import compare  # noqa: E402
from benchmark.harness.entries import _columns  # noqa: E402
from benchmark.reference import q3 as ref_q3  # noqa: E402
from tests.test_resident_served import Served, _text  # noqa: E402

SF, SEED = 0.01, 2147483659
#: (table, its generator, its parts): the configuration's, cut in scale
TABLES = (("lineitem", lineitem, 2), ("orders", orders, 2),
          ("customer", customer, 1))

MISSES = "spark_tpu_compile_cache_misses"
#: the join's own, and the metrics sink's two of the filters
JOIN_COUNTERS = ("spark_tpu_join_output_rows", "spark_tpu_rtf_tested",
                 "spark_tpu_rtf_pruned")
MS_KEYS = ("rtf_build_ms_", "join_build_ms_", "join_probe_ms_")
SLOTS = "spark_tpu_rtf_slots"
#: Q3's stage with no learned capacity on a filter, lowered at this
#: file's scale and seed: the digest PR 40's tree gives in the sandbox
#: (the Bloom filters' build and probe changed the text; from PR 37 to
#: PR 39 it read a04f09dd...), held so that a change which moves the
#: text, and so misses every machine's compile cache, is seen
MASKED_SHA256 = \
    "cc7bf213488c45f837f29c17306bd6a3fac24687a88b95fcf77b20190a0d447e"


class JoinServed(Served):
    """A service over the three tables, and a client of it."""

    QUERIES = ("q3",)

    def __init__(self, directories, **overrides):
        from spark_tpu import Conf
        from spark_tpu.io.sources import ParquetSource
        from spark_tpu.service.server import SqlService
        self.directories = directories
        self.sources = {name: ParquetSource(d, name)
                        for name, d in directories.items()}
        conf = Conf().set("spark_tpu.service.port", 0)
        for key, value in overrides.items():
            conf.set(key, value)

        def register(session):
            for name, source in self.sources.items():
                session.register_table(name, source)

        self.svc = SqlService(conf, init_session=register).start()
        self.base = f"http://127.0.0.1:{self.svc.port}"

    def cache_entries(self):
        from spark_tpu.io.device_cache import CACHE
        tokens = {s.cache_token() for s in self.sources.values()}
        return [k for k in CACHE._entries if k[0] in tokens]

    def grown(self, names, requests=1):
        """Growth of the counters `names` over `requests` requests."""
        before = self.counters()
        for _ in range(requests):
            self.request()
        after = self.counters()
        return [after.get(n, 0) - before.get(n, 0) for n in names]


@pytest.fixture(scope="module")
def directories(tmp_path_factory):
    root = tmp_path_factory.mktemp("tpch_join")
    out = {}
    for name, gen, parts in TABLES:
        d = str(root / name)
        os.makedirs(d)
        for part in range(parts):
            gen.write_part(SF, SEED, parts, part, d)
        out[name] = d
    return out


@pytest.fixture(scope="module")
def served(directories):
    from spark_tpu.execution import executor
    from spark_tpu.io.device_cache import CACHE
    with pytest.MonkeyPatch.context() as patch:
        # at SF0.01 the filters' probes have 32,768 and 8,192 slots:
        # under the floor from which the capacity loop compacts one
        patch.setattr(executor, "FILTER_COMPACT_MIN_SLOTS", 1024)
        s = JoinServed(directories)
        # the device-table cache is the process's: a new service's
        # gauges read 0 until its first query ends, whatever the cache
        # has seen
        s.before, s.cache_before = s.counters(), dict(CACHE.stats())
        s.first = s.request()  # loads three scans, compiles the stage
        s.after_first, s.cache_after_first = \
            s.counters(), dict(CACHE.stats())
        # applies the capacities the first learned for the two
        # filters, and compiles the compacted stage
        s.second = s.request()
        s.after_second = s.counters()
        yield s
        s.svc.stop()


def test_q3_three_times_agrees_with_the_reference(served, directories):
    reference = ref_q3.compute({}, directories, None)
    assert not reference["tie_at_limit"]
    requests = [{"queries": [
        {"query": "q3", "status": a["status"],
         "answer": _columns(a["columns"], a["rows"])} for a in answers]}
        for answers in (served.first, served.second, served.request())]
    verdict = compare.judge(requests, {"q3": reference}, {}, {})
    assert verdict["correct"], verdict
    assert all(n["value"] == 0 for n in verdict["numbers"].values())
    answer = requests[0]["queries"][0]["answer"]
    assert list(answer) == ref_q3.OUTPUT
    assert len(answer["l_orderkey"]) == ref_q3.LIMIT
    # a date is served as its ISO string, and compared as served
    assert all(len(d) == 10 and d < "1995-03-15"
               for d in answer["o_orderdate"])
    # revenue descending, as the query orders it
    assert list(answer["revenue"]) == sorted(answer["revenue"],
                                             reverse=True)


def test_the_stage_compiles_on_the_first_and_second_submission_only(served):
    """`adaptive.enabled` at its default: the first run's capacity loop
    converges and learns, from the two filters' own counts, the
    capacities their survivors fit; the second submission asks for
    that stage and compiles it; what it converged to is what every
    later one asks for."""
    first = served.after_first[MISSES] - served.before.get(MISSES, 0)
    assert first >= 1
    assert served.after_second[MISSES] - served.after_first[MISSES] == 1
    for _ in range(3):
        assert served.grown([MISSES]) == [0]
    # one dispatch a request: the capacity loop asks for no other
    assert served.grown(["spark_tpu_stage_dispatches"], 2) == [2]
    assert served.after_second["spark_tpu_stage_dispatches"] \
        - served.before.get("spark_tpu_stage_dispatches", 0) == 2


def test_three_entries_then_hits(served):
    grew = {k: served.cache_after_first[k] - served.cache_before[k]
            for k in ("misses", "hits")}
    assert grew == {"misses": 3, "hits": 0}
    assert len(served.cache_entries()) == 3
    for n in (1, 2, 3):
        assert served.grown(["spark_tpu_device_cache_hits",
                             "spark_tpu_device_cache_misses",
                             "spark_tpu_scans_streamed"]) == [3, 0, 0]
    assert len(served.cache_entries()) == 3


def test_the_join_counters_repeat_exactly(served):
    from spark_tpu.observability.metrics import is_registered_metric
    first = [served.after_first[n] - served.before.get(n, 0)
             for n in JOIN_COUNTERS]
    rows, tested, pruned = first
    assert rows > 0 and 0 < pruned < tested
    for _ in range(3):
        assert served.grown(JOIN_COUNTERS) == first
    text = served.get("/metrics").decode()
    assert is_registered_metric("join_output_rows")
    for name in JOIN_COUNTERS:
        assert f"# TYPE {name} counter" in text, name
    # the counters are the sums of what the query's own record holds
    metrics = served.timeline(served.request()[0])["metrics"]
    assert rows == sum(v for k, v in metrics.items()
                       if k.startswith("join_rows_"))
    assert tested == sum(v for k, v in metrics.items()
                         if k.startswith("rtf_tested_"))


def test_the_filters_slots_shrink_from_the_second_request_on(served):
    """`rtf_slots`: the first request's filters hand on their probes'
    slots, every later one's the learned capacities, which hold the
    same survivors: `rtf_fill_pct`, the benchmark's reader of the
    three counters, rises and then stands."""
    from benchmark.layer_metrics import rtf_fill_pct
    first = served.after_first[SLOTS] - served.before.get(SLOTS, 0)
    second = served.after_second[SLOTS] - served.after_first[SLOTS]
    assert first == 32768 + 8192
    assert 0 < second <= first // 4
    before = served.counters()
    assert served.grown([SLOTS], 2) == [2 * second]
    after = served.counters()
    assert f"# TYPE {SLOTS} counter" in served.get("/metrics").decode()

    def fill(a, b):
        return rtf_fill_pct.read({"counters_before": a,
                                  "counters_after": b})

    masked = fill(served.before, served.after_first)
    assert 0 < masked < 10 < fill(before, after) <= 100
    assert fill(before, after) == fill(served.after_first,
                                       served.after_second)
    # a program without the counter (the parent), a window without a
    # filter: nothing to read
    assert fill({}, {k: v for k, v in after.items() if k != SLOTS}) is None
    assert fill(after, after) is None


def test_the_dispatch_span_names_the_joins_and_their_kernels(served):
    spans = [s for s in served.timeline(served.request()[0])["spans"]
             if s["name"] == "dispatch"]
    assert len(spans) == 1
    attrs = spans[0]["attrs"]
    kernels = dict(k.split("=") for k in attrs["join_kernels"].split(","))
    assert attrs["joins"] == len(kernels) >= 2
    assert set(kernels.values()) <= {"sort", "hash"}
    # and the filters that hand on a compacted batch, with its slots
    caps = dict(c.split("=") for c in attrs["rtf_caps"].split(","))
    assert caps == {"rf0": "512", "rf1": "2048"}
    first = [s for s in served.timeline(served.first[0])["spans"]
             if s["name"] == "dispatch"]
    assert "rtf_caps" not in first[0]["attrs"]


def test_the_milliseconds_stay_in_the_record_and_leave_the_program(
        served, directories, tmp_path):
    """`last_metrics` (the timeline's `metrics`) and the event log keep
    the `*_ms_*` keys with the meaning they had, on a stage-cache hit
    too; the lowered text of the stage names none of them."""
    from spark_tpu import Conf, history
    from spark_tpu.io.sources import ParquetSource
    from spark_tpu.session import SparkTpuSession
    from spark_tpu.testing.stage_lowering import lower_stage
    hit = served.timeline(served.request()[0])["metrics"]
    # the submission that traced the stage every later one finds
    made = served.timeline(served.second[0])["metrics"]
    ms = {k: v for k, v in hit.items() if k.startswith(MS_KEYS)}
    assert ms and all(isinstance(v, float) and v > 0 for v in ms.values())
    assert ms == {k: v for k, v in made.items() if k.startswith(MS_KEYS)}

    log_dir = str(tmp_path / "events")
    session = SparkTpuSession(
        conf=Conf().set("spark_tpu.sql.eventLog.dir", log_dir),
        register_active=False)
    for name, d in directories.items():
        session.register_table(name, ParquetSource(d, name))
    qe = session.sql(_text("q3"))._qe()
    qe.execute_batch()
    assert set(ms) == {k for k in qe.last_metrics if k.startswith(MS_KEYS)}
    summary = history.runtime_filter_summary(history.read_event_log(log_dir))
    assert len(summary) >= 1 and (summary["build_ms"] > 0).all()

    text = lower_stage(qe).as_text()
    assert "join_rows_" in text and "rtf_tested_" in text  # outputs' names
    assert "_ms_" not in text


_LOWER = """
import hashlib, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
from spark_tpu import Conf
from spark_tpu.io.sources import ParquetSource
from spark_tpu.session import SparkTpuSession
from spark_tpu.testing.stage_lowering import lower_stage
session = SparkTpuSession(conf=Conf(), register_active=False)
for name, d in {directories!r}.items():
    session.register_table(name, ParquetSource(d, name))
text = lower_stage(session.sql(open({sql!r}).read())._qe()).as_text()
print("SHA256", hashlib.sha256(text.encode()).hexdigest(), len(text))
"""


def test_the_stage_text_is_the_same_in_two_processes(directories):
    """Until PR 37 a join's stage held the milliseconds its own tracing
    took as float32 constants, so no two processes lowered the same
    text and JAX's persistent cache never hit."""
    code = _LOWER.format(
        repo=REPO, directories=directories,
        sql=os.path.join(REPO, "benchmark", "queries", "q3.sql"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    seen = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("SHA256")][-1]
        seen.append(line.split()[1:])
    assert seen[0] == seen[1], seen
    # no capacity learned: the masked program
    assert seen[0][0] == MASKED_SHA256
    assert int(seen[0][1]) > 10_000  # a whole stage, not a stub


def _segment_place(seed):
    """Where `BUILDING` stands among `c_mktsegment`'s values in the
    order `seed`'s rows bring them."""
    col = customer.part_table(SF, seed, 1, 0).column("c_mktsegment")
    return col.combine_chunks().dictionary_encode().dictionary \
        .to_pylist().index("BUILDING")


def test_the_stage_text_is_the_same_for_another_seeds_data(
        directories, tmp_path):
    """`c_mktsegment = 'BUILDING'` stands in the program as a table by
    dictionary code. Until PR 37 a table held on one device kept its
    dictionary in the order the rows brought the values, so Q3 had
    five texts, one a place of `BUILDING`, and a set of seeds compiled
    up to five times. A held table's dictionary is sorted now."""
    from spark_tpu import Conf
    from spark_tpu.io.sources import ParquetSource
    from spark_tpu.session import SparkTpuSession
    from spark_tpu.testing.stage_lowering import lower_stage
    here = _segment_place(SEED)
    other = next(s for s in range(SEED + 1, SEED + 64)
                 if _segment_place(s) != here)
    there = {}
    for name, gen, parts in TABLES:
        there[name] = str(tmp_path / name)
        os.makedirs(there[name])
        for part in range(parts):
            gen.write_part(SF, other, parts, part, there[name])
    shas = []
    for dirs in (directories, there):
        session = SparkTpuSession(conf=Conf(), register_active=False)
        for name, d in dirs.items():
            session.register_table(name, ParquetSource(d, name))
        text = lower_stage(session.sql(_text("q3"))._qe()).as_text()
        shas.append(hashlib.sha256(text.encode()).hexdigest())
    assert shas[0] == shas[1]
