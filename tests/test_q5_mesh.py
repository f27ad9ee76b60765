"""TPC-H Q5 across chips, as the benchmark's cell `tpch-sf10-q5-mesh4.q5`
drives it: the six tables under `spark_tpu.sql.mesh.size` on the CPU's
virtual devices. The answer against `spark_tpu/tpch/golden.py` under
four and eight shards, with every join broadcast and with every join
shuffled; the cell's own conf served over `POST /sql` against the
benchmark's reference; the plan the cell runs, pinned at SF10's
estimates (a later change of it is then made on purpose); and the
counters the cell's metrics read, which are the whole mesh's."""

import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.datagen import lineitem  # noqa: E402
from benchmark.harness import compare  # noqa: E402
from benchmark.harness.entries import _columns  # noqa: E402
from benchmark.reference import q5 as ref_q5  # noqa: E402
from tests.test_q5 import Q5Served, _write  # noqa: E402
from tests.test_resident_served import _text  # noqa: E402
from tests.test_tpch import _norm  # noqa: E402

SEED = 2147483659
MESH = "spark_tpu.sql.mesh.size"
THRESHOLD = "spark_tpu.sql.autoBroadcastJoinThreshold"
#: a fallback to one device would answer right and prove nothing
NO_FALLBACK = {"spark_tpu.execution.meshFallback.enabled": False}
#: the tables' rows and dense keys grow with the scale; nation and
#: region are the clause's fixed rows at any scale
SCALED = ("lineitem", "orders", "customer", "supplier")
KEYS = ("orderkey", "custkey", "suppkey")
#: SF10 over the tests' SF0.01
SF10 = 1000
#: the cell's plan at SF10: each join by its probe side's keys
CELL_JOINS = {"n_regionkey": "broadcast", "c_nationkey": "broadcast",
              "o_custkey": "shuffle", "l_orderkey": "shuffle",
              "l_suppkey,c_nationkey": "broadcast"}


def _session(register, shards, **conf):
    from spark_tpu import Conf
    from spark_tpu.session import SparkTpuSession
    c = Conf().set(MESH, shards)
    for key, value in {**NO_FALLBACK, **conf}.items():
        c.set(key, value)
    session = SparkTpuSession(conf=c, register_active=False)
    register(session)
    return session


def _joins(plan_text):
    """{the probe side's keys: the strategy} of every join in a plan."""
    return {",".join(k.strip("' ") for k in left.split(",")): strategy
            for left, strategy in re.findall(
                r"JoinExec\(inner, \[(.*?)\] = .*strategy=(\w+)\)",
                plan_text)}


@pytest.fixture(scope="module")
def golden_path(tmp_path_factory):
    from spark_tpu.tpch.datagen import write_parquet
    return write_parquet(str(tmp_path_factory.mktemp("tpch_q5_mesh")), 0.002)


@pytest.mark.parametrize("plan", ["broadcast", "shuffle"])
@pytest.mark.parametrize("shards", [4, 8])
def test_q5_across_chips_equals_the_golden(golden_path, shards, plan):
    from spark_tpu.tpch import golden as G
    from spark_tpu.tpch import queries as Q
    conf = {THRESHOLD: 64} if plan == "shuffle" else {}
    session = _session(lambda s: Q.register_tables(s, golden_path), shards,
                       **conf)
    qe = session.sql(_text("q5"))._qe()
    assert set(_joins(qe.executed_plan.tree_string()).values()) == {plan}
    got = _norm(qe.collect().to_pandas()).sort_values("n_name")
    want = G.GOLDEN["q5"](golden_path).sort_values("n_name")
    G.compare(got.reset_index(drop=True), want.reset_index(drop=True))


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    """The cell's tables from its generators, at SF0.01."""
    return _write(tmp_path_factory.mktemp("q5_mesh"), SEED)


def _register(tables):
    from spark_tpu.io.sources import ParquetSource

    def register(session):
        for name, d in tables.items():
            session.register_table(name, ParquetSource(d, name))
    return register


def _verdict(answers, tables):
    requests = [{"queries": [{"query": "q5", "status": a["status"],
                              "answer": a["answer"]}]} for a in answers]
    return compare.judge(requests, {"q5": ref_q5.compute({}, tables, None)},
                         {}, {})


def test_the_cells_conf_served_equals_the_reference(tables):
    """The configuration's `conf` and nothing else, over `POST /sql`:
    every join broadcast at this size, and the answer exact."""
    import json
    with open(os.path.join(REPO, "benchmark", "configs",
                           "tpch-sf10-q5-mesh4.json")) as f:
        conf = json.load(f)["conf"]
    assert conf == {MESH: 4}
    served = Q5Served(tables, **conf, **NO_FALLBACK)
    try:
        payloads = [served.request()[0] for _ in range(2)]
        spans = [s for s in served.timeline(payloads[-1])["spans"]
                 if s["name"] == "dispatch"]
    finally:
        served.svc.stop()
    answers = [{"status": p["status"],
                "answer": _columns(p["columns"], p["rows"])}
               for p in payloads]
    verdict = _verdict(answers, tables)
    assert verdict["correct"], verdict
    assert len(spans) == 1 and spans[0]["attrs"]["mesh"] == 4
    strategies = dict(kv.split("=") for kv in
                      spans[0]["attrs"]["join_strategies"].split(","))
    assert len(strategies) == 5
    assert set(strategies.values()) == {"broadcast"}


@pytest.fixture
def at_sf10(monkeypatch):
    """The scans' row estimates and key domains as SF10's footers give
    them: what the planner and the join order see in the cell."""
    from spark_tpu.io.sources import ParquetSource
    rows, stats = ParquetSource.estimated_rows, ParquetSource.column_stats

    def estimated_rows(self):
        n = rows(self)
        return n * SF10 if self.name in SCALED else n

    def column_stats(self):
        found = stats(self)
        if not found or self.name not in SCALED:
            return found
        out = {}
        for name, s in found.items():
            s = dict(s)
            if name.endswith(KEYS) and s.get("max") is not None:
                s["max"] = s["max"] * SF10
            out[name] = s
        return out

    monkeypatch.setattr(ParquetSource, "estimated_rows", estimated_rows)
    monkeypatch.setattr(ParquetSource, "column_stats", column_stats)


def _run(session, text):
    """The query twice, the second's counter growth and its record."""
    session.sql(text)._qe().collect()
    before = session.metrics.snapshot()["counters"]
    qe = session.sql(text)._qe()
    answer = qe.collect().to_pandas()
    after = session.metrics.snapshot()["counters"]
    grown = {k: after[k] - before.get(k, 0) for k in after}
    return qe, answer, grown


def test_the_cells_plan_at_sf10s_estimates(tables, at_sf10):
    """LINEITEM and ORDERS hashed to the shards by key, SUPPLIER and the
    NATION/REGION chain broadcast, and no runtime filter on LINEITEM:
    ORDERS' three columns, estimated before the pushed date filter, are
    over `runtimeFilter.creationSideThreshold`. The answer is exact."""
    session = _session(_register(tables), 4)
    qe, answer, grown = _run(session, _text("q5"))
    text = qe.executed_plan.tree_string()
    assert _joins(text) == CELL_JOINS, text
    assert re.search(r"ExchangeExec\(HashPartitioning\(keys=\('l_orderkey',"
                     r"\).*\n\s*ScanExec\(lineitem", text), text
    filters = re.findall(r"RuntimeFilterExec\((\w+) IN", text)
    assert filters == ["n_regionkey"], text
    assert grown["stage_dispatches"] == 1 and grown["compile_cache_misses"] \
        == 0
    verdict = _verdict([{"status": "ok", "answer": {
        c: compare.as_served(answer[c].tolist()) for c in answer}}], tables)
    assert verdict["correct"], verdict


def test_the_counters_are_the_whole_meshs(tables, at_sf10):
    """Under the cell's plan: `join_output_rows` and `join_widest_rows`
    count each join's rows over every shard, as the same joins count
    them on one device (where `join_rows_*` is the fullest shard's, a
    capacity); `exchange_rows` and `exchange_bytes` carry every line of
    LINEITEM once; `exchange_slot_bytes` is n x n x each exchange's
    block x its row width, as the `dispatch` span's `exch_blocks` say."""
    register = _register(tables)
    qe, _a, grown = _run(_session(register, 4), _text("q5"))
    # one device would stream LINEITEM at SF10's estimate, as the cell
    # says; a budget that holds it keeps the same stage to compare
    one, _b, alone = _run(_session(
        register, 0, **{"spark_tpu.sql.io.deviceCacheBytes": 16 << 30}),
        _text("q5"))
    m, single = qe.last_metrics, one.last_metrics

    def by_tag(metrics, prefix):
        return {k[len(prefix):]: v for k, v in metrics.items()
                if k.startswith(prefix)}

    joined = by_tag(m, "join_mesh_rows_")
    assert sorted(joined) == ["j0", "j1", "j2", "j3", "j4"]
    # the same joins, in the same order, on one device
    assert joined == {t: single[f"join_rows_{t}"] for t in joined}
    assert all(m[f"join_rows_{t}"] <= v for t, v in joined.items())
    assert m["join_rows_j3"] < joined["j3"]  # the fullest shard's alone
    assert grown["join_output_rows"] == sum(joined.values())
    assert grown["join_widest_rows"] == max(joined.values())
    # one device adds its filters' creation-side semi-joins, which a
    # mesh does not build
    semi = by_tag(single, "join_rows_cj")
    assert alone["join_output_rows"] == sum(joined.values()) \
        + sum(semi.values())
    assert "exchange_rows" not in alone or alone["exchange_rows"] == 0

    rows, sent = by_tag(m, "exch_rows_"), by_tag(m, "exch_bytes_")
    assert grown["exchange_rows"] == sum(rows.values())
    assert grown["exchange_bytes"] == sum(sent.values())
    n_lines = lineitem.rows(tables["lineitem"])
    width = 4 * 8  # four 64-bit columns, no null so no validity
    assert any(rows[t] == n_lines and sent[t] == n_lines * width
               for t in rows), (rows, n_lines)

    dispatch = [s for s in qe.spans.spans if s.name == "dispatch"]
    assert len(dispatch) == 1
    attrs = dispatch[0].attrs
    blocks = {t: int(b) for t, b in (kv.split("=") for kv in
                                     attrs["exch_blocks"].split(","))}
    slots = by_tag(m, "exch_slot_bytes_")
    assert set(blocks) == set(slots) == set(rows)
    for t, block in blocks.items():
        assert block >= m[f"exch_max_{t}"]
        if rows[t]:
            assert slots[t] == 4 * 4 * block * (sent[t] // rows[t])
    assert grown["exchange_slot_bytes"] == sum(slots.values())
    assert dict(kv.split("=") for kv in
                attrs["join_strategies"].split(",")) == {
        "j0": "broadcast", "j1": "broadcast", "j2": "shuffle",
        "j3": "shuffle", "j4": "broadcast"}


def _blocks(qe):
    """{tag: block} of the last `dispatch` span's `exch_blocks`."""
    dispatch = [s for s in qe.spans.spans if s.name == "dispatch"][-1]
    return {t: int(b) for t, b in (kv.split("=") for kv in
                                   dispatch.attrs["exch_blocks"].split(","))}


def _learned(session):
    """{tag: block} of the exchange blocks the capacity store holds."""
    return {k[len("exch:"):]: v for caps in session._aqe_caps.values()
            for k, v in caps.items() if k.startswith("exch:")}


def _executed(session, text, tables):
    """One execution of `text`: its record, its counters' growth, and
    whether its answer is the reference's."""
    before = session.metrics.snapshot()["counters"]
    qe = session.sql(text)._qe()
    answer = qe.collect().to_pandas()
    after = session.metrics.snapshot()["counters"]
    grown = {k: after[k] - before.get(k, 0) for k in after}
    verdict = _verdict([{"status": "ok", "answer": {
        c: compare.as_served(answer[c].tolist()) for c in answer}}], tables)
    assert verdict["correct"], verdict
    return qe, grown


def _fills(qe):
    """{tag: the block its fullest bucket fills} of every exchange."""
    from spark_tpu.columnar import bucket_capacity
    return {t: bucket_capacity(max(qe.last_metrics[f"exch_max_{t}"], 8))
            for t in _blocks(qe)}


def test_a_converged_mesh_learns_each_large_exchanges_block(
        tables, at_sf10, monkeypatch):
    """Under the cell's plan, every exchange over the threshold: the
    first execution runs the seeded blocks and stores for each whose
    fullest bucket fits half of it the block that bucket fills,
    counting each in `exchange_blocks_learned`, and drops the seeded
    stage; the second compiles the stage with those blocks once, the
    third compiles nothing, and every answer is the reference's."""
    from spark_tpu.execution import executor
    monkeypatch.setattr(executor, "EXCHANGE_SHRINK_MIN_SLOTS", 1)
    session, text = _session(_register(tables), 4), _text("q5")
    first, grown = _executed(session, text, tables)
    seeded, fills = _blocks(first), _fills(first)
    want = {t: fills[t] for t in seeded if 2 * fills[t] <= seeded[t]}
    assert "e0" in want and len(want) >= 2, (seeded, fills)
    assert _learned(session) == want
    assert grown["compile_cache_misses"] == 1
    assert grown["exchange_blocks_learned"] == len(want)
    # the seeded stage is not kept: no later execution runs it
    assert first._last_stage_key not in session._stage_cache

    second, grown = _executed(session, text, tables)
    assert grown["compile_cache_misses"] == 1
    assert grown.get("exchange_blocks_learned", 0) == 0
    assert second._last_stage_key in session._stage_cache
    assert _blocks(second) == {**seeded, **want}
    assert all(block >= second.last_metrics[f"exch_max_{t}"]
               for t, block in _blocks(second).items())
    # the same rows cross the mesh in fewer slots
    m1, m2 = first.last_metrics, second.last_metrics
    assert {k: v for k, v in m2.items() if k.startswith("exch_rows_")} \
        == {k: v for k, v in m1.items() if k.startswith("exch_rows_")}
    assert sum(v for k, v in m2.items() if k.startswith("exch_slot_")) \
        < sum(v for k, v in m1.items() if k.startswith("exch_slot_"))

    third, grown = _executed(session, text, tables)
    assert grown["compile_cache_misses"] == 0
    assert grown.get("exchange_blocks_learned", 0) == 0
    assert _blocks(third) == _blocks(second)


def test_an_exchange_under_the_threshold_keeps_its_seeded_block(
        tables, at_sf10, monkeypatch):
    """The threshold at LINEITEM's receive buffer: its exchange learns,
    the smaller ones keep the blocks they were seeded with."""
    from spark_tpu.execution import executor
    session, text = _session(_register(tables), 4), _text("q5")
    monkeypatch.setattr(executor, "EXCHANGE_SHRINK_MIN_SLOTS", 1 << 40)
    first, grown = _executed(session, text, tables)
    assert _learned(session) == {}
    assert grown.get("exchange_blocks_learned", 0) == 0
    seeded, rows = _blocks(first), first.last_metrics
    widest = max(seeded, key=lambda t: rows[f"exch_rows_{t}"])
    monkeypatch.setattr(executor, "EXCHANGE_SHRINK_MIN_SLOTS",
                        4 * seeded[widest])
    # a fresh session: the store learns from a first execution alone
    session = _session(_register(tables), 4)
    _executed(session, text, tables)
    assert _learned(session) == {widest: _fills(first)[widest]}
    second, grown = _executed(session, text, tables)
    assert len(seeded) > 1
    assert _blocks(second) == {**seeded, widest: _fills(first)[widest]}


def test_a_learned_block_too_small_grows_back_on_overflow(
        tables, at_sf10, monkeypatch):
    """A learned block below the rows that arrive: the exchange
    overflows, the retry grows the block to what its fullest bucket
    fills, and the answer is the reference's."""
    from spark_tpu.execution import executor
    monkeypatch.setattr(executor, "EXCHANGE_SHRINK_MIN_SLOTS", 1)
    session, text = _session(_register(tables), 4), _text("q5")
    first, _grown = _executed(session, text, tables)
    fills = _fills(first)
    widest = max(fills, key=lambda t: first.last_metrics[f"exch_rows_{t}"])
    (caps,) = session._aqe_caps.values()
    caps[f"exch:{widest}"] = fills[widest] // 4
    second, grown = _executed(session, text, tables)
    dispatches = [s for s in second.spans.spans if s.name == "dispatch"]
    assert len(dispatches) == 2
    assert second.last_metrics[f"exch_max_{widest}"] > fills[widest] // 4
    assert _blocks(second)[widest] == fills[widest]
    assert _learned(session)[widest] == fills[widest]
    assert grown.get("exchange_blocks_learned", 0) == 0
    # grown, not re-planned: the joins are the cell's
    assert not second._join_overrides
    assert _joins(second.executed_plan.tree_string()) == CELL_JOINS
