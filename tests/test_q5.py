"""TPC-H Q5 as the benchmark's cell `tpch-sf1-q5.q5` drives it (PR 41):
six tables registered with a `SqlService`, Q5's text over `POST /sql`,
the answer against the benchmark's plain reference on three seeds; the
join order the reorder's domain estimate chooses, whose widest join at
SF0.1 is a tenth of the parent's; the counter and the span attribute
that say so on the served path; and a join on a hashed key pair that
stays exact when every pair collides."""

import os
import sys

import numpy as np
import pandas as pd
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.datagen import (customer, lineitem, nation,  # noqa: E402
                               orders, region, supplier)
from benchmark.harness import compare  # noqa: E402
from benchmark.harness.entries import _columns  # noqa: E402
from benchmark.reference import q5 as ref_q5  # noqa: E402
from tests.test_join_served import JoinServed  # noqa: E402
from tests.test_resident_served import _text  # noqa: E402

SF = 0.01
SEEDS = (2147483659, 3141592653, 4294967311)
#: (table, its generator, its parts): the configuration's, cut in scale
TABLES = (("lineitem", lineitem, 2), ("orders", orders, 2),
          ("customer", customer, 1), ("supplier", supplier, 1),
          ("nation", nation, 1), ("region", region, 1))
WIDEST = "spark_tpu_join_widest_rows"
REORDER = "spark_tpu.sql.cbo.joinReorder"


class Q5Served(JoinServed):
    QUERIES = ("q5",)


def _write(root, seed):
    out = {}
    for name, gen, parts in TABLES:
        d = os.path.join(str(root), name)
        os.makedirs(d)
        for part in range(parts):
            gen.write_part(SF, seed, parts, part, d)
        out[name] = d
    return out


@pytest.fixture(scope="module")
def seeded(tmp_path_factory):
    """{seed: (the tables' directories, a service over them)}: each
    service sent Q5 twice, the masked stage and the compacted one."""
    from spark_tpu.execution import executor
    out = {}
    with pytest.MonkeyPatch.context() as patch:
        # at SF0.01 the filters' probes are under the floor from which
        # the capacity loop compacts one (as tests/test_join_served.py)
        patch.setattr(executor, "FILTER_COMPACT_MIN_SLOTS", 1024)
        for seed in SEEDS:
            dirs = _write(tmp_path_factory.mktemp(f"q5_{seed}"), seed)
            s = Q5Served(dirs)
            s.answers = [s.request()[0] for _ in range(2)]
            out[seed] = (dirs, s)
        yield out
        for _dirs, s in out.values():
            s.svc.stop()


@pytest.mark.parametrize("seed", SEEDS)
def test_q5_agrees_with_the_reference(seeded, seed):
    dirs, served = seeded[seed]
    reference = ref_q5.compute({}, dirs, None)
    assert reference["table"]["n_name"], "no Asian nation has a line"
    requests = [{"queries": [{
        "query": "q5", "status": a["status"],
        "answer": _columns(a["columns"], a["rows"])}]}
        for a in served.answers + [served.request()[0]]]
    verdict = compare.judge(requests, {"q5": reference}, {}, {})
    assert verdict["correct"], verdict
    assert all(n["value"] == 0 for n in verdict["numbers"].values())
    answer = requests[0]["queries"][0]["answer"]
    assert list(answer) == ref_q5.OUTPUT
    assert set(answer["n_name"]) <= {"CHINA", "INDIA", "INDONESIA",
                                     "JAPAN", "VIETNAM"}
    assert list(answer["revenue"]) == sorted(answer["revenue"],
                                             reverse=True)


def test_the_widest_join_is_counted_once_a_query(seeded):
    """`join_widest_rows` grows by the largest `join_rows_*` of the
    request's one stage, and is on `/metrics`."""
    from spark_tpu.observability.metrics import is_registered_metric
    _dirs, served = seeded[SEEDS[0]]
    before = served.counters()
    payload = served.request()[0]
    grown = served.counters()[WIDEST] - before[WIDEST]
    metrics = served.timeline(payload)["metrics"]
    joined = [v for k, v in metrics.items() if k.startswith("join_rows_")]
    assert len(joined) >= 5 and grown == max(joined) > 0
    assert is_registered_metric("join_widest_rows")
    assert f"# TYPE {WIDEST} counter" in served.get("/metrics").decode()


def test_the_dispatch_span_says_how_the_key_pair_was_packed(seeded):
    """LINEITEM's line meets SUPPLIER on (`l_suppkey`, `c_nationkey`):
    two int64 columns that no bound packs into 63 bits, so the program
    hashes the pair and re-verifies each column."""
    _dirs, served = seeded[SEEDS[0]]
    spans = [s for s in served.timeline(served.request()[0])["spans"]
             if s["name"] == "dispatch"]
    assert len(spans) == 1
    attrs = spans[0]["attrs"]
    keys = dict(k.split("=") for k in attrs["join_keys"].split(","))
    kernels = dict(k.split("=") for k in attrs["join_kernels"].split(","))
    assert keys and set(keys) <= set(kernels)
    assert set(keys.values()) == {"hashed"}


# -- the order at SF0.1, with the engine's own data ---------------------------

@pytest.fixture(scope="module")
def sf01(tmp_path_factory):
    from spark_tpu import Conf
    from spark_tpu.session import SparkTpuSession
    from spark_tpu.tpch import queries as Q
    from spark_tpu.tpch.datagen import write_parquet
    path = write_parquet(str(tmp_path_factory.mktemp("tpch_sf01")), 0.1)
    session = SparkTpuSession(conf=Conf(), register_active=False)
    Q.register_tables(session, path)
    qe = session.sql(_text("q5"))._qe()
    answer = qe.collect().to_pandas()
    return session, qe, answer


def test_at_sf01_the_widest_join_is_a_tenth_of_the_parents(sf01):
    """The parent priced `c_nationkey = s_nationkey` as a key join and
    took it before the fact path: its widest join at SF0.1 put out
    185,131 rows (ISSUE 41's CPU run; 95,834 with the reorder off).
    With the domain estimate the order is region, nation, customer,
    orders, lineitem, supplier, and its widest join is LINEITEM's lines
    of the Asian customers' 1994 orders: 19,577 rows."""
    session, qe, answer = sf01
    joined = {k: v for k, v in qe.last_metrics.items()
              if k.startswith("join_rows_")}
    assert max(joined.values()) <= 100_000, joined
    [decision] = qe.reorder_decisions
    assert decision["order"] == ["nation", "region", "customer", "orders",
                                 "lineitem", "supplier"], decision
    session.conf.set(REORDER, False)
    try:
        off = session.sql(_text("q5")).to_pandas()
    finally:
        session.conf.set(REORDER, True)
    pd.testing.assert_frame_equal(answer, off)
    assert len(answer) == 5


def _joins(plan):
    from spark_tpu.plan import physical as P
    out = [plan] if isinstance(plan, P.JoinExec) else []
    for child in plan.children:
        out.extend(_joins(child))
    return out


def test_no_join_is_customer_and_supplier_on_the_nation_alone(sf01):
    """The cycle's edge is a second key of the join that brings
    SUPPLIER in, and CUSTOMER reaches NATION by `c_nationkey =
    n_nationkey`, which the query implies and does not state."""
    _session, qe, _answer = sf01
    keys = [sorted(k.name() for k in j.left_keys + j.right_keys)
            for j in _joins(qe.executed_plan)]
    assert ["c_nationkey", "s_nationkey"] not in keys
    assert ["c_nationkey", "l_suppkey", "s_nationkey", "s_suppkey"] in keys
    assert ["c_nationkey", "n_nationkey"] in keys


# -- the estimate --------------------------------------------------------------

def _costs(domains, est=((1000, 1.0), (2000, 1.0))):
    from spark_tpu.plan.join_reorder import _Costs
    groups = [[(0, "a"), (1, "b")]]
    return _Costs(list(est), groups, domains, [(0, "a", 1, "b")])


@pytest.mark.parametrize("domains,rows", [
    # 25 values on both sides: every row meets a 25th of the other's
    ({(0, "a"): 25, (1, "b"): 25}, 1000 * 2000 / 25),
    # b is a key of its relation (2,000 values in 2,000 rows): FK
    ({(0, "a"): 25, (1, "b"): 2000}, 2000),
    # a domain unknown: the FK estimate, as before PR 41
    ({(0, "a"): 25, (1, "b"): None}, 2000),
])
def test_a_key_repeated_on_both_sides_is_no_key_join(domains, rows):
    costs = _costs(domains)
    out, _keys = costs.step(1000.0, 1.0, costs.keys_of(0), 1, 1)
    assert out == pytest.approx(rows)


def test_a_second_key_narrows_a_key_join():
    """SUPPLIER's step: `l_suppkey = s_suppkey` is a key join, and the
    nation, 25 values on both sides, keeps a 25th of its rows."""
    from spark_tpu.plan.join_reorder import _Costs
    est = [(6000, 1.0), (100, 1.0)]
    groups = [[(0, "l_suppkey"), (1, "s_suppkey")],
              [(0, "c_nationkey"), (1, "s_nationkey")]]
    domains = {(0, "l_suppkey"): 100, (1, "s_suppkey"): 100,
               (0, "c_nationkey"): 25, (1, "s_nationkey"): 25}
    costs = _Costs(est, groups, domains, [])
    out, _keys = costs.step(6000.0, 1.0, frozenset(), 1, 1)
    assert out == pytest.approx(6000 / 25)
    links = costs.links(1, 1)
    assert [(pick[1], mine[1]) for _b, pick, mine in links] == [
        ("l_suppkey", "s_suppkey"), ("c_nationkey", "s_nationkey")]


# -- a hashed pair that collides -----------------------------------------------

def test_a_colliding_hashed_pair_is_still_exact(monkeypatch):
    """Every pair of int64 keys hashed into four values: the unique
    build's duplicate flag re-plans the join onto the expansion, whose
    re-verify of each column keeps the answer exact, and the span says
    `hashed`."""
    import jax.numpy as jnp

    from spark_tpu import Conf
    from spark_tpu.plan import physical
    from spark_tpu.session import SparkTpuSession
    monkeypatch.setattr(physical, "_mix64",
                        lambda x: x.astype(jnp.int64) & 3)
    rs = np.random.default_rng(41)
    left = pd.DataFrame({"ka41": rs.integers(0, 30, 400),
                         "kb41": rs.integers(0, 7, 400),
                         "v41": np.arange(400)})
    right = pd.DataFrame({"kc41": np.repeat(np.arange(30), 7),
                          "kd41": np.tile(np.arange(7), 30),
                          "w41": np.arange(210) * 10})
    session = SparkTpuSession(conf=Conf(), register_active=False)
    df = session.create_dataframe(left).join(
        session.create_dataframe(right), left_on=["ka41", "kb41"],
        right_on=["kc41", "kd41"])
    qe = df._qe()
    got = qe.collect().to_pandas().sort_values("v41").reset_index(drop=True)
    want = left.merge(right, left_on=["ka41", "kb41"],
                      right_on=["kc41", "kd41"]).sort_values("v41") \
        .reset_index(drop=True)
    assert len(want) == 400
    assert (got["w41"].to_numpy() == want["w41"].to_numpy()).all()
    attrs = [s.attrs for s in qe.spans.spans if s.name == "dispatch"]
    assert attrs and all(a.get("join_keys", "").endswith("=hashed")
                         for a in attrs)


# -- planning a tree of five joins ----------------------------------------------

def test_planning_q5_asks_each_table_for_its_schema_once(seeded, monkeypatch):
    """A `Join`'s schema asked each side twice more through
    `right_name_map`, so a left-deep tree of five joins asked its
    leaves 3^5 times, and a Parquet table rebuilt its engine schema at
    every ask: 11,996 builds and 54 ms of Q5's parse, optimize and plan
    on the CPU before PR 41, 9 ms after. Now a table builds it once, and a join asks each
    side once."""
    from spark_tpu import Conf
    from spark_tpu.io import sources
    from spark_tpu.io.sources import ParquetSource
    from spark_tpu.plan import logical as L
    from spark_tpu.session import SparkTpuSession
    built, asked = [], []
    made = sources._arrow_schema_to_engine
    monkeypatch.setattr(sources, "_arrow_schema_to_engine",
                        lambda schema: built.append(1) or made(schema))
    scan_schema = L.Scan.schema
    monkeypatch.setattr(L.Scan, "schema",
                        lambda self: asked.append(1) or scan_schema(self))
    dirs, _served = seeded[SEEDS[0]]
    session = SparkTpuSession(conf=Conf(), register_active=False)
    for name, d in dirs.items():
        session.register_table(name, ParquetSource(d, name))
    qe = session.sql(_text("q5"))._qe()
    qe.executed_plan
    assert len(built) == len(TABLES)
    del asked[:]
    joined = qe.optimized_plan
    while not isinstance(joined, L.Join):
        joined = joined.children[0]
    joined.schema()
    # a Filter and a Project above a scan may ask again: by the tables,
    # not by a power of the joins
    assert len(asked) <= 2 * len(TABLES)
