#!/usr/bin/env python
"""chip_smoke.py: the quickest proof that the served SQL path still
starts on the chip.

One process, one TPU chip, the entry points a user calls:

1. device   jax must report a TPU, or the script exits non-zero before
            any work. There is no CPU path here.
2. data     TPC-H at SF1 (the specification's smallest scale), made
            from --seed under <checkout>/data/tpch (git-ignored).
3. serve    `SqlService` on an ephemeral port; Q1, Q6, Q3, Q5 over HTTP
            `POST /sql`, each three times, cold then warm twice, each
            compared with the independent pandas golden; the second
            and third submission must compile no stage
            (`compile_cache_misses` stands), but that a join query's
            second may compile one, the stage whose runtime filters
            hand on their survivors compacted, and then goes a fourth
            time; every status record must
            be `ok` with no fault events, and `/metrics` must count no
            retry, no OOM-ladder rung and no mesh fallback.
4. aggregate  the reference AggregateBenchmark's "linear keys" shape at
            full width (83,886,080 rows into 65,536 groups) under
            `aggregate.kernelMode=auto` and `scatter`: both equal the
            closed form and each other, and under `auto` the Pallas
            kernel (`tpu_custom_call`) is in the compiled program.
5. stop     the service stops; per-phase seconds are printed.

`--chips 4` runs the mesh path instead (device, data, then under
`spark_tpu.sql.mesh.size=4` with `meshFallback` off: a grouped
aggregate and Q3 against single-device runs and the goldens, and
between them the request of the benchmark's four-chip cell, Q1 then
`q15max`, served over `POST /sql` twice: with a cache budget cut so
that Q1 streams over the mesh, then as the cell runs it, both scans
held sharded over the chips' device-table caches) and no other phase.

Q5 is in the default set since PR 41. Until then its plan joined
CUSTOMER to SUPPLIER on the nation key alone and expanded some 18 M
rows at SF1, and on the chip it had not answered when this script's
900 s HTTP timeout ended the run (PERF.md, PR 22). With the reorder's
domain estimate its first submission compiles and answers in 62.1-62.6
s and its second, the stage whose filters compact, in 52.5-53.9 s with
an empty cache (PERF.md, PR 41: the benchmark's cell on one v5e, the
same stage text as here, which `--queries Q5` found in that cache:
7.2 s and 4.0 s); the default set took 430 s of the script's 1200
without it (PR 40). `--queries` picks a subset, e.g. `Q1,Q6`.

Any failure in any phase raises and the process exits non-zero at
once: nothing here catches an error and carries on, and nothing falls
back to another configuration. The last line of stdout is
`{"ok": true, "device": {"platform", "kind", "count"}}` as JAX reports
the device. Times printed on the way are information, not claims.

`--sf` and `--agg-rows` shrink the data for a rehearsal that calls the
phase functions on the CPU (tests/test_chip_smoke.py); run as a script
the device phase refuses anything but a TPU whatever the size.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import sys
import time
import urllib.request

import pandas as pd

CHECKOUT = os.path.dirname(os.path.abspath(__file__))

KERNEL_KEY = "spark_tpu.sql.aggregate.kernelMode"
MESH_KEY = "spark_tpu.sql.mesh.size"
MESH_FALLBACK_KEY = "spark_tpu.execution.meshFallback.enabled"

#: the TPC-H queries served by default, scan-aggregates first, the
#: joins after (see the docstring)
SERVED = ("Q1", "Q6", "Q3", "Q5")

#: of --queries, what the mesh phase runs: the join queries (hash
#: exchange, broadcast join, global sort)
MESH_QUERIES = ("Q3", "Q5")

#: reference AggregateBenchmark, "linear keys": range(20 << 22) into
#: 65,536 groups (bench.py builds the same DataFrame)
AGG_ROWS = 20 << 22
AGG_GROUPS = 65536

#: the mesh phase's grouped aggregate: partial per shard, hash exchange
#: over all_to_all, final merge (10,000 supplier keys at SF1)
MESH_AGG_SQL = ("select l_suppkey, sum(l_quantity) as qty, "
                "count(*) as n from lineitem group by l_suppkey")

#: the request of the benchmark's cell `tpch-sf10-mesh4.q1q15max`, by
#: the cell's own texts (benchmark/queries/<name>.sql). A chunk under
#: the table's rows has the residency verdict asked, as it is at SF10;
#: with the engine's own cache budget both scans are then held, laid
#: over the chips. What makes SF1's Q1 stream over the mesh in three
#: chunks instead, as a scan does that exceeds the chips' caches: a
#: chip's budget whose half is under a shard's part of the scan's
#: estimate (996 MB over four shards: 249 MB)
CELL_QUERIES = ("q1", "q15max")
CELL_CONF = {"spark_tpu.sql.execution.streamingChunkRows": 1 << 21}
CELL_STREAM_CONF = {**CELL_CONF,
                    "spark_tpu.sql.io.deviceCacheBytes": 256 << 20}

#: recovery actions the engine records per query; the smoke accepts none
FAULT_PREFIX = "spark_tpu_fault_"


def log(msg: str) -> None:
    print(msg, flush=True)


# -- 1. device ---------------------------------------------------------------


def phase_device(min_count: int) -> dict:
    """Refuse anything but a TPU with at least `min_count` chips."""
    import jax
    import jaxlib

    import spark_tpu  # noqa: F401 — places the compile cache
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: jax found no TPU (devices: {devs}); "
                 f"this script has no CPU path")
    if dev["count"] < min_count:
        sys.exit(f"chip_smoke: {min_count} chips asked for, "
                 f"{dev['count']} visible")
    from importlib.metadata import version
    libtpu = version("libtpu")
    log(f"device: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu} kind={dev['kind']!r} count={dev['count']} "
        f"compile_cache={jax.config.jax_compilation_cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    return dev


class CacheCounter:
    """Counts JAX's own persistent-compilation-cache events, so a run
    can say whether it compiled or loaded."""

    def __init__(self):
        import jax
        self.events: collections.Counter = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name.startswith("/jax/compilation_cache/cache_"):
            self.events[name.rsplit("/", 1)[-1]] += 1

    def line(self) -> str:
        return (f"compile cache: hits={self.events['cache_hits']} "
                f"misses={self.events['cache_misses']}")


# -- 2. data -----------------------------------------------------------------


def phase_data(sf: float, seed: int) -> str:
    from spark_tpu.tpch.datagen import write_parquet
    path = os.path.join(CHECKOUT, "data", "tpch", f"sf{sf:g}")
    write_parquet(path, sf, seed)
    import pyarrow.parquet as pq
    rows = pq.read_metadata(
        os.path.join(path, "lineitem.parquet")).num_rows
    log(f"data: TPC-H sf={sf:g} seed={seed} lineitem_rows={rows} "
        f"at {path}")
    return path


# -- 3. serve ----------------------------------------------------------------


def _http_json(url: str, body: dict = None, timeout: float = 900):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)


def _check_golden(got: pd.DataFrame, path: str, qname: str) -> None:
    from spark_tpu.tpch import golden as G
    want = G.GOLDEN[qname](path)
    G.compare(G.normalize_decimals(got)[list(want.columns)]
              .reset_index(drop=True), want.reset_index(drop=True))


def start_service(path: str, overrides: dict = None):
    from spark_tpu import Conf
    from spark_tpu.service.server import SqlService
    from spark_tpu.tpch import queries as Q
    conf = Conf()
    conf.set("spark_tpu.service.port", 0)
    for key, value in (overrides or {}).items():
        conf.set(key, value)
    return SqlService(
        conf, init_session=lambda s: Q.register_tables(s, path)).start()


def _check_status_record(base: str, resp: dict) -> None:
    rec = _http_json(f"{base}/queries/{resp['query_id']}")
    assert rec["status"] == "ok", rec
    assert not rec.get("fault_events"), rec
    assert not rec.get("fault_summary"), rec


def _metrics(base: str) -> dict:
    from spark_tpu.observability.metrics import parse_prometheus_text
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as resp:
        return parse_prometheus_text(resp.read().decode())


def _clean_metrics(base: str, completed: int) -> dict:
    """`/metrics`, after asserting that `completed` queries completed,
    none failed and no recovery action ran."""
    prom = _metrics(base)
    assert prom.get("spark_tpu_service_completed", 0) >= completed, prom
    assert not prom.get("spark_tpu_queries_failed"), prom
    recovered = {k: v for k, v in prom.items()
                 if k.startswith(FAULT_PREFIX) and v}
    assert not recovered, f"recovery actions ran: {recovered}"
    return prom


MISSES = "spark_tpu_compile_cache_misses"


def _stage_compiles(base: str) -> int:
    """The stage cache's misses so far: the count of stage compiles."""
    return int(_metrics(base).get(MISSES, 0))


def phase_serve(svc, path: str, queries=SERVED) -> None:
    """`queries` over HTTP, each three times, cold then warm twice:
    golden parity, clean status records, clean /metrics, and a stage
    compiled by the first submission alone (until PR 37 a join's second
    submission compiled again, and its "warm" time was a compile:
    PR 37's fault was a compile on every submission). A join query
    goes a fourth time, and its compiles by submission may read
    [>=1, <=1, 0, 0]: the first execution of a text whose runtime
    filter prunes hard learns the capacity its survivors fit, and the
    second compiles the stage that hands them on compacted, once, by
    design (PR 38); the third and the fourth must compile nothing."""
    from spark_tpu.tpch import sql_queries as SQLQ
    base = f"http://127.0.0.1:{svc.port}"
    sent = 0
    for name in queries:
        ms, compiles = [], [_stage_compiles(base)]
        joins = name in MESH_QUERIES
        runs = ("cold", "warm", "warm again") \
            + (("and again",) if joins else ())
        for _run in runs:
            t0 = time.perf_counter()
            resp = _http_json(f"{base}/sql",
                              {"sql": getattr(SQLQ, name)})
            ms.append((time.perf_counter() - t0) * 1e3)
            assert resp["status"] == "ok", resp
            got = pd.DataFrame(resp["rows"], columns=resp["columns"])
            _check_golden(got, path, name.lower())
            _check_status_record(base, resp)
            compiles.append(_stage_compiles(base))
        sent += len(runs)
        grown = [b - a for a, b in zip(compiles, compiles[1:])]
        assert grown[0] >= 1 and grown[1] <= int(joins) \
            and not any(grown[2:]), \
            f"{name}: stage compiles by submission {grown}: a warm " \
            f"submission compiled"
        log(f"serve: {name} rows={resp['row_count']} golden=ok "
            f"cold_ms={ms[0]:.1f} warm_ms={ms[1]:.1f} "
            + " ".join(f"warm_again_ms={t:.1f}" for t in ms[2:])
            + f" compiles={grown}")
    prom = _clean_metrics(base, sent)
    log(f"serve: /metrics completed="
        f"{int(prom['spark_tpu_service_completed'])} retries=0 "
        f"oom_rungs=0 mesh_fallback=0")


# -- 4. aggregate at full width ----------------------------------------------


def _linear_keys(spark, n_rows: int):
    from spark_tpu import functions as F
    from spark_tpu.functions import col
    return (spark.range(n_rows)
            .select(F.pmod(col("id"), AGG_GROUPS).alias("k"))
            .group_by(col("k")).agg(F.sum(col("k")).alias("sum(k)")))


def programs_with_kernel(spark) -> list:
    """Stage-cache keys of the streamed range aggregate whose compiled
    program holds the Pallas kernel. The session's stage cache keeps
    the jitted chunk loop the query ran (`stream_range_aggregate`);
    compiling it again is a cache hit."""
    streamed = {k: fn for k, fn in spark._stage_cache.items()
                if k.startswith("stream_range:")}
    assert streamed, (f"the aggregate did not take the streamed range "
                      f"path: {[k[:60] for k in spark._stage_cache]}")
    return [k for k, fn in streamed.items()
            if "tpu_custom_call" in fn.lower().compile().as_text()]


def phase_aggregate(spark, n_rows: int) -> None:
    """Linear keys under kernelMode auto and scatter: closed form, each
    other, and last the proof of which kernel each program holds."""
    assert n_rows % AGG_GROUPS == 0, n_rows
    per_key = n_rows // AGG_GROUPS
    results, with_kernel = {}, {}
    for mode in ("auto", "scatter"):
        spark.conf.set(KERNEL_KEY, mode)
        spark._stage_cache.clear()
        ms = []
        for _run in ("cold", "warm"):
            t0 = time.perf_counter()
            qe = _linear_keys(spark, n_rows)._qe()
            got = qe.collect().to_pandas()
            ms.append((time.perf_counter() - t0) * 1e3)
            assert not qe.fault_summary, qe.fault_summary
        got = got.sort_values("k").reset_index(drop=True)
        assert got["k"].tolist() == list(range(AGG_GROUPS)), got["k"][:5]
        assert (got["sum(k)"] == got["k"] * per_key).all(), got.head()
        results[mode] = got
        with_kernel[mode] = programs_with_kernel(spark)
        log(f"aggregate: kernelMode={mode} rows={n_rows} "
            f"groups={AGG_GROUPS} closed_form=ok "
            f"pallas_kernel_in_program={bool(with_kernel[mode])} "
            f"cold_ms={ms[0]:.1f} warm_ms={ms[1]:.1f}")
    spark.conf.set(KERNEL_KEY, "auto")
    pd.testing.assert_frame_equal(results["auto"], results["scatter"])
    assert not with_kernel["scatter"], with_kernel["scatter"]
    assert with_kernel["auto"], (
        "kernelMode=auto on a TPU, yet no compiled program of the "
        "aggregate holds a tpu_custom_call: the Pallas kernel did not "
        "run")


# -- the mesh, behind --chips 4 ----------------------------------------------


def _mesh_agg_golden(path: str) -> pd.DataFrame:
    from spark_tpu.tpch import golden as G
    li = G.normalize_decimals(pd.read_parquet(
        os.path.join(path, "lineitem.parquet"),
        columns=["l_suppkey", "l_quantity"]))
    return (li.groupby("l_suppkey", as_index=False)
            .agg(qty=("l_quantity", "sum"), n=("l_quantity", "size")))


def _bytes_in_use(n: int):
    """Bytes in use by device, or None where the backend keeps no
    memory statistics (the CPU's)."""
    import jax
    stats = [d.memory_stats() for d in jax.devices()[:n]]
    if not all(stats):
        return None
    return [int(st["bytes_in_use"]) for st in stats]


def phase_mesh_served(path: str, n: int, conf: dict, streams: bool) -> None:
    """The four-chip cell's request, Q1 then `q15max`, over `POST /sql`
    from a `SqlService` under mesh.size=n with no fallback, cold then
    warm: Q1 against the pandas golden, `q15max` against the
    benchmark's exact reference, clean status records, and a
    `/metrics` that counts the mesh's stages and exchanges and no
    recovery. Under a `conf` that `streams`, Q1 streams over the mesh
    on both passes; else both scans are loaded once, laid over the
    devices, and found there: no chunk is ingested, and the devices'
    bytes in use grow by the same."""
    from benchmark.reference import q15max as reference
    from spark_tpu.io.device_cache import CACHE
    want = float(reference.combine([reference.partial(
        os.path.join(path, "lineitem.parquet"))])[0]["max_revenue"])
    texts = {}
    for name in CELL_QUERIES:
        with open(os.path.join(CHECKOUT, "benchmark", "queries",
                               name + ".sql")) as f:
            texts[name] = f.read()
    # a scan this process holds already is never streamed, nor loaded;
    # and what an earlier phase left for the collector goes now, not
    # between this phase's two readings of the devices' memory
    CACHE.clear()
    gc.collect()
    names = ("mesh_stage_dispatches", "stage_dispatches", "exchange_rows",
             "exchange_bytes", "shard_rows_max", "shard_rows_total",
             "scans_streamed", "scans_resident", "ingest_chunks")
    svc = start_service(path, {MESH_KEY: n, MESH_FALLBACK_KEY: False,
                               **conf})
    base = f"http://127.0.0.1:{svc.port}"
    try:
        # the registry is the process's: what this service adds to it
        before = _clean_metrics(base, 0)
        loads, in_use = CACHE.sharded_loads, _bytes_in_use(n)
        ms = {}
        for _run in ("cold", "warm"):
            for name in CELL_QUERIES:
                t0 = time.perf_counter()
                resp = _http_json(f"{base}/sql", {"sql": texts[name]})
                ms.setdefault(name, []).append(
                    (time.perf_counter() - t0) * 1e3)
                assert resp["status"] == "ok", resp
                if name == "q1":
                    _check_golden(pd.DataFrame(
                        resp["rows"], columns=resp["columns"]), path, name)
                else:
                    assert resp["rows"] == [
                        {"one": 1, "max_revenue": want}], (resp["rows"], want)
                _check_status_record(base, resp)
        prom = _clean_metrics(base, 2 * len(CELL_QUERIES))
        counted = {k: int(prom.get("spark_tpu_" + k, 0)
                          - before.get("spark_tpu_" + k, 0)) for k in names}
        counted["sharded_loads"] = CACHE.sharded_loads - loads
        assert counted["mesh_stage_dispatches"] \
            == counted["stage_dispatches"] >= 2 * len(CELL_QUERIES), counted
        assert counted["exchange_rows"] and counted["shard_rows_total"], \
            counted
        if streams:
            # Q1 over the mesh on both passes, in more than a chunk
            assert counted["scans_streamed"] >= 2, counted
            assert counted["ingest_chunks"] >= 4, counted
        else:
            assert counted["scans_streamed"] == 0, counted
            assert counted["ingest_chunks"] == 0, counted
            assert counted["scans_resident"] == 2 * len(CELL_QUERIES), \
                counted
            assert counted["sharded_loads"] == len(CELL_QUERIES), counted
            grown = None if in_use is None else [
                b - a for a, b in zip(in_use, _bytes_in_use(n))]
            # what the cache holds now was put where it is used: no
            # device took more than a tenth above another
            assert grown is None or 0 < max(grown) <= 1.1 * min(grown), grown
            log(f"mesh: served bytes in use by device, grown by {grown}")
    finally:
        svc.stop()
    how = "streamed" if streams else "held"
    for name in CELL_QUERIES:
        log(f"mesh: served ({how}) {name} mesh.size={n} reference=ok "
            f"cold_ms={ms[name][0]:.1f} warm_ms={ms[name][1]:.1f}")
    log(f"mesh: served ({how}) /metrics " + json.dumps(counted)
        + " retries=0 mesh_fallback=0")


def phase_mesh(spark, path: str, n: int, queries=("Q3",),
               stream_conf: dict = CELL_STREAM_CONF,
               cell_conf: dict = CELL_CONF) -> None:
    """A grouped aggregate, the four-chip cell's served request
    (streamed under `stream_conf`, then held under `cell_conf`; a
    rehearsal's are smaller) and `queries` under mesh.size=n against
    single-device runs and the goldens; no single-device fallback, and
    the mesh run must really lay its batches over n devices."""
    from spark_tpu.tpch import golden as G
    from spark_tpu.tpch import queries as Q
    from spark_tpu.tpch import sql_queries as SQLQ
    Q.register_tables(spark, path)
    spark.conf.set(MESH_FALLBACK_KEY, False)
    # the cheapest program to compile first; each run meets its golden
    # as soon as it ends, so a run cut short still says what passed
    cases = [("grouped_agg", MESH_AGG_SQL, "l_suppkey")]
    cases += [(q, getattr(SQLQ, q), None) for q in queries]
    for name, sql, sort_key in cases:
        runs = {}
        for size in (n, 0):
            spark.conf.set(MESH_KEY, size)
            t0 = time.perf_counter()
            qe = spark.sql(sql)._qe()
            batch, _, _ = qe.execute_batch()
            got = G.normalize_decimals(batch.to_arrow().to_pandas())
            ms = (time.perf_counter() - t0) * 1e3
            assert not qe.fault_summary, qe.fault_summary
            assert "mesh_fallback" not in qe.last_metrics, qe.last_metrics
            spread = {d.id for c in batch.columns.values()
                      for d in c.data.sharding.device_set}
            assert len(spread) == max(size, 1), (
                f"{name}: mesh.size={size} but the stage's batch lies "
                f"on devices {sorted(spread)}")
            exchanged = sum(int(v) for k, v in qe.last_metrics.items()
                            if k.startswith("exch_rows_"))
            assert bool(exchanged) == bool(size), qe.last_metrics
            # row counts per operator repeat exactly on the CPU's
            # virtual mesh: a wrong answer here is found by diffing them
            log(f"mesh: {name} mesh.size={size} counters=" + json.dumps(
                {k: v for k, v in sorted(qe.last_metrics.items())
                 if "_ms_" not in k}))
            if sort_key:
                got = got.sort_values(sort_key).reset_index(drop=True)
                G.compare(got, _mesh_agg_golden(path))
            else:
                _check_golden(got, path, name.lower())
            runs[size] = got
            log(f"mesh: {name} mesh.size={size} rows={len(got)} "
                f"golden=ok devices={len(spread)} "
                f"exchanged_rows={exchanged} ms={ms:.1f}")
        G.compare(runs[n], runs[0])
        log(f"mesh: {name} mesh == single-device ok")
        if name == "grouped_agg":
            # before the join queries, whose compiles take minutes: a
            # run cut short there still says whether the cell's path held
            phase_mesh_served(path, n, stream_conf, streams=True)
            phase_mesh_served(path, n, cell_conf, streams=False)
    spark.conf.set(MESH_KEY, 0)


def assert_devices_held_data(n: int) -> None:
    """Every chip shows memory in use only if shards really went
    there. (TPU only: the CPU backend reports no memory statistics.)"""
    import jax
    peaks = {d.id: d.memory_stats()["peak_bytes_in_use"]
             for d in jax.devices()[:n]}
    assert all(v > 0 for v in peaks.values()), peaks
    log(f"mesh: peak bytes in use per device {peaks}")


# -- main --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run the mesh phase and nothing else")
    ap.add_argument("--queries", default=",".join(SERVED),
                    help="TPC-H queries to serve, e.g. Q1,Q6,Q3,Q5")
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (rehearsals shrink it)")
    ap.add_argument("--agg-rows", type=int, default=AGG_ROWS,
                    help="rows of the linear-keys aggregate")
    args = ap.parse_args(argv)
    queries = tuple(q.strip().upper() for q in args.queries.split(","))

    seconds = {}

    def timed(name, fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        seconds[name] = round(time.perf_counter() - t0, 1)
        return out

    dev = timed("device", phase_device, args.chips)
    cache = CacheCounter()
    path = timed("data", phase_data, args.sf, args.seed)

    from spark_tpu import SparkTpuSession
    spark = SparkTpuSession.builder().get_or_create()
    if args.chips == 4:
        timed("mesh", phase_mesh, spark, path, 4,
              tuple(q for q in queries if q in MESH_QUERIES))
        assert_devices_held_data(4)
    else:
        svc = timed("start", start_service, path)
        try:
            timed("serve", phase_serve, svc, path, queries)
            timed("aggregate", phase_aggregate, spark, args.agg_rows)
        finally:
            timed("stop", svc.stop)
    log(cache.line())
    log("seconds: " + json.dumps(seconds))
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
