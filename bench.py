"""Headline benchmark, run by the driver on real TPU hardware.

Primary metric — the EXACT reference shape of `AggregateBenchmark.scala:69-75`
("aggregate with linear keys"): ``range(20<<22).selectExpr("(id & 65535)
as k").groupBy(k).sum()`` — 83.9M rows, 65,536 groups, a SUM per group.
The apples-to-apples comparator is its best row, **84.3 M rows/s**
(codegen=T vectorized hashmap=T, `AggregateBenchmark-results.txt:41`,
Xeon Platinum 8171M). Round 2 benchmarked a 100-group count against that
row — a far easier shape — per VERDICT weak #3; the 100-group
BASELINE-config-1 metric is kept as a secondary row.

Also benchmarked: global stddev over `range(100<<20)` vs the reference's
91.4 M rows/s (`AggregateBenchmark-results.txt:18-24` "stat functions"),
and the TPC-H north-star queries (Q1/Q6/Q3/Q5) with result parity
against the independent pandas goldens, per-query wall-clock in `extra`
(the `TPCDSQueryBenchmark.scala:54` pattern).

Output is timeout-proof (round-5 ran into the driver's rc:124 with zero
parseable output): every section prints its OWN complete JSON line the
moment it finishes (flushed), each section runs under a SIGALRM
deadline, AND the aggregate summary line {"metric", "value", "unit",
"vs_baseline", "extra"} is rewritten (with "partial": true) after every
section — a killed or hung run leaves both per-section lines and a
parseable partial summary. Consumers take the LAST summary-shaped line;
the final rewrite drops the partial marker.

Round-5 post-mortem (rc:124, parsed:null): per-section budgets of 900s
x 5 sections + 1650s of SF10 never fit the driver's outer `timeout`, so
the kill arrived with nothing parseable emitted. The matrix now fits a
TOTAL budget (`BENCH_TOTAL_BUDGET_S`, default 2400s) enforced on top of
tighter per-section deadlines (`BENCH_SECTION_BUDGET_S`, default 420s):
each section gets min(section budget, remaining total), sections past
the total are SKIPPED with their own JSON line, and the SF10 sweep is
opt-in (`BENCH_RUN_SF10=1`) instead of default — the default matrix
completes inside the budget with a final (non-partial) summary.
"""

import contextlib
import json
import os
import signal
import tempfile
import time

import numpy as np

# AggregateBenchmark.scala:69 "aggregate with linear keys"
N_KEYS = 20 << 22            # 83,886,080 rows
KEYS_BASELINE = 84.3e6       # M rows/s, vectorized hashmap row
# AggregateBenchmark.scala:57 "stat functions" / stddev
N_STDDEV = 100 << 20         # 104,857,600 rows
STDDEV_BASELINE = 91.4e6
# BASELINE config 1 (kept as a secondary metric)
N_100G = 1_000_000_000

TPCH_SF = float(os.environ.get("BENCH_TPCH_SF", "1"))
TPCH_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "tpch", f"sf{TPCH_SF:g}")
TPCDS_SF = float(os.environ.get("BENCH_TPCDS_SF", "1"))
TPCDS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "data", "tpcds", f"sf{TPCDS_SF:g}")


class SectionTimeout(BaseException):
    """BaseException, NOT Exception: section bodies (stddev fallbacks,
    kernel_pick per-mode loop) catch broad Exception for infra
    failures, and the deadline must punch through those handlers."""


@contextlib.contextmanager
def _section_deadline(seconds: float):
    """SIGALRM-backed per-section bound. A section that blows its budget
    raises SectionTimeout at the next Python bytecode (a single hung C
    call can still stall past it, but the per-section JSON lines already
    printed survive any outer `timeout` kill)."""
    if seconds <= 0 or not hasattr(signal, "SIGALRM"):
        yield
        return

    def handler(signum, frame):
        raise SectionTimeout()

    old = signal.signal(signal.SIGALRM, handler)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def _emit(section: str, status: str, t0: float, data: dict) -> None:
    print(json.dumps({"section": section, "status": status,
                      "elapsed_s": round(time.perf_counter() - t0, 1),
                      "data": data}), flush=True)


#: the bench session whose flight recorder section failures dump
#: bundles from (set by _arm_flight_recorder in main)
_FLIGHTREC_SESSION = None


def _arm_flight_recorder(spark) -> None:
    """Arm the always-on flight recorder for every section: ring
    recording on, bundles under the bench output dir, and the session
    registered so _run_section can dump on a timeout/error."""
    global _FLIGHTREC_SESSION
    spark.conf.set("spark_tpu.sql.flightRecorder.enabled", "true")
    spark.conf.set("spark_tpu.sql.flightRecorder.dir",
                   os.path.join(tempfile.gettempdir(),
                                "spark-tpu-bench-flightrec"))
    _FLIGHTREC_SESSION = spark


def _section_bundle(name: str, detail: str):
    """Dump a flight-recorder bundle for a failed/timed-out section;
    returns its path (None when unarmed or the dump failed)."""
    if _FLIGHTREC_SESSION is None:
        return None
    from spark_tpu.observability.flight_recorder import FlightRecorder
    rec = FlightRecorder.of(_FLIGHTREC_SESSION)
    if rec is None:
        return None
    return rec.dump(f"bench_{name}", extra={"section": name,
                                            "detail": detail})


def _run_section(name: str, fn, budget_s: float) -> dict:
    """Run one bench section under its own deadline and emit its JSON
    line immediately; always returns a dict (possibly {'error': ...}).
    A timeout or error additionally dumps a flight-recorder bundle and
    carries its path in the JSON line ('bundle'): the post-mortem for
    a wedged section starts from the bundle, not from rerunning it."""
    t0 = time.perf_counter()
    data = None
    try:
        with _section_deadline(budget_s):
            data = fn()
        _emit(name, "ok", t0, data)
        return data
    except SectionTimeout:
        if data is not None:
            # the alarm fired in the window between fn() returning and
            # the deadline context disarming it: the section DID finish
            _emit(name, "ok", t0, data)
            return data
        detail = f"section timeout after {budget_s:g}s"
        data = {f"{name}_error": detail,
                "bundle": _section_bundle(name, detail)}
        _emit(name, "timeout", t0, data)
        return data
    except Exception as e:  # noqa: BLE001
        detail = f"{type(e).__name__}: {e}"[:300]
        data = {f"{name}_error": detail,
                "bundle": _section_bundle(name, detail)}
        _emit(name, "error", t0, data)
        return data


def _time3(run_sync):
    run_sync()  # warmup: compile + first run
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        run_sync()
        times.append(time.perf_counter() - t0)
    return min(times)


def _warm_best2(run_once):
    """Warmup + best-of-2 for the TPC query sections: `run_once`
    returns (qe, result); returns (qe, result, best_seconds). ONE
    definition so the tpch and tpcds sections cannot drift on the
    warmup protocol."""
    run_once()  # warmup: compile + first ingest
    best = None
    qe = got = None
    for _ in range(2):
        t0 = time.perf_counter()
        qe, got = run_once()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return qe, got, best


def _prediction_sidecars(qe, extra: dict, key: str) -> None:
    """Analyzer/planner self-grading sidecars (mean |error| of the
    plan-time size predictions vs this run's observed metrics) under
    `<key>_pred_err_pct` / `<key>_pred_under` — shared by the tpch and
    tpcds sections so the grading semantics cannot drift."""
    from spark_tpu.history import grade_predictions
    graded = grade_predictions(qe.plan_predictions or [],
                               qe.last_metrics)
    errs = [abs(g["err_pct"]) for g in graded
            if g.get("err_pct") is not None]
    if errs:
        extra[f"{key}_pred_err_pct"] = round(sum(errs) / len(errs), 1)
        extra[f"{key}_pred_under"] = sum(
            1 for g in graded if g["grade"] == "under")


def bench_linear_keys(spark):
    """(id & 65535) keys, sum per group — the reference's headline shape.
    pmod(id, 65536) == id & 65535 for the non-negative range ids, and its
    statically non-negative range keeps the kernel's limb count minimal
    (the same property `& 65535` gives the reference's codegen)."""
    from spark_tpu import functions as F
    from spark_tpu.functions import col

    df = (spark.range(N_KEYS)
          .select(F.pmod(col("id"), 65536).alias("k"))
          .group_by(col("k")).agg(F.sum(col("k")).alias("sum(k)")))
    qe = df._qe()

    def run_sync():
        b, _, _ = qe.execute_batch()
        # a host pull of the result is the sync point: the timing
        # ends when the sums are on the host
        import jax
        jax.device_get(b.columns["sum(k)"].data)
        return b

    best = _time3(run_sync)
    b, _, _ = qe.execute_batch()
    pdf = b.to_arrow().to_pydict()
    assert sorted(pdf["k"]) == list(range(65536)), pdf["k"][:5]
    per_key = N_KEYS // 65536
    assert pdf["sum(k)"][pdf["k"].index(7)] == 7 * per_key
    return N_KEYS / best


def bench_stddev(spark):
    """Falls back kernelMode=scatter, then unstreamed, on compile
    failure (so whatever it prints is not one configuration — the
    `benchmark` PR replaces this ladder with one cell that fails)."""
    from spark_tpu import functions as F
    from spark_tpu.functions import col

    def attempt():
        df = spark.range(N_STDDEV).agg(F.stddev(col("id")).alias("sd"))
        qe = df._qe()

        def run_sync():
            b, _, _ = qe.execute_batch()
            import jax
            return float(jax.device_get(b.columns["sd"].data)[0])

        best = _time3(run_sync)
        sd = run_sync()
        want = np.sqrt((N_STDDEV**2 - 1) / 12.0)  # stddev of 0..N-1
        assert abs(sd - want) / want < 1e-6, (sd, want)
        return N_STDDEV / best

    kern_key = "spark_tpu.sql.aggregate.kernelMode"
    chunk_key = "spark_tpu.sql.execution.streamingChunkRows"
    fallbacks = [{}, {kern_key: "scatter"},
                 {kern_key: "scatter", chunk_key: N_STDDEV * 2}]
    last = None
    for fb in fallbacks:
        old = {k: spark.conf.get(k) for k in fb}
        try:
            for k, v in fb.items():
                spark.conf.set(k, v)
            return attempt()
        except AssertionError:
            raise
        except Exception as e:  # compile/runtime infra failure: retry
            last = e
        finally:
            for k, v in old.items():
                spark.conf.set(k, v)
    raise last


def bench_100_groups(spark):
    from spark_tpu.functions import col

    df = spark.range(N_100G).group_by((col("id") % 100).alias("k")).count()
    qe = df._qe()

    def run_sync():
        b, _, _ = qe.execute_batch()
        import jax
        jax.device_get(b.columns["count"].data)
        return b

    best = _time3(run_sync)
    b, _, _ = qe.execute_batch()
    pdf = b.to_arrow().to_pydict()
    assert sorted(pdf["k"]) == list(range(100)), pdf["k"][:5]
    assert all(c == N_100G // 100 for c in pdf["count"]), pdf["count"][:5]
    return N_100G / best


def bench_kernel_pick(spark):
    """Measure the 65k-group headline shape under each aggregate kernel
    (factorized MXU matmul vs XLA scatter) ON HARDWARE and report both —
    the winner is chosen by measurement, not fixed at trace time
    (round-4 VERDICT weak #1)."""
    from spark_tpu import functions as F
    from spark_tpu.functions import col

    kern_key = "spark_tpu.sql.aggregate.kernelMode"
    out = {}
    for mode in ("matmul", "scatter"):
        try:
            spark.conf.set(kern_key, mode)
            df = (spark.range(N_KEYS)
                  .select(F.pmod(col("id"), 65536).alias("k"))
                  .group_by(col("k")).agg(F.sum(col("k")).alias("s")))
            qe = df._qe()

            def run_sync():
                b, _, _ = qe.execute_batch()
                import jax
                jax.device_get(b.columns["s"].data)

            out[f"kern_{mode}_rows_per_sec_M"] = round(
                N_KEYS / _time3(run_sync) / 1e6, 1)
        except Exception as e:
            out[f"kern_{mode}_error"] = f"{type(e).__name__}: {e}"[:160]
        finally:
            spark.conf.set(kern_key, "auto")
    return out


def bench_join_microbench(spark):
    """Hash vs sort join-kernel microbench: inner-join probe rows/s at
    1M and 16M probe rows against a 64k-row build side (duplicate keys
    included so the many-to-many expansion runs). The result feeds the
    kernel-choice heuristics (join.hashMinProbeRows /
    hashProbeBuildRatio) with measured crossover data per platform."""
    import numpy as np
    import pandas as pd

    from spark_tpu import functions as F
    from spark_tpu.functions import col

    mode_key = "spark_tpu.sql.join.kernelMode"
    old_mode = spark.conf.get(mode_key)
    build_n = 1 << 16
    # BENCH_JOIN_PROBE_ROWS: comma list of probe sizes (preflight
    # smokes shrink it; the default pair is the BENCH trajectory shape)
    probe_sizes = [int(v) for v in os.environ.get(
        "BENCH_JOIN_PROBE_ROWS", f"{1 << 20},{1 << 24}").split(",")]
    rs = np.random.RandomState(42)
    dim = pd.DataFrame({
        # ~1/16 duplicated build keys: exercises expansion without
        # blowing the out_cap past the probe capacity
        "k2": np.concatenate([
            np.arange(build_n - (build_n >> 4), dtype=np.int64),
            rs.randint(0, build_n >> 4, build_n >> 4)]),
        "w": np.arange(build_n, dtype=np.int64)})
    spark.register_table("jmb_dim", dim)
    out = {}
    try:
        for probe_n in probe_sizes:
            label = f"{probe_n >> 20}m" if probe_n >= 1 << 20 \
                else f"{probe_n >> 10}k"
            fact = pd.DataFrame({
                "k": rs.randint(0, build_n, probe_n).astype(np.int64),
                "v": np.arange(probe_n, dtype=np.int64)})
            spark.register_table("jmb_fact", fact)
            for mode in ("sort", "hash"):
                spark.conf.set(mode_key, mode)
                # aggregate the join output so timing measures the
                # kernel, not a multi-million-row host transfer
                df = (spark.table("jmb_fact")
                      .join(spark.table("jmb_dim"), left_on=col("k"),
                            right_on=col("k2"))
                      .agg(F.sum(col("v") + col("w")).alias("s")))
                qe = df._qe()

                def run_sync():
                    b, _, _ = qe.execute_batch()
                    import jax
                    jax.device_get(b.columns["s"].data)
                    return b

                best = _time3(run_sync)
                out[f"join_{label}_{mode}_rows_per_sec_M"] = round(
                    probe_n / best / 1e6, 1)
            srt = out[f"join_{label}_sort_rows_per_sec_M"]
            hsh = out[f"join_{label}_hash_rows_per_sec_M"]
            if srt:
                out[f"join_{label}_hash_speedup"] = round(hsh / srt, 3)
    finally:
        spark.conf.set(mode_key, old_mode)
    return out


#: compile-cache child: one fresh process running Q1+Q3 with the
#: persistent AOT compile cache pointed at argv[2] — prints compile
#: span ms (eager AOT under the cache, so the span is the true
#: trace+compile or deserialize cost), first-run e2e ms, disk
#: hit/miss counters and a result digest. Run twice by
#: bench_compile_cache: cold (empty dir) then warm (same dir).
_CC_CHILD = r'''
import hashlib, json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
from spark_tpu import SparkTpuSession
from spark_tpu.tpch import golden as G
from spark_tpu.tpch import queries as Q

path, cc_dir = sys.argv[1], sys.argv[2]
spark = SparkTpuSession.builder().get_or_create()
spark.conf.set("spark_tpu.sql.compileCache.enabled", True)
spark.conf.set("spark_tpu.sql.compileCache.dir", cc_dir)
Q.register_tables(spark, path)
out = {}
for name in ("q1", "q3"):
    t0 = time.perf_counter()
    qe = Q.QUERIES[name](spark)._qe()
    got = qe.collect().to_pandas()
    e2e = (time.perf_counter() - t0) * 1e3
    # compile spans ONLY: the deserialize sub-span is nested inside
    # its compile span's interval, so summing both would double count
    compile_ms = sum(s.dur_ms for s in qe.spans.spans
                     if s.name == "compile")
    digest = hashlib.md5(G.normalize_decimals(got)
                         .to_csv(index=False).encode()).hexdigest()
    out[name] = {"e2e_ms": round(e2e, 1),
                 "compile_ms": round(compile_ms, 1), "md5": digest}
m = spark.metrics
out["disk_hits"] = int(m.counter("compile_cache_disk_hits").value)
out["disk_misses"] = int(m.counter("compile_cache_disk_misses").value)
out["deser_ms"] = round(float(m.counter("compile_cache_deser_ms").value), 1)
print("CCBENCH " + json.dumps(out), flush=True)
'''


def bench_compile_cache(spark):
    """Cold-vs-warm-PROCESS compile cost for the persistent AOT
    compile cache (execution/compile_cache.py): TPC-H Q1+Q3 each run
    in a FRESH subprocess against one shared cache dir — the first
    child pays trace + XLA compile and serializes, the second must
    open warm (compile_cache_disk_hits >= 1) with byte-identical
    results, paying deserialization only. The children are pinned to
    CPU: the TPU runtime is single-client and this parent holds the
    chip, so CPU XLA compile time is the measured proxy (the
    mechanism is backend-agnostic; disk hits + parity are asserted
    either way, and compile_cache_backend labels the rows)."""
    import subprocess
    import sys
    import tempfile

    from spark_tpu.tpch.datagen import write_parquet

    base = tempfile.mkdtemp(prefix="bench_cc_")
    sf_path = os.path.join(base, "sf")
    write_parquet(sf_path, 0.01)
    cc_dir = os.path.join(base, "cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run_child():
        proc = subprocess.run(
            [sys.executable, "-c", _CC_CHILD, sf_path, cc_dir],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        for line in proc.stdout.splitlines():
            if line.startswith("CCBENCH "):
                return json.loads(line[len("CCBENCH "):])
        raise RuntimeError(
            f"compile-cache child rc={proc.returncode}: "
            f"{proc.stderr[-400:]}")

    cold = run_child()
    warm = run_child()
    assert warm["disk_hits"] >= 1, (cold, warm)
    out = {"compile_cache_backend": "cpu",
           "compile_cache_warm_disk_hits": warm["disk_hits"],
           "compile_cache_warm_disk_misses": warm["disk_misses"],
           "compile_cache_warm_deser_ms": warm["deser_ms"]}
    for q in ("q1", "q3"):
        assert cold[q]["md5"] == warm[q]["md5"], (q, cold, warm)
        out[f"tpch_{q}_compile_cold_ms"] = cold[q]["compile_ms"]
        out[f"tpch_{q}_compile_warm_ms"] = warm[q]["compile_ms"]
        out[f"tpch_{q}_e2e_cold_ms"] = cold[q]["e2e_ms"]
        out[f"tpch_{q}_e2e_warm_ms"] = warm[q]["e2e_ms"]
    return out


def bench_tpch(spark, sf: float, path: str, queries=("q1", "q6", "q3",
                                                     "q5"),
               float_atol: float = 1e-4, deadline: float = None):
    """Generate (cached) SF data, run the queries timed, check parity.
    `deadline` (perf_counter value): remaining queries are skipped once
    passed, so a slow scale factor can never starve the whole bench."""
    from spark_tpu.tpch import golden as G
    from spark_tpu.tpch import queries as Q
    from spark_tpu.tpch.datagen import write_parquet

    write_parquet(path, sf)
    Q.register_tables(spark, path)
    extra = {}
    # XLA cost/HBM sidecars (flops, bytes accessed, peak HBM demand per
    # query) ride along with the wall-clock rows, so BENCH rounds form a
    # real perf trajectory: time deltas become attributable to compute
    # vs movement vs memory pressure. Capture pays one extra analysis
    # compile per stage key (memoized session-wide), on the warmup run.
    cost_key = "spark_tpu.sql.observability.xlaCost"
    old_cost_mode = spark.conf.get(cost_key)
    spark.conf.set(cost_key, "on")
    try:
        return _bench_tpch_queries(spark, sf, queries, float_atol,
                                   deadline, path, extra)
    finally:
        spark.conf.set(cost_key, old_cost_mode)


def _bench_tpch_queries(spark, sf, queries, float_atol, deadline, path,
                        extra):
    from spark_tpu.tpch import golden as G
    from spark_tpu.tpch import queries as Q

    for name in queries:
        if deadline is not None and time.perf_counter() > deadline:
            extra[f"tpch_{name}_sf{sf:g}_skipped"] = "time budget"
            continue
        df_fn = Q.QUERIES[name]

        def run_once():
            qe = df_fn(spark)._qe()
            b, _, _ = qe.execute_batch()
            return qe, b.to_arrow().to_pandas()

        # partial-progress recovery sidecar: chunks replayed by the
        # per-chunk retry across this query's runs. MUST stay 0 on a
        # clean run — nonzero means the TPU runtime flaked mid-stream
        # (and the stream resumed instead of restarting)
        rec0 = spark.metrics.counter("rec_chunks_replayed").value
        # elastic-mesh sidecar baselines: gang restarts applied and
        # rows the straggler rebalancer shifted — both MUST stay 0 on
        # a clean single-host round; nonzero means the mesh healed
        # (or rebalanced) mid-bench instead of degrading
        mr0 = spark.metrics.counter("mesh_restart_attempts").value
        rb0 = spark.metrics.counter("rebalance_rows").value
        # ingest-pipeline sidecar baselines (registry counters)
        stall0 = spark.metrics.counter("ingest_stall_ms").value
        overlap0 = spark.metrics.counter("ingest_overlap_ms").value
        # keep the FIRST (warmup) run's qe: its compile/deserialize
        # spans carry the compile cost this query paid in this
        # process (the compile-cache trajectory sidecar; ~0 once the
        # session's stage cache is warm from an earlier section)
        first_qe = []

        def run_once_capturing():
            r = run_once()
            if not first_qe:
                first_qe.append(r[0])
            return r

        qe, got, best = _warm_best2(run_once_capturing)
        extra[f"tpch_{name}_sf{sf:g}_ms"] = round(best * 1e3, 1)
        # compile spans only — the deserialize sub-span is nested
        # inside its compile span, so including it would double count
        extra[f"tpch_{name}_sf{sf:g}_compile_ms"] = round(sum(
            s.dur_ms for s in first_qe[0].spans.spans
            if s.name == "compile"), 1)
        # ingest vs compute split of the last run (VERDICT r3 next-1d):
        # with the device-table cache warm, ingest should be ~0
        for phase in ("ingest", "execution", "streaming"):
            if phase in qe.phase_times:
                extra[f"tpch_{name}_{phase}_ms"] = round(
                    qe.phase_times[phase] * 1e3, 1)
        # XLA cost/HBM accounting sidecar (observability/xla_cost.py):
        # total flops + bytes accessed across the query's compiled
        # stages, and the worst single-stage peak HBM demand
        costs = [c for c in qe.stage_costs.values()
                 if c.get("flops") is not None
                 or c.get("peak_hbm_bytes") is not None]
        if costs:
            extra[f"tpch_{name}_sf{sf:g}_flops"] = int(
                sum(c.get("flops") or 0 for c in costs))
            extra[f"tpch_{name}_sf{sf:g}_xla_bytes"] = int(
                sum(c.get("bytes_accessed") or 0 for c in costs))
            extra[f"tpch_{name}_sf{sf:g}_peak_hbm_bytes"] = int(max(
                c.get("peak_hbm_bytes") or 0 for c in costs))
        extra[f"tpch_{name}_sf{sf:g}_rec_chunks_replayed"] = int(
            spark.metrics.counter("rec_chunks_replayed").value - rec0)
        extra[f"tpch_{name}_sf{sf:g}_mesh_restarts"] = int(
            spark.metrics.counter("mesh_restart_attempts").value - mr0)
        extra[f"tpch_{name}_sf{sf:g}_rebalanced_rows"] = int(
            spark.metrics.counter("rebalance_rows").value - rb0)
        # hash-join kernel sidecar: per-join table build/probe program
        # cost (0.0 when every join took the sort path — expected on
        # small probes under kernelMode=auto)
        extra[f"tpch_{name}_sf{sf:g}_join_build_ms"] = round(sum(
            v for k, v in qe.last_metrics.items()
            if k.startswith("join_build_ms_")), 3)
        slots = [v for k, v in qe.last_metrics.items()
                 if k.startswith("join_table_slots_")]
        if slots:
            extra[f"tpch_{name}_sf{sf:g}_join_table_slots"] = int(
                max(slots))
        # ingest pipeline sidecar: decode time hidden behind compute
        # vs consumer stalls, across this query's warmup+timed runs
        extra[f"tpch_{name}_sf{sf:g}_ingest_overlap_ms"] = round(
            spark.metrics.counter("ingest_overlap_ms").value
            - overlap0, 3)
        extra[f"tpch_{name}_sf{sf:g}_ingest_stall_ms"] = round(
            spark.metrics.counter("ingest_stall_ms").value - stall0, 3)
        # analyzer self-grading sidecar: the BENCH trajectory shows
        # whether the estimators feeding AQE seeds and runtime-filter
        # sizing are getting tighter or drifting
        _prediction_sidecars(qe, extra, f"tpch_{name}_sf{sf:g}")
        # static-analyzer sidecar: findings per query (the BENCH
        # trajectory must show analyzer noise staying at zero on the
        # TPC-H suite; a nonzero count is either a real hazard at this
        # scale factor or an analyzer regression — both reportable)
        extra[f"tpch_{name}_sf{sf:g}_analysis_findings"] = int(
            len(qe.analysis_findings or []))
        # runtime-filter observability: fraction of probe rows the
        # injected Bloom/min-max filters pruned before the exchanges
        tested = sum(v for k, v in qe.last_metrics.items()
                     if k.startswith("rtf_tested_"))
        pruned = sum(v for k, v in qe.last_metrics.items()
                     if k.startswith("rtf_pruned_"))
        if tested:
            extra[f"tpch_{name}_sf{sf:g}_rtf_pruned_ratio"] = round(
                pruned / tested, 4)
        # result parity vs the independent pandas implementation
        got = G.normalize_decimals(got)
        want = G.GOLDEN[name](path)
        if name == "q5":
            got = got.sort_values("n_name").reset_index(drop=True)
            want = want.sort_values("n_name").reset_index(drop=True)
        G.compare(got.reset_index(drop=True), want,
                  float_rtol=1e-6, float_atol=float_atol)
        extra[f"tpch_{name}_parity"] = True
    _tpch_udf_sidecars(spark, sf, deadline, extra)
    return extra


def _tpch_udf_sidecars(spark, sf, deadline, extra) -> None:
    """Python-UDF lane sidecars over real TPC-H data: a revenue UDF
    over the (pruned) lineitem scan in both lanes, so the BENCH
    trajectory prices the worker pool's IPC overhead against the
    in-process lane at scale — plus the worker lane's batch count and
    its prediction grading (udf_batches/udf_rows hit/over/under)."""
    if deadline is not None and time.perf_counter() > deadline:
        extra[f"tpch_udf_sf{sf:g}_skipped"] = "time budget"
        return
    from spark_tpu.functions import col, pandas_udf, to_date
    from spark_tpu.history import grade_predictions

    @pandas_udf(returnType="double")
    def disc_price(ep, d):
        # the decimal columns arrive as object-dtype Decimal series
        return ep.astype("float64") * (1.0 - d.astype("float64"))

    mode_key = "spark_tpu.sql.udf.mode"
    batch_key = "spark_tpu.sql.udf.arrow.maxRecordsPerBatch"

    def run(mode):
        spark.conf.set(mode_key, mode)
        qe = (spark.table("lineitem")
              .filter(col("l_shipdate") <= to_date("1998-09-02"))
              .select(disc_price(col("l_extendedprice"),
                                 col("l_discount")).alias("p")))._qe()
        t0 = time.perf_counter()
        b, _, _ = qe.execute_batch()
        dt = time.perf_counter() - t0
        return qe, b.to_arrow().to_pandas(), dt

    old_batch = spark.conf.get(batch_key)
    try:
        qe_in, got_in, t_in = run("inprocess")
        rows = len(got_in)
        extra[f"tpch_udf_sf{sf:g}_inprocess_ms"] = round(t_in * 1e3, 1)
        qe_w, got_w, t_w = run("worker")
        extra[f"tpch_udf_sf{sf:g}_worker_ms"] = round(t_w * 1e3, 1)
        if rows:
            extra[f"tpch_udf_sf{sf:g}_rows_per_sec_M"] = round(
                rows / t_w / 1e6, 2)
        assert got_w.equals(got_in), "udf worker-lane parity broke"
        u = qe_w.udf_summary or {}
        extra[f"tpch_udf_sf{sf:g}_worker_batches"] = int(
            u.get("batches", 0))
        extra[f"tpch_udf_sf{sf:g}_worker_restarts"] = int(
            u.get("worker_restarts", 0))
        # grade the analyzer's batch/row prediction against this run
        graded = grade_predictions(
            qe_w.plan_predictions or [],
            {"udf_batches": u.get("batches"), "udf_rows": u.get("rows")})
        errs = [abs(g["err_pct"]) for g in graded
                if g["kind"].startswith("udf")
                and g.get("err_pct") is not None]
        if errs:
            extra[f"tpch_udf_sf{sf:g}_pred_err_pct"] = round(
                sum(errs) / len(errs), 1)
    finally:
        spark.conf.set(mode_key, "inprocess")
        spark.conf.set(batch_key, old_batch)


def bench_tpcds(spark, sf: float, path: str,
                queries=("q3", "q19", "q68"), float_atol: float = 1e-3,
                deadline: float = None):
    """TPC-DS tranche section: generate (cached) SF data, run the
    representative snowflake queries timed with result parity against
    the independent pandas goldens, and emit the `tpcds_*_ms` rows the
    perf gate tracks plus the prediction-error and join-reorder
    sidecars — the reference's committed perf baselines are TPC-DS
    (`TPCDSQueryBenchmark.scala:54`), so the BENCH trajectory now has
    the same spine."""
    from spark_tpu.tpcds import SQL_QUERIES, register_tables
    from spark_tpu.tpcds import golden as G
    from spark_tpu.tpcds.datagen import write_parquet

    write_parquet(path, sf)
    register_tables(spark, path)
    extra = {}
    for name in queries:
        if deadline is not None and time.perf_counter() > deadline:
            extra[f"tpcds_{name}_sf{sf:g}_skipped"] = "time budget"
            continue

        def run_once():
            qe = spark.sql(SQL_QUERIES[name])._qe()
            b, _, _ = qe.execute_batch()
            return qe, b.to_arrow().to_pandas()

        qe, got, best = _warm_best2(run_once)
        extra[f"tpcds_{name}_sf{sf:g}_ms"] = round(best * 1e3, 1)
        for phase in ("ingest", "execution", "streaming"):
            if phase in qe.phase_times:
                extra[f"tpcds_{name}_{phase}_ms"] = round(
                    qe.phase_times[phase] * 1e3, 1)
        # self-grading sidecars (incl. basis cbo-reorder predictions),
        # plus whether the reorder pass changed this query's join
        # SEQUENCE (kind "order" — an orientation-only flip must not
        # read as a reorder, same discipline as tests/preflight)
        _prediction_sidecars(qe, extra, f"tpcds_{name}_sf{sf:g}")
        extra[f"tpcds_{name}_sf{sf:g}_reordered"] = int(any(
            d.get("kind") == "order"
            for d in (qe.reorder_decisions or [])))
        extra[f"tpcds_{name}_sf{sf:g}_analysis_findings"] = int(
            len(qe.analysis_findings or []))
        # result parity vs the independent pandas implementation
        got = G.normalize_decimals(got)
        want = G.GOLDEN[name](path)
        G.compare(got[list(want.columns)].reset_index(drop=True), want,
                  float_rtol=1e-6, float_atol=float_atol)
        extra[f"tpcds_{name}_parity"] = True
    return extra


def obs_conf_on(base_dir: str) -> dict:
    """EVERY observability output's conf, pointed at base_dir — the
    ONE definition of 'all sinks on' shared by this bench section and
    the preflight stage-5 overhead gate (a new observability key added
    here is automatically measured by both)."""
    return {"spark_tpu.sql.eventLog.dir": base_dir + "/ev",
            "spark_tpu.sql.trace.dir": base_dir + "/tr",
            "spark_tpu.sql.metrics.sink": "jsonl,prometheus",
            "spark_tpu.sql.metrics.dir": base_dir + "/m",
            "spark_tpu.sql.observability.xlaCost": "on",
            "spark_tpu.sql.observability.shardSpans": "on",
            "spark_tpu.sql.status.enabled": "true",
            "spark_tpu.sql.flightRecorder.enabled": "true",
            "spark_tpu.sql.flightRecorder.dir": base_dir + "/fr",
            "spark_tpu.sql.planChangeValidation": "full"}


OBS_CONF_OFF = {"spark_tpu.sql.eventLog.dir": "",
                "spark_tpu.sql.trace.dir": "",
                "spark_tpu.sql.metrics.sink": "",
                "spark_tpu.sql.observability.xlaCost": "off",
                "spark_tpu.sql.observability.shardSpans": "off",
                "spark_tpu.sql.status.enabled": "false",
                "spark_tpu.sql.flightRecorder.enabled": "false",
                "spark_tpu.sql.planChangeValidation": "off"}


def measure_obs_overhead(spark, run, base_dir: str, best_of: int = 3
                         ) -> dict:
    """Warm best-of-N wall-clock of `run` with all observability ON
    (obs_conf_on) vs OFF (OBS_CONF_OFF); restores the caller's conf.
    Used by the bench `obs_overhead` section and the preflight gate."""
    on_conf = obs_conf_on(base_dir)
    saved = {k: spark.conf.get(k) for k in on_conf}

    def best(fn):
        fn()  # warm: compile + cache fill
        times = []
        for _ in range(best_of):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return min(times)

    try:
        for k, v in OBS_CONF_OFF.items():
            spark.conf.set(k, v)
        off_s = best(run)
        for k, v in on_conf.items():
            spark.conf.set(k, v)
        on_s = best(run)
    finally:
        for k, v in saved.items():
            spark.conf.set(k, v)
    return {"obs_overhead_ms": round((on_s - off_s) * 1e3, 1),
            "obs_overhead_pct": round((on_s - off_s) / off_s * 100, 1)
            if off_s > 0 else None,
            "obs_off_ms": round(off_s * 1e3, 1),
            "obs_on_ms": round(on_s * 1e3, 1)}


def bench_streaming(spark):
    """Durable-streaming section: a file-source stateful stream where
    ~6% of a 4096-group domain changes per trigger — the shape the
    incremental state store (execution/state_store.py) exists for.
    Sidecars: `streaming_rows_per_s` (micro-batch throughput incl.
    per-trigger delta persistence), `streaming_state_delta_bytes`
    (steady-state delta size) vs `streaming_state_snapshot_bytes`
    (the full-state write it replaces — the ratio is the incremental
    win), and `streaming_restore_ms` (fresh-query recovery =
    newest snapshot + <= snapshotEveryDeltas delta replays)."""
    import tempfile

    import pandas as pd

    from spark_tpu import functions as F
    from spark_tpu.functions import col

    base = tempfile.mkdtemp(prefix="bench_stream_")
    src_dir = os.path.join(base, "src")
    os.makedirs(src_dir)
    ck = os.path.join(base, "ck")
    domain = 4096
    batch_rows = 1 << 16
    n_batches = 12
    schema = pd.DataFrame({"k": pd.Series([], dtype=np.int64),
                           "v": pd.Series([], dtype=np.int64)})
    records = []

    class _Cap:
        def on_streaming_batch(self, event):
            records.append(event.record)

    cap = _Cap()
    spark.add_listener(cap)
    try:
        def build():
            src = spark.file_stream(src_dir, schema_df=schema)
            return (src.to_df()
                    .group_by(F.pmod(col("k"), domain).alias("g"))
                    .agg(F.sum(col("v")).alias("s"),
                         F.count().alias("c"))
                    .write_stream(ck))

        q = build()
        rng = np.random.RandomState(11)
        total_rows = 0
        t0 = time.perf_counter()
        for i in range(n_batches):
            if i == 0:
                k = np.arange(batch_rows, dtype=np.int64)  # all groups
            else:
                # ~6% of groups churn per trigger
                hot = rng.choice(domain, domain // 16, replace=False)
                k = hot[rng.randint(0, len(hot), batch_rows)] \
                    .astype(np.int64)
            pd.DataFrame({"k": k, "v": np.ones(batch_rows, np.int64)}) \
                .to_parquet(os.path.join(src_dir, f"b{i:04d}.parquet"))
            q.process_available()
            total_rows += batch_rows
        elapsed = time.perf_counter() - t0
        # fresh-query recovery wall-clock (snapshot + delta replays)
        r0 = spark.metrics.counter("streaming_restore_ms").value
        q2 = build()
        restore_ms = spark.metrics.counter(
            "streaming_restore_ms").value - r0
        replayed = q2._store.last_restore_replayed
    finally:
        spark.remove_listener(cap)
    snaps = [r["state_bytes"] for r in records
             if r["kind"] == "snapshot"]
    deltas = [r["state_bytes"] for r in records if r["kind"] == "delta"]
    out = {"streaming_rows_per_s": round(total_rows / elapsed, 1),
           "streaming_batches": len(records),
           "streaming_restore_ms": round(restore_ms, 1),
           "streaming_restore_replayed_deltas": int(replayed)}
    if snaps and deltas:
        out["streaming_state_snapshot_bytes"] = int(max(snaps))
        out["streaming_state_delta_bytes"] = int(
            sum(deltas) / len(deltas))
        out["streaming_delta_ratio"] = round(
            out["streaming_state_delta_bytes"] / max(snaps), 4)
    return out


def bench_streaming_network(spark):
    """Unattended-streaming section: the socket network source
    (io/network_source.py) driven by an in-process FrameProducer.
    Sidecars: `streaming_net_rows_per_s_f<N>` (end-to-end micro-batch
    throughput — wire transfer + durable frame persistence + stateful
    fold — at two frame sizes: small frames bound replay cost, large
    frames amortize the round-trip), `streaming_net_reconnect_ms`
    (wall-clock from a mid-stream connection kill to the next batch
    committed over a fresh handshake) with the observed
    `streaming_reconnects` delta, and the host-spill tier:
    `streaming_spilled_ms` vs `streaming_resident_ms` for the SAME
    event-time stream (the spill tax), `streaming_spill_bytes` and the
    `streaming_spill_parity` byte-identical check."""
    import tempfile

    import pandas as pd

    from spark_tpu import functions as F
    from spark_tpu.functions import col
    from spark_tpu.io.network_source import FrameProducer
    from spark_tpu.streaming import (SPILL_BYTES_KEY, SPILL_PARTS_KEY,
                                     MemoryStream)

    base = tempfile.mkdtemp(prefix="bench_stream_net_")
    schema = pd.DataFrame({"k": pd.Series([], dtype=np.int64),
                           "v": pd.Series([], dtype=np.int64)})
    rng = np.random.RandomState(13)
    out = {}

    # -- throughput at two frame sizes
    n_frames = 8
    for rows in (4096, 65536):
        prod = FrameProducer()
        port = prod.start()
        try:
            src = spark.network_stream("127.0.0.1", port, schema)
            q = (src.to_df()
                 .group_by(F.pmod(col("k"), 1024).alias("g"))
                 .agg(F.sum(col("v")).alias("s"))
                 .write_stream(os.path.join(base, f"ck_{rows}")))
            frames = [pd.DataFrame(
                {"k": rng.randint(0, 1 << 20, rows).astype(np.int64),
                 "v": np.ones(rows, np.int64)})
                for _ in range(n_frames)]
            prod.send(frames[0])
            q.process_available()  # warmup: compile + first handshake
            t0 = time.perf_counter()
            for d in frames[1:]:
                prod.send(d)
            q.process_available()
            dt = time.perf_counter() - t0
            out[f"streaming_net_rows_per_s_f{rows}"] = round(
                rows * (n_frames - 1) / dt, 1)
            src.close()
        finally:
            prod.close()

    # -- reconnect recovery latency (kill mid-stream, fresh handshake)
    prod = FrameProducer()
    port = prod.start()
    try:
        rc0 = spark.metrics.counter("streaming_reconnects").value
        src = spark.network_stream("127.0.0.1", port, schema)
        q = (src.to_df().filter(col("v") >= 0)
             .write_stream(os.path.join(base, "ck_rc"),
                           output_mode="append"))
        d = pd.DataFrame({"k": np.arange(4096, dtype=np.int64),
                          "v": np.ones(4096, np.int64)})
        prod.send(d)
        q.process_available()
        prod.kill_connection()
        prod.send(d)
        t0 = time.perf_counter()
        q.process_available()
        out["streaming_net_reconnect_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        out["streaming_reconnects"] = int(
            spark.metrics.counter("streaming_reconnects").value - rc0)
        src.close()
    finally:
        prod.close()

    # -- host-spill tier: spilled vs resident timing + output parity
    def event_rounds():
        ts0 = pd.Timestamp("2024-01-01")
        return [pd.DataFrame(
            {"ts": ts0 + pd.to_timedelta(
                rng.randint(0, 1280, 4096), unit="s"),
             "v": np.ones(4096)}) for _ in range(4)]

    rng = np.random.RandomState(13)
    rounds_r = event_rounds()
    rng = np.random.RandomState(13)
    rounds_s = event_rounds()  # identical data for both runs

    def run_event(tag, rounds):
        src = MemoryStream(spark, pd.DataFrame(
            {"ts": [pd.Timestamp("2024-01-01")], "v": [0.0]}))
        q = (src.to_df().with_watermark("ts", "10 seconds")
             .group_by(F.window(col("ts"), "10 seconds").alias("w"))
             .agg(F.sum(col("v")).alias("s"))
             .write_stream(os.path.join(base, f"ck_{tag}")))
        src.add_data(rounds[0])
        q.process_available()  # warmup batch
        t0 = time.perf_counter()
        for d in rounds[1:]:
            src.add_data(d)
            q.process_available()
        return q, time.perf_counter() - t0

    q_r, dt_r = run_event("resident", rounds_r)
    old_spill = spark.conf.get(SPILL_BYTES_KEY)
    old_parts = spark.conf.get(SPILL_PARTS_KEY)
    sp0 = spark.metrics.counter("streaming_spill_bytes").value
    try:
        spark.conf.set(SPILL_BYTES_KEY, 1)
        spark.conf.set(SPILL_PARTS_KEY, 16)
        q_s, dt_s = run_event("spilled", rounds_s)
    finally:
        spark.conf.set(SPILL_BYTES_KEY, old_spill or 0)
        spark.conf.set(SPILL_PARTS_KEY, old_parts or 16)
    out["streaming_resident_ms"] = round(dt_r * 1e3, 1)
    out["streaming_spilled_ms"] = round(dt_s * 1e3, 1)
    out["streaming_spill_bytes"] = int(
        spark.metrics.counter("streaming_spill_bytes").value - sp0)
    a = q_r.latest().sort_values("w").reset_index(drop=True)
    b = q_s.latest().sort_values("w").reset_index(drop=True)
    out["streaming_spill_parity"] = bool(a.equals(b))
    return out


def bench_obs_overhead(spark):
    """Observability tax on the wall-clock (satellite of the flight
    -recorder PR): TPC-H Q1 at a small SF, warm, best-of-3, with ALL
    sinks + xlaCost + per-shard spans ON vs everything OFF. The
    `obs_overhead_ms` / `obs_overhead_pct` sidecars make the tax
    visible across BENCH rounds; preflight stage 5 gates it at 10%."""
    import tempfile

    from spark_tpu.tpch import queries as Q
    from spark_tpu.tpch.datagen import write_parquet

    base = tempfile.mkdtemp(prefix="bench_obs_")
    write_parquet(base + "/sf", 0.01)
    Q.register_tables(spark, base + "/sf")
    return measure_obs_overhead(
        spark, lambda: Q.QUERIES["q1"](spark)._qe().collect(), base)


def bench_udf(spark):
    """Python-UDF lane section: rows/s for one vectorized pandas_udf
    over a synthetic frame, in-process vs the Arrow-batched worker
    pool, the worker lane at TWO `udf.arrow.maxRecordsPerBatch` sizes
    (the batch size is the lane's one tuning knob: small batches bound
    replay cost, large batches amortize the IPC round-trip). Sidecars:
    `udf_inprocess_rows_per_sec_M`, `udf_worker_rows_per_sec_M_b<N>`
    per batch size, plus the observed batch/restart counters from the
    worker runs."""
    import pandas as pd

    from spark_tpu.functions import col, pandas_udf

    mode_key = "spark_tpu.sql.udf.mode"
    batch_key = "spark_tpu.sql.udf.arrow.maxRecordsPerBatch"
    n = 1 << 20
    batch_sizes = (16384, 131072)

    @pandas_udf(returnType="double")
    def fused(x, y):
        return x * 1.0001 + y.fillna(0.0) * 0.5

    df = (spark.range(n)
          .select(fused(col("id"), col("id")).alias("v")))

    def run_once():
        qe = df._qe()
        t0 = time.perf_counter()
        b, _, _ = qe.execute_batch()
        dt = time.perf_counter() - t0
        return qe, b, dt

    def best2():
        run_once()  # warmup: compile + (worker mode) pool spawn
        qe = best = None
        for _ in range(2):
            qe, _, dt = run_once()
            best = dt if best is None else min(best, dt)
        return qe, best

    out = {"udf_rows": n}
    old_mode = spark.conf.get(mode_key)
    old_batch = spark.conf.get(batch_key)
    try:
        spark.conf.set(mode_key, "inprocess")
        _, best = best2()
        out["udf_inprocess_rows_per_sec_M"] = round(n / best / 1e6, 2)
        spark.conf.set(mode_key, "worker")
        for bs in batch_sizes:
            spark.conf.set(batch_key, bs)
            qe, best = best2()
            out[f"udf_worker_rows_per_sec_M_b{bs}"] = round(
                n / best / 1e6, 2)
            summ = getattr(qe, "udf_summary", None) or {}
            out[f"udf_worker_batches_b{bs}"] = summ.get("batches")
            restarts = summ.get("worker_restarts")
            if restarts:
                out[f"udf_worker_restarts_b{bs}"] = restarts
    finally:
        spark.conf.set(mode_key, old_mode or "inprocess")
        if old_batch is not None:
            spark.conf.set(batch_key, old_batch)
    return out


def main():
    from spark_tpu import SparkTpuSession

    spark = SparkTpuSession.builder().get_or_create()
    _arm_flight_recorder(spark)
    budget = float(os.environ.get("BENCH_SECTION_BUDGET_S", "420"))
    total_budget = float(os.environ.get("BENCH_TOTAL_BUDGET_S", "2400"))
    t_run0 = time.perf_counter()

    def remaining() -> float:
        return total_budget - (time.perf_counter() - t_run0)

    def run_budgeted(name: str, fn, want_s: float) -> dict:
        """_run_section under the TOTAL budget: a section whose slice
        has shrunk below 30s is skipped (with its own JSON line) so the
        run always reaches the final summary rewrite inside the
        driver's outer timeout."""
        left = remaining()
        if left < 30:
            data = {f"{name}_skipped": f"total budget "
                                       f"({total_budget:g}s) exhausted"}
            _emit(name, "skipped", time.perf_counter(), data)
            return data
        return _run_section(name, fn, min(want_s, left))

    # The aggregate summary is REWRITTEN (one flushed JSON line, marked
    # "partial": true) after EVERY section, so a global `timeout` kill
    # mid-run still leaves a parseable summary of each finished section
    # (a run cut at its time limit used to leave nothing). The consumer
    # takes the LAST summary-shaped line; the final rewrite drops the
    # partial marker and is byte-identical in shape to the legacy line.
    summary = {"metric": "linear_keys_agg_rows_per_sec", "value": None,
               "unit": "M rows/s", "vs_baseline": None, "extra": {}}
    extra = summary["extra"]

    def emit_summary(final=False):
        out = summary if final else dict(summary, partial=True)
        print(json.dumps(out), flush=True)

    keys = run_budgeted(
        "linear_keys",
        lambda: {"keys_rows_per_sec_M":
                 round(bench_linear_keys(spark) / 1e6, 1)},
        budget)
    keys_rps = keys.get("keys_rows_per_sec_M")
    summary["value"] = keys_rps
    summary["vs_baseline"] = (round(keys_rps * 1e6 / KEYS_BASELINE, 3)
                              if keys_rps is not None else None)
    if keys_rps is None:
        extra.update(keys)  # surface the headline failure in the summary
    emit_summary()

    def stddev_section():
        rps = bench_stddev(spark)
        return {"stddev_rows_per_sec_M": round(rps / 1e6, 1),
                "stddev_vs_baseline": round(rps / STDDEV_BASELINE, 3)}

    extra.update(run_budgeted("stddev", stddev_section, budget))
    emit_summary()
    extra.update(run_budgeted(
        "grouped100",
        lambda: {"grouped100_rows_per_sec_M":
                 round(bench_100_groups(spark) / 1e6, 1)},
        budget))
    emit_summary()
    extra.update(run_budgeted(
        "kernel_pick", lambda: bench_kernel_pick(spark), budget))
    emit_summary()
    extra.update(run_budgeted(
        "join_microbench", lambda: bench_join_microbench(spark),
        budget))
    emit_summary()
    extra.update(run_budgeted(
        "obs_overhead", lambda: bench_obs_overhead(spark),
        min(budget, 240)))
    emit_summary()
    # durable streaming: micro-batch throughput + incremental
    # state-store delta-vs-snapshot bytes + fresh-query restore cost
    extra.update(run_budgeted(
        "streaming", lambda: bench_streaming(spark),
        min(budget, 240)))
    emit_summary()
    # unattended streaming: network-source throughput at two frame
    # sizes, reconnect recovery latency, spilled-vs-resident state
    extra.update(run_budgeted(
        "streaming_network", lambda: bench_streaming_network(spark),
        min(budget, 240)))
    emit_summary()
    # Python-UDF lane: in-process vs Arrow worker pool rows/s at two
    # batch sizes (the lane's tuning knob)
    extra.update(run_budgeted(
        "udf", lambda: bench_udf(spark), min(budget, 240)))
    emit_summary()
    # persistent compile cache: cold vs warm PROCESS compile cost via
    # two fresh subprocesses sharing one cache dir
    extra.update(run_budgeted(
        "compile_cache", lambda: bench_compile_cache(spark),
        min(budget, 300)))
    emit_summary()
    # the TPC-H trajectory is the headline consumer of BENCH rounds:
    # give it whatever remains of the total budget (at least its
    # section slice) so earlier overruns can't starve it entirely
    tpch_budget = max(budget, min(2 * budget, remaining() - 30))
    extra.update(run_budgeted(
        f"tpch_sf{TPCH_SF:g}",
        lambda: bench_tpch(
            spark, TPCH_SF, TPCH_PATH,
            deadline=time.perf_counter()
            + min(tpch_budget, max(remaining(), 1)) * 0.9),
        tpch_budget))
    emit_summary()
    # TPC-DS tranche: the reference's own committed-baseline suite (3
    # representative snowflake queries under the same budget machinery)
    extra.update(run_budgeted(
        f"tpcds_sf{TPCDS_SF:g}",
        lambda: bench_tpcds(
            spark, TPCDS_SF, TPCDS_PATH,
            deadline=time.perf_counter()
            + min(budget, max(remaining(), 1)) * 0.9),
        budget))
    emit_summary()

    # SF10: the north-star scale on one chip (VERDICT r4 #2). The
    # device-table cache budget rises so the pruned lineitem goes
    # RESIDENT (~3.6GB in 16GB HBM): warm runs then skip host ingest.
    # Opt-in (BENCH_RUN_SF10=1): the default matrix must fit the total
    # budget, and r05 proved the SF10 sweep alone can blow it.
    if os.environ.get("BENCH_RUN_SF10") \
            and not os.environ.get("BENCH_SKIP_SF10"):
        sf10_path = os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "data", "tpch", "sf10")
        sf10_budget = float(os.environ.get("BENCH_SF10_BUDGET_S", "1500"))

        def sf10_section():
            spark.conf.set("spark_tpu.sql.io.deviceCacheBytes", 12 << 30)
            try:
                return bench_tpch(
                    spark, 10, sf10_path, float_atol=1e-3,
                    deadline=time.perf_counter() + sf10_budget)
            finally:
                spark.conf.set("spark_tpu.sql.io.deviceCacheBytes",
                               6 << 30)

        extra.update(run_budgeted("tpch_sf10", sf10_section,
                                  sf10_budget * 1.1))

    emit_summary(final=True)


if __name__ == "__main__":
    main()
