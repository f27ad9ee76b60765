#!/usr/bin/env python
"""Perf-regression gate: TPC smoke against a self-calibrated baseline.

This gate runs the TPC-H/TPC-DS smoke (small scale factor, current
backend) and fails preflight when a `tpc*_ms` reading regresses more
than the threshold against the recorded baseline:

- The baseline lives in PERF_BASELINE.json, keyed by platform+scale
  and a coarse machine fingerprint. A missing entry self-calibrates
  from the current measurement, then passes with a note.
- Regression = current > baseline * (1 + threshold) AND current >
  baseline + abs_floor_ms (small queries jitter; a 25% blowup of 80ms
  is noise, of 800ms is a regression).

Usage:
    scripts/perf_gate.py [--update]        # --update re-calibrates
Env:
    PERF_GATE_SF (default 0.01), PERF_GATE_THRESHOLD_PCT (default 25),
    PERF_GATE_FLOOR_MS (default 200), PERF_GATE_QUERIES (q1,q3),
    PERF_GATE_COMPILE=1 (opt-in: also measure + gate the
    compile-cache cold/warm-process rows, tpch_q*_compile_*_ms)
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_PATH = os.path.join(REPO, "PERF_BASELINE.json")
sys.path.insert(0, REPO)


#: the compile-cache section's cold/warm-process rows
#: (bench_compile_cache), gated like wall-clock under PERF_GATE_COMPILE
_COMPILE_RX = r"tpch_q\d+_compile_(?:cold|warm)_ms$"


def _time3(run_once) -> float:
    run_once()  # warmup: compile + ingest
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        run_once()
        times.append(time.perf_counter() - t0)
    return round(min(times) * 1e3, 1)


def measure(sf: float, queries, tpcds_queries=()) -> dict:
    """Warm min-of-3 wall-clock per query at `sf` on the current
    backend — the same shapes bench.py's tpch/tpcds sections time.
    `queries` are TPC-H DataFrame names (tpch_<q>_ms keys);
    `tpcds_queries` are TPC-DS SQL names (tpcds_<q>_ms keys)."""
    import tempfile

    from spark_tpu import SparkTpuSession
    from spark_tpu.tpch import queries as Q
    from spark_tpu.tpch.datagen import write_parquet

    path = os.path.join(tempfile.gettempdir(),
                        f"perf_gate_tpch_sf{sf:g}")
    write_parquet(path, sf)  # cached across runs (datagen skips fresh)
    spark = SparkTpuSession.builder().get_or_create()
    Q.register_tables(spark, path)
    out = {}
    for name in queries:
        df_fn = Q.QUERIES[name]

        def run_once():
            qe = df_fn(spark)._qe()
            b, _, _ = qe.execute_batch()
            return b.to_arrow()

        out[f"tpch_{name}_ms"] = _time3(run_once)
    if tpcds_queries:
        from spark_tpu.tpcds import SQL_QUERIES, register_tables
        from spark_tpu.tpcds.datagen import write_parquet as ds_write
        ds_path = os.path.join(tempfile.gettempdir(),
                               f"perf_gate_tpcds_sf{sf:g}")
        ds_write(ds_path, sf)
        register_tables(spark, ds_path)
        for name in tpcds_queries:
            sql = SQL_QUERIES[name]

            def run_once_ds():
                qe = spark.sql(sql)._qe()
                b, _, _ = qe.execute_batch()
                return b.to_arrow()

            out[f"tpcds_{name}_ms"] = _time3(run_once_ds)
    return out


def platform_key(sf: float) -> str:
    """Backend + scale + a coarse machine fingerprint (arch, core
    count). Wall-clock baselines only gate between comparable hosts:
    the same numbers on a machine of a different shape would fail
    preflight on hardware variance, not regressions — a key mismatch
    self-recalibrates instead."""
    import platform

    import jax
    return (f"{jax.default_backend()}-sf{sf:g}"
            f"-{platform.machine()}-c{os.cpu_count()}")


def main(argv) -> int:
    threshold = float(os.environ.get("PERF_GATE_THRESHOLD_PCT", "25"))
    floor_ms = float(os.environ.get("PERF_GATE_FLOOR_MS", "200"))
    queries = [q.strip() for q in os.environ.get(
        "PERF_GATE_QUERIES", "q1,q3").split(",") if q.strip()]
    tpcds_queries = [q.strip() for q in os.environ.get(
        "PERF_GATE_TPCDS_QUERIES", "q3,q19").split(",") if q.strip()]
    update = "--update" in argv

    sf = float(os.environ.get("PERF_GATE_SF", "0.01"))
    current = measure(sf, queries, tpcds_queries)
    if os.environ.get("PERF_GATE_COMPILE"):
        # opt-in (two fresh subprocesses, ~1min): the compile-cache
        # cold/warm-process rows join the gated set — a warm-compile
        # regression (deserialization suddenly recompiling) fails
        # preflight like a wall-clock regression would
        import bench
        cc = bench.bench_compile_cache(None)
        current.update({k: float(v) for k, v in cc.items()
                        if re.match(_COMPILE_RX, k)})
    key = platform_key(sf)

    baselines = {}
    if os.path.exists(BASELINE_PATH):
        try:
            baselines = json.load(open(BASELINE_PATH))
        except ValueError:
            baselines = {}
    entry = baselines.get(key)

    if entry is None or update:
        # calibrate from the current measurement
        entry = dict(current, calibrated_against="self",
                     calibrated_ts=round(time.time(), 1))
        baselines[key] = entry
        with open(BASELINE_PATH, "w") as f:
            json.dump(baselines, f, indent=1, sort_keys=True)
            f.write("\n")
        print(json.dumps({"perf_gate": "calibrated", "platform": key,
                          "source": "self", "current": current}))
        return 0

    # metrics measured for the first time on an existing baseline (the
    # tpcds family landing on a platform calibrated pre-tranche):
    # self-calibrate JUST the missing keys so the next run gates them
    missing = {k: v for k, v in current.items() if k not in entry}
    if missing:
        entry.update(missing)
        baselines[key] = entry
        with open(BASELINE_PATH, "w") as f:
            json.dump(baselines, f, indent=1, sort_keys=True)
            f.write("\n")
        print(json.dumps({"perf_gate": "extended", "platform": key,
                          "new_metrics": missing}))

    failures = []
    for metric, now in sorted(current.items()):
        base = entry.get(metric)
        if base is None:
            continue
        if now > base * (1 + threshold / 100) and now > base + floor_ms:
            failures.append(f"{metric}: {now:.1f}ms vs baseline "
                            f"{base:.1f}ms (>{threshold:g}% + "
                            f"{floor_ms:g}ms floor)")
    verdict = {"perf_gate": "fail" if failures else "ok",
               "platform": key, "current": current,
               "baseline": {k: v for k, v in entry.items()
                            if k.startswith(("tpch_", "tpcds_"))}}
    if failures:
        verdict["regressions"] = failures
    print(json.dumps(verdict))
    if failures:
        print("perf gate FAILED (recalibrate with scripts/perf_gate.py "
              "--update if the regression is intended):",
              file=sys.stderr)
        for f_ in failures:
            print("  " + f_, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
