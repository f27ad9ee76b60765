#!/usr/bin/env bash
# Preflight gate: run before committing. Any stage failing exits this
# script nonzero (set -e + explicit rc checks). Four stages:
#   1. the tier-1 test suite (`-m 'not slow'`, six xdist workers, one
#      file a worker, a virtual 8-device CPU mesh via tests/conftest.py)
#   2. dryrun_multichip(8): jit + run the distributed collectives path
#      end to end with single-chip parity checks
#   3. source lint: every registered pass of the lint framework
#      (scripts/lint.py --all: metric prefixes, conf-key registration,
#      fault-site wiring, tracer-leak shapes, the concurrency passes,
#      the rule registry)
#   4. service smoke: start the SQL service (spark_tpu/service/) on an
#      ephemeral port, POST TPC-H Q1 over HTTP, assert golden parity
#      of the JSON result, that GET /metrics parses as Prometheus text
#      exposition, that the live history API serves the query
#      (GET /queries listing + /queries/<id>/timeline with spans and
#      stage peak-HBM + /queries/<id>/plan), and a clean shutdown
#
# A timing taken on a CPU is no evidence of speed: the benchmark
# (BENCHMARK.json, benchmark/) measures on the chip.
#
# Usage: scripts/preflight.sh [--fast]
#   --fast skips the tier-1 suite (stages 2-4 still run) for quick
#   inner-loop checks; CI and end-of-round runs must use the default.

set -euo pipefail
cd "$(dirname "$0")/.."

FAST=0
[ "${1:-}" = "--fast" ] && FAST=1

echo "== preflight: $(date -u +%FT%TZ) =="

if [ "$FAST" -eq 0 ]; then
    echo "-- stage 1/4: tier-1 test suite --"
    log="${TMPDIR:-/tmp}/_preflight_t1.log"
    rm -f "$log"
    set +e  # keep control on pytest failure so the diagnostic prints
    timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
        python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider \
        -p xdist -n 6 --dist loadfile -p no:randomly 2>&1 | tee "$log"
    rc=${PIPESTATUS[0]}
    set -e
    if [ "$rc" -ne 0 ]; then
        echo "preflight FAILED: tier-1 suite rc=$rc" >&2
        exit "$rc"
    fi
else
    echo "-- stage 1/4: SKIPPED (--fast) --"
fi

echo "-- stage 2/4: dryrun_multichip(8) --"
env JAX_PLATFORMS=cpu python -c "
import __graft_entry__ as g
g.dryrun_multichip(8)
"

echo "-- stage 3/4: source lint (scripts/lint.py --all) --"
env JAX_PLATFORMS=cpu python scripts/lint.py --all

echo "-- stage 4/4: SQL service smoke --"
# Start the concurrent SQL service on an ephemeral port, POST TPC-H Q1
# over HTTP, check golden parity of the JSON rows, scrape-parse
# GET /metrics, then shut down cleanly.
env JAX_PLATFORMS=cpu python - <<'EOF3'
import json
import tempfile
import urllib.request

import pandas as pd

from spark_tpu import Conf
from spark_tpu.observability.metrics import parse_prometheus_text
from spark_tpu.service.server import SqlService
from spark_tpu.tpch import golden as G
from spark_tpu.tpch import queries as Q
from spark_tpu.tpch import sql_queries as SQLQ
from spark_tpu.tpch.datagen import write_parquet

path = tempfile.mkdtemp(prefix="preflight_service_") + "/sf"
write_parquet(path, 0.001)

conf = Conf()
conf.set("spark_tpu.service.port", 0)
# stage-cost capture on, so /queries/<id>/timeline can serve peak-HBM
conf.set("spark_tpu.sql.observability.xlaCost", "on")
svc = SqlService(conf,
                 init_session=lambda s: Q.register_tables(s, path)).start()
try:
    req = urllib.request.Request(
        f"http://127.0.0.1:{svc.port}/sql",
        data=json.dumps({"sql": SQLQ.Q1}).encode(),
        headers={"Content-Type": "application/json"})
    resp = json.load(urllib.request.urlopen(req, timeout=300))
    assert resp["status"] == "ok", resp
    got = pd.DataFrame(resp["rows"], columns=resp["columns"])
    want = G.GOLDEN["q1"](path)
    G.compare(G.normalize_decimals(got)[list(want.columns)]
              .reset_index(drop=True), want.reset_index(drop=True))
    # structured status record fed by the listener bus
    rec = json.load(urllib.request.urlopen(
        f"http://127.0.0.1:{svc.port}/queries/{resp['query_id']}",
        timeout=30))
    assert rec["status"] == "ok" and rec["engine_query_id"] >= 1, rec
    # live Prometheus exposition parses
    prom = parse_prometheus_text(urllib.request.urlopen(
        f"http://127.0.0.1:{svc.port}/metrics", timeout=30)
        .read().decode())
    assert prom.get("spark_tpu_service_completed", 0) >= 1, prom
    assert prom.get("spark_tpu_queries_total", 0) >= 1, prom
    # live query history API: listing + timeline + plan (the flight
    # recorder over HTTP — no JSONL scraping)
    listing = json.load(urllib.request.urlopen(
        f"http://127.0.0.1:{svc.port}/queries", timeout=30))
    assert listing["total"] >= 1 and any(
        q["id"] == resp["query_id"] and q["status"] == "ok"
        for q in listing["queries"]), listing
    tl = json.load(urllib.request.urlopen(
        f"http://127.0.0.1:{svc.port}/queries/{resp['query_id']}"
        f"/timeline", timeout=30))
    assert tl["spans"] and any(
        s.get("name") == "dispatch" for s in tl["spans"]), tl["spans"]
    assert any(s.get("peak_hbm_bytes") for s in tl["stages"]), tl
    assert isinstance(tl["shards"], list), tl  # [] single-chip, never absent
    pl = json.load(urllib.request.urlopen(
        f"http://127.0.0.1:{svc.port}/queries/{resp['query_id']}/plan",
        timeout=30))
    assert pl["physical"] and pl["sql"], pl
finally:
    svc.stop()
print(json.dumps({"preflight_service_smoke": "ok",
                  "rows": int(resp["row_count"])}))
EOF3

echo "== preflight PASSED =="
