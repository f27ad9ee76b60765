"""What the device-table cache holds for a cell's queries, beside what
the residency decision estimated for their scans; and how many polling
ticks each `dispatch.sync` slept through (the harness drops a span's
attributes, the timeline keeps them).

    python3 benchmark/tests/cache_entries.py --workload <name> --seed <n>

Runs the cell's warm-up, then `--requests` requests. Prints one JSON
line per cache entry of the cell's tables (columns, pushed filters,
capacity, live rows, bytes held, `estimated_scan_bytes` of the same
scan), one line with the growth of `stage_dispatches` and
`dispatch_sync_ticks` a request and the `ticks` of every `dispatch.sync`
span by query, and the device's memory line. A program without the
counters or the attribute (the parent commit) prints them as null.
`--rehearse` runs the cell's small size on the CPU; no number from
there is reported.
"""

import argparse
import collections
import json
import os
import sys
import urllib.error

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, CHECKOUT)

COUNTERS = ("spark_tpu_stage_dispatches", "spark_tpu_dispatch_sync_ticks")


def scans_of(session, text):
    """The scans of a query's executed plan."""
    out = []

    def walk(node):
        if hasattr(node, "pushed_filters"):
            out.append(node)
        for c in node.children:
            walk(c)

    walk(session.sql(text)._qe().executed_plan)
    return out


def entry_lines(cell, tables):
    """One dict per (query, scan): the cache's entry for it, if any."""
    import numpy as np
    from spark_tpu import SparkTpuSession
    from spark_tpu.io import device_cache
    from spark_tpu.io.sources import ParquetSource
    session = SparkTpuSession.builder().get_or_create()
    for name, path in tables.items():
        session.register_table(name, ParquetSource(path, name))
    lines = []
    for q in cell.queries:
        if "text" not in q:
            continue
        for scan in scans_of(session, q["text"]):
            key = device_cache.scan_cache_key(scan)
            held = device_cache.CACHE._entries.get(key)
            line = {"query": q["name"], "columns": list(key[1] or ()),
                    "pushed_filters": list(key[2]),
                    "estimated_scan_bytes":
                        device_cache.estimated_scan_bytes(scan),
                    "estimated_rows": scan.source.estimated_rows(),
                    "cached": held is not None}
            if held is not None:
                batch, nbytes = held
                sel = batch.selection
                line.update(
                    capacity=int(batch.capacity), bytes_held=int(nbytes),
                    live_rows=int(np.asarray(sel).sum()) if sel is not None
                    else int(batch.capacity),
                    column_bytes={c: int(col.data.nbytes) + (
                        int(col.validity.nbytes)
                        if col.validity is not None else 0)
                        for c, col in batch.columns.items()})
            lines.append(line)
    return lines


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147483693)
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from benchmark.harness import cell as C
    from benchmark.harness import entries, loop, spec
    from benchmark.tests import rehearsal
    cell = rehearsal.small_cell(args.workload) if args.rehearse \
        else spec.load_cell(args.workload)
    with C.worker_pool(cell) as pool:
        data = C.submit_data(cell, pool, args.seed,
                             os.path.join(CHECKOUT, "benchmark", "data"))
        tables, _ = C.finish_data(data)
        entry = entries.ENTRIES[cell.config["entry"]](cell, tables)
        try:
            loop.closed_loop(
                entry, cell.queries, None,
                max_requests=int(cell.traffic["warmup_requests"]))
            before = entry.counters()
            window = loop.closed_loop(entry, cell.queries, None,
                                      max_requests=args.requests)
            after = entry.counters()
            ticks = collections.defaultdict(list)
            for req in window["requests"]:
                for q in req["queries"]:
                    if q.get("id") is None:
                        continue
                    try:
                        tl = json.loads(entry._get(
                            f"/queries/{q['id']}/timeline"))
                    except urllib.error.HTTPError:
                        continue  # aged out of the bounded query log
                    ticks[q["query"]].extend(
                        (s.get("attrs") or {}).get("ticks")
                        for s in tl.get("spans") or []
                        if s["name"] == "dispatch.sync")
            for line in entry_lines(cell, tables):
                print(json.dumps(dict(line, workload=cell.name,
                                      seed=args.seed)), flush=True)
        finally:
            entry.stop()
    n = len(window["requests"])
    print(json.dumps({
        "workload": cell.name, "requests": n,
        "a_request": {c: (after[c] - before.get(c, 0.0)) / n
                      if c in after else None for c in COUNTERS},
        "sync_ticks_by_query": ticks,
        "request_ms": sorted(round(r["client_ms"], 3)
                             for r in window["requests"]),
        "device": C.device_line(jax.devices(), cell.chips)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
