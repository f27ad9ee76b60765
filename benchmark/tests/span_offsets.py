"""Where the harness puts an engine span against where the engine put
it: runs a cell's traced window as `run.py --trace 1` does, keeps the
profiler's file, and pairs every span of the timeline, as
`harness/entries.py` anchors it at the client's send, with the
engine's own `spark_tpu.<name>` annotation in the same trace.

    python3 benchmark/tests/span_offsets.py --workload <name> --out <dir>

Prints the ingest counters' growth a request and the most spans one
query recorded, the host plane (as `trace_dump.py` does), the lines
(threads) that hold `spark_tpu.*` events and whether each lies inside
its `bench.request`, and per span name the offset anchored start minus
annotated start. A program without annotations (the parent commit)
prints that it found none. `--rehearse` runs the cell's small size on
the CPU; no number from there is reported.
"""

import argparse
import collections
import json
import os
import statistics
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, CHECKOUT)

PREFIX = "spark_tpu."


def host_events(path: str):
    """(requests, annotations) of the host plane: `bench.request`
    events as (start_ns, end_ns), sorted, and `spark_tpu.*` events as
    (start_ns, end_ns, name, line index)."""
    from jax.profiler import ProfileData
    from benchmark.harness import trace
    requests, annotations = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                span = (float(e.start_ns), float(e.start_ns + e.duration_ns))
                if e.name == trace.REQUEST:
                    requests.append(span)
                elif e.name.startswith(PREFIX):
                    annotations.append(span + (e.name[len(PREFIX):], i))
    return sorted(requests), sorted(annotations)


def offsets(requests_ns, annotations, requests):
    """{name: [anchored start - annotated start, ms]} over the window,
    pairing the spans of one name inside a request in order of start;
    and the names whose counts differ (left unpaired)."""
    out, unpaired = collections.defaultdict(list), set()
    for (a_ns, b_ns), req in zip(requests_ns, requests):
        anchored = collections.defaultdict(list)
        for q in req["queries"]:
            for s in q.get("spans") or []:
                anchored[s["name"]].append(
                    a_ns + (s["t0"] - req["t_send"]) * 1e9)
        annotated = collections.defaultdict(list)
        for s0, _, name, _ in annotations:
            if a_ns <= s0 < b_ns:
                annotated[name].append(s0)
        for name, starts in annotated.items():
            if len(anchored[name]) != len(starts):
                unpaired.add(name)
                continue
            for x, y in zip(sorted(anchored[name]), sorted(starts)):
                out[name].append((x - y) / 1e6)
    return out, unpaired


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147483693)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchmark.harness import cell as C
    from benchmark.harness import entries, loop, spec
    from benchmark.tests import rehearsal, trace_dump
    os.makedirs(args.out, exist_ok=True)
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
            window, _ = C._traced_window(entry, cell, args.rehearse,
                                         args.out)
            for req in window["requests"]:
                for q in req["queries"]:
                    entry.details(q, timeline=True)
        finally:
            entry.stop()
    n_req = len(window["requests"])
    after = window["requests"][-1]["counters_after"]
    grew = {k: (v - before.get(k, 0.0)) / n_req for k, v in after.items()
            if "ingest_" in k or "scans_" in k}
    most = max(len(q.get("spans") or []) for r in window["requests"]
               for q in r["queries"])
    print(f"span_offsets: counters a request over {n_req} requests: "
          f"{json.dumps(grew)}; most spans in one query {most} "
          f"(the recorder drops past 1000)")
    sums = collections.defaultdict(list)
    for r in window["requests"]:
        by_name = collections.Counter()
        for q in r["queries"]:
            for sp in q.get("spans") or []:
                by_name[sp["name"]] += (sp["t1"] - sp["t0"]) * 1e3
        for span_name, ms in by_name.items():
            sums[span_name].append(ms)
    print("span_offsets: ms a request by span name (median): " + json.dumps(
        {n: round(statistics.median(v), 3) for n, v in sorted(sums.items())}))
    (name,) = [f for f in os.listdir(args.out) if f.endswith(".xplane.pb")]
    path = os.path.join(args.out, name)
    trace_dump.dump(path)
    requests_ns, annotations = host_events(path)
    if not annotations:
        print("span_offsets: the trace holds no spark_tpu.* annotation")
        return 0
    lines = collections.Counter(line for _, _, _, line in annotations)
    inside = sum(1 for s0, s1, _, _ in annotations
                 if any(a <= s0 and s1 <= b for a, b in requests_ns))
    print(f"span_offsets: {len(annotations)} spark_tpu.* events on host "
          f"lines {dict(lines)}; {inside} inside a bench.request")
    by_name, unpaired = offsets(requests_ns, annotations,
                                window["requests"])
    report = {}
    for span_name, ms in sorted(by_name.items()):
        report[span_name] = {"n": len(ms), "median_ms": statistics.median(ms),
                             "min_ms": min(ms), "max_ms": max(ms)}
        print(f"  {span_name:18s} n={len(ms):<4d} anchored - annotated: "
              f"median {statistics.median(ms):9.3f} ms  "
              f"[{min(ms):9.3f}, {max(ms):9.3f}]")
    every = [x for ms in by_name.values() for x in ms]
    print(json.dumps({"span_offsets": {
        "workload": args.workload, "events": len(annotations),
        "inside_request": inside, "lines": len(lines),
        "median_ms": statistics.median(every) if every else None,
        "unpaired": sorted(unpaired), "by_name": report}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
