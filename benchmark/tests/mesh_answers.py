"""The answers of a cell's queries as the service sends them, kept
byte for byte, so that a run over the mesh can be held against a run
on one device (by hand; the chips cannot be had in one call at the
price of one).

    python3 benchmark/tests/mesh_answers.py --workload <name> --seed <n> \\
        --mesh-size <0|4> --out chiprun_out/answers.mesh<k>.json
    python3 benchmark/tests/mesh_answers.py --same <a.json> <b.json>

The first form makes the seed's data (or finds it), starts the cell's
entry with `spark_tpu.sql.mesh.size` set to `--mesh-size` over the
configuration's conf, sends the cell's request twice (cold, warm),
holds every answer to the plain references as a run does, and writes
each answer's `columns` and `rows` as the JSON text the service sent,
with the names of the spans each query left and the growth of the
mesh's counters. The second form exits 0 when both files hold the
same answers for the same workload and seed, byte for byte.
`--rehearse` runs the cell's small size on the CPU.
"""

import argparse
import collections
import hashlib
import json
import os
import sys
import urllib.request

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, CHECKOUT)

MESH_KEY = "spark_tpu.sql.mesh.size"
COUNTERS = ("stage_dispatches", "mesh_stage_dispatches", "exchange_rows",
            "exchange_bytes", "shard_rows_max", "shard_rows_total",
            "scans_streamed", "scans_resident", "ingest_chunks")


def same(a_path: str, b_path: str) -> int:
    with open(a_path) as f:
        a = json.load(f)
    with open(b_path) as f:
        b = json.load(f)
    for key in ("workload", "seed", "answers"):
        if a[key] != b[key]:
            print(f"{key} differs: {a_path} against {b_path}")
            return 1
    print(f"same answers, byte for byte: {len(a['answers'])} answers of "
          f"{a['workload']} seed {a['seed']}, mesh.size {a['mesh_size']} "
          f"on {a['device']['count']} x {a['device']['kind']} against "
          f"mesh.size {b['mesh_size']} on {b['device']['count']} x "
          f"{b['device']['kind']}; sha256 {a['sha256']}")
    return 0


def post(entry, text: str) -> dict:
    req = urllib.request.Request(
        entry.base + "/sql", data=json.dumps({"sql": text}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=1800) as resp:
        return json.loads(resp.read())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--same", nargs=2)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--mesh-size", type=int)
    ap.add_argument("--out")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.same:
        return same(*args.same)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from benchmark.harness import cell as C
    from benchmark.harness import compare, entries, spec
    from benchmark.tests import rehearsal
    cell = rehearsal.small_cell(args.workload) if args.rehearse \
        else spec.load_cell(args.workload)
    cell.config["conf"] = dict(cell.config.get("conf", {}),
                               **{MESH_KEY: args.mesh_size})
    with C.worker_pool(cell) as pool:
        tables, _ = C.finish_data(C.submit_data(
            cell, pool, args.seed,
            os.path.join(CHECKOUT, "benchmark", "data")))
        entry = entries.HttpEntry(cell, tables)
        try:
            before = entry.counters()
            requests, answers, spans = [], [], {}
            for _pass in ("cold", "warm"):
                queries = []
                for q in cell.queries:
                    payload = post(entry, q["text"])
                    queries.append({
                        "query": q["name"], "status": payload["status"],
                        "answer": entries._columns(payload["columns"],
                                                   payload["rows"])})
                    answers.append({
                        "query": q["name"],
                        "columns": json.dumps(payload["columns"]),
                        "rows": json.dumps(payload["rows"])})
                    tl = json.loads(entry._get(
                        f"/queries/{payload['query_id']}/timeline"))
                    spans[q["name"]] = collections.Counter(
                        s["name"] for s in tl.get("spans") or [])
                requests.append({"queries": queries})
            after = entry.counters()
        finally:
            entry.stop()
        references = {
            q["name"]: spec.module("reference", q["reference"]).compute(
                cell.config, tables, pool) for q in cell.queries}
    verdict = compare.judge(requests, references, before, after)
    out = {"workload": cell.name, "seed": args.seed,
           "mesh_size": args.mesh_size,
           "device": C.device_line(jax.devices(), max(args.mesh_size, 1)),
           "correct": verdict["correct"], "compared": verdict["numbers"],
           "spans_warm": spans,
           "counters_two_requests": {
               c: after.get("spark_tpu_" + c, 0.0)
               - before.get("spark_tpu_" + c, 0.0) for c in COUNTERS},
           "answers": answers,
           "sha256": hashlib.sha256(
               json.dumps(answers).encode()).hexdigest()}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)
    print(json.dumps({k: v for k, v in out.items() if k != "answers"}),
          flush=True)
    return 0 if verdict["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
