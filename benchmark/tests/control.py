"""The control that has to come out as not correct.

The configurations state exact answers (decimal sums as scaled int64,
int64 sums). The step that would tempt a later PR is a floating
accumulator on the device, so the control is the plain reference put in
the program's place and computed in floating point. Each reference
names its own controls (`CONTROLS`: float64 and float32 for Q1; float32
for the linear keys, where float64 holds every sum exactly and so is no
lower precision). Each control's answer goes through the same
`compare.judge` as a served answer and has to read `correct: false`.

    python3 benchmark/tests/control.py --workload <name> --seeds 1 2 3

runs it at the cell's own size (for a TPC-H cell it makes the seed's
data first) and prints one JSON line per seed and precision with the
compared numbers. `benchmark/tests/test_control.py` keeps it as a test
at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmark.harness import compare, spec  # noqa: E402


def control_verdicts(cell: spec.Cell, tables, pool):
    """{(query, precision): judge(...)} with the control's answer in the
    program's place."""
    out = {}
    for q in cell.queries:
        ref = spec.module("reference", q["reference"])
        exact = ref.compute(cell.config, tables, pool)
        for precision in ref.CONTROLS:
            ctl = ref.compute(cell.config, tables, pool, precision)
            answer = {c: compare.as_served(v)
                      for c, v in ctl["table"].items()}
            requests = [{"queries": [{"query": q["name"], "status": "ok",
                                      "answer": answer}]}]
            out[(q["name"], precision)] = compare.judge(
                requests, {q["name"]: exact}, {}, {})
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--data-root",
                    default=os.path.join(CHECKOUT, "benchmark", "data"))
    args = ap.parse_args()
    from benchmark.harness import cell as C
    cell = spec.load_cell(args.workload)
    failed_to_fail = 0
    with C.worker_pool(cell) as pool:
        for seed in args.seeds:
            tables, _ = C.finish_data(
                C.submit_data(cell, pool, seed, args.data_root))
            for (query, precision), v in control_verdicts(
                    cell, tables, pool).items():
                print(json.dumps({
                    "workload": cell.name, "seed": seed, "query": query,
                    "control": precision, "correct": v["correct"],
                    "compared": v["numbers"]}), flush=True)
                failed_to_fail += bool(v["correct"])
    return 1 if failed_to_fail else 0


if __name__ == "__main__":
    sys.exit(main())
