#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, the chips the cell asks for, nothing but a TPU. Data and
inputs come from `--seed`. The last line of standard output is one JSON
object: `correct`, `attempted`, `failed`, `metrics` (with `--trace 0`
the cell's end-to-end metrics, with `--trace 1` its per-layer metrics),
`device`, with `--trace 1` `breakdown`, and last `compared`, each number
that decided `correct` beside its limit. Any failure before that exits
non-zero and prints no result. `benchmark/README.md` says how the cells
are laid out as files.
"""

import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import cell as C
    from benchmark.harness import spec
    if not os.path.isdir(os.path.join(CHECKOUT, "spark_tpu")):
        sys.exit("benchmark: no program to measure: this checkout holds no "
                 "spark_tpu/")
    cell = spec.load_cell(args.workload)
    # the data's processes start before this process imports JAX, and
    # work while it does
    with C.worker_pool(cell) as pool:
        data = C.submit_data(cell, pool, args.seed,
                             os.path.join(CHECKOUT, "benchmark", "data"))
        return C.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, pool, data)


if __name__ == "__main__":
    sys.exit(main())
