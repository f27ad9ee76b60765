"""Look at one trace by hand: runs a cell's traced run as `run.py`
does, keeps the profiler's file and prints its planes, lines and the
longest events of each line.

    python3 benchmark/tests/trace_dump.py --workload <name> --out <dir>
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, CHECKOUT)


def dump(path: str, top: int = 12) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            lo = min(e.start_ns for e in events)
            hi = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"span {lo / 1e9:.6f}..{hi / 1e9:.6f} s")
            total = collections.Counter()
            count = collections.Counter()
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            for name, ns in total.most_common(top):
                print(f"      {ns / 1e6:12.3f} ms  x{count[name]:<6} {name[:110]}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147483693)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from benchmark.harness import cell as C
    from benchmark.harness import spec
    os.makedirs(args.out, exist_ok=True)
    cell = spec.load_cell(args.workload)
    with C.worker_pool(cell) as pool:
        data = C.submit_data(cell, pool, args.seed,
                             os.path.join(CHECKOUT, "benchmark", "data"))
        C.run_cell(cell, args.seed, 1.0, True, T_START, pool, data,
                   keep_trace=args.out)
    for f in sorted(os.listdir(args.out)):
        if f.endswith(".xplane.pb"):
            dump(os.path.join(args.out, f))
    return 0


if __name__ == "__main__":
    sys.exit(main())
