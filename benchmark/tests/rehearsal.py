"""Drives the rest of a run on the CPU at a tiny size: everything
`run.py` does after its look for a TPU. The cells are the committed
ones with their scale cut by the file beside each configuration,
`configs/<configuration>.small.json` (`rehearsal`: the size of a
rehearsed run; `control`: the size at which the controls are kept as a
test); their data goes under a directory the caller gives. No number
from here is ever reported.
"""

from __future__ import annotations

import io
import json
import os
import time
from typing import Dict, List

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import cell as C  # noqa: E402
from benchmark.harness import spec  # noqa: E402


def cells() -> List[str]:
    """Every cell of `BENCHMARK.json`."""
    with open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def small_cell(workload: str, size: str = "rehearsal") -> spec.Cell:
    with open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = spec.load_cell(workload, bench)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    small = files[cell.config["name"]][:-len(".json")] + ".small.json"
    with open(os.path.join(spec.CHECKOUT, small)) as f:
        sizes = json.load(f)
    cell.config.update(sizes["rehearsal"])
    cell.config.update(sizes[size])
    return cell


def run(workload: str, seed: int, traced: bool, data_root: str,
        seconds: float = 1.0) -> Dict:
    """The last line of a rehearsed run, parsed."""
    cell = small_cell(workload)
    out, err = io.StringIO(), io.StringIO()
    with C.worker_pool(cell) as pool:
        data = C.submit_data(cell, pool, seed, data_root)
        C.run_cell(cell, seed, seconds, traced, time.perf_counter(), pool,
                   data, require_tpu=False, out=out, err=err)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    line["_stderr"] = err.getvalue()
    return line


if __name__ == "__main__":
    import sys
    import tempfile
    root = tempfile.mkdtemp(prefix="bench_rehearsal_")
    for name in sys.argv[1:] or cells():
        for traced in (False, True):
            line = run(name, 2147483659, traced, root)
            print(name, "trace", int(traced), line.pop("_stderr"))
            print(json.dumps(line)[:3000])
