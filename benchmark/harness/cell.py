"""One run of one cell: set-up, warm-up, the measured window, the
comparison with the reference, the metrics, the last line.

`run_cell` is what `run.py` calls after it has refused anything but a
TPU. The tests call it with `require_tpu=False` to rehearse the rest of
a run on the CPU at a tiny size; a number from such a run is never
reported.
"""

from __future__ import annotations

import collections
import contextlib
import json
import multiprocessing
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ProcessPoolExecutor
from typing import Dict, Optional

from . import compare, entries, loop, spec, trace, work

#: JAX's own events: a program compiled, or loaded from the persistent
#: cache. Neither may happen inside the measured window.
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileCounter:
    def __init__(self):
        import jax
        self.events: collections.Counter = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _secs: float, **_kw) -> None:
        if name in COMPILE_EVENTS:
            self.events[name.rsplit("/", 1)[-1]] += 1

    def total(self) -> int:
        return sum(self.events.values())


def _worker_init() -> None:
    """A worker is one core's worth of work: pyarrow's own thread
    pools would otherwise start a thread per core of the host in every
    worker."""
    import pyarrow as pa
    pa.set_cpu_count(1)
    pa.set_io_thread_count(1)


@contextlib.contextmanager
def worker_pool(cell: spec.Cell):
    """The pool of processes that make the data and, after the window,
    the reference's partial sums. They import numpy and pyarrow, never
    JAX. As many as this process may use cores (its affinity, which is
    what a shared host grants; `os.cpu_count()` counts the whole host).
    Yields None for a configuration with no table."""
    if not cell.config.get("tables"):
        yield None
        return
    n = min(len(os.sched_getaffinity(0)),
            max(int(t["parts"]) for t in cell.config["tables"].values()))
    pool = ProcessPoolExecutor(
        max_workers=n, mp_context=multiprocessing.get_context("spawn"),
        initializer=_worker_init)
    try:
        yield pool
    finally:
        pool.shutdown(wait=True, cancel_futures=True)


def submit_data(cell: spec.Cell, pool, seed: int, data_root: str) -> Dict:
    """Start every table's generator; {table: (module, dir, futures)}."""
    out = {}
    root = os.path.join(data_root, cell.config["name"])
    for name, t in cell.config.get("tables", {}).items():
        gen = spec.module("datagen", t["generator"])
        d, futures = gen.submit(pool, float(cell.config["scale_factor"]),
                                seed, int(t["parts"]),
                                os.path.join(root, name))
        out[name] = (gen, d, futures, int(t["parts"]))
    return out


def finish_data(data: Dict):
    """Wait for the generators: ({table: directory}, {table: rows})."""
    tables, table_rows = {}, {}
    for name, (gen, d, futures, parts) in data.items():
        gen.finish(d, futures, parts)
        tables[name] = d
        table_rows[name] = gen.rows(d)
    return tables, table_rows


def device_line(devices, chips: int) -> Dict:
    """The device as JAX reports it. The peak on the fullest chip is
    the allocator's peak of live buffers plus its peak of memory
    reserved for the loaded programs' temporaries: on a TPU
    `peak_bytes_in_use` leaves those out (a program with 207 MB of
    temporaries and no argument reads 9.8 MB there and 206 MB under
    `peak_bytes_reserved`; PERF.md, Findings)."""
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def _resolve(value, cell: spec.Cell, table_rows: Dict[str, int]) -> int:
    """A query file's row or group count: a number, {"table": name}
    (the generated table's rows) or {"config": key}."""
    if isinstance(value, dict):
        if "table" in value:
            return int(table_rows[value["table"]])
        return int(cell.config[value["config"]])
    return int(value)


def _traced_window(entry, cell: spec.Cell, host_ops: bool,
                   keep_trace: Optional[str]):
    """The mix's `trace_requests` requests under the JAX profiler, each
    inside a `bench.request` annotation: (the window, the reduced
    trace). The trace goes under TMPDIR and is removed once reduced."""
    from jax import profiler
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    options = profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        profiler.start_trace(log_dir, profiler_options=options)
        try:
            window = loop.closed_loop(
                entry, cell.queries, None,
                max_requests=int(cell.traffic["trace_requests"]),
                annotate=lambda i: profiler.TraceAnnotation(trace.REQUEST,
                                                            i=i),
                counters_each=True)
        finally:
            profiler.stop_trace()
        xplane = trace.newest_xplane(log_dir)
        reduced = trace.reduce_file(xplane, host_ops=host_ops)
        if keep_trace:  # tests/trace_dump.py looks at one by hand
            shutil.copy(xplane, keep_trace)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    if not reduced or not reduced.get("busy_s"):
        raise SystemExit("benchmark: the trace holds no operation on the "
                         "device")
    return window, reduced


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, pool, data: Dict, require_tpu: bool = True,
             out=sys.stdout, err=sys.stderr,
             keep_trace: Optional[str] = None) -> int:
    import jax

    import spark_tpu  # noqa: F401 — places the compile cache
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: jax found no TPU (devices: {devices}); "
                         f"the benchmark has no CPU path")
    if len(devices) < cell.chips:
        raise SystemExit(f"benchmark: {cell.chips} chips asked for, "
                         f"{len(devices)} visible")
    # a rehearsal on the CPU has no peak to stand against: its roofline
    # is a placeholder, and none of its numbers is ever reported
    peak = work.peaks(devices[0].device_kind) if require_tpu else \
        {"bytes_per_s": 1.0, "ops_per_s": 1.0}
    compiles = CompileCounter()

    # -- set-up: data from the seed, the entry, the warm-up ---------------
    tables, table_rows = finish_data(data)
    entry = entries.ENTRIES[cell.config["entry"]](cell, tables)
    try:
        warm = loop.closed_loop(
            entry, cell.queries, None,
            max_requests=int(cell.traffic["warmup_requests"]))
        bad = [q for r in warm["requests"] for q in r["queries"]
               if q["status"] != "ok"]
        if bad:
            raise SystemExit(f"benchmark: warm-up failed: {bad[0]}")
        compiled_setup = compiles.total()
        counters_before = entry.counters()

        # -- the measured window -------------------------------------------
        setup_s = time.perf_counter() - t_start
        cpu_before = time.process_time()
        if traced:
            window, reduced = _traced_window(
                entry, cell, host_ops=not require_tpu, keep_trace=keep_trace)
        else:
            window = loop.closed_loop(entry, cell.queries, seconds)
            reduced = None
        # this process's CPU seconds over the window, all threads: the
        # same work at more of them is the shared host, not the program
        host_cpu_s = time.process_time() - cpu_before
        compiled_window = compiles.total() - compiled_setup
        counters_after = entry.counters()
        device = device_line(devices, cell.chips)
        requests = window["requests"]
        for req in requests:
            for q in req["queries"]:
                entry.details(q, timeline=traced)
    finally:
        entry.stop()

    if compiled_window:
        raise SystemExit(
            f"benchmark: {compiled_window} program(s) compiled or loaded "
            f"inside the measured window ({dict(compiles.events)}); "
            f"the warm-up does not cover the window's shapes")

    # -- the reference, once the window has closed -------------------------
    t_ref = time.perf_counter()
    references = {}
    for q in cell.queries:
        if q["name"] not in references:
            ref = spec.module("reference", q["reference"])
            references[q["name"]] = ref.compute(cell.config, tables, pool)
    verdict = compare.judge(requests, references, counters_before,
                            counters_after)
    reference_s = time.perf_counter() - t_ref

    # -- the metrics ---------------------------------------------------------
    works = [work.query_work(q, _resolve(q["rows"], cell, table_rows),
                             _resolve(q["groups"], cell, table_rows))
             for q in cell.queries]
    request_work = work.request_work(works)
    run = {"cell": cell, "requests": requests, "window_s": window["window_s"],
           "setup_s": setup_s, "counters_before": counters_before,
           "counters_after": counters_after, "trace": reduced,
           "work": request_work, "peak": peak,
           "least": work.least_seconds(request_work, peak)}
    metrics = {}
    kind, reported = ("layer_metrics", cell.per_layer) if traced \
        else ("end_to_end", cell.end_to_end)
    for m in reported:
        value = spec.module(kind, m["name"]).read(run)
        if value is None:
            print(f"benchmark: {m['name']} found nothing to read in "
                  f"{cell.name}; left out", file=err)
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    line = {"correct": verdict["correct"], "attempted": len(requests),
            "failed": sum(1 for r in requests if r["failed"]),
            "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        top = sorted(reduced["ops_s"].items(), key=lambda kv: -kv[1])[:10]
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in top],
            "idle_gaps": trace.attribute_gaps(reduced, requests)}
    line["info"] = {"workload": cell.name, "seed": seed,
                    "window_s": window["window_s"], "setup_s": setup_s,
                    "reference_s": reference_s, "host_cpu_s": host_cpu_s,
                    "compiles_in_setup": compiled_setup,
                    "work": run["work"], "least_s": run["least"]}
    line["compared"] = verdict["numbers"]
    for name, n in verdict["numbers"].items():
        print(f"compared {name} {n['value']!r} limit {n['limit']!r}",
              file=err)
    print(f"correct {verdict['correct']}", file=err, flush=True)
    print(json.dumps(line), file=out, flush=True)
    return 0
