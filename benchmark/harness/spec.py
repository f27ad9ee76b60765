"""Finds everything a cell is made of by the names in `BENCHMARK.json`.

A cell (one entry of `workloads`) names a configuration and a traffic
mix. The configuration's file is the one `configs` gives; the mix is
`benchmark/traffic/<traffic>.json`; each query of the mix is
`benchmark/queries/<query>.json`; each metric the cell reports has a
reader `benchmark/end_to_end/<metric>.py` or
`benchmark/layer_metrics/<metric>.py`. Nothing here knows the name of a
cell, a configuration, a mix, a query or a metric.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Dict, List

#: the checkout: <checkout>/benchmark/harness/spec.py
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(CHECKOUT, "benchmark")


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    queries: List[Dict]          # the request's queries, in order
    end_to_end: List[Dict]       # BENCHMARK.json entries this cell reports
    per_layer: List[Dict]


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, benchmark: Dict = None) -> Cell:
    bench = benchmark or _json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(CHECKOUT, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    if (traffic["loop"], traffic["clients"]) != ("closed", 1):
        raise SystemExit(f"benchmark: mix {w['traffic']!r} asks for a "
                         f"{traffic['loop']} loop of {traffic['clients']} "
                         f"clients; harness/loop.py is one client's closed "
                         f"loop")
    queries = []
    for q in traffic["queries"]:
        spec = _json(os.path.join(HERE, "queries", q + ".json"))
        if "text_file" in spec:
            with open(os.path.join(HERE, "queries", spec["text_file"])) as f:
                spec["text"] = f.read()
        queries.append(spec)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic, queries=queries,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)])


def module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, for kind in datagen, reference,
    queries, end_to_end, layer_metrics."""
    return importlib.import_module(f"benchmark.{kind}.{name}")

