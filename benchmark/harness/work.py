"""The work a request needs, whatever implements it, and the least time
the chip could take for it.

bytes = rows scanned x the device width of the columns the query reads
        + groups x the width of an output row
operations = one per row per aggregate expression

Device widths: an int64 key or a decimal (scaled int64) 8 bytes, a
date, an int32 or a dictionary code 4. The least time is the larger of
bytes over the chip's bytes/s and operations over its op/s, from
`peaks.json` keyed by `device_kind`. A device that is not in the table
is an error, never a default.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

WIDTH = {"int64": 8, "decimal": 8, "float64": 8, "date": 4, "int32": 4,
         "string": 4, "float32": 4}


def peaks(device_kind: str) -> Dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"benchmark: device kind {device_kind!r} is not in "
                         f"peaks.json ({sorted(table)}); add it with its "
                         f"source")
    return table[device_kind]


def query_work(query: Dict, rows: int, groups: int) -> Dict:
    """Bytes and operations of one query over `rows` input rows into
    `groups` output rows."""
    row_in = sum(WIDTH[t] for t in query["columns_read"].values())
    row_out = sum(WIDTH[t] for t in query["output_columns"].values())
    return {"rows": rows,
            "bytes": rows * row_in + groups * row_out,
            "ops": rows * int(query["aggregates"])}


def request_work(works: List[Dict]) -> Dict:
    return {k: sum(w[k] for w in works) for k in ("rows", "bytes", "ops")}


def least_seconds(work: Dict, peak: Dict) -> Dict:
    by_bytes = work["bytes"] / peak["bytes_per_s"]
    by_ops = work["ops"] / peak["ops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "bytes" if by_bytes >= by_ops else "ops"}
