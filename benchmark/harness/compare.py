"""What decides `correct`: every answer of the window against the plain
reference, and the no-recovery guarantee.

Four numbers, each with the limit 0 (the comparison is exact: decimal
sums and counts are exact in the engine and in the reference, and a
JSON float of an exact decimal is the correctly rounded double of it on
both sides):

  value_gap   the widest relative gap |served - reference| /
              max(|reference|, 1) over every value of every answer
  rows_off    answers whose group keys are not exactly the reference's
              (a row missing, extra or twice)
  not_ok      requests that never answered or whose status is not `ok`
  recovered   recovery actions: fault events and fault-summary entries
              of the requests' status records plus the growth of every
              `spark_tpu_fault_*` counter and of `queries_failed`
"""

from __future__ import annotations

from decimal import Decimal
from typing import Dict, List

import numpy as np

LIMITS = {"value_gap": 0.0, "rows_off": 0, "not_ok": 0, "recovered": 0}

#: counters of the engine whose growth is a recovery action or a failure
FAULT_PREFIX = "spark_tpu_fault_"
FAILED = "spark_tpu_queries_failed"


def as_served(values) -> np.ndarray:
    """A reference column as the entry serves it: an exact decimal
    becomes its correctly rounded double (JSON has no decimal)."""
    values = list(values)
    if values and isinstance(values[0], Decimal):
        return np.array([float(v) for v in values])
    return np.array(values)


def _sorted(table: Dict[str, np.ndarray], keys: List[str]):
    order = np.lexsort([table[k] for k in reversed(keys)])
    return {c: np.asarray(v)[order] for c, v in table.items()}


def answer_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
               keys: List[str]):
    """(rows_off, value_gap) of one answer against the reference's."""
    if got is None or set(got) != set(want) \
            or len(got[keys[0]]) != len(want[keys[0]]):
        return 1, float("inf")
    got, want = _sorted(got, keys), _sorted(want, keys)
    for k in keys:
        if not np.array_equal(got[k], want[k]):
            return 1, float("inf")
    gap = 0.0
    for c in want:
        if c in keys:
            continue
        g, w = got[c], want[c]
        if g.dtype.kind in "iu" and w.dtype.kind in "iu":
            diff = np.abs(g.astype(np.int64) - w.astype(np.int64))
        else:
            diff = np.abs(g.astype(np.float64) - w.astype(np.float64))
            if np.isnan(diff).any():
                return 0, float("inf")
        scale = np.maximum(np.abs(w.astype(np.float64)), 1.0)
        gap = max(gap, float((diff / scale).max()) if len(diff) else 0.0)
    return 0, gap


def judge(requests: List[Dict], references: Dict[str, Dict],
          counters_before: Dict, counters_after: Dict) -> Dict:
    """The compared numbers of a window and whether each request was
    right. `references[query] = {"keys": [...], "table": {...}}`."""
    value_gap, rows_off, not_ok, recovered = 0.0, 0, 0, 0
    served = {name: {c: as_served(v) for c, v in ref["table"].items()}
              for name, ref in references.items()}
    for req in requests:
        bad = False
        for q in req["queries"]:
            if q["status"] != "ok":
                not_ok += 1
                bad = True
                continue
            off, gap = answer_gap(q["answer"], served[q["query"]],
                                  references[q["query"]]["keys"])
            rows_off += off
            if off == 0:
                value_gap = max(value_gap, gap)
            faults = len(q.get("fault_events") or []) \
                + len(q.get("fault_summary") or {})
            recovered += faults
            bad = bad or off > 0 or gap > LIMITS["value_gap"] or faults > 0
        req["failed"] = bad
    for name, after in counters_after.items():
        if name.startswith(FAULT_PREFIX) or name == FAILED:
            recovered += int(after - counters_before.get(name, 0))
    numbers = {"value_gap": value_gap, "rows_off": rows_off,
               "not_ok": not_ok, "recovered": recovered}
    return {"numbers": {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in numbers.items()},
            "correct": all(v <= LIMITS[k] for k, v in numbers.items())}
