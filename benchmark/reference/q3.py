"""Plain reference for TPC-H Q3 (shipping priority, SEGMENT=BUILDING,
DATE=1995-03-15): the ten unshipped orders of the segment's customers
with the largest revenue.

Independent of the engine, in the manner of `reference/q1.py` (whose
reading of a decimal column's unscaled integers it shares): numpy over
the integers that Parquet holds, and no join operator. The three
filters compare integers (the segment's dictionary code, int32 days).
The joins are lookups by index: the segment's customers are a boolean
table over `c_custkey`, and ORDERS' keys are dense from 1
(`datagen/orders.py`), so an order's date, priority and whether it
qualifies (dated before the day, bought by such a customer) are arrays
indexed by `o_orderkey`, which every `l_orderkey` indexes in turn. Each
Parquet part of LINEITEM sums `l_extendedprice * (100 - l_discount)` by
order in int64 (unscaled units at scale 4), which is exact: a term is
under 1.1e9 and an order has at most seven lines; an order's lines all
lie in one part. The ten largest by (revenue descending, `o_orderdate`
ascending) are the answer. **A tie at the tenth place** (the eleventh
row equal to the tenth in both) is an answer the query's text does not
decide: it is detected, printed to standard error and returned under
`tie_at_limit`, and broken by `l_orderkey` so that the reference at
least repeats.

`KEYS`, which `harness/compare.py` holds to exact equality and sorts
by, are `l_orderkey` and `o_orderdate`: the date is the ISO string that
the service's JSON codec serves for a date (`service/server.py`,
`_table_rows`), compared as served, and a string cannot be a value.
The third group key, `o_shippriority`, is an integer that its order
decides and is compared as a value, with the same limit 0: it is the
answer's last column, and `tests/test_faults.py` adds one to the last
column of every cell's answer and wants a `value_gap` for it.

`precision` is for the controls that must fail the comparison
(`CONTROLS`, run by `benchmark/tests/control.py`): "float64" and
"float32" take the same rows, chosen exactly, hold each order's running
sum of dollars in that floating type, and choose the ten by it. An
order's revenue is a sum over at most seven rows, so the float64
control is within a unit or two in the last place of exact and may
round to the exact answer's double on every one of the ten rows
(PERF.md section 2). The benchmark itself only ever calls "exact".

Imports numpy and pyarrow only (worker processes never import JAX).
"""

from __future__ import annotations

import sys
from decimal import Decimal
from typing import Dict, List, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from benchmark.reference.q1 import _unscaled, part_files

COLUMNS = ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"]
ORDER_COLUMNS = ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]
CUSTOMER_COLUMNS = ["c_custkey", "c_mktsegment"]

_EPOCH = np.datetime64("1970-01-01", "D")
SEGMENT = "BUILDING"
#: o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15'
DAY = int((np.datetime64("1995-03-15", "D") - _EPOCH).astype(np.int32))
LIMIT = 10

KEYS = ["l_orderkey", "o_orderdate"]

#: the precisions below the configuration's exact decimals
CONTROLS = ("float64", "float32")

OUTPUT = ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]

Partial = Tuple[np.ndarray, np.ndarray]  # order keys, their revenue


def segment_customers(directory: str) -> np.ndarray:
    """is_segment[c_custkey]: the customers of the segment."""
    t = pq.read_table(directory, columns=CUSTOMER_COLUMNS)
    keys = t["c_custkey"].to_numpy()
    table = np.zeros(int(keys.max()) + 1, dtype=bool)
    table[keys] = pc.equal(t["c_mktsegment"], SEGMENT).to_numpy(
        zero_copy_only=False)
    return table


def open_orders(directory: str, is_segment: np.ndarray,
                leave_out=()) -> Dict[str, np.ndarray]:
    """Arrays indexed by `o_orderkey` (dense from 1; slot 0 unused):
    `ok` (dated before the day and bought by a customer of the
    segment), `date` (int32 days) and `priority`. `leave_out` names
    filters to skip ("date", "segment"): the tests' planted faults."""
    t = pq.read_table(directory, columns=ORDER_COLUMNS)
    keys = t["o_orderkey"].to_numpy()
    n = int(keys.max()) + 1
    date = np.zeros(n, dtype=np.int32)
    date[keys] = t["o_orderdate"].cast(pa.int32()).to_numpy()
    priority = np.zeros(n, dtype=np.int32)
    priority[keys] = t["o_shippriority"].to_numpy()
    ok = np.zeros(n, dtype=bool)
    ok[keys] = True  # an order that is not in the table joins nothing
    if "date" not in leave_out:
        ok &= date < DAY
    if "segment" not in leave_out:
        cust = np.zeros(n, dtype=np.int64)
        cust[keys] = t["o_custkey"].to_numpy()
        ok &= is_segment[cust]
    return {"ok": ok, "date": date, "priority": priority}


def partial(path: str, ok: np.ndarray, precision: str = "exact",
            leave_out=()) -> Partial:
    """(the qualifying orders with a line shipped after the day, each
    one's revenue over those lines) of one Parquet file of LINEITEM:
    in unscaled integers at scale 4, or in dollars when `precision` is
    a floating type."""
    t = pq.read_table(path, columns=COLUMNS)
    key = t["l_orderkey"].to_numpy()
    m = ok[np.minimum(key, len(ok) - 1)] & (key < len(ok))
    if "ship" not in leave_out:
        m &= t["l_shipdate"].cast(pa.int32()).to_numpy() > DAY
    key = key[m]
    ext = _unscaled(t["l_extendedprice"])[m]
    disc = _unscaled(t["l_discount"])[m]
    if precision == "exact":
        terms = ext * (100 - disc)
    else:
        f = np.dtype(precision).type
        terms = (ext.astype(f) / f(100)) * (f(1) - disc.astype(f) / f(100))
    orders, slot = np.unique(key, return_inverse=True)
    sums = np.zeros(len(orders), dtype=terms.dtype)
    # one by one in the terms' own type: exact for int64, a running
    # sum in the float for a control
    np.add.at(sums, slot, terms)
    return orders, sums


def top(partials: List[Partial], orders: Dict[str, np.ndarray],
        precision: str = "exact", limit: int = LIMIT):
    """(the answer's rows, whether the place after the last ties with
    it): the `limit` largest by (revenue desc, o_orderdate asc), a tie
    in both broken by l_orderkey."""
    keys = np.concatenate([p[0] for p in partials])
    revenue = np.concatenate([p[1] for p in partials])
    date = orders["date"][keys]
    order = np.lexsort((keys, date, -revenue))
    head = order[:limit + 1]
    tie = len(head) > limit and revenue[head[limit]] == revenue[head[limit - 1]] \
        and date[head[limit]] == date[head[limit - 1]]
    rows = []
    for i in head[:limit]:
        rev = Decimal(int(revenue[i])).scaleb(-4) if precision == "exact" \
            else float(revenue[i])
        day = str(_EPOCH + np.timedelta64(int(date[i]), "D"))
        rows.append(dict(zip(OUTPUT, [int(keys[i]), rev, day,
                                      int(orders["priority"][keys[i]])])))
    return rows, bool(tie)


def partials(directory: str, pool, ok: np.ndarray,
             precision: str = "exact", leave_out=()) -> List[Partial]:
    files = part_files(directory)
    if not files:
        raise FileNotFoundError(f"no Parquet parts under {directory}")
    if pool is None:
        return [partial(f, ok, precision, leave_out) for f in files]
    n = len(files)
    return list(pool.map(partial, files, [ok] * n, [precision] * n,
                         [leave_out] * n))


def compute(config: Dict, tables: Dict[str, str], pool,
            precision: str = "exact", leave_out=(),
            limit: int = LIMIT) -> Dict:
    """The reference's answer as the harness compares it: the key
    columns and {column: values}; `tie_at_limit` beside them."""
    orders = open_orders(tables["orders"],
                         segment_customers(tables["customer"]), leave_out)
    rows, tie = top(partials(tables["lineitem"], pool, orders["ok"],
                             precision, leave_out), orders, precision, limit)
    if tie:
        print(f"reference q3: rows {limit} and {limit + 1} tie in revenue "
              f"and o_orderdate; the query's text does not say which is "
              f"served", file=sys.stderr)
    return {"keys": KEYS, "tie_at_limit": tie,
            "table": {c: [r[c] for r in rows] for c in OUTPUT}}
