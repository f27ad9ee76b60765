"""Plain reference for the maximum of TPC-H Q15's revenue view
(top supplier, DATE=1996-01-01): `select max(total_revenue) from
revenue`, where `revenue` is clause 2.4.15's view, the sum of
`l_extendedprice * (1 - l_discount)` by `l_suppkey` over the lines
shipped in the quarter that starts on the date. The join to SUPPLIER
that names the winner is not part of the cell (the configuration
generates `lineitem` alone).

Independent of the engine, in the manner of `reference/q1.py` (whose
reading of a decimal column's unscaled integers it shares): numpy over
the integers that Parquet holds. It reads the four columns the query
needs, keeps the quarter's rows by comparing int32 days, and sums
`l_extendedprice * (100 - l_discount)` of each Parquet part by supplier
in int64 (unscaled units at scale 4), which is exact: a term is under
1.1e9 and a supplier has some tens of rows in the quarter. The parts'
sums are merged by supplier in int64 (a supplier's whole quarter is
under 1e12) and the maximum is taken as a Python integer.

The answer is one row with no key, and `harness/compare.py` lines an
answer up with the reference's by a key column: `queries/q15max.sql`
puts the constant column `one` beside the maximum, and so does this.

`precision` is for the controls that must fail the comparison
(`CONTROLS`, run by `benchmark/tests/control.py`): "float64" and
"float32" take the same rows, chosen exactly, and hold each supplier's
running sum of dollars in that floating type, part by part, as an
accumulator table on a device would. A supplier's sum is over some
tens of rows, so the float64 control is a few units in the last place
off and a seed on which it rounds to the exact answer's double is
possible (PERF.md section 2). The benchmark itself only ever calls
"exact".

Imports numpy and pyarrow only (worker processes never import JAX).
"""

from __future__ import annotations

from decimal import Decimal
from typing import Dict, List, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from benchmark.reference.q1 import _unscaled, part_files

COLUMNS = ["l_suppkey", "l_extendedprice", "l_discount", "l_shipdate"]

_EPOCH = np.datetime64("1970-01-01", "D")
#: l_shipdate >= date '1996-01-01' and < that date + interval '3' month
SHIP_FROM = int((np.datetime64("1996-01-01", "D") - _EPOCH).astype(np.int32))
SHIP_BEFORE = int((np.datetime64("1996-04-01", "D") - _EPOCH)
                  .astype(np.int32))

KEYS = ["one"]

#: the precisions below the configuration's exact decimals
CONTROLS = ("float64", "float32")

OUTPUT = ["one", "max_revenue"]

Partial = Tuple[np.ndarray, np.ndarray]  # suppliers, their revenue


def keep(ship: np.ndarray) -> np.ndarray:
    """The rows the view's filter keeps, from days."""
    return (ship >= SHIP_FROM) & (ship < SHIP_BEFORE)


def by_supplier(supp: np.ndarray, terms: np.ndarray) -> Partial:
    """(the suppliers in order, each one's sum of `terms`): the terms
    are added one by one in their own type, which for int64 is exact
    and for a float is a running sum in that float."""
    suppliers, slot = np.unique(supp, return_inverse=True)
    sums = np.zeros(len(suppliers), dtype=terms.dtype)
    np.add.at(sums, slot, terms)
    return suppliers, sums


def partial(path: str, precision: str = "exact") -> Partial:
    """The view's rows of one Parquet file: the suppliers that shipped
    in the quarter and each one's revenue there, in unscaled integers
    at scale 4, or in dollars when `precision` is a floating type."""
    t = pq.read_table(path, columns=COLUMNS)
    m = keep(t["l_shipdate"].cast(pa.int32()).to_numpy())
    supp = t["l_suppkey"].to_numpy()[m]
    ext = _unscaled(t["l_extendedprice"])[m]
    disc = _unscaled(t["l_discount"])[m]
    if precision == "exact":
        return by_supplier(supp, ext * (100 - disc))
    f = np.dtype(precision).type
    return by_supplier(
        supp, (ext.astype(f) / f(100)) * (f(1) - disc.astype(f) / f(100)))


def view(partials: List[Partial]) -> Partial:
    """The `revenue` view: the parts' rows merged by supplier, in the
    partials' own type and order."""
    return by_supplier(np.concatenate([p[0] for p in partials]),
                       np.concatenate([p[1] for p in partials]))


def combine(partials: List[Partial], precision: str = "exact") -> List[Dict]:
    """The query's one row. The exact maximum is a `Decimal` at scale 4
    (the product of two DECIMAL(15,2)); the controls give a float."""
    _suppliers, revenue = view(partials)
    if not len(revenue):
        raise ValueError("no line shipped in the quarter: the view is "
                         "empty and its maximum is NULL")
    if precision == "exact":
        top = Decimal(int(revenue.max())).scaleb(-4)
    else:
        top = float(revenue.max())
    return [dict(zip(OUTPUT, [1, top]))]


def partials(directory: str, pool, precision: str = "exact") -> List[Partial]:
    files = part_files(directory)
    if not files:
        raise FileNotFoundError(f"no Parquet parts under {directory}")
    if pool is None:
        return [partial(f, precision) for f in files]
    return list(pool.map(partial, files, [precision] * len(files)))


def rows(directory: str, pool, precision: str = "exact") -> List[Dict]:
    return combine(partials(directory, pool, precision), precision)


def compute(config: Dict, tables: Dict[str, str], pool,
            precision: str = "exact") -> Dict:
    """The reference's answer as the harness compares it: the key
    column and {column: values}."""
    out = rows(tables["lineitem"], pool, precision)
    return {"keys": KEYS,
            "table": {c: [r[c] for r in out] for c in OUTPUT}}
