"""Plain reference for TPC-H Q6 (forecasting revenue change,
DATE=1994-01-01, DISCOUNT=0.06, QUANTITY=24).

Independent of the engine, in the manner of `reference/q1.py` (whose
reading of a decimal column's unscaled integers it shares): numpy over
the integers that Parquet holds. It reads the four columns the
query needs, takes each `decimal128` column as its low int64 words
(unscaled units at scale 2) and the date as int32 days, keeps the rows
the query's filter keeps by comparing integers (a discount between 0.05
and 0.07 is 5 to 7 hundredths, a quantity under 24 is under 2400), and
sums `l_extendedprice * l_discount` of each Parquet part in int64,
which is exact: a product is under 1.1e8 at scale 4 and a part of
0.5 M rows keeps some 10,000 of them. The parts' sums are added as
Python integers.

The query has one group and no key, and `harness/compare.py` lines an
answer up with the reference's by a key column: `queries/q6.sql` puts
the constant column `one` beside the sum, and so does this.

`precision` is for the controls that must fail the comparison
(`CONTROLS`, run by `benchmark/tests/control.py`): "float64" and
"float32" take the same rows, chosen exactly, and sum their dollars in
that floating type. The benchmark itself only ever calls "exact".

Imports numpy and pyarrow only (worker processes never import JAX).
"""

from __future__ import annotations

from decimal import Decimal
from typing import Dict, List

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from benchmark.reference.q1 import _unscaled, part_files

COLUMNS = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]

_EPOCH = np.datetime64("1970-01-01", "D")
#: l_shipdate >= date '1994-01-01' and < that date + interval '1' year
SHIP_FROM = int((np.datetime64("1994-01-01", "D") - _EPOCH).astype(np.int32))
SHIP_BEFORE = int((np.datetime64("1995-01-01", "D") - _EPOCH)
                  .astype(np.int32))
#: l_discount between 0.06 - 0.01 and 0.06 + 0.01, in hundredths
DISCOUNT_MIN, DISCOUNT_MAX = 5, 7
#: l_quantity < 24, in hundredths
QUANTITY_BELOW = 2400

KEYS = ["one"]

#: the precisions below the configuration's exact decimals
CONTROLS = ("float64", "float32")

OUTPUT = ["one", "revenue"]


def keep(ship: np.ndarray, disc: np.ndarray, qty: np.ndarray) -> np.ndarray:
    """The rows the query's filter keeps, from days and hundredths."""
    return ((ship >= SHIP_FROM) & (ship < SHIP_BEFORE)
            & (disc >= DISCOUNT_MIN) & (disc <= DISCOUNT_MAX)
            & (qty < QUANTITY_BELOW))


def partial(path: str, precision: str = "exact") -> List:
    """[revenue, rows kept] of one Parquet file: the revenue in
    unscaled integers at scale 4, or in dollars when `precision` is a
    floating type."""
    t = pq.read_table(path, columns=COLUMNS)
    disc = _unscaled(t["l_discount"])
    m = keep(t["l_shipdate"].cast(pa.int32()).to_numpy(), disc,
             _unscaled(t["l_quantity"]))
    ext, disc = _unscaled(t["l_extendedprice"])[m], disc[m]
    if precision == "exact":
        return [int((ext * disc).sum(dtype=np.int64)), int(m.sum())]
    f = np.dtype(precision).type
    terms = (ext.astype(f) / f(100)) * (disc.astype(f) / f(100))
    # a running sum in the type itself, as an accumulator on the device
    # would hold it (numpy's pairwise sum is kinder)
    return [float(np.cumsum(terms, dtype=terms.dtype)[-1]) if len(terms)
            else 0.0, int(m.sum())]


def combine(partials: List[List], precision: str = "exact") -> List[Dict]:
    """The query's one row. The exact revenue is a `Decimal` at scale
    4 (the product of two DECIMAL(15,2)); the controls give a float."""
    revenue = sum(p[0] for p in partials)
    if precision == "exact":
        revenue = Decimal(revenue).scaleb(-4)
    return [dict(zip(OUTPUT, [1, revenue]))]


def rows(directory: str, pool, precision: str = "exact") -> List[Dict]:
    files = part_files(directory)
    if not files:
        raise FileNotFoundError(f"no Parquet parts under {directory}")
    if pool is None:
        partials = [partial(f, precision) for f in files]
    else:
        partials = list(pool.map(partial, files, [precision] * len(files)))
    return combine(partials, precision)


def kept_rows(directory: str) -> int:
    """How many rows of the table the filter keeps (PERF.md gives it
    beside what the device-table cache holds for the query)."""
    return sum(partial(f)[1] for f in part_files(directory))


def compute(config: Dict, tables: Dict[str, str], pool,
            precision: str = "exact") -> Dict:
    """The reference's answer as the harness compares it: the key
    column and {column: values}."""
    out = rows(tables["lineitem"], pool, precision)
    return {"keys": KEYS,
            "table": {c: [r[c] for r in out] for c in OUTPUT}}
