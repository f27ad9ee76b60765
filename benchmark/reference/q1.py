"""Plain reference for TPC-H Q1 (pricing summary report, DELTA=90).

Independent of the engine: numpy over the integers that Parquet holds.
It reads only the seven columns the query needs, takes each
`decimal128` column as its low int64 words (unscaled units, no
`Decimal` object per row) and each date as int32 days, and sums each
Parquet part in int64, which is exact: the largest sum of a part of
2.5 M rows, `sum_charge` at scale 6, is about 5e16 (int64 holds
9.2e18). The parts' sums are added as Python integers, which have no
limit (the whole of SF10 comes to 1.0e18).

One partial per Parquet part (`partial`, run side by side by the
harness's pool of processes), combined by `combine` into the rows
Spark's semantics give: `sum(decimal(15,2))` keeps its scale, products
add scales, `avg` is the exact quotient rounded HALF_UP to scale 6.

`precision` is for the controls that must fail the comparison
(`CONTROLS`, run by `benchmark/tests/control.py`): "float64" and
"float32" compute the same sums as dollars in that floating type, as a
tempting faster path would. The benchmark itself only ever calls
"exact".

Imports numpy and pyarrow only (worker processes never import JAX).
"""

from __future__ import annotations

import glob
import os
from decimal import Decimal
from typing import Dict, List

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

COLUMNS = ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
           "l_discount", "l_tax", "l_shipdate"]

#: date '1998-12-01' - interval '90' day
SHIP_LIMIT = int((np.datetime64("1998-09-02", "D")
                  - np.datetime64("1970-01-01", "D")).astype(np.int32))

KEYS = ["l_returnflag", "l_linestatus"]

#: the precisions below the configuration's exact decimals
CONTROLS = ("float64", "float32")

OUTPUT = ["l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
          "sum_disc_price", "sum_charge", "avg_qty", "avg_price", "avg_disc",
          "count_order"]


def _unscaled(col: pa.ChunkedArray) -> np.ndarray:
    """decimal128 column -> int64 unscaled units (its values fit 64
    bits: the low word of each 128-bit little-endian pair)."""
    out = []
    for chunk in col.chunks:
        words = np.frombuffer(chunk.buffers()[1], dtype=np.int64)
        out.append(words[2 * chunk.offset:2 * (chunk.offset + len(chunk)):2])
    return np.concatenate(out) if len(out) != 1 else out[0]


def _codes(col: pa.ChunkedArray):
    """string or dictionary column -> (int codes, the values they
    index), with no Python object per row."""
    if not pa.types.is_dictionary(col.type):
        col = pc.dictionary_encode(col)
    arr = col.unify_dictionaries().combine_chunks()
    return (arr.indices.to_numpy(zero_copy_only=False).astype(np.int64),
            arr.dictionary.to_pylist())


def partial(path: str, precision: str = "exact") -> Dict:
    """Per-group sums of one Parquet file: {(flag, status): [sum_qty,
    sum_base, sum_disc_price, sum_charge, sum_discount, count]} in
    unscaled integers (scales 2, 2, 4, 6, 2), or in dollars when
    `precision` is a floating type."""
    t = pq.read_table(path, columns=COLUMNS, read_dictionary=KEYS)
    ship = t["l_shipdate"].cast(pa.int32()).to_numpy()
    keep = ship <= SHIP_LIMIT
    qty = _unscaled(t["l_quantity"])[keep]
    ext = _unscaled(t["l_extendedprice"])[keep]
    disc = _unscaled(t["l_discount"])[keep]
    tax = _unscaled(t["l_tax"])[keep]
    flag, flags = _codes(t["l_returnflag"])
    status, statuses = _codes(t["l_linestatus"])
    key = (flag * len(statuses) + status)[keep]
    if precision == "exact":
        disc_price = ext * (100 - disc)
        charge = disc_price * (100 + tax)
        cols = [qty, ext, disc_price, charge, disc]
    else:
        f = np.dtype(precision).type
        q, e, d, x = (a.astype(f) / f(100) for a in (qty, ext, disc, tax))
        dp = e * (f(1) - d)
        cols = [q, e, dp, dp * (f(1) + x), d]
    out = {}
    for k in np.unique(key):
        m = key == k
        if precision == "exact":
            sums = [int(c[m].sum(dtype=np.int64)) for c in cols]
        else:
            # a running sum in the type itself, as an accumulator on
            # the device would hold it (numpy's pairwise sum is kinder)
            sums = [float(np.cumsum(c[m], dtype=c.dtype)[-1]) for c in cols]
        out[(flags[k // len(statuses)], statuses[k % len(statuses)])] = \
            sums + [int(m.sum())]
    return out


def part_files(directory: str) -> List[str]:
    return sorted(glob.glob(os.path.join(directory, "*.parquet")))


def _avg(total: int, scale: int, count: int) -> Decimal:
    """total/count at scale 6, HALF_UP, in integers (totals are >= 0)."""
    q, r = divmod(total * 10 ** (6 - scale), count)
    return Decimal(q + (2 * r >= count)).scaleb(-6)


def combine(partials: List[Dict], precision: str = "exact") -> List[Dict]:
    """The query's rows, ordered by (l_returnflag, l_linestatus).
    Exact values are `Decimal` and `int`; the floating controls give
    floats."""
    acc: Dict = {}
    for p in partials:
        for k, v in p.items():
            a = acc.setdefault(k, [0] * 6)
            for i, x in enumerate(v):
                a[i] += x
    rows = []
    for (flag, status), (q, e, dp, ch, d, n) in sorted(acc.items()):
        if precision == "exact":
            vals = [Decimal(q).scaleb(-2), Decimal(e).scaleb(-2),
                    Decimal(dp).scaleb(-4), Decimal(ch).scaleb(-6),
                    _avg(q, 2, n), _avg(e, 2, n), _avg(d, 2, n), n]
        else:
            vals = [q, e, dp, ch, round(q / n, 6), round(e / n, 6),
                    round(d / n, 6), n]
        rows.append(dict(zip(OUTPUT, [flag, status] + vals)))
    return rows


def rows(directory: str, pool, precision: str = "exact") -> List[Dict]:
    files = part_files(directory)
    if not files:
        raise FileNotFoundError(f"no Parquet parts under {directory}")
    if pool is None:
        partials = [partial(f, precision) for f in files]
    else:
        partials = list(pool.map(partial, files, [precision] * len(files)))
    return combine(partials, precision)


def compute(config: Dict, tables: Dict[str, str], pool,
            precision: str = "exact") -> Dict:
    """The reference's answer as the harness compares it: the key
    columns and {column: values}."""
    out = rows(tables["lineitem"], pool, precision)
    return {"keys": KEYS,
            "table": {c: [r[c] for r in out] for c in OUTPUT}}
