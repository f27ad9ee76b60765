"""Plain reference for TPC-H Q5 (local supplier volume, REGION=ASIA,
DATE=1994-01-01): the revenue of each Asian nation from the lines of
1994's orders whose customer and supplier are both of that nation.

Independent of the engine, in the manner of `reference/q3.py` (whose
reading of Parquet's integers it shares): numpy over the integers that
Parquet holds, and no join operator. The joins are lookups by index:

- ASIA's nations are a boolean over `n_nationkey`, from REGION's
  `r_name` and NATION's `n_regionkey`;
- a customer's and a supplier's nation are arrays indexed by
  `c_custkey` and `s_suppkey`, dense from 1 (`datagen/customer.py`,
  `datagen/supplier.py`);
- a qualifying order (dated in 1994, its customer Asian) holds its
  customer's nation in an array over `o_orderkey`, dense from 1
  (`datagen/orders.py`), and -1 otherwise;
- a line counts where its order qualifies and
  `c_nationkey[o_custkey] == s_nationkey[l_suppkey]`: that one test is
  `c_nationkey = s_nationkey` and, through the order's nation, the
  supplier's being Asian.

Each Parquet part of LINEITEM sums `l_extendedprice * (100 -
l_discount)` by nation in int64 (unscaled units at scale 4), which is
exact: a term is under 1.1e9 and a nation's lines number some 0.25 M
at SF1. The nations with a line are the answer, its order by revenue
left to `harness/compare.py`, which sorts by `KEYS`.

`KEYS` is `n_name`, the name the answer is grouped by, and `revenue` is
a value with the limit 0.

`precision` is for the controls that must fail the comparison
(`CONTROLS`, run by `benchmark/tests/control.py`): "float64" and
"float32" take the same lines, chosen exactly, and hold each nation's
running sum of dollars in that floating type (PERF.md section 2). The
benchmark itself only ever calls "exact".

Imports numpy and pyarrow only (worker processes never import JAX).
"""

from __future__ import annotations

from decimal import Decimal
from typing import Dict, List

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from benchmark.reference.q1 import _unscaled, part_files

COLUMNS = ["l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"]
ORDER_COLUMNS = ["o_orderkey", "o_custkey", "o_orderdate"]
CUSTOMER_COLUMNS = ["c_custkey", "c_nationkey"]
SUPPLIER_COLUMNS = ["s_suppkey", "s_nationkey"]
NATION_COLUMNS = ["n_nationkey", "n_name", "n_regionkey"]
REGION_COLUMNS = ["r_regionkey", "r_name"]

_EPOCH = np.datetime64("1970-01-01", "D")
REGION = "ASIA"
#: o_orderdate >= date '1994-01-01' and < that date + interval '1' year
START = int((np.datetime64("1994-01-01", "D") - _EPOCH).astype(np.int32))
END = int((np.datetime64("1995-01-01", "D") - _EPOCH).astype(np.int32))
NATIONS = 25

KEYS = ["n_name"]

#: the precisions below the configuration's exact decimals
CONTROLS = ("float64", "float32")

OUTPUT = ["n_name", "revenue"]


def _by_key(directory: str, columns: List[str]) -> np.ndarray:
    """The second column indexed by the first, a dense key from 0 or 1
    (a key that is not in the table reads -1)."""
    t = pq.read_table(directory, columns=columns)
    keys = t[columns[0]].to_numpy()
    out = np.full(int(keys.max()) + 1, -1, dtype=np.int64)
    out[keys] = t[columns[1]].to_numpy()
    return out


def nations(tables: Dict[str, str]):
    """(is_asian[n_nationkey], n_name by n_nationkey)."""
    r = pq.read_table(tables["region"], columns=REGION_COLUMNS)
    in_region = np.zeros(int(r["r_regionkey"].to_numpy().max()) + 1,
                         dtype=bool)
    in_region[r["r_regionkey"].to_numpy()] = pc.equal(
        r["r_name"], REGION).to_numpy(zero_copy_only=False)
    n = pq.read_table(tables["nation"], columns=NATION_COLUMNS)
    keys = n["n_nationkey"].to_numpy()
    asian = np.zeros(NATIONS, dtype=bool)
    asian[keys] = in_region[n["n_regionkey"].to_numpy()]
    names = [""] * NATIONS
    for k, name in zip(keys, n["n_name"].to_pylist()):
        names[k] = name
    return asian, names


def order_nations(tables: Dict[str, str], asian: np.ndarray) -> np.ndarray:
    """By `o_orderkey`: the nation of a qualifying order's customer,
    -1 for every other order."""
    c_nation = _by_key(tables["customer"], CUSTOMER_COLUMNS)
    t = pq.read_table(tables["orders"], columns=ORDER_COLUMNS)
    keys = t["o_orderkey"].to_numpy()
    date = t["o_orderdate"].cast(pa.int32()).to_numpy()
    nation = c_nation[t["o_custkey"].to_numpy()]
    ok = (date >= START) & (date < END) & (nation >= 0)
    ok[ok] = asian[nation[ok]]
    out = np.full(int(keys.max()) + 1, -1, dtype=np.int64)
    out[keys] = np.where(ok, nation, -1)
    return out


def partial(path: str, order_nation: np.ndarray, s_nation: np.ndarray,
            precision: str = "exact"):
    """(revenue by nation, lines by nation), NATIONS slots each, of one
    Parquet file of LINEITEM: the revenue in unscaled integers at
    scale 4, or in dollars when `precision` is a floating type."""
    t = pq.read_table(path, columns=COLUMNS)
    key = t["l_orderkey"].to_numpy()
    supp = t["l_suppkey"].to_numpy()
    nation = order_nation[np.minimum(key, len(order_nation) - 1)]
    nation = np.where(key < len(order_nation), nation, -1)
    supp_nation = s_nation[np.minimum(supp, len(s_nation) - 1)]
    m = (nation >= 0) & (supp < len(s_nation)) & (nation == supp_nation)
    ext = _unscaled(t["l_extendedprice"])[m]
    disc = _unscaled(t["l_discount"])[m]
    if precision == "exact":
        terms = ext * (100 - disc)
    else:
        f = np.dtype(precision).type
        terms = (ext.astype(f) / f(100)) * (f(1) - disc.astype(f) / f(100))
    sums = np.zeros(NATIONS, dtype=terms.dtype)
    # one by one in the terms' own type: exact for int64, a running
    # sum in the float for a control
    np.add.at(sums, nation[m], terms)
    lines = np.bincount(nation[m], minlength=NATIONS)
    return sums, lines


def compute(config: Dict, tables: Dict[str, str], pool,
            precision: str = "exact") -> Dict:
    """The reference's answer as the harness compares it: the key
    column and {column: values}."""
    asian, names = nations(tables)
    order_nation = order_nations(tables, asian)
    s_nation = _by_key(tables["supplier"], SUPPLIER_COLUMNS)
    files = part_files(tables["lineitem"])
    if not files:
        raise FileNotFoundError(f"no Parquet parts under "
                                f"{tables['lineitem']}")
    n = len(files)
    args = ([order_nation] * n, [s_nation] * n, [precision] * n)
    parts = list(pool.map(partial, files, *args)) if pool is not None \
        else [partial(f, *a) for f, *a in zip(files, *args)]
    revenue = parts[0][0].copy()
    lines = parts[0][1].copy()
    for sums, counted in parts[1:]:
        revenue += sums
        lines += counted
    rows = [k for k in range(NATIONS) if lines[k]]
    value = (lambda v: Decimal(int(v)).scaleb(-4)) if precision == "exact" \
        else float
    return {"keys": KEYS,
            "table": {"n_name": [names[k] for k in rows],
                      "revenue": [value(revenue[k]) for k in rows]}}
