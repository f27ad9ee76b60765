"""TPC-H `customer` from a seed, as Parquet parts.

Clause 1.4's eight columns at their types (identifiers int64, money
`decimal128(15,2)`, text as plain strings), `150,000 x SF` rows with
dense keys from 1, cut into `parts` files of one row group each (SF1's
whole table is 24 MB). Every column comes from a stream of this
table's own (`SeedSequence((seed, STREAM))`), a part at a time, so the
same seed gives the same rows under the same number of parts, and
neither `lineitem`'s nor `orders`' rows move:

- `c_name` `Customer#` and the key in nine digits; `c_address` 10..40
  characters and `c_comment` 29..116 (clause 4.2.2: 0.4 to 1.6 times
  the average of 73; the column's size is 117) cut from
  `lineitem.py`'s pool of the grammar's words, where dbgen draws an
  address from its own alphabet;
- `c_nationkey` uniform over 0..24; `c_phone` the country code
  `c_nationkey + 10` and three groups of digits (clause 4.2.2.9);
- `c_acctbal` uniform over -999.99..9999.99; `c_mktsegment` uniform
  over the five segments.

`datagen/orders.py` gives an order to two customers of every three
(clause 4.2.3), by arithmetic on the keys, so this table needs nothing
from it.

Imports numpy and pyarrow only (worker processes never import JAX).
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

from benchmark.datagen import lineitem as L
from benchmark.datagen import orders as O
from benchmark.datagen.lineitem import (MARKER, find, finish,  # noqa: F401
                                        rows, table_dir)

STREAM = 0x637573746F6D  # "custom"

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
NATIONS = 25
ADDRESS_MIN, ADDRESS_MAX = 10, 40
COMMENT_MIN, COMMENT_MAX = 29, 116

_MONEY = pa.decimal128(15, 2)
SCHEMA = pa.schema([
    ("c_custkey", pa.int64()), ("c_name", pa.string()),
    ("c_address", pa.string()), ("c_nationkey", pa.int64()),
    ("c_phone", pa.string()), ("c_acctbal", _MONEY),
    ("c_mktsegment", pa.string()), ("c_comment", pa.string())])


def key_range(sf: float, parts: int, part: int):
    """Customers [lo, hi) of part `part` (0-based key index)."""
    n = O.customers(sf)
    return (n * part) // parts, (n * (part + 1)) // parts


def phones(rs, nation: np.ndarray) -> pa.Array:
    """`CC-LLL-LLL-LLLL`: the country code is the nation's key + 10."""
    n = len(nation)
    out = np.full((n, 15), ord("-"), dtype=np.uint8)
    O.write_digits(out, 0, 2, nation + 10)
    O.write_digits(out, 3, 3, rs.integers(100, 1000, n, dtype=np.int64))
    O.write_digits(out, 7, 3, rs.integers(100, 1000, n, dtype=np.int64))
    O.write_digits(out, 11, 4, rs.integers(1000, 10000, n, dtype=np.int64))
    return O.fixed_strings(out)


def part_table(sf: float, seed: int, parts: int, part: int) -> pa.Table:
    lo, hi = key_range(sf, parts, part)
    n = hi - lo
    rs = O.own_stream(seed, STREAM, parts, part)
    keys = np.arange(lo + 1, hi + 1, dtype=np.int64)
    nation = rs.integers(0, NATIONS, n, dtype=np.int64)
    return pa.table({
        "c_custkey": pa.array(keys),
        "c_name": O.numbered("Customer#", keys, 9),
        "c_address": O.comments(rs, n, ADDRESS_MIN, ADDRESS_MAX),
        "c_nationkey": pa.array(nation),
        "c_phone": phones(rs, nation),
        "c_acctbal": L._dec(rs.integers(-99_999, 999_999 + 1, n,
                                        dtype=np.int64)),
        "c_mktsegment": L._pick(
            rs.integers(0, len(SEGMENTS), n, dtype=np.int8), SEGMENTS),
        "c_comment": O.comments(rs, n, COMMENT_MIN, COMMENT_MAX),
    }, schema=SCHEMA)


def write_part(sf: float, seed: int, parts: int, part: int,
               directory: str) -> int:
    return O.write_groups(lambda _g: part_table(sf, seed, parts, part),
                          SCHEMA, 1, directory, part)


def submit(pool, sf: float, seed: int, parts: int, root: str):
    """Start the parts on `pool`: (directory, futures), no futures
    where an earlier run's data was found."""
    return O.submit_parts(write_part, pool, sf, seed, parts, root)
