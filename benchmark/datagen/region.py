"""TPC-H `region`, as one Parquet part: clause 4.2.3's fixed 5 rows.

Clause 1.4's three columns at their types (`r_regionkey` int64,
`r_name` and `r_comment` plain strings): keys 0..4 with the clause's
names, and `r_comment` 31..115 characters (0.4 to 1.6 times the
average of 72) cut from `lineitem.py`'s pool of the grammar's words by
a stream of this table's own. The scale factor does not change the
table; the seed changes the comments alone.

Imports numpy and pyarrow only (worker processes never import JAX).
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

from benchmark.datagen import orders as O
from benchmark.datagen.lineitem import (MARKER, find, finish,  # noqa: F401
                                        rows, table_dir)

STREAM = 0x726567696F6E  # "region"

#: clause 4.2.3: R_NAME by R_REGIONKEY 0..4
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
COMMENT_MIN, COMMENT_MAX = 31, 115

SCHEMA = pa.schema([
    ("r_regionkey", pa.int64()), ("r_name", pa.string()),
    ("r_comment", pa.string())])


def part_table(sf: float, seed: int, parts: int, part: int) -> pa.Table:
    lo = (len(REGIONS) * part) // parts
    hi = (len(REGIONS) * (part + 1)) // parts
    rs = O.own_stream(seed, STREAM, parts, part)
    return pa.table({
        "r_regionkey": pa.array(np.arange(lo, hi, dtype=np.int64)),
        "r_name": pa.array(REGIONS[lo:hi]),
        "r_comment": O.comments(rs, hi - lo, COMMENT_MIN, COMMENT_MAX),
    }, schema=SCHEMA)


def write_part(sf: float, seed: int, parts: int, part: int,
               directory: str) -> int:
    return O.write_groups(lambda _g: part_table(sf, seed, parts, part),
                          SCHEMA, 1, directory, part)


def submit(pool, sf: float, seed: int, parts: int, root: str):
    """Start the parts on `pool`: (directory, futures), no futures
    where an earlier run's data was found."""
    return O.submit_parts(write_part, pool, sf, seed, parts, root)
