"""TPC-H `nation`, as one Parquet part: clause 4.2.3's fixed 25 rows.

Clause 1.4's four columns at their types (`n_nationkey` and
`n_regionkey` int64, `n_name` and `n_comment` plain strings). The keys,
names and regions are the clause's own list, written out here so that
the benchmark's generators stay independent of the engine's
(`spark_tpu/tpch/datagen.py` has the same list); `n_comment` is 31..114
characters (0.4 to 1.6 times the average of 72) cut from
`lineitem.py`'s pool of the grammar's words by a stream of this
table's own. The scale factor does not change the table; the seed
changes the comments alone.

Imports numpy and pyarrow only (worker processes never import JAX).
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

from benchmark.datagen import orders as O
from benchmark.datagen.lineitem import (MARKER, find, finish,  # noqa: F401
                                        rows, table_dir)

STREAM = 0x6E6174696F6E  # "nation"

#: clause 4.2.3: (N_NAME, N_REGIONKEY) by N_NATIONKEY 0..24
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
COMMENT_MIN, COMMENT_MAX = 31, 114

SCHEMA = pa.schema([
    ("n_nationkey", pa.int64()), ("n_name", pa.string()),
    ("n_regionkey", pa.int64()), ("n_comment", pa.string())])


def part_table(sf: float, seed: int, parts: int, part: int) -> pa.Table:
    lo = (len(NATIONS) * part) // parts
    hi = (len(NATIONS) * (part + 1)) // parts
    rs = O.own_stream(seed, STREAM, parts, part)
    return pa.table({
        "n_nationkey": pa.array(np.arange(lo, hi, dtype=np.int64)),
        "n_name": pa.array([name for name, _ in NATIONS[lo:hi]]),
        "n_regionkey": pa.array([r for _, r in NATIONS[lo:hi]],
                                type=pa.int64()),
        "n_comment": O.comments(rs, hi - lo, COMMENT_MIN, COMMENT_MAX),
    }, schema=SCHEMA)


def write_part(sf: float, seed: int, parts: int, part: int,
               directory: str) -> int:
    return O.write_groups(lambda _g: part_table(sf, seed, parts, part),
                          SCHEMA, 1, directory, part)


def submit(pool, sf: float, seed: int, parts: int, root: str):
    """Start the parts on `pool`: (directory, futures), no futures
    where an earlier run's data was found."""
    return O.submit_parts(write_part, pool, sf, seed, parts, root)
