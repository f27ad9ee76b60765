"""TPC-H `supplier` from a seed, as one Parquet part.

Clause 1.4's seven columns at their types (identifiers int64, money
`decimal128(15,2)`, text as plain strings), `10,000 x SF` rows with
dense keys from 1, which are the keys `lineitem.py` draws `l_suppkey`
from (uniform over 1 .. 10,000 x SF; dbgen derives it from
`l_partkey`). Every column comes from a stream of this table's own
(`SeedSequence((seed, STREAM))`), so no other table's rows move:

- `s_name` `Supplier#` and the key in nine digits; `s_address` 10..40
  characters and `s_comment` 25..100 (clause 4.2.2: 0.4 to 1.6 times
  the average of 63) cut from `lineitem.py`'s pool of the grammar's
  words (dbgen plants "Customer ... Complaints" in a few comments,
  which Q5 does not read);
- `s_nationkey` uniform over 0..24; `s_phone` the country code
  `s_nationkey + 10` and three groups of digits (clause 4.2.2.9);
- `s_acctbal` uniform over -999.99..9999.99.

Imports numpy and pyarrow only (worker processes never import JAX);
`submit`, which the run's own process calls, asks the program one
question once the part is under way (`needs_a_program_that_orders_q5`).
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

from benchmark.datagen import customer as C
from benchmark.datagen import lineitem as L
from benchmark.datagen import orders as O
from benchmark.datagen.lineitem import (MARKER, find, finish,  # noqa: F401
                                        rows, table_dir)

STREAM = 0x737570706C69  # "suppli"

NATIONS = 25
ADDRESS_MIN, ADDRESS_MAX = 10, 40
COMMENT_MIN, COMMENT_MAX = 25, 100

_MONEY = pa.decimal128(15, 2)
SCHEMA = pa.schema([
    ("s_suppkey", pa.int64()), ("s_name", pa.string()),
    ("s_address", pa.string()), ("s_nationkey", pa.int64()),
    ("s_phone", pa.string()), ("s_acctbal", _MONEY),
    ("s_comment", pa.string())])


def suppliers(sf: float) -> int:
    """The keys `lineitem.py` draws `l_suppkey` from: 1 .. this."""
    return max(1, int(L.SUPPS_PER_SF * sf))


def part_table(sf: float, seed: int, parts: int, part: int) -> pa.Table:
    n_all = suppliers(sf)
    lo, hi = (n_all * part) // parts, (n_all * (part + 1)) // parts
    n = hi - lo
    rs = O.own_stream(seed, STREAM, parts, part)
    keys = np.arange(lo + 1, hi + 1, dtype=np.int64)
    nation = rs.integers(0, NATIONS, n, dtype=np.int64)
    return pa.table({
        "s_suppkey": pa.array(keys),
        "s_name": O.numbered("Supplier#", keys, 9),
        "s_address": O.comments(rs, n, ADDRESS_MIN, ADDRESS_MAX),
        "s_nationkey": pa.array(nation),
        "s_phone": C.phones(rs, nation),
        "s_acctbal": L._dec(rs.integers(-99_999, 999_999 + 1, n,
                                        dtype=np.int64)),
        "s_comment": O.comments(rs, n, COMMENT_MIN, COMMENT_MAX),
    }, schema=SCHEMA)


def write_part(sf: float, seed: int, parts: int, part: int,
               directory: str) -> int:
    return O.write_groups(lambda _g: part_table(sf, seed, parts, part),
                          SCHEMA, 1, directory, part)


#: the process counter that the program which plans Q5's joins by
#: their keys' domains registers (PR 41)
WIDEST_COUNTER = "join_widest_rows"


def needs_a_program_that_orders_q5() -> None:
    """SUPPLIER is made for Q5, whose `c_nationkey = s_nationkey` joins
    two tables on a key of 25 values. A program of before PR 41 prices
    that edge as a key join and takes it first: at SF1 its plan makes
    some 11.7 M and 18.5 M rows by the many-to-many expansion under
    the capacity loop, and cannot warm Q5 up within the 300 s
    `harness/entries.py` gives a request. The driver lays a PR's
    benchmark files over the parent's checkout and wants a parent that
    cannot run a new cell to say so soon: such a program is told apart
    by the counter it does not register, and the run ends here with
    the reason, as `orders.py` ends a program that cannot join."""
    from spark_tpu.observability.metrics import is_registered_metric
    if not is_registered_metric(WIDEST_COUNTER):
        raise SystemExit(
            f"benchmark: this program registers no counter "
            f"{WIDEST_COUNTER!r}: it predates the join order that knows "
            f"a many-to-many key (PR 41) and cannot warm Q5 up within a "
            f"request's time limit (benchmark/datagen/supplier.py)")


def submit(pool, sf: float, seed: int, parts: int, root: str):
    """Start the parts on `pool`: (directory, futures), no futures
    where an earlier run's data was found."""
    started = O.submit_parts(write_part, pool, sf, seed, parts, root)
    needs_a_program_that_orders_q5()
    return started
