"""TPC-H `orders` from a seed, as Parquet parts, consistent with
`datagen/lineitem.py`'s rows of the same seed, scale and parts.

Clause 1.4's nine columns at their types (identifiers int64, money
`decimal128(15,2)`, `o_orderdate` date32, `o_shippriority` int32, text
as plain strings). **The keys and the dates are `lineitem.py`'s own**:
order keys are dense, `1 .. 1,500,000 x SF` (dbgen's are sparse), cut
into `parts x GROUPS` pieces by `lineitem.order_range`, and the date of
every order of a piece is the first draw of that piece's stream
(`SeedSequence(seed).spawn(parts x GROUPS)[piece]`), which
`lineitem.group_table` repeats over the order's lines. This module
replays that stream through the draws `o_orderstatus` needs (the
dates, the lines an order, and past quantity and part to the ship
dates) by importing `lineitem.py`'s names, and edits nothing there:
`l_shipdate - o_orderdate` is 1..121 for every line and every
`l_orderkey` has its order. So ORDERS takes LINEITEM's number of parts.

Every other column comes from a stream of this table's own
(`SeedSequence((seed, STREAM))`), so `lineitem`'s rows do not move:

- `o_custkey`: uniform over the customers whose key is not divisible
  by 3 (clause 4.2.3: a third of the customers have no order);
- `o_orderstatus`: **derived**, as the clause says: F where every line
  of the order has `l_linestatus` F (shipped by CURRENTDATE), O where
  every line has O, P otherwise;
- `o_totalprice`: **drawn**, uniform between the least and the largest
  total of dbgen's own SF1 ORDERS (857.71 and 555,285.16), not summed
  from the lines (that would replay every draw of `lineitem`);
- `o_orderpriority` one of the five, `o_clerk` `Clerk#` and nine
  digits out of `1000 x SF` clerks, `o_shippriority` 0, `o_comment`
  19..78 characters cut from `lineitem.py`'s pool of the grammar's
  words.

Imports numpy and pyarrow only (worker processes never import JAX);
`submit`, which the run's own process calls, asks the program one
question once the parts are under way (`needs_a_program_that_joins`).
"""

from __future__ import annotations

import os
import shutil

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from benchmark.datagen import lineitem as L
from benchmark.datagen.lineitem import (GROUPS, MARKER,  # noqa: F401
                                        find, finish, rows, table_dir)

#: spawn entropy of this table's own streams, beside the seed
STREAM = 0x6F7264657273  # "orders"

CUSTOMERS_PER_SF = 150_000
CLERKS_PER_SF = 1_000
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
STATUS = ["F", "O", "P"]
#: clause 4.2.2: O_COMMENT is a text string of average length 49 (the
#: column's size is 79), between 0.4 and 1.6 times that long
COMMENT_MIN, COMMENT_MAX = 19, 78
#: cents: the least and the largest O_TOTALPRICE of dbgen's SF1 ORDERS
TOTAL_MIN, TOTAL_MAX = 85_771, 55_528_516

_MONEY = pa.decimal128(15, 2)
SCHEMA = pa.schema([
    ("o_orderkey", pa.int64()), ("o_custkey", pa.int64()),
    ("o_orderstatus", pa.string()), ("o_totalprice", _MONEY),
    ("o_orderdate", pa.date32()), ("o_orderpriority", pa.string()),
    ("o_clerk", pa.string()), ("o_shippriority", pa.int32()),
    ("o_comment", pa.string())])


def customers(sf: float) -> int:
    return max(1, int(CUSTOMERS_PER_SF * sf))


def comments(rs, n: int, shortest: int, longest: int) -> pa.Array:
    """n texts of shortest..longest characters, each a slice of
    `lineitem.py`'s pool."""
    pool = L._text_pool()
    lengths = rs.integers(shortest, longest + 1, n, dtype=np.int64)
    start = rs.integers(0, len(pool) - longest, n, dtype=np.int64)
    windows = np.lib.stride_tricks.sliding_window_view(pool, longest)
    return L._cut(windows, start, lengths)


def write_digits(out: np.ndarray, at: int, count: int,
                 numbers: np.ndarray) -> None:
    """`numbers`, zero-padded to `count` digits, into columns
    at..at+count of the byte rows `out`."""
    rest = numbers.astype(np.int64)
    for d in range(count):
        out[:, at + count - 1 - d] = 48 + rest % 10
        rest = rest // 10


def fixed_strings(out: np.ndarray) -> pa.Array:
    """The byte rows of `out`, all one width, as plain strings."""
    n, width = out.shape
    offsets = np.arange(n + 1, dtype=np.int32) * width
    return pa.Array.from_buffers(
        pa.string(), n,
        [None, pa.py_buffer(offsets), pa.py_buffer(out.reshape(-1))])


def numbered(prefix: str, numbers: np.ndarray, digits: int) -> pa.Array:
    """`<prefix><number, zero-padded to digits>` as plain strings."""
    out = np.empty((len(numbers), len(prefix) + digits), dtype=np.uint8)
    out[:, :len(prefix)] = np.frombuffer(prefix.encode(), dtype=np.uint8)
    write_digits(out, len(prefix), digits, numbers)
    return fixed_strings(out)


def own_stream(seed: int, stream: int, pieces: int, piece: int):
    return np.random.default_rng(
        np.random.SeedSequence((seed, stream)).spawn(pieces)[piece])


def replay_lineitem(sf: float, seed: int, pieces: int, piece: int):
    """(o_orderdate, o_orderstatus code) of the piece's orders, from
    the piece's `lineitem` stream: the draws of `lineitem.group_table`
    in its order, as far as the ship dates."""
    lo, hi = L.order_range(sf, pieces, piece)
    n_ord = hi - lo
    rs = np.random.default_rng(
        np.random.SeedSequence(seed).spawn(pieces)[piece])
    o_date = rs.integers(L.START, L.LAST_ORDER + 1, n_ord, dtype=np.int32)
    n_line = rs.integers(1, 8, n_ord, dtype=np.int64)
    n = int(n_line.sum())
    rs.integers(1, 51, n, dtype=np.int64)  # the quantities
    n_part = max(1, int(L.PARTS_PER_SF * sf))
    rs.integers(1, n_part + 1, n, dtype=np.int64)  # the parts
    ship = np.repeat(o_date, n_line) + rs.integers(1, 122, n, dtype=np.int32)
    open_line = (ship > L.CUTOFF).astype(np.int64)  # l_linestatus O
    starts = np.zeros(n_ord, dtype=np.int64)
    starts[1:] = np.cumsum(n_line)[:-1]
    open_lines = np.add.reduceat(open_line, starts)
    # F=0 where no line is open, O=1 where every line is, P=2 otherwise
    status = np.where(open_lines == 0, 0,
                      np.where(open_lines == n_line, 1, 2)).astype(np.int8)
    return lo, hi, o_date, status


def group_table(sf: float, seed: int, parts: int, part: int,
                group: int) -> pa.Table:
    """The orders of one row group of one part: those whose lines are
    in the same row group of `lineitem`'s same part."""
    pieces, piece = parts * GROUPS, part * GROUPS + group
    lo, hi, o_date, status = replay_lineitem(sf, seed, pieces, piece)
    n = hi - lo
    rs = own_stream(seed, STREAM, pieces, piece)
    n_cust = customers(sf)
    # the j-th customer whose key is not divisible by 3: 1, 2, 4, 5, ...
    j = rs.integers(0, n_cust - n_cust // 3, n, dtype=np.int64)
    n_clerk = max(1, int(CLERKS_PER_SF * sf))
    return pa.table({
        "o_orderkey": pa.array(np.arange(lo + 1, hi + 1, dtype=np.int64)),
        "o_custkey": pa.array(3 * (j // 2) + j % 2 + 1),
        "o_orderstatus": L._pick(status, STATUS),
        "o_totalprice": L._dec(rs.integers(TOTAL_MIN, TOTAL_MAX + 1, n,
                                           dtype=np.int64)),
        "o_orderdate": L._date(o_date),
        "o_orderpriority": L._pick(
            rs.integers(0, len(PRIORITIES), n, dtype=np.int8), PRIORITIES),
        "o_clerk": numbered("Clerk#", rs.integers(1, n_clerk + 1, n,
                                                  dtype=np.int64), 9),
        "o_shippriority": pa.array(np.zeros(n, dtype=np.int32)),
        "o_comment": comments(rs, n, COMMENT_MIN, COMMENT_MAX),
    }, schema=SCHEMA)


def part_table(sf: float, seed: int, parts: int, part: int) -> pa.Table:
    return pa.concat_tables([group_table(sf, seed, parts, part, g)
                             for g in range(GROUPS)])


def write_groups(make, schema, groups: int, directory: str,
                 part: int) -> int:
    """Write `make(group)` for each row group of part `part`; returns
    the rows. Written under a temporary name first, so a part that
    exists is whole."""
    final = os.path.join(directory, f"part-{part:04d}.parquet")
    tmp = final + ".tmp"
    n = 0
    with pq.ParquetWriter(tmp, schema) as writer:
        for g in range(groups):
            table = make(g)
            writer.write_table(table)
            n += table.num_rows
    os.replace(tmp, final)
    return n


def write_part(sf: float, seed: int, parts: int, part: int,
               directory: str) -> int:
    return write_groups(lambda g: group_table(sf, seed, parts, part, g),
                        SCHEMA, GROUPS, directory, part)


def submit_parts(write, pool, sf: float, seed: int, parts: int, root: str):
    """`lineitem.submit` for another table's `write_part`."""
    found = find(root, seed, parts)
    if found:
        return found, []
    shutil.rmtree(root, ignore_errors=True)
    d = table_dir(root, seed)
    os.makedirs(d)
    return d, [pool.submit(write, sf, seed, parts, p, d)
               for p in range(parts)]


#: the process counter every program since PR 37 registers
JOIN_COUNTER = "join_output_rows"


def needs_a_program_that_joins() -> None:
    """ORDERS is made to be joined, and a program of before PR 37
    cannot warm a join up inside the harness's limits: it compiles the
    join's stage on each of its first two submissions, 340 s each for
    Q3 at SF1 on a v5e where `harness/entries.py` gives a request
    300 s, so its run spends over ten minutes failing (PERF.md, PR 37,
    call 11: not out after 480 s). The driver lays a PR's benchmark
    files over the parent's checkout and wants a parent that cannot
    run a new cell to say so soon: such a program is told apart by the
    counter it does not register, and the run ends here with the
    reason. The run's process imports the program next in any case
    (`harness/cell.py::run_cell`)."""
    from spark_tpu.observability.metrics import is_registered_metric
    if not is_registered_metric(JOIN_COUNTER):
        raise SystemExit(
            f"benchmark: this program registers no counter "
            f"{JOIN_COUNTER!r}: it predates the join's stage that "
            f"compiles once (PR 37) and cannot warm a join over ORDERS "
            f"up within a request's time limit "
            f"(benchmark/datagen/orders.py)")


def submit(pool, sf: float, seed: int, parts: int, root: str):
    """Start the parts on `pool`: (directory, futures), no futures
    where an earlier run's data was found."""
    started = submit_parts(write_part, pool, sf, seed, parts, root)
    needs_a_program_that_joins()
    return started
