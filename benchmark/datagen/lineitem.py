"""TPC-H `lineitem` from a seed, vectorised, as Parquet parts.

A copy of the `lineitem` part of `spark_tpu/tpch/datagen.py` (same
shapes and types; dbgen-shaped, not dbgen-identical) without a Python
object per row: every decimal is built from an int64 buffer, and every
string column is a plain `pa.string()` array built from its offsets and
bytes, as dbgen's or Spark's files hold them (the Parquet writer makes
its own page dictionaries). Where the original leaves the
specification's domains, this one keeps to them (clause 4.2.3): orders
are dated up to 1998-08-02 (the original stops 121 days sooner, so
Q1's date filter never cut a row), `l_extendedprice` is the quantity
times the part's retail price of 900.00 to 2098.99 (the original draws
a tenth of that), and `l_comment` is the text string of clause 4.2.2
(10 to 43 characters, cut from a pool of the grammar's words, as dbgen
cuts it from its text pool; the original has 71 values).

The orders are cut into `parts` files of `GROUPS` row groups; each row
group draws from a stream of its own, spawned from
`SeedSequence(seed)`, and the files are made side by side by a pool of
processes. The number of parts comes from the configuration and not
from the machine: the same seed gives the same rows on any number of
cores.

This module imports numpy and pyarrow only. Worker processes import it
and must never import JAX (one process holds the chip).
"""

from __future__ import annotations

import os
import shutil
from typing import List

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

EPOCH = np.datetime64("1970-01-01", "D")
#: clause 4.2.3: O_ORDERDATE is uniform between STARTDATE and ENDDATE -
#: 151 days (1998-12-31 less 151), CURRENTDATE is 1995-06-17
START = int((np.datetime64("1992-01-01", "D") - EPOCH).astype(np.int32))
LAST_ORDER = int((np.datetime64("1998-08-02", "D") - EPOCH).astype(np.int32))
CUTOFF = int((np.datetime64("1995-06-17", "D") - EPOCH).astype(np.int32))

SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
RETURNFLAGS = ["A", "N", "R"]
LINESTATUS = ["F", "O"]

#: clause 4.2.2: a text string of average length 27 (the column's size
#: is 44) is between 0.4 and 1.6 times that long
COMMENT_MIN, COMMENT_MAX = 10, 43
#: words of the specification's text grammar (clause 4.2.2)
WORDS = ("foxes ideas theodolites pinto beans instructions dependencies "
         "excuses platelets asymptotes courts dolphins multipliers "
         "sauternes warthogs frets dinos attainments somas braids "
         "accounts packages requests deposits realms pains sleep wake are "
         "cajole haggle nag use boost affix detect integrate maintain "
         "nod was lose sublate solve thrash promise engage hinder print "
         "furious sly careful blithe quick fluffy slow quiet ruthless "
         "thin close dogged daring brave stealthy permanent enticing idle "
         "busy regular final ironic even bold silent sometimes always "
         "never furiously slyly carefully blithely quickly fluffily "
         "slowly quietly ruthlessly thinly closely doggedly daringly "
         "about above according to across after against along alongside "
         "of among around at atop before behind beneath beside besides "
         "between beyond by despite during except for from in place "
         "inside instead into near outside over past since through "
         "throughout toward under until up upon without with within the "
         "special pending unusual express . , ; : ? ! --").split()
POOL_BYTES = 1 << 20
BLOCK = 1 << 16  # rows of a string column worked at a time
#: row groups of a part: each is made and written by itself, so a
#: worker holds a quarter of a part at a time
GROUPS = 4

_MONEY = pa.decimal128(15, 2)
#: clause 1.4.1, LINEITEM: identifiers int64, decimals DECIMAL(15,2),
#: dates DATE32, fixed and variable text as strings
SCHEMA = pa.schema([
    ("l_orderkey", pa.int64()), ("l_partkey", pa.int64()),
    ("l_suppkey", pa.int64()), ("l_linenumber", pa.int32()),
    ("l_quantity", _MONEY), ("l_extendedprice", _MONEY),
    ("l_discount", _MONEY), ("l_tax", _MONEY),
    ("l_returnflag", pa.string()), ("l_linestatus", pa.string()),
    ("l_shipdate", pa.date32()), ("l_commitdate", pa.date32()),
    ("l_receiptdate", pa.date32()), ("l_shipinstruct", pa.string()),
    ("l_shipmode", pa.string()), ("l_comment", pa.string())])

ORDERS_PER_SF = 1_500_000
PARTS_PER_SF = 200_000
SUPPS_PER_SF = 10_000


def _dec(x: np.ndarray, scale: int = 2) -> pa.Array:
    """int64 unscaled units -> decimal128(15, scale), from the
    little-endian 128-bit buffer."""
    raw = np.empty((len(x), 2), dtype=np.int64)
    raw[:, 0] = x
    raw[:, 1] = x >> 63
    return pa.Array.from_buffers(pa.decimal128(15, scale), len(x),
                                 [None, pa.py_buffer(raw)])


def _date(days: np.ndarray) -> pa.Array:
    return pa.array(days.astype(np.int32, copy=False),
                    type=pa.int32()).cast(pa.date32())


def _cut(rows: np.ndarray, index: np.ndarray,
         lengths: np.ndarray) -> pa.Array:
    """Row i of the result is the first lengths[i] bytes of
    rows[index[i]]. Worked in blocks of rows, so the temporaries stay
    small beside the result."""
    offsets = np.zeros(len(index) + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    data = np.empty(int(offsets[-1]), dtype=np.uint8)
    across = np.arange(rows.shape[1])
    for lo in range(0, len(index), BLOCK):
        hi = min(lo + BLOCK, len(index))
        keep = across < lengths[lo:hi, None]
        data[offsets[lo]:offsets[hi]] = rows[index[lo:hi]][keep]
    return pa.Array.from_buffers(
        pa.string(), len(index),
        [None, pa.py_buffer(offsets.astype(np.int32)), pa.py_buffer(data)])


def _pick(codes: np.ndarray, values: List[str]) -> pa.Array:
    """values[codes] as plain strings."""
    rows = np.zeros((len(values), max(map(len, values))), dtype=np.uint8)
    for i, v in enumerate(values):
        rows[i, :len(v)] = np.frombuffer(v.encode(), dtype=np.uint8)
    lengths = np.array([len(v) for v in values], dtype=np.int64)
    return _cut(rows, codes, lengths[codes])


def _text_pool() -> np.ndarray:
    """The pool that comments are cut from: the grammar's words in an
    order of its own, the same in every run."""
    rs = np.random.default_rng(0)
    words = rs.choice(len(WORDS), POOL_BYTES // 4)
    text = " ".join(WORDS[i] for i in words).encode()[:POOL_BYTES]
    return np.frombuffer(text, dtype=np.uint8)


def _comments(rs, n: int) -> pa.Array:
    """n texts of 10..43 characters, each a slice of the pool."""
    pool = _text_pool()
    lengths = rs.integers(COMMENT_MIN, COMMENT_MAX + 1, n, dtype=np.int64)
    start = rs.integers(0, len(pool) - COMMENT_MAX, n, dtype=np.int64)
    windows = np.lib.stride_tricks.sliding_window_view(pool, COMMENT_MAX)
    return _cut(windows, start, lengths)


def order_range(sf: float, pieces: int, piece: int):
    """Orders [lo, hi) of piece `piece` of `pieces` (0-based order
    index)."""
    n_ord = max(1, int(ORDERS_PER_SF * sf))
    return (n_ord * piece) // pieces, (n_ord * (piece + 1)) // pieces


def group_table(sf: float, seed: int, parts: int, part: int,
                group: int) -> pa.Table:
    """The rows of one row group of one part, all 16 columns at the
    specification's types."""
    pieces, piece = parts * GROUPS, part * GROUPS + group
    lo, hi = order_range(sf, pieces, piece)
    n_ord = hi - lo
    rs = np.random.default_rng(
        np.random.SeedSequence(seed).spawn(pieces)[piece])
    n_part = max(1, int(PARTS_PER_SF * sf))
    n_supp = max(1, int(SUPPS_PER_SF * sf))

    o_date = rs.integers(START, LAST_ORDER + 1, n_ord, dtype=np.int32)
    n_line = rs.integers(1, 8, n_ord, dtype=np.int64)  # 1..7 an order
    l_orderkey = np.repeat(np.arange(lo + 1, hi + 1, dtype=np.int64), n_line)
    l_odate = np.repeat(o_date, n_line)
    n = len(l_orderkey)
    starts = np.zeros(n_ord, dtype=np.int64)
    starts[1:] = np.cumsum(n_line)[:-1]
    l_linenumber = (np.arange(n, dtype=np.int64)
                    - np.repeat(starts, n_line) + 1).astype(np.int32)

    qty = rs.integers(1, 51, n, dtype=np.int64)
    partkey = rs.integers(1, n_part + 1, n, dtype=np.int64)
    # clause 4.2.3: L_EXTENDEDPRICE = L_QUANTITY x the part's
    # P_RETAILPRICE, 900.00 to 2098.99, here in cents
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    ship = l_odate + rs.integers(1, 122, n, dtype=np.int32)
    commit = l_odate + rs.integers(30, 91, n, dtype=np.int32)
    receipt = ship + rs.integers(1, 31, n, dtype=np.int32)
    returned = receipt <= CUTOFF
    # A=0, N=1, R=2: returned rows split evenly between A and R
    returnflag = np.where(returned, rs.integers(0, 2, n, dtype=np.int8) * 2,
                          np.int8(1))
    linestatus = (ship > CUTOFF).astype(np.int8)  # F=0, O=1

    return pa.table({
        "l_orderkey": pa.array(l_orderkey),
        "l_partkey": pa.array(partkey),
        "l_suppkey": pa.array(rs.integers(1, n_supp + 1, n, dtype=np.int64)),
        "l_linenumber": pa.array(l_linenumber),
        "l_quantity": _dec(qty * 100),
        "l_extendedprice": _dec(qty * retail_cents),
        "l_discount": _dec(rs.integers(0, 11, n, dtype=np.int64)),
        "l_tax": _dec(rs.integers(0, 9, n, dtype=np.int64)),
        "l_returnflag": _pick(returnflag, RETURNFLAGS),
        "l_linestatus": _pick(linestatus, LINESTATUS),
        "l_shipdate": _date(ship),
        "l_commitdate": _date(commit),
        "l_receiptdate": _date(receipt),
        "l_shipinstruct": _pick(rs.integers(0, len(SHIPINSTRUCT), n,
                                            dtype=np.int8), SHIPINSTRUCT),
        "l_shipmode": _pick(rs.integers(0, len(SHIPMODES), n, dtype=np.int8),
                            SHIPMODES),
        "l_comment": _comments(rs, n),
    }, schema=SCHEMA)


def part_table(sf: float, seed: int, parts: int, part: int) -> pa.Table:
    return pa.concat_tables([group_table(sf, seed, parts, part, g)
                             for g in range(GROUPS)])


def write_part(sf: float, seed: int, parts: int, part: int,
               directory: str) -> int:
    """Make chunk `part` and write it, a row group at a time; returns
    its row count. Written under a temporary name first, so a part
    that exists is whole."""
    final = os.path.join(directory, f"part-{part:04d}.parquet")
    tmp = final + ".tmp"
    rows = 0
    with pq.ParquetWriter(tmp, SCHEMA) as writer:
        for g in range(GROUPS):
            table = group_table(sf, seed, parts, part, g)
            writer.write_table(table)
            rows += table.num_rows
    os.replace(tmp, final)
    return rows


MARKER = "_complete"


def table_dir(root: str, seed: int) -> str:
    return os.path.join(root, f"seed{seed}")


def find(root: str, seed: int, parts: int):
    """The seed's directory if an earlier run finished it, else None."""
    d = table_dir(root, seed)
    marker = os.path.join(d, MARKER)
    if not os.path.exists(marker):
        return None
    with open(marker) as f:
        if f.read().split() != [str(parts)]:
            return None
    return d


def submit(pool, sf: float, seed: int, parts: int, root: str):
    """Start the chunks on `pool`. Returns (directory, futures); with
    no futures the data was found. On a miss every other seed's data
    is removed first, so a checkout holds one seed's data at a time."""
    found = find(root, seed, parts)
    if found:
        return found, []
    shutil.rmtree(root, ignore_errors=True)
    d = table_dir(root, seed)
    os.makedirs(d)
    return d, [pool.submit(write_part, sf, seed, parts, p, d)
               for p in range(parts)]


def finish(directory: str, futures, parts: int) -> int:
    """Wait for the chunks; returns the rows written (0 if found)."""
    if not futures:
        return 0
    rows = sum(f.result() for f in futures)
    with open(os.path.join(directory, MARKER), "w") as f:
        f.write(f"{parts}\n")
    return rows


def rows(directory: str) -> int:
    """Rows of the table as its Parquet parts record them."""
    return sum(pq.read_metadata(os.path.join(directory, f)).num_rows
               for f in sorted(os.listdir(directory))
               if f.endswith(".parquet"))
