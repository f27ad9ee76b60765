"""Table sources: the host->HBM ingest edge.

Plays the role of the reference's DataSource V2 read stack
(`connector/read/ScanBuilder` -> `Scan` -> `Batch` with
`SupportsPushDownFilters` / `SupportsPushDownRequiredColumns`) and of the
vectorized Parquet reader (`VectorizedParquetRecordReader.java:54`): the
C++ Arrow/Parquet reader does columnar decode + predicate/column pushdown
on host (handing over low-cardinality string columns as the files' own
dictionary codes), then string columns are brought onto one dictionary,
columns are filled into padded, pooled buffers and device_put — ingest is
the only place bytes cross host->device (SURVEY.md section 2.4).
"""

from __future__ import annotations

import collections
import contextlib
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.dataset as pa_dataset

from .. import types as T
from ..columnar import (Batch, HostColumn, _arrow_to_column,
                        as_dictionary_pieces, bucket_capacity, device_dtype,
                        merge_dictionaries)
from ..observability.spans import span
from ..expr import (And, BinaryComparison, ColumnRef, EQ, Expression, GE, GT,
                    In, IsNull, LE, LT, Literal, NE, Not, Or)
from .host_buffers import POOL, SETS_PER_STREAM


def _decimal_literal_scalar(col_field: pa.Field, value):
    """Coerce a numeric literal to the column's decimal type for a
    pushed comparison — pyarrow cannot compare decimal to float64.
    Returns None when the value is not exactly representable at the
    column's scale (the conjunct then stays residual-only, where the
    device compares in float and is exact)."""
    import decimal as D
    if not isinstance(value, (int, float)) or isinstance(value, bool):
        return None
    d = D.Decimal(str(value))
    q = d.quantize(D.Decimal(1).scaleb(-col_field.type.scale))
    if q != d:
        return None  # rounding would change the predicate
    return pa.scalar(q, type=col_field.type)


def expr_to_arrow(e: Expression, schema: Optional[pa.Schema] = None):
    """Convert a pushable predicate to a pyarrow.dataset expression.
    Returns None when not convertible (the conjunct stays residual)."""
    if isinstance(e, ColumnRef):
        return pc.field(e._name)
    if isinstance(e, Literal):
        v = e.value
        if isinstance(e._dtype, T.DateType):
            import datetime
            # date literals carry either epoch days (SQL to_date path)
            # or a datetime.date (F.lit(date) path)
            if not isinstance(v, datetime.date):
                v = datetime.date(1970, 1, 1) + \
                    datetime.timedelta(days=int(v))
        return pa.scalar(v) if not isinstance(v, Expression) else None
    if isinstance(e, BinaryComparison):
        le, re = e.children
        l = expr_to_arrow(le, schema)
        r = expr_to_arrow(re, schema)
        if l is None or r is None:
            return None
        # decimal column vs numeric literal: coerce the literal
        if schema is not None:
            for col_e, is_left in ((le, True), (re, False)):
                lit_e = re if is_left else le
                if isinstance(col_e, ColumnRef) and isinstance(lit_e, Literal):
                    idx = schema.get_field_index(col_e._name)
                    if idx >= 0 and pa.types.is_decimal(schema.field(idx).type):
                        s = _decimal_literal_scalar(schema.field(idx),
                                                    lit_e.value)
                        if s is None:
                            return None
                        if is_left:
                            r = s
                        else:
                            l = s
        ops = {EQ: lambda a, b: a == b, NE: lambda a, b: a != b,
               LT: lambda a, b: a < b, LE: lambda a, b: a <= b,
               GT: lambda a, b: a > b, GE: lambda a, b: a >= b}
        return ops[type(e)](l, r)
    if isinstance(e, And):
        l, r = (expr_to_arrow(c, schema) for c in e.children)
        return None if l is None or r is None else l & r
    if isinstance(e, Or):
        l, r = (expr_to_arrow(c, schema) for c in e.children)
        return None if l is None or r is None else l | r
    if isinstance(e, Not):
        c = expr_to_arrow(e.children[0], schema)
        return None if c is None else ~c
    if isinstance(e, In):
        c = expr_to_arrow(e.children[0], schema)
        return None if c is None else c.isin(list(e.values))
    if isinstance(e, IsNull):
        c = expr_to_arrow(e.children[0], schema)
        return None if c is None else c.is_null()
    return None


class TableSource:
    name: str = "<source>"

    def schema(self) -> T.Schema:
        raise NotImplementedError

    def can_push(self, e: Expression) -> bool:
        return False

    def load(self, required_columns: Optional[Sequence[str]],
             pushed_filters: Sequence[Expression],
             placement=None) -> Batch:
        """The scan's rows as one device Batch; under a `placement`
        (`columnar.ShardedPlacement`) dealt over a mesh's shards."""
        raise NotImplementedError

    def estimated_rows(self) -> Optional[int]:
        return None

    def column_stats(self) -> Optional[dict]:
        """Per-column statistics, `{name: {"min", "max", "null_count",
        "row_groups"}}`, or None when unavailable. Parquet sources read
        these from footers (no row data touched); consumers are the
        reorder cost model's range selectivities
        (plan/join_reorder.py) and the analyzer's SUM_I64_OVERFLOW
        magnitude bounds (analysis/plan_analyzer.py). Advisory only:
        min/max are BOUNDS over the whole dataset, never per-row
        truth, so consumers may only use them to widen/narrow
        estimates — never for correctness."""
        return None

    def cache_token(self):
        """Identity stamp for the device-table cache; None = uncacheable.
        Must change whenever the underlying data can differ."""
        return None


def _arrow_schema_to_engine(schema: pa.Schema) -> T.Schema:
    fields = []
    for f in schema:
        if pa.types.is_list(f.type) or pa.types.is_large_list(f.type):
            elem = _arrow_schema_to_engine(
                pa.schema([pa.field("e", f.type.value_type)])).fields[0]
            dt: T.DataType = T.ArrayType(elem.dtype)
        else:
            dt = device_dtype(f.name, f.type)
        fields.append(T.Field(f.name, dt, f.nullable))
    return T.Schema(fields)


#: rows from which a chunk's columns are done side by side: below it
#: a column is a few megabytes and starting threads costs more than
#: they save
_SIDE_BY_SIDE_ROWS = 1 << 20


def _side_by_side(fn, items: list, rows: int) -> list:
    """`fn` over the columns of a chunk of `rows` rows: a large
    chunk's on threads of their own, which live for this call (pyarrow
    and numpy release the GIL over a column). Every result is read, so
    what a column raised is raised here."""
    workers = min(len(items), os.cpu_count() or 1)
    if workers < 2 or rows < _SIDE_BY_SIDE_ROWS:
        return [fn(item) for item in items]
    with ThreadPoolExecutor(
            max_workers=workers,
            thread_name_prefix="spark-tpu-ingest-column") as pool:
        return list(pool.map(fn, items))


#: a string column of a chunk as `DictUnifier` hands it to the fill:
#: its `pieces`, dictionary-typed; `maps`, a piece's int32 table from
#: its codes to the column's global ones (None: they are the global
#: ones); `kind`, how the column arrived (`read` dictionary-typed,
#: `encoded` from plain strings); the `seconds` it took
CodedColumn = collections.namedtuple("CodedColumn",
                                     "pieces maps kind seconds")


class DictUnifier:
    """Grows one global dictionary per string column across chunks so
    device codes are comparable between chunks (append-only: codes handed
    out earlier stay valid). The analog of the reference's per-column
    dictionary pages being resolved to one dictionary at read time.

    What it does is work on dictionaries, never on rows: a chunk's
    pieces of a column (the scanner's record batches, as they were
    cut) keep their own codes, and the unifier answers with one int32
    map a piece from those codes to the global ones, which the fill
    (`columnar.fill_padded`) applies on its one pass over the rows. A
    column that arrives dictionary-typed (a Parquet column read with
    its page dictionaries, `_dictionary_columns`; a dictionary column
    of an in-memory table) has its pieces' dictionaries merged, a map
    made once while consecutive pieces share a dictionary, and the
    merged one mapped into the global one. A column that arrives as
    plain strings (in-memory tables, CSV, JSON, a Parquet column whose
    footers did not qualify it) is hashed row by row first, into one
    dictionary for the chunk (`dictionary_encode`). It runs under the
    thread that runs `ChunkIterator._host_next` (the prefetch worker,
    or the consumer with prefetch off), inside its `chunk.unify` span,
    the string columns of a large chunk side by side."""

    def __init__(self):
        self.dicts = {}

    def _to_global(self, name: str, chunk_dict: pa.Array,
                   used) -> Optional[np.ndarray]:
        """Append to the column's global dictionary the values of the
        chunk's dictionary that it lacks and that a row of the chunk
        carries. A page dictionary also holds the values of rows that
        a pushed filter or the chunk's cut left out; those get no
        code, as they got none when rows were hashed, so a dictionary
        (and the direct aggregate's domain, sized from it) holds what
        the scan returned and nothing else. `used(new)` gives the mask
        of the values a row carries, at least among the `new` ones
        (the one pass over rows, codes only, made only where a value
        is new and only until each new one was seen), None where every
        value is carried (the chunk was encoded from its rows). Returns the
        int32 map from the chunk's codes to the global ones, or None
        where the chunk's codes already are the global ones."""
        glob = self.dicts.setdefault(name, chunk_dict.slice(0, 0))
        present = pc.index_in(chunk_dict, value_set=glob)
        if present.null_count:
            new = pc.is_null(present)
            if used is not None:
                new = pc.and_(new, pa.array(used(new.to_numpy(
                    zero_copy_only=False))))
            if pc.any(new).as_py():
                glob = pa.concat_arrays([glob, chunk_dict.filter(new)])
                self.dicts[name] = glob
                present = pc.index_in(chunk_dict, value_set=glob)
        # a value no row carries has no code, and no row asks for one
        mapping = present.fill_null(0).to_numpy().astype(
            np.int32, copy=False)
        if len(mapping) <= len(glob) and np.array_equal(
                mapping, np.arange(len(mapping))):
            return None
        return mapping

    def unify_column(self, name: str,
                     col: pa.ChunkedArray) -> CodedColumn:
        """A string column of a chunk, its values in the column's
        global dictionary and its rows untouched."""
        t0 = time.perf_counter()
        kind = "read" if pa.types.is_dictionary(col.type) else "encoded"
        if pa.types.is_large_string(col.type):
            col = col.cast(pa.string())
        pieces = as_dictionary_pieces(col)
        merged, local = merge_dictionaries([p.dictionary for p in pieces])

        def used(new: np.ndarray) -> np.ndarray:
            # piece by piece, until every new value was seen in a row:
            # as a rule in the chunk's first record batch
            mask = np.zeros(len(merged), dtype=bool)
            for piece, to_merged in zip(pieces, local):
                codes = pc.unique(piece.indices).drop_null().to_numpy()
                mask[codes if to_merged is None else to_merged[codes]] = True
                if mask[new].all():
                    break
            return mask

        to_global = self._to_global(
            name, merged.cast(pa.string()),
            used if kind == "read" else None)
        maps, made = [], {}
        for to_merged in local:
            if to_global is None or to_merged is None:
                maps.append(to_merged if to_global is None else to_global)
                continue
            if id(to_merged) not in made:
                made[id(to_merged)] = to_global[to_merged]
            maps.append(made[id(to_merged)])
        return CodedColumn(pieces, maps, kind, time.perf_counter() - t0)


class HostChunk:
    """A chunk's host half, as `ChunkIterator._host_next` hands it to
    `_to_device`: `columns` by name, a `columnar.HostColumn` filled and
    padded (a list column: its Arrow pieces, converted at the put),
    and the pooled `buffers` they stand in."""

    __slots__ = ("rows", "columns", "buffers")

    def __init__(self, rows: int):
        self.rows = rows
        self.columns = {}
        self.buffers = []


class ChunkIterator:
    """Single-pass iterator of uniform-capacity Batches over a record
    -batch stream; `.dictionaries` holds the final global dictionaries.

    Who does what: `_host_next` makes a chunk's host half on whichever
    thread runs it (the prefetch worker, or the consumer with prefetch
    off): it cuts the chunk as slices of the reader's record batches
    (`chunk.decode`), has `DictUnifier` map the string columns' codes
    (`chunk.unify`) and fills every column's padded buffer straight
    from those slices (`chunk.convert`, one a column, a large chunk's
    columns side by side): a row is copied once, and into a buffer of
    the process's `HostBufferPool` that an earlier chunk or request
    has touched. `_to_device`, always on the consumer, only puts
    (`chunk.to_device` with one `chunk.put` a column) and notes each
    buffer with the array made from it; the set goes back to the pool
    when the stream's fourth chunk wants one (a stream has at most
    `SETS_PER_STREAM` out), and the pool hands a buffer out again
    once its array is ready. `close` (the drivers call it on every
    exit) gives back every set, put or only filled.

    Once `observe` has bound it to a query, the spans above are
    recorded and every chunk counts into `ingest_chunks` /
    `ingest_rows` / `ingest_put_bytes`, by where its buffers came
    from `ingest_buffers_reused` / `ingest_buffers_allocated` and, by
    how its string columns arrived, `ingest_dict_columns_read` /
    `ingest_dict_columns_encoded` (`DictUnifier`). `chunk.unify`
    carries `dict_ms`, a `chunk.convert` the `bytes` it filled."""

    def __init__(self, batches_iter, chunk_rows: int, pool=None):
        self._batches = batches_iter
        self._chunk_rows = chunk_rows
        self._capacity = None
        self._pending = []
        self._pending_rows = 0
        self._done = False
        self._failed: Optional[BaseException] = None
        self._unifier = DictUnifier()
        self._metrics = None
        self._recorder = None
        self._cause = None   # the consumer's open span, for the worker
        self._chunk = 0      # ordinal of the next chunk taken
        self._pool = pool if pool is not None else POOL
        #: chunks filled and not yet put: the worker's and the queue's
        self._filled = []
        #: sets that were put, oldest first, as (buffer, array made
        #: from it): kept until this stream wants a set again, so that
        #: its first SETS_PER_STREAM chunks draw as many sets whatever
        #: the two threads' timing, and a later stream finds them all
        self._put = collections.deque()
        self._fills = 0      # chunks filled so far
        #: {pool key: buffers a chunk} of the widest chunk so far
        self._shape = collections.Counter()
        self._closed = False

    def observe(self, metrics, recorder, cause=None) -> None:
        """Bind the stream to its query's counters and spans (called
        on the consumer thread, by `maybe_prefetch`); `cause` is the
        span the worker's spans name as their parent, by default the
        one open on the calling thread."""
        self._metrics = metrics
        self._recorder = recorder
        if cause is None and recorder is not None:
            cause = recorder.current()
        self._cause = cause

    def _host_span(self, name: str, **attrs):
        """A span of the host half (`_host_next`), which the prefetch
        worker may run, a column's on a thread of its own: a thread
        the query starts does not see the query's context, so these go
        through the bound recorder, with the consumer's open span as
        their cause. The consumer's own spans (`chunk.wait`,
        `chunk.to_device`) open through `span`."""
        if self._recorder is None:
            return contextlib.nullcontext()
        return self._recorder.span(name, parent=self._cause, **attrs)

    @property
    def dictionaries(self):
        return self._unifier.dicts

    def __iter__(self):
        return self

    def _fill(self) -> None:
        if self._failed is not None:
            # the underlying reader raised mid-stream: a generator dies
            # when an exception propagates through it, so continuing
            # would silently truncate the stream to the buffered prefix
            # (reading as end-of-stream). Poison the iterator instead —
            # a per-chunk ingest retry re-raises the original error and
            # the whole-query ladder restarts the stream fresh.
            raise self._failed
        while not self._done and self._pending_rows < self._chunk_rows:
            try:
                rb = next(self._batches)
            except StopIteration:
                self._done = True
                break
            except Exception as e:
                self._failed = e
                raise
            self._pending.append(rb)
            self._pending_rows += rb.num_rows

    def _take_chunk(self) -> Optional[pa.Table]:
        """One chunk's Arrow slice off the stream (the shared cursor
        advance of __next__ and skip_chunks, so both cut identical
        chunk boundaries), or None at end of stream: slices of the
        pending record batches, no row copied. The `chunk.decode`
        span: a Parquet scanner reads ahead on threads of its own, so
        this is the wait for its batches and the slicing around it,
        not the decode's CPU time."""
        with self._host_span("chunk.decode", chunk=self._chunk) as sp:
            self._fill()
            if self._pending_rows == 0:
                if sp is not None:
                    self._recorder.discard(sp)  # end of stream
                return None
            table = pa.Table.from_batches(self._pending)
            take = min(self._pending_rows, self._chunk_rows)
            chunk = table.slice(0, take)
            if sp is not None:  # what the chunk was cut from, and is
                sp.attrs.update(rows=take, batches=len(self._pending),
                                bytes=chunk.nbytes)
            rest = table.slice(take)
            self._pending = rest.to_batches() if rest.num_rows else []
            self._pending_rows = rest.num_rows
            self._chunk += 1
            return chunk

    def skip_chunks(self, n: int) -> int:
        """Advance the cursor past the next `n` chunks without
        dictionary-unifying, filling a buffer or moving bytes to the
        device — the checkpoint-restore path resumes a stream at a
        chunk cursor. Returns how many chunks were actually skipped
        (fewer when the stream ends first)."""
        skipped = 0
        while skipped < int(n):
            if self._take_chunk() is None:
                break
            skipped += 1
        return skipped

    def _draw(self, buffers: list, np_dtype) -> np.ndarray:
        """A pooled buffer of the chunk's capacity, noted in the
        chunk's `buffers`."""
        buf, reused = self._pool.take(np_dtype, self._capacity)
        buffers.append(buf)
        if self._metrics is not None:
            self._metrics.counter(
                "ingest_buffers_reused" if reused
                else "ingest_buffers_allocated").inc()
        return buf

    def _fill_column(self, buffers: list, name: str, pieces: list,
                     maps: Optional[list]) -> HostColumn:
        """One column of the chunk in a pooled buffer: every piece
        written where it belongs, the rest zeroed (a buffer that was
        used holds an older chunk's rows there)."""
        with self._host_span("chunk.convert", column=name) as sp:
            dt = device_dtype(name, pieces[0].type)
            col = HostColumn(
                name, dt, self._draw(buffers, dt.np_dtype),
                lambda: self._draw(buffers, np.bool_),
                self._unifier.dicts.get(name) if maps is not None else None)
            for piece, code_map in zip(pieces, maps or [None] * len(pieces)):
                col.append(piece, code_map)
            col.data[col.rows:] = 0
            if col.validity is not None:
                col.validity[col.rows:] = False
            if sp is not None:
                sp.attrs["bytes"] = col.nbytes
        return col

    def _host_next(self) -> Optional[HostChunk]:
        """One chunk's host half, every column filled and padded, or
        None at end of stream. All the per-chunk host work lives here;
        device placement stays in __next__ — the split the prefetcher
        (PrefetchChunkIterator) overlaps with device compute."""
        chunk = self._take_chunk()
        if chunk is None:
            return None
        if self._capacity is None:
            self._capacity = bucket_capacity(self._chunk_rows)
        rows = chunk.num_rows
        strings = [(n, c) for n, c in zip(chunk.column_names, chunk.columns)
                   if pa.types.is_string(c.type)
                   or pa.types.is_large_string(c.type)
                   or pa.types.is_dictionary(c.type)]
        with self._host_span("chunk.unify", chunk=self._chunk - 1,
                             rows=rows) as sp:
            coded = dict(zip((n for n, _ in strings), _side_by_side(
                lambda item: self._unifier.unify_column(*item),
                strings, rows)))
            if sp is not None:  # thread time, summed over the columns
                sp.attrs["dict_ms"] = round(
                    sum(c.seconds for c in coded.values()) * 1e3, 3)
        if self._metrics is not None:
            for how in ("read", "encoded"):
                self._metrics.counter("ingest_dict_columns_" + how).inc(
                    sum(1 for c in coded.values() if c.kind == how))
        host = HostChunk(rows)
        if self._fills >= SETS_PER_STREAM and self._put:
            # the set of the chunk three before: long put, and the
            # pool waits for its transfers if they are not over
            for buf, arr in self._put.popleft():
                self._pool.give(buf, arr)
        self._fills += 1

        def fill(item):
            name, col = item
            if pa.types.is_list(col.type) or pa.types.is_large_list(col.type):
                return col  # offsets are not fixed-width: at the put
            if name in coded:
                return self._fill_column(host.buffers, name,
                                         coded[name].pieces,
                                         coded[name].maps)
            return self._fill_column(host.buffers, name, col.chunks, None)

        try:
            host.columns = dict(zip(chunk.column_names, _side_by_side(
                fill, list(zip(chunk.column_names, chunk.columns)), rows)))
        except BaseException as e:
            for buf in host.buffers:
                self._pool.give(buf)
            if isinstance(e, Exception):
                # the chunk's rows are off the stream: a replay would
                # skip them, so it raises this again instead
                self._failed = e
            raise
        self._shape |= collections.Counter(  # the most of each key
            self._pool.key_of(buf) for buf in host.buffers)
        self._filled.append(host)
        return host

    def _to_device(self, host: HostChunk) -> Batch:
        self._filled.remove(host)
        cols = {}
        try:
            with span("chunk.to_device", rows=host.rows):
                for name, col in host.columns.items():
                    cols[name] = col.put() if isinstance(col, HostColumn) \
                        else _arrow_to_column(name, col, host.rows,
                                              self._capacity)
                batch = Batch(cols, jnp.arange(self._capacity) < host.rows)
        finally:
            # a buffer goes back with the array that was made from it:
            # the pool hands it out again once that one is ready
            made = {}
            for name, col in host.columns.items():
                if isinstance(col, HostColumn) and name in cols:
                    made[id(col.data)] = cols[name].data
                    if col.validity is not None:
                        made[id(col.validity)] = cols[name].validity
            self._put.append([(buf, made.get(id(buf)))
                              for buf in host.buffers])
        if self._metrics is not None:
            self._metrics.counter("ingest_chunks").inc()
            self._metrics.counter("ingest_rows").inc(host.rows)
            # what was put: the padded buffers (the selection mask is
            # made on the device)
            self._metrics.counter("ingest_put_bytes").inc(sum(
                c.data.nbytes + (c.validity.nbytes
                                 if c.validity is not None else 0)
                for c in batch.columns.values()))
        return batch

    def close(self) -> None:
        """Give back the buffers of chunks that were filled and never
        put, and leave the pool with what a stream of this shape
        needs. On every exit: the end of the stream, a driver's
        `finally`, `PrefetchChunkIterator.close` once its worker is
        joined."""
        if self._closed:
            return
        self._closed = True
        for host in self._filled:
            for buf in host.buffers:
                self._pool.give(buf)
        self._filled = []
        while self._put:
            for buf, arr in self._put.popleft():
                self._pool.give(buf, arr)
        if self._shape:
            self._pool.trim(self._shape)

    def __next__(self) -> Batch:
        host = None if self._closed else self._host_next()
        if host is None:
            self.close()
            raise StopIteration
        return self._to_device(host)


import itertools

_SOURCE_TOKENS = itertools.count()


class ArrowTableSource(TableSource):
    """In-memory table (the reference's LocalRelation / InMemoryRelation)."""

    def __init__(self, name: str, table: pa.Table):
        self.name = name
        self.table = table
        # fresh per-source stamp: re-registering a name builds a new
        # source object, so a stale device-cache hit is impossible
        self._cache_token = ("arrow", next(_SOURCE_TOKENS))

    def cache_token(self):
        return self._cache_token

    def schema(self) -> T.Schema:
        return _arrow_schema_to_engine(self.table.schema)

    def can_push(self, e: Expression) -> bool:
        return expr_to_arrow(e, self.table.schema) is not None

    def estimated_rows(self):
        return self.table.num_rows

    #: row bound above which in-memory stats are skipped: unlike a
    #: Parquet footer read, computing them means min/max SCANS over
    #: the whole table, and the optimize path must stay cheap
    _STATS_MAX_ROWS = 1 << 22

    def column_stats(self) -> Optional[dict]:
        """In-memory analog of the Parquet footer read: one vectorized
        min/max pass per numeric/temporal column, cached per source
        (re-registering a table builds a fresh source). Tables past
        _STATS_MAX_ROWS report no stats rather than paying full-column
        scans during optimization."""
        cached = getattr(self, "_column_stats", None)
        if cached is not None:
            return cached
        if self.table.num_rows > self._STATS_MAX_ROWS:
            self._column_stats = {}
            return self._column_stats
        stats: dict = {}
        for name, col in zip(self.table.column_names, self.table.columns):
            at = col.type
            if not (pa.types.is_integer(at) or pa.types.is_floating(at)
                    or pa.types.is_decimal(at) or at == pa.date32()):
                continue
            try:
                mm = pc.min_max(col)
                lo, hi = mm["min"].as_py(), mm["max"].as_py()
            except Exception:  # noqa: BLE001 — stats are advisory
                continue
            if lo is None or hi is None:
                continue
            stats[name] = {"min": lo, "max": hi,
                           "null_count": col.null_count, "row_groups": 1}
        self._column_stats = stats
        return stats

    def load(self, required_columns, pushed_filters,
             placement=None) -> Batch:
        from ..testing import faults
        faults.fire("scan_load")  # chaos seam: host->HBM ingest edge
        t = self.table
        for f in pushed_filters:
            ae = expr_to_arrow(f, self.table.schema)
            if ae is not None:
                t = t.filter(ae)
        if required_columns is not None:
            t = t.select(list(required_columns))
        return Batch.from_arrow(t, placement=placement)

    def load_chunks(self, required_columns, pushed_filters,
                    chunk_rows: int) -> ChunkIterator:
        t = self.table
        for f in pushed_filters:
            ae = expr_to_arrow(f, self.table.schema)
            if ae is not None:
                t = t.filter(ae)
        if required_columns is not None:
            t = t.select(list(required_columns))
        return ChunkIterator(iter(t.to_batches()), chunk_rows)


class CsvSource(ArrowTableSource):
    """CSV via the C++ Arrow reader (reference: csv/CSVFileFormat +
    UnivocityParser; here native decode + dictionary-encoding happen
    before any bytes reach the device). Eagerly read: CSV has no
    row-group skipping, so pushdown happens post-parse in Arrow."""

    def __init__(self, path: str, name: Optional[str] = None, **options):
        import pyarrow.csv as pa_csv
        parse = pa_csv.ParseOptions(
            delimiter=options.get("sep", options.get("delimiter", ",")))
        read = pa_csv.ReadOptions(
            autogenerate_column_names=not options.get("header", True))
        table = pa_csv.read_csv(path, parse_options=parse,
                                read_options=read)
        super().__init__(name or os.path.basename(path).split(".")[0],
                         table)


class JsonSource(ArrowTableSource):
    """Line-delimited JSON via the C++ Arrow reader (reference:
    json/JsonFileFormat + JacksonParser)."""

    def __init__(self, path: str, name: Optional[str] = None):
        import pyarrow.json as pa_json
        table = pa_json.read_json(path)
        super().__init__(name or os.path.basename(path).split(".")[0],
                         table)


#: the largest dictionary page (bytes between a column chunk's
#: dictionary page and its first data page, as stored) of a column
#: that is read dictionary-typed. A writer gives up on a chunk's
#: dictionary once it passes its page limit (1 MiB by default, for
#: Arrow, parquet-mr and Spark alike) and writes the rest of the chunk
#: plain; such a chunk keeps a dictionary page of about that limit,
#: several times this bound even when compressed.
_DICT_PAGE_MAX_BYTES = 64 << 10


def _dictionary_columns(dataset) -> list:
    """The string columns of a Parquet dataset to read dictionary-typed
    (`ParquetReadOptions.dictionary_columns`), decided from the footers
    alone: every row group holds the column with a dictionary page of
    at most `_DICT_PAGE_MAX_BYTES`, and the dictionary pages together
    are under a byte a row (an entry takes its length's 4 bytes and
    its characters, so there are several rows to an entry). Such a
    column reaches `DictUnifier` as the file's own codes and a handful
    of values. Any other (a column whose writer fell back to plain
    pages, as it does for comments and names; one written without
    dictionaries; one of mostly distinct values) would have the reader
    hash its rows into a dictionary per batch and the unifier hash
    those again, so it is read plain and encoded once, as before.
    The choice moves time only: either way the chunks decode to the
    same strings."""
    names = {f.name for f in dataset.schema
             if pa.types.is_string(f.type)
             or pa.types.is_large_string(f.type)}
    if not names:
        return []
    pages: dict = {}   # name -> [dictionary page bytes, values]
    try:
        for frag in dataset.get_fragments():
            md = frag.metadata
            wanted = [i for i in range(md.num_columns)
                      if md.schema.column(i).path in names]
            for rg in range(md.num_row_groups):
                rgm = md.row_group(rg)
                for i in wanted:
                    col = rgm.column(i)
                    if not col.num_values:
                        continue
                    page = (col.data_page_offset
                            - col.dictionary_page_offset
                            if col.has_dictionary_page else 0)
                    if not 0 < page <= _DICT_PAGE_MAX_BYTES:
                        names.discard(col.path_in_schema)
                        continue
                    seen = pages.setdefault(col.path_in_schema, [0, 0])
                    seen[0] += page
                    seen[1] += col.num_values
    except (OSError, pa.ArrowException):
        return []   # an unreadable footer fails the scan, where it did
    return [f.name for f in dataset.schema
            if f.name in names and f.name in pages
            and pages[f.name][0] <= pages[f.name][1]]


class ParquetSource(TableSource):
    """Parquet directory/file via the C++ Arrow dataset reader: column
    pruning + row-group predicate skipping happen in native code before
    any bytes reach the device.

    One dataset object serves `schema`, `can_push`, `column_stats`,
    `estimated_rows`, `load` (resident) and `load_chunks` (streamed).
    It is opened so that the string columns `_dictionary_columns`
    names arrive as `dictionary<int32, string>`, the page
    dictionaries' own codes, on the scanner's threads; what is left
    for the thread that takes the batches is work on dictionaries
    (`DictUnifier`, `columnar.merge_dictionaries`) and the one copy
    of the codes (`columnar.fill_padded`). Pushed filters
    compare such a column with string literals as they do a plain
    one. `file_schema` stays the files' own, for a writer that
    appends to them."""

    def __init__(self, path: str, name: Optional[str] = None):
        self.path = path
        self.name = name or os.path.basename(path).split(".")[0]
        self._dataset = pa_dataset.dataset(path, format="parquet")
        self.file_schema: pa.Schema = self._dataset.schema
        dict_columns = _dictionary_columns(self._dataset)
        if dict_columns:
            self._dataset = pa_dataset.dataset(
                path, format=pa_dataset.ParquetFileFormat(
                    read_options=pa_dataset.ParquetReadOptions(
                        dictionary_columns=dict_columns)))
        self._column_stats: Optional[dict] = None
        self._schema: Optional[T.Schema] = None

    def column_stats(self) -> Optional[dict]:
        """Per-column min/max + null/row-group counts merged across
        every fragment's footer row-group statistics (the C++ reader
        exposes them without touching row data). Cached per source —
        the source object is rebuilt on re-registration, so staleness
        follows the same lifecycle as cache_token. A column missing
        min/max in ANY row group is omitted entirely (a partial bound
        is not a bound)."""
        if self._column_stats is not None:
            return self._column_stats
        stats: dict = {}
        dropped = set()
        n_groups = 0
        try:
            for frag in self._dataset.get_fragments():
                md = frag.metadata
                for rg in range(md.num_row_groups):
                    n_groups += 1
                    rgm = md.row_group(rg)
                    for ci in range(rgm.num_columns):
                        col = rgm.column(ci)
                        name = col.path_in_schema
                        st = col.statistics
                        if name in dropped:
                            continue
                        if st is None or not st.has_min_max:
                            dropped.add(name)
                            stats.pop(name, None)
                            continue
                        cur = stats.get(name)
                        nulls = st.null_count if st.has_null_count \
                            else None
                        if cur is None:
                            stats[name] = {"min": st.min, "max": st.max,
                                           "null_count": nulls,
                                           "row_groups": 1}
                        else:
                            cur["min"] = min(cur["min"], st.min)
                            cur["max"] = max(cur["max"], st.max)
                            if nulls is None:
                                cur["null_count"] = None
                            elif cur["null_count"] is not None:
                                cur["null_count"] += nulls
                            cur["row_groups"] += 1
        except Exception:  # noqa: BLE001 — stats are advisory
            self._column_stats = {}
            return self._column_stats
        # a column absent from some row group has no dataset-wide bound
        for name in list(stats):
            if stats[name]["row_groups"] != n_groups:
                del stats[name]
        self._column_stats = stats
        return self._column_stats

    def cache_token(self):
        """(path, per-file (size, mtime_ns)) stamp: rewriting any file in
        the dataset invalidates cached device tables for it."""
        stamps = []
        try:
            for f in self._dataset.files:
                st = os.stat(f)
                stamps.append((f, st.st_size, st.st_mtime_ns))
        except OSError:
            return None
        return ("parquet", self.path, tuple(stamps))

    def schema(self) -> T.Schema:
        # the dataset's schema is fixed at construction and the engine's
        # is immutable: made once (a plan's every node asks for it)
        if self._schema is None:
            self._schema = _arrow_schema_to_engine(self._dataset.schema)
        return self._schema

    def can_push(self, e: Expression) -> bool:
        return expr_to_arrow(e, self._dataset.schema) is not None

    def estimated_rows(self):
        try:
            return sum(f.metadata.num_rows for f in self._dataset.get_fragments())
        except Exception:
            return None

    def load(self, required_columns, pushed_filters,
             placement=None) -> Batch:
        from ..testing import faults
        faults.fire("scan_load")  # chaos seam: host->HBM ingest edge
        ae = None
        for f in pushed_filters:
            e = expr_to_arrow(f, self._dataset.schema)
            if e is not None:
                ae = e if ae is None else (ae & e)
        t = self._dataset.to_table(
            columns=list(required_columns) if required_columns is not None else None,
            filter=ae)
        return Batch.from_arrow(t, placement=placement)

    def load_chunks(self, required_columns, pushed_filters,
                    chunk_rows: int) -> ChunkIterator:
        ae = None
        for f in pushed_filters:
            e = expr_to_arrow(f, self._dataset.schema)
            if e is not None:
                ae = e if ae is None else (ae & e)
        scanner = self._dataset.scanner(
            columns=list(required_columns) if required_columns is not None else None,
            filter=ae, batch_size=min(chunk_rows, 1 << 20))
        return ChunkIterator(scanner.to_batches(), chunk_rows)


# ---------------------------------------------------------------------------
# Double-buffered ingest (SURVEY 2.5 "Async/overlap": the shuffle-fetch/
# compute pipelining seat, host->HBM edition)
# ---------------------------------------------------------------------------

INGEST_PREFETCH_KEY = "spark_tpu.sql.ingest.prefetch"


class PrefetchChunkIterator:
    """Double-buffered wrapper over a ChunkIterator: a background thread
    makes chunk N+1 ready in HOST buffers (``ChunkIterator._host_next``
    — pyarrow and numpy release the GIL, so this genuinely overlaps
    the consumer's work) while the consumer places and launches
    chunk N. Who does what: the Arrow scanner's own threads read and
    decode Parquet pages (string columns that qualify come as the
    file's dictionary codes: ``ParquetSource``); the WORKER takes the
    chunk's record batches off the scanner (``chunk.decode``: a wait),
    has the string columns' codes mapped to their global dictionaries
    (``chunk.unify``: work on dictionaries, ``DictUnifier``) and fills
    every column's padded numpy buffer straight from the batches
    (``chunk.convert``, a large chunk's columns on threads of their
    own), in pooled memory that was touched before
    (``io/host_buffers.py``); the CONSUMER only places the buffers
    (``chunk.to_device``: one ``chunk.put`` a column) and launches the
    chunk program. Bounded to ONE in-flight chunk (a size-1 queue),
    and device placement stays on the CONSUMER thread, so HBM
    residency, arbiter leases and the per-chunk retry/checkpoint
    semantics of the streaming drivers are unchanged.

    Fault behavior: the worker runs each host decode under `retry`,
    which the chunk driver hands over (``execution/chunk_stream.py``:
    the SAME per-chunk retry path its compute steps use, with the
    ``ingest_prefetch`` chaos seam) — a transient fault fired at the
    seam replays exactly one chunk's decode (`rec_chunks_replayed`
    counts it); a real reader failure poisons the inner iterator as
    before and surfaces on the consumer thread for the whole-query
    ladder. Without `retry` a decode runs once, plainly.

    Observability: the consumer's wait for a chunk (the pipeline
    failing to hide the host's work) is the ``chunk.wait`` span and,
    summed, the ``ingest_stall_ms`` counter of the process registry;
    the worker's own time stands in the inner iterator's
    ``chunk.decode`` / ``chunk.unify`` / ``chunk.convert`` spans, on
    the worker's and its column threads' ``tid`` (the benchmark's
    ``ingest_*_ms_p50`` metrics read them)."""

    def __init__(self, inner: ChunkIterator, conf, retry=None,
                 metrics=None):
        # `conf` is read no longer (the retry policy that read it
        # arrives as `retry`); callers still pass it in second place
        self._inner = inner
        self._retry = retry or (lambda step, chunk: step())
        self._metrics = metrics
        self._started = False
        self._closed = False
        self._chunk = 0  # next chunk ordinal the worker will decode
        import queue as _queue
        import threading
        import weakref
        self._queue: "_queue.Queue" = _queue.Queue(maxsize=1)
        # the worker is handed this event (never `self`): when the
        # consumer abandons the iterator without close() — a fault
        # unwinding a chunk driver mid-stream — the iterator becomes
        # unreachable (the thread holds no ref to it), this finalizer
        # fires, and the worker exits instead of spinning forever on
        # its full queue holding a decoded chunk
        self._stop = threading.Event()
        self._finalizer = weakref.finalize(self, self._stop.set)
        #: the worker thread, kept so close() can JOIN it (bounded):
        #: a daemon thread must not outlive its query — the lockwatch
        #: stress test asserts none does
        self._thread: "threading.Thread | None" = None

    # -- ChunkIterator surface ---------------------------------------------

    @property
    def dictionaries(self):
        return self._inner.dictionaries

    def skip_chunks(self, n: int) -> int:
        """Checkpoint-restore cursor advance; only valid before the
        worker starts (the drivers skip right after load_chunks)."""
        if self._started:
            raise RuntimeError("skip_chunks after prefetch started")
        skipped = self._inner.skip_chunks(n)
        self._chunk += skipped
        return skipped

    def __iter__(self):
        return self

    # -- pipeline -----------------------------------------------------------

    @staticmethod
    def _worker(host_next, retry, q, stop, chunk) -> None:
        # deliberately a staticmethod over plain arguments: holding a
        # ref to the iterator would keep it reachable forever and its
        # abandonment finalizer (see __init__) could never fire
        import queue as _queue
        while not stop.is_set():
            try:
                item = ("ok", retry(host_next, chunk=chunk))
            except BaseException as e:  # noqa: BLE001 — relayed verbatim
                item = ("err", e)
            # bounded put that notices close()/abandonment: the worker
            # must not strand blocked on a full size-1 queue forever
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    break
                except _queue.Full:
                    continue
            if item[0] == "err" or item[1] is None:
                return
            chunk += 1

    def __next__(self) -> Batch:
        import threading
        import time as _time
        if self._closed:
            raise StopIteration
        if not self._started:
            self._started = True
            # the worker's thread made and running: once a stream, and
            # not a wait for a chunk (25 ms where a thread's stack and
            # arena are dear: my chip runs, PR 39, PERF.md)
            with span("prefetch.start"):
                self._thread = threading.Thread(
                    target=self._worker, daemon=True,
                    name="spark-tpu-ingest-prefetch",
                    args=(self._inner._host_next, self._retry,
                          self._queue, self._stop, self._chunk))
                self._thread.start()
        # one interval, read twice: the `chunk.wait` span and the
        # `ingest_stall_ms` counter (every wait counts, the last one
        # for the end of the stream too)
        with span("chunk.wait"):
            t0 = _time.perf_counter()
            kind, payload = self._queue.get()
            stall_s = _time.perf_counter() - t0
        if self._metrics is not None:
            self._metrics.counter("ingest_stall_ms").inc(
                round(stall_s * 1e3, 3))
        if kind == "err":
            self._closed = True
            raise payload
        if payload is None:
            self._closed = True
            self._inner.close()  # every chunk was put: nothing is out
            raise StopIteration
        return self._inner._to_device(payload)

    def close(self, timeout_s: float = 5.0) -> None:
        """Stop the worker AND join it with a bounded timeout
        (early-exit consumers: external LIMIT). Setting the stop event
        alone left the thread parked up to one put-poll interval — and
        a bug there would strand it invisibly; joining makes "no
        daemon thread outlives its query" an enforced contract (the
        lockwatch stress test asserts it). The queue is drained first
        so a worker blocked mid-put unblocks immediately instead of
        riding out its 0.1s poll."""
        self._closed = True
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            import queue as _queue
            try:
                while True:
                    self._queue.get_nowait()
            except _queue.Empty:
                pass
            t.join(timeout_s)
            if t.is_alive():
                import warnings
                warnings.warn(
                    f"ingest-prefetch worker failed to exit within "
                    f"{timeout_s}s of close()")
        self._thread = None
        # with the worker joined, what it filled and nobody put (the
        # drained queue's chunk, the one it was blocked on) goes back
        # to the buffer pool
        self._inner.close()


# ---------------------------------------------------------------------------
# File stream source helpers (the FileStreamSource half that belongs to
# the IO layer: directory listing + per-file decode; the offset/seen-log
# machinery lives with the micro-batch loop in streaming.py)
# ---------------------------------------------------------------------------


def list_stream_files(path: str) -> list:
    """Data files under `path` ordered by (mtime_ns, name) — the
    FileStreamSource discovery order (the reference sorts its seen-map
    candidates by modification time too, `FileStreamSource.scala`).
    Hidden files, `_`-prefixed metadata (the sink's `_metadata/`
    manifest dir, `_SUCCESS` markers) and `.tmp`/`.crc` in-flight
    names are not data."""
    entries = []
    try:
        names = os.listdir(path)
    except OSError:
        return entries
    for name in names:
        if name.startswith((".", "_")) or \
                name.endswith((".tmp", ".crc")):
            continue
        full = os.path.join(path, name)
        try:
            st = os.stat(full)
        except OSError:
            continue  # vanished between listdir and stat
        if not os.path.isfile(full):
            continue
        entries.append({"name": name, "mtime_ns": int(st.st_mtime_ns),
                        "size": int(st.st_size)})
    entries.sort(key=lambda e: (e["mtime_ns"], e["name"]))
    return entries


def decode_stream_file(path: str, fmt: str) -> pa.Table:
    """One stream file -> Arrow table via the native readers. Raises on
    any decode failure (torn/partial writes, wrong format) — the
    caller quarantines or fails per
    spark_tpu.streaming.source.file.strict."""
    if fmt == "parquet":
        import pyarrow.parquet as pq
        return pq.read_table(path)
    if fmt == "csv":
        import pyarrow.csv as pa_csv
        return pa_csv.read_csv(path)
    if fmt == "json":
        import pyarrow.json as pa_json
        return pa_json.read_json(path)
    raise ValueError(f"unsupported stream file format {fmt!r} "
                     f"(parquet, csv, json)")


def maybe_prefetch(chunks, conf, recovery=None, retry=None, cause=None):
    """Wrap a chunk stream in the double-buffered prefetcher when
    ``spark_tpu.sql.ingest.prefetch`` is on. The chunk driver
    (execution/chunk_stream.py) routes every `load_chunks` result
    through here and hands the worker its per-chunk `retry(step,
    chunk=)` — results are identical on/off, only ingest/compute
    overlap changes."""
    if not isinstance(chunks, ChunkIterator):
        return chunks
    from ..observability.spans import current_recorder
    metrics = getattr(recovery, "metrics", None)
    chunks.observe(metrics, current_recorder(), cause)
    if not bool(conf.get(INGEST_PREFETCH_KEY)):
        return chunks
    return PrefetchChunkIterator(chunks, conf, retry=retry,
                                 metrics=metrics)
