"""The streamed scan's padded buffers: `columnar.fill_padded` writes a
piece of an Arrow column where it belongs, once (against the
conversion it replaced, byte for byte), `ChunkIterator` fills pooled
buffers on whichever thread makes the chunk's host half, and
`io/host_buffers.py` hands a buffer out again only when the device
array made from it is ready and does not read it."""

import decimal
import os
import threading

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_tpu import functions as F
from spark_tpu import types as T
from spark_tpu.columnar import (_ARROW_TO_DTYPE, _arrow_to_padded,
                                bucket_capacity)
from spark_tpu.functions import col
from spark_tpu.io import host_buffers, sources
from spark_tpu.io.host_buffers import HostBufferPool
from spark_tpu.io.sources import (ArrowTableSource, ChunkIterator,
                                  ParquetSource, PrefetchChunkIterator)
from spark_tpu.testing import faults
from spark_tpu.testing.lockwatch import LockWatch

CHUNK_KEY = "spark_tpu.sql.execution.streamingChunkRows"
CACHE_KEY = "spark_tpu.sql.io.deviceCacheBytes"
PREFETCH_KEY = "spark_tpu.sql.ingest.prefetch"


# -- the conversion the fill replaced, kept as the reference -----------------


def _reference_padded(name, col, n, cap):
    """`columnar._arrow_to_padded` as it was before the fill routine:
    the column made one array, converted, then copied into a new
    padded buffer."""
    arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
    at = arr.type
    dictionary = None
    if pa.types.is_null(at):
        arr = arr.cast(pa.string())
        at = arr.type
    if pa.types.is_string(at) or pa.types.is_large_string(at):
        arr = arr.dictionary_encode()
        at = arr.type
    if pa.types.is_dictionary(at):
        # since PR 37 a held table's dictionary is sorted on one device
        # too (over a mesh since PR 36): the old codes, by rank
        import pyarrow.compute as pc
        order = pc.sort_indices(arr.dictionary).to_numpy(
            zero_copy_only=False)
        rank = np.empty(len(order), dtype=np.int32)
        rank[order] = np.arange(len(order), dtype=np.int32)
        dictionary = arr.dictionary.take(pa.array(order))
        np_data = pc.fill_null(arr.indices, 0).cast(pa.int32()).to_numpy(
            zero_copy_only=False)
        if len(rank):    # a column of nulls alone has no entry
            np_data = rank[np_data]
        dt = T.STRING
    elif pa.types.is_decimal(at):
        dt = T.DecimalType(at.precision, at.scale)
        if arr.type.bit_width != 128:
            arr = arr.cast(pa.decimal128(38, at.scale))
        raw = np.frombuffer(arr.buffers()[1], dtype=np.int64,
                            count=2 * (arr.offset + len(arr)))
        lo = raw[2 * arr.offset::2]
        if at.precision > 18:
            mism = raw[2 * arr.offset + 1::2] != lo >> 63
            if arr.null_count:
                mism = mism & ~np.asarray(arr.is_null()).astype(bool)
            if mism.any():
                raise OverflowError(
                    f"decimal column {name} exceeds int64 unscaled range")
        np_data = lo
    elif at == pa.date32():
        dt = T.DATE
        np_data = arr.cast(pa.int32()).to_numpy(zero_copy_only=False)
    elif pa.types.is_timestamp(at):
        dt = T.TIMESTAMP
        np_data = arr.cast(pa.timestamp("us")).cast(pa.int64()).to_numpy(
            zero_copy_only=False)
    else:
        dt = _ARROW_TO_DTYPE[at]
        np_data = arr.cast(pa.from_numpy_dtype(dt.np_dtype)).to_numpy(
            zero_copy_only=False)
    valid_np = None
    if arr.null_count > 0:
        valid_np = np.zeros(cap, dtype=np.bool_)
        valid_np[:n] = ~np.asarray(arr.is_null())
        np_data = np.where(valid_np[:n], np_data,
                           np.zeros((), dtype=dt.np_dtype))
    padded = np.zeros(cap, dtype=dt.np_dtype)
    padded[:n] = np_data
    return dt, padded, valid_np, dictionary


N = 3000          # rows of a test column
PIECES = (0, 700, 701, 1900, N)   # where its chunks are cut


def _values(kind, rng):
    """(python values without nulls, arrow type) of one supported type."""
    ints = rng.integers(-10**6, 10**6, N)
    if kind == "decimal12_2":
        return [decimal.Decimal(int(x)) / 100 for x in ints], \
            pa.decimal128(12, 2)
    if kind == "decimal38_2":    # precision > 18, every value fits
        return [decimal.Decimal(int(x)) * 10**9 / 100 for x in ints], \
            pa.decimal128(38, 2)
    if kind == "decimal256":
        return [decimal.Decimal(int(x)) / 100 for x in ints], \
            pa.decimal256(40, 2)
    if kind == "date32":
        return [int(x) for x in rng.integers(0, 20000, N)], pa.date32()
    if kind.startswith("timestamp"):
        unit = kind.split("_")[1]   # whole microseconds in every unit
        return [int(x) * 1000 for x in rng.integers(0, 2**31, N)], \
            pa.timestamp(unit)
    if kind == "bool":
        return [bool(x) for x in rng.integers(0, 2, N)], pa.bool_()
    if kind in ("int8", "int16", "int32", "int64"):
        # the old conversion took a column with nulls through float64:
        # exact only below 2**53 (`test_wide_int64_beside_nulls...`)
        bits = min(int(kind[3:]), 53)
        return [int(x) for x in rng.integers(
            -2**(bits - 1), 2**(bits - 1), N)], getattr(pa, kind)()
    if kind in ("float32", "float64"):
        return [float(x) for x in rng.standard_normal(N).astype(kind)], \
            getattr(pa, kind)()
    if kind in ("string", "large_string"):
        return [str(x) for x in rng.choice(["A", "N", "R", "late"], N)], \
            getattr(pa, kind)()
    raise AssertionError(kind)


def _column(kind, nulls, rng):
    """A ChunkedArray of `kind` cut at PIECES, every chunk a slice with
    a non-zero offset into a longer array."""
    if kind == "null":
        return pa.chunked_array([pa.nulls(b - a).slice(0) for a, b in
                                 zip(PIECES, PIECES[1:])], pa.null())
    if kind.startswith("dictionary"):
        # each chunk its own dictionary, some values shared, one unused
        index_type = getattr(pa, kind.split("_")[1])()
        chunks = []
        for i, (a, b) in enumerate(zip(PIECES, PIECES[1:])):
            values = [["A", "N", "R"], ["R", "unused", "A"],
                      ["N", "A", "R"], ["late", "R"]][i]
            codes = rng.integers(0, len(values), b - a + 2)
            if kind.endswith("unused"):
                codes[codes == 1] = 0
            mask = rng.random(b - a + 2) < 0.2 if nulls else None
            chunks.append(pa.DictionaryArray.from_arrays(
                pa.array(codes, index_type, mask=mask),
                pa.array(values)).slice(2))
        return pa.chunked_array(chunks)
    values, at = _values(kind, rng)
    if nulls:
        values = [None if rng.random() < 0.2 else v for v in values]
    chunks = []
    for a, b in zip(PIECES, PIECES[1:]):
        # three rows of another chunk first: the piece's offset is 3
        lead = values[:3]
        chunks.append(pa.array(lead + values[a:b], at).slice(3))
    return pa.chunked_array(chunks, at)


KINDS = ["decimal12_2", "decimal38_2", "decimal256", "date32",
         "timestamp_us", "timestamp_ms", "timestamp_ns", "bool", "int8",
         "int16", "int32", "int64", "float32", "float64", "string",
         "large_string", "dictionary_int8", "dictionary_int32",
         "dictionary_int64_unused", "null"]


def _assert_same(host, want, n):
    dt, padded, valid, dictionary = want
    assert host.dtype == dt and host.rows == n
    assert host.data.dtype == padded.dtype
    assert host.data.tobytes() == padded.tobytes()
    if valid is None:
        assert host.validity is None
    else:
        assert host.validity.tobytes() == valid.tobytes()
    put = host.put()
    if dictionary is None:
        assert put.dictionary is None
    else:
        assert put.dictionary.to_pylist() == dictionary.to_pylist()
        assert put.dictionary.type == dictionary.type


@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
@pytest.mark.parametrize("kind", KINDS)
def test_fill_is_the_old_conversion_byte_for_byte(kind, nulls):
    """Every supported type, in chunks that are slices at a non-zero
    offset: the same dtype, buffer, mask and dictionary as the column
    made one array and converted."""
    col_ = _column(kind, nulls, np.random.default_rng(len(kind) + nulls))
    cap = bucket_capacity(N)
    assert all(c.offset > 0 for c in col_.chunks) or kind == "null"
    _assert_same(_arrow_to_padded("c", col_, N, cap),
                 _reference_padded("c", col_, N, cap), N)
    # and as one array (a list column's elements arrive so)
    one = col_.combine_chunks()
    if isinstance(one, pa.ChunkedArray):  # dictionaries: already one
        one = one.chunk(0)
    _assert_same(_arrow_to_padded("c", one, N, cap),
                 _reference_padded("c", one, N, cap), N)


@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
def test_decimal_beyond_int64_raises_as_before(nulls):
    big = [decimal.Decimal(2**70), None if nulls else decimal.Decimal(1)]
    col_ = pa.chunked_array([pa.array([decimal.Decimal(5)] * 3,
                                      pa.decimal128(38, 0)),
                             pa.array(big, pa.decimal128(38, 0))])
    for convert in (_arrow_to_padded, _reference_padded):
        with pytest.raises(OverflowError, match="column c exceeds int64"):
            convert("c", col_, 5, 8)
    # under a null the high limb may hold anything
    fits = pa.chunked_array([pa.array(
        [decimal.Decimal(-7), None, decimal.Decimal(2**62)],
        pa.decimal128(38, 0))])
    _assert_same(_arrow_to_padded("c", fits, 3, 8),
                 _reference_padded("c", fits, 3, 8), 3)


def test_wide_int64_beside_nulls_is_exact():
    """Where the fill departs from the conversion it replaced: that
    one read a column with nulls through `to_numpy`, which makes
    float64 of it and rounds what lies beyond 2**53; the fill reads
    the values where they lie."""
    wide = [2**62 + 1, None, -(2**61) - 3, 2**53 + 1]
    for at in (pa.int64(), pa.timestamp("us")):
        col_ = pa.chunked_array([pa.array(wide, at)])
        host = _arrow_to_padded("c", col_, 4, 8)
        assert host.data[:4].tolist() == [2**62 + 1, 0, -(2**61) - 3,
                                          2**53 + 1]
        assert host.validity[:4].tolist() == [True, False, True, True]
        assert _reference_padded("c", col_, 4, 8)[1][0] != 2**62 + 1


def test_unsupported_type_is_refused_by_name():
    with pytest.raises(TypeError, match="uint8.*column c"):
        _arrow_to_padded("c", pa.chunked_array([pa.array([1], pa.uint8())]),
                         1, 8)


# -- the streamed path: pooled buffers, chunk boundaries ---------------------

STREAM_KINDS = ["decimal12_2", "date32", "timestamp_ms", "bool", "int64",
                "float64", "null"]
CHUNK = 1024


def _stream_table(rng, nulls=True):
    cols = {k: _column(k, nulls and k != "date32", rng)
            for k in STREAM_KINDS}
    cols["typed"] = _column("dictionary_int8", nulls, rng)
    cols["plain"] = _column("string", False, rng)
    return pa.table(cols)


@pytest.fixture
def pool(monkeypatch):
    """A pool of the test's own in place of the process's."""
    fresh = HostBufferPool()
    monkeypatch.setattr(sources, "POOL", fresh)
    return fresh


def _pool_count(pool):
    return sum(len(v) for v in pool._free.values()) + len(pool._in_flight)


def _host_chunks(it):
    """Every chunk's host half as `_to_device` was handed it: copies
    of the padded buffers (the pool may hand them out again)."""
    seen = []
    to_device = it._to_device

    def spy(host):
        seen.append({n: (c.data.copy(),
                         None if c.validity is None else c.validity.copy(),
                         c.dictionary)
                     for n, c in host.columns.items()})
        return to_device(host)

    it._to_device = spy
    return seen


def test_chunks_are_the_old_conversion_of_their_slices(pool):
    """Record batches of 700 rows under chunks of 1,024: a batch
    straddles every boundary and the last chunk is short. Filled into
    buffers that an earlier stream left full of other bytes, every
    buffer is, byte for byte and zero tail included, what the chunk's
    slice converted to before; a string column's codes decode to the
    slice's strings."""
    table = _stream_table(np.random.default_rng(5))
    batches = table.combine_chunks().to_batches(max_chunksize=700)
    assert len(list(ChunkIterator(iter(batches), CHUNK))) == -(-N // CHUNK)
    pooled = [b for bufs in pool._free.values() for b in bufs]
    assert len(pooled) >= len(table.column_names) and not pool._in_flight
    for buf in pooled:      # what an older chunk's rows might be
        buf.view(np.uint8)[:] = 0xFF
    it = ChunkIterator(iter(batches), CHUNK)
    seen = _host_chunks(it)
    made = _pool_count(pool)
    assert len(list(it)) == len(seen) == -(-N // CHUNK) and N % CHUNK
    assert _pool_count(pool) == made    # nothing made new: all reused
    for i, chunk in enumerate(seen):
        want = table.slice(i * CHUNK, CHUNK)
        n = want.num_rows
        for name in table.column_names:
            data, valid, dictionary = chunk[name]
            dt, padded, ref_valid, ref_dict = _reference_padded(
                name, want.column(name), n, CHUNK)
            assert data.dtype == padded.dtype
            assert not data[n:].view(np.uint8).any()    # the tail is zero
            assert (valid is None) == (ref_valid is None), (name, i)
            if ref_valid is not None:
                assert valid.tobytes() == ref_valid.tobytes()
            if ref_dict is None or name == "null":
                assert data.tobytes() == padded.tobytes(), (name, i)
            else:   # global codes: compare what they decode to
                keep = slice(0, n) if valid is None else valid
                assert dictionary.take(pa.array(data[keep])).to_pylist() \
                    == ref_dict.take(pa.array(padded[keep])).to_pylist()
                if valid is not None:   # zero under a null
                    assert not data[:n][~valid[:n]].any()


def test_skip_chunks_cuts_the_same_chunks_and_touches_no_buffer(pool):
    table = _stream_table(np.random.default_rng(6), nulls=False)
    batches = table.combine_chunks().to_batches(max_chunksize=700)
    whole = [b.to_arrow() for b in ChunkIterator(iter(batches), CHUNK)]
    pool.trim({})
    it = ChunkIterator(iter(batches), CHUNK)
    assert it.skip_chunks(2) == 2
    assert _pool_count(pool) == 0 and not it._filled
    rest = [b.to_arrow() for b in it]
    assert [t.to_pylist() for t in rest] == [t.to_pylist() for t in whole[2:]]


# -- the pool's one rule -------------------------------------------------------


class _SlowArray:
    """Stands for a device array whose transfer the test ends."""

    def __init__(self):
        self.done = threading.Event()

    def is_deleted(self):
        return False

    def is_ready(self):
        return self.done.is_set()

    def block_until_ready(self):
        assert self.done.wait(10)
        return self

    addressable_shards = ()


def test_buffer_in_flight_is_waited_for_not_replaced():
    pool = HostBufferPool()
    buf, reused = pool.take(np.int64, 64)
    assert not reused and not buf.any() and buf.ctypes.data % 64 == 16
    arr = _SlowArray()
    pool.give(buf, arr)
    got = []
    t = threading.Thread(target=lambda: got.append(pool.take(np.int64, 64)))
    t.start()
    t.join(0.3)
    assert t.is_alive() and not got     # not handed out, not replaced
    # another shape does not wait for it
    other, reused = pool.take(np.int32, 64)
    assert not reused and other.dtype == np.int32
    arr.done.set()
    t.join(10)
    assert not t.is_alive()
    assert got[0][0] is buf and got[0][1] is True
    assert pool.idle_bytes() == 0


def _aligned(dtype, capacity):
    """A zeroed buffer on a 64-byte line."""
    dtype = np.dtype(dtype)
    raw = np.zeros(capacity * dtype.itemsize + 64, dtype=np.uint8)
    start = (-raw.ctypes.data) % 64
    return raw[start:start + capacity * dtype.itemsize].view(dtype)


def _backend_takes_aligned_buffers():
    buf = _aligned(np.int64, 64)
    return host_buffers._reads_host_memory(
        jax.block_until_ready(jax.device_put(buf)), buf)


def test_buffer_the_array_reads_is_never_handed_out_again():
    """The CPU backend takes a 64-byte-aligned host buffer as the
    array's own storage: such a buffer must not come back, whatever
    `is_ready` says; one it copied does."""
    pool = HostBufferPool()
    aligned = _aligned(np.int64, 64)
    aligned[:] = np.arange(64)
    arr = jax.block_until_ready(jax.device_put(aligned))
    if not host_buffers._reads_host_memory(arr, aligned):
        pytest.skip("this backend copied an aligned buffer")
    pool.give(aligned, arr)
    got, reused = pool.take(np.int64, 64)
    assert not reused and got is not aligned
    assert pool.idle_bytes() == 0       # let go, not kept
    # the pool's own buffers are copied, and do come back
    got[:] = 7
    arr2 = jax.block_until_ready(jax.device_put(got))
    assert not host_buffers._reads_host_memory(arr2, got)
    pool.give(got, arr2)
    again, reused = pool.take(np.int64, 64)
    assert reused and again is got
    again[:] = 9
    assert np.asarray(arr2).tolist() == [7] * 64


def test_trim_keeps_three_sets_of_the_last_shape():
    pool = HostBufferPool()
    key8, key4 = pool.key(np.int64, 32), pool.key(np.int32, 32)
    for _ in range(5):
        pool.give(np.zeros(32, np.int64))
        pool.give(np.zeros(32, np.int32))
    pool.give(np.zeros(16, np.int64))
    pool.trim({key8: 1})
    assert {k: len(v) for k, v in pool._free.items()} == {
        key8: host_buffers.SETS_PER_STREAM}
    assert pool.idle_bytes() == 3 * 32 * 8
    pool.trim({key4: 2})
    assert pool.idle_bytes() == 0


@pytest.mark.parametrize("aliasing", [False, True],
                         ids=["copied", "aliased"])
def test_recycled_buffers_change_no_earlier_chunk(pool, monkeypatch,
                                                  aliasing):
    """Chunks kept on the device read the same after later chunks and
    a second stream were filled into the same pool, also where the
    backend takes the host buffer as the array's storage (then the
    pool lets every buffer go)."""
    if aliasing:
        monkeypatch.setattr(host_buffers, "_new_buffer", _aligned)
    table = _stream_table(np.random.default_rng(8))
    batches = table.combine_chunks().to_batches(max_chunksize=700)
    kept = list(ChunkIterator(iter(batches), CHUNK))
    before = [b.to_arrow().to_pylist() for b in kept]
    taken = []
    take = pool.take

    def spy(dtype, capacity):
        buf, reused = take(dtype, capacity)
        taken.append(reused)
        return buf, reused

    monkeypatch.setattr(pool, "take", spy)
    for b in ChunkIterator(iter(batches[::-1]), CHUNK):
        jax.block_until_ready(b)
    assert [b.to_arrow().to_pylist() for b in kept] == before
    rows = [r for chunk in before for r in chunk]
    assert rows == table.to_pylist()
    if aliasing and _backend_takes_aligned_buffers():
        assert not any(taken) and _pool_count(pool) == 0  # all let go
    else:
        assert any(taken)


# -- every exit gives every set back --------------------------------------------


def _counting(monkeypatch):
    """Buffers made new since the call."""
    made = []
    new_buffer = host_buffers._new_buffer

    def counted(dtype, capacity):
        made.append((dtype, capacity))
        return new_buffer(dtype, capacity)

    monkeypatch.setattr(host_buffers, "_new_buffer", counted)
    return made


def _int_batches(n_batches, rows=700, fail_at=None):
    for i in range(n_batches):
        if i == fail_at:
            raise OSError("reader lost its file")
        yield pa.record_batch({
            "a": pa.array(np.arange(i * rows, (i + 1) * rows)),
            "b": pa.array(np.arange(rows, dtype=np.int32))})


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch_on", "prefetch_off"])
def test_close_mid_stream_returns_every_set(session, pool, monkeypatch,
                                            prefetch):
    made = _counting(monkeypatch)
    inner = ChunkIterator(_int_batches(40), CHUNK)
    it = PrefetchChunkIterator(inner, session.conf) if prefetch else inner
    first = next(it)
    second = next(it)
    assert int(first.columns["a"].data[0]) == 0
    assert int(second.columns["a"].data[0]) == CHUNK
    it.close()
    it.close()  # idempotent
    # a set a chunk filled so far (with prefetch a third, or its start)
    assert 2 * 2 <= len(made) <= 2 * host_buffers.SETS_PER_STREAM
    assert _pool_count(pool) == len(made) and not inner._filled
    with pytest.raises(StopIteration):
        next(it)
    LockWatch().assert_no_thread_leak(timeout_s=5)


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch_on", "prefetch_off"])
def test_reader_error_returns_every_set(session, pool, monkeypatch,
                                        prefetch):
    made = _counting(monkeypatch)
    inner = ChunkIterator(_int_batches(40, fail_at=7), CHUNK)
    it = PrefetchChunkIterator(inner, session.conf) if prefetch else inner
    got = []
    with pytest.raises(OSError, match="reader lost its file"):
        for b in it:
            got.append(b)
    assert len(got) == 7 * 700 // CHUNK
    it.close()
    assert made and _pool_count(pool) == len(made)
    LockWatch().assert_no_thread_leak(timeout_s=5)


def test_fill_error_returns_the_chunk_s_buffers_and_poisons(pool,
                                                            monkeypatch):
    """A column that cannot be filled (a decimal beyond int64) fails
    the chunk: its buffers go back, and a replay raises the same
    error rather than skip the chunk's rows."""
    made = _counting(monkeypatch)
    ok = pa.array([decimal.Decimal(1)] * CHUNK, pa.decimal128(38, 0))
    bad = pa.array([decimal.Decimal(2**70)] * CHUNK, pa.decimal128(38, 0))
    it = ChunkIterator(iter([
        pa.record_batch({"a": pa.array(np.arange(CHUNK)), "d": ok}),
        pa.record_batch({"a": pa.array(np.arange(CHUNK)), "d": bad}),
        pa.record_batch({"a": pa.array(np.arange(CHUNK)), "d": ok})]),
        CHUNK)
    next(it)
    for _ in range(2):
        with pytest.raises(OverflowError):
            next(it)
    it.close()
    assert _pool_count(pool) == len(made)


# -- through a query: answers, counters, reuse ------------------------------------

FILES, FILE_ROWS, Q_CHUNK = 3, 2500, 2048


@pytest.fixture(scope="module")
def parquet_table(tmp_path_factory):
    """Three Parquet files of a string, a decimal, an int64 with nulls
    and a date column, in row groups that divide neither a file nor a
    chunk."""
    path = str(tmp_path_factory.mktemp("ingest_buffers"))
    rng = np.random.default_rng(17)
    frames = []
    for f in range(FILES):
        n = FILE_ROWS
        t = pa.table({
            "k": pa.array(rng.choice(["A", "N", "R"], n)),
            "d": pa.array([decimal.Decimal(int(x)) / 100 for x in
                           rng.integers(0, 10**6, n)],
                          pa.decimal128(12, 2)),
            "v": pa.array(rng.integers(0, 1000, n), pa.int64(),
                          mask=rng.random(n) < 0.1),
            "day": pa.array(rng.integers(8000, 11000, n).astype(np.int32),
                            pa.int32()).cast(pa.date32())})
        pq.write_table(t, os.path.join(path, f"part-{f}.parquet"),
                       row_group_size=900)
        frames.append(t.to_pandas())
    return path, pd.concat(frames, ignore_index=True)


COUNTERS = ("ingest_put_bytes", "ingest_rows", "ingest_chunks",
            "ingest_buffers_reused", "ingest_buffers_allocated")


def _run(session, path, prefetch):
    session.register_table("ingest_buffers_t",
                           ParquetSource(path, "ingest_buffers_t"))
    session.conf.set(CHUNK_KEY, Q_CHUNK)
    session.conf.set(CACHE_KEY, 0)
    session.conf.set(PREFETCH_KEY, prefetch)
    before = {k: session.metrics.counter(k).value for k in COUNTERS}
    qe = (session.table("ingest_buffers_t").group_by(col("k"))
          .agg(F.sum(col("d")).alias("d"), F.sum(col("v")).alias("v"),
               F.count(col("v")).alias("n"),
               F.max(col("day")).alias("day")))._qe()
    out = qe.collect().to_pandas().sort_values("k").reset_index(drop=True)
    grew = {k: session.metrics.counter(k).value - before[k]
            for k in COUNTERS}
    return out, grew, qe


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch_on", "prefetch_off"])
def test_streamed_aggregate_answers_and_counts_as_before(
        session, parquet_table, pool, prefetch):
    """The answer pandas gives, and the counters the path it replaced
    read: every chunk padded to the one capacity, a code 4 B, a
    decimal's and an int64's value 8 B, a date 4 B, one mask byte a
    row for the column with nulls."""
    path, frame = parquet_table
    out, grew, qe = _run(session, path, prefetch)
    want = frame.groupby("k").agg(
        d=("d", "sum"), v=("v", "sum"), n=("v", "count"),
        day=("day", "max")).reset_index()
    assert out["k"].tolist() == want["k"].tolist()
    assert [decimal.Decimal(str(x)) for x in out["d"]] == want["d"].tolist()
    assert out["v"].astype(int).tolist() == want["v"].astype(int).tolist()
    assert out["n"].tolist() == want["n"].tolist()
    assert [str(x)[:10] for x in out["day"]] == \
        [str(x)[:10] for x in want["day"]]
    n_chunks = -(-FILES * FILE_ROWS // Q_CHUNK)
    assert grew["ingest_rows"] == FILES * FILE_ROWS
    assert grew["ingest_chunks"] == n_chunks
    assert grew["ingest_put_bytes"] == n_chunks * Q_CHUNK * (4 + 8 + 8 + 1 + 4)
    # a buffer a column a chunk, the mask among them
    assert grew["ingest_buffers_reused"] + grew["ingest_buffers_allocated"] \
        == 5 * n_chunks
    assert grew["ingest_buffers_allocated"] <= \
        5 * host_buffers.SETS_PER_STREAM
    converts = [s for s in qe.spans.spans if s.name == "chunk.convert"]
    assert len(converts) == 4 * n_chunks
    assert sum(s.attrs["bytes"] for s in converts) == grew["ingest_put_bytes"]
    LockWatch().assert_no_thread_leak(timeout_s=5)


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch_on", "prefetch_off"])
def test_second_stream_allocates_nothing(session, parquet_table, pool,
                                         prefetch):
    """What the first stream leaves in the pool is what the second
    needs: no buffer made new, one reused a column (and mask) a
    chunk; on `/metrics` under their names."""
    from spark_tpu.observability.metrics import (parse_prometheus_text,
                                                 prometheus_text)
    path, _ = parquet_table
    first, grew1, _ = _run(session, path, prefetch)
    assert grew1["ingest_buffers_allocated"] > 0
    held = pool.idle_bytes()
    second, grew2, _ = _run(session, path, prefetch)
    pd.testing.assert_frame_equal(first, second)
    n_chunks = -(-FILES * FILE_ROWS // Q_CHUNK)
    assert grew2["ingest_buffers_allocated"] == 0
    assert grew2["ingest_buffers_reused"] == 5 * n_chunks
    assert pool.idle_bytes() == held    # nothing grows between requests
    assert held <= host_buffers.SETS_PER_STREAM * Q_CHUNK * (4 + 8 + 8 + 1 + 4)
    served = parse_prometheus_text(
        prometheus_text(session.metrics.snapshot()))
    for k in ("ingest_buffers_reused", "ingest_buffers_allocated"):
        assert served["spark_tpu_" + k] == session.metrics.counter(k).value
    LockWatch().assert_no_thread_leak(timeout_s=5)


def test_replayed_chunk_returns_every_set(session, parquet_table, pool,
                                          monkeypatch):
    """A transient fault at the prefetcher's seam replays one chunk's
    host half through `ChunkRetrier`: the same answer, and every
    buffer that was made is in the pool when the query ends."""
    path, _ = parquet_table
    clean, _, _ = _run(session, path, True)
    pool.trim({})
    made = _counting(monkeypatch)
    replayed = session.metrics.counter("rec_chunks_replayed").value
    with faults.inject(session.conf, "ingest_prefetch:unavailable:2") as plan:
        out, grew, _ = _run(session, path, True)
    assert ("ingest_prefetch", 2, "unavailable") in plan.fired_log
    assert session.metrics.counter("rec_chunks_replayed").value \
        == replayed + 1
    pd.testing.assert_frame_equal(out, clean)
    assert grew["ingest_buffers_allocated"] == len(made)
    assert _pool_count(pool) == len(made)
    LockWatch().assert_no_thread_leak(timeout_s=5)


def test_resident_load_never_draws_from_the_pool(session, parquet_table,
                                                 pool, monkeypatch):
    """`Batch.from_arrow` shares the fill and not the pool: a
    device-table cache entry must not pin pooled host memory."""
    path, frame = parquet_table
    made = _counting(monkeypatch)
    batch = ParquetSource(path, "t").load(None, [])
    assert int(batch.num_rows()) == len(frame)
    assert not made and _pool_count(pool) == 0
    table = pa.Table.from_pandas(frame)
    assert ArrowTableSource("t", table).load(None, []).to_arrow() \
        .column("v").to_pylist() == table.column("v").to_pylist()
    assert not made and _pool_count(pool) == 0


def test_pool_lock_is_declared_and_the_passes_hold():
    from spark_tpu.analysis.concurrency.registry import GUARDED_BY, LOCKS
    from spark_tpu.analysis.lints import run_passes
    (decl,) = [d for d in LOCKS if d.lock_id == "io.host_buffers"]
    assert (decl.cls, decl.attr) == ("HostBufferPool", "_lock")
    ranks = {d.lock_id: d.rank for d in LOCKS}
    # a leaf: above the counters a stream incs beside it, below the
    # span recorder's own
    assert ranks["metrics.histogram"] < decl.rank < ranks["obs.spans"]
    assert {g.attr for g in GUARDED_BY if g.cls == "HostBufferPool"} \
        == {"_free", "_in_flight"}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = run_passes(["guarded-by", "lock-order"], repo=repo)
    assert [v.render() for v in out] == []
