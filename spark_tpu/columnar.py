"""Columnar batch substrate: the device-side data representation.

This replaces the reference's row/columnar tier (UnsafeRow
`sql/catalyst/src/main/java/.../expressions/UnsafeRow.java:62`,
`ColumnarBatch.java:30`, `OnHeap/OffHeapColumnVector.java`) with a
TPU-native struct-of-arrays design (SURVEY.md section 2.4):

- a :class:`Column` is one flat ``jax.Array`` of a fixed device dtype plus
  an optional boolean validity array (NULL mask) and, for strings, a
  host-side pyarrow dictionary (values live on host; codes on device);
- a :class:`Batch` is an ordered dict of Columns sharing a *capacity*
  (padded row count) and a *selection* mask marking live rows. Filters
  update the selection instead of compacting, keeping shapes static for
  XLA (the static-shape discipline of SURVEY.md section 7);
- capacities are rounded up to buckets so XLA recompiles O(log n) times
  across input sizes, not O(n).

Batch is registered as a JAX pytree so whole batches flow through
``jax.jit`` / ``shard_map`` directly.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from . import types as T
from .observability.spans import span


def bucket_capacity(n: int, growth: float = 2.0, floor: int = 8) -> int:
    """Round n up to the padding bucket (power-of-growth), bounding the
    number of distinct compiled shapes."""
    if n <= floor:
        return floor
    k = math.ceil(math.log(n / floor, growth))
    return int(floor * growth ** k)


class Column:
    """One device column: data + optional validity + optional host dictionary.

    `prov` (provenance) is a trace-time-only hint set by gathering
    operators (joins): ``(base_data, base_validity, idx, present)`` with
    the invariant ``data == take(base_data, idx)`` and ``validity ==
    (take(base_validity, idx) &) present``. A downstream gather composes
    indices (``base[idx[p]]``) instead of gathering the materialized
    data (``(base[idx])[p]``), so in a chain of joins each payload
    column is gathered ONCE from its origin and XLA dead-code-eliminates
    the intermediate per-column gathers — the columnar late-
    materialization the reference gets from row-at-a-time pipelining.
    prov is NOT part of the pytree, so it never crosses a jit boundary
    (dropping it is always sound: `data` stays eagerly defined)."""

    __slots__ = ("data", "validity", "dtype", "dictionary", "prov", "bits",
                 "offsets", "elem_validity")

    def __init__(self, data, dtype: T.DataType, validity=None,
                 dictionary: Optional[pa.Array] = None, prov=None,
                 bits: Optional[int] = None, offsets=None,
                 elem_validity=None):
        self.data = data
        self.dtype = dtype
        self.validity = validity  # None means all-valid
        self.dictionary = dictionary  # host pyarrow array for StringType
        self.prov = prov
        # optional static value bound: values in [0, 2^bits) — lets
        # int64 arithmetic take single-pass f64 fast paths (see Vec.bits)
        self.bits = bits
        # ARRAY columns (T.ArrayType): `data` holds the FLATTENED
        # elements, `offsets` (int32 [rows+1]) marks each row's slice,
        # `elem_validity` is the per-ELEMENT null mask (`validity` stays
        # per-row) — the Arrow List layout (UnsafeArrayData.java:1 seat)
        self.offsets = offsets
        self.elem_validity = elem_validity

    @property
    def capacity(self) -> int:
        if self.offsets is not None:
            return self.offsets.shape[0] - 1
        return self.data.shape[0]

    def with_data(self, data, validity="__keep__") -> "Column":
        v = self.validity if validity == "__keep__" else validity
        return Column(data, self.dtype, v, self.dictionary)

    def __repr__(self) -> str:
        return (f"Column({self.dtype!r}, cap={self.capacity}, "
                f"nullable={self.validity is not None}, "
                f"dict={len(self.dictionary) if self.dictionary is not None else None})")


def _col_flatten(c: Column):
    children = [c.data]
    flags = [c.validity is not None, c.offsets is not None,
             c.elem_validity is not None]
    if flags[0]:
        children.append(c.validity)
    if flags[1]:
        children.append(c.offsets)
    if flags[2]:
        children.append(c.elem_validity)
    return tuple(children), (tuple(flags), c.dtype, c.dictionary)


def _col_unflatten(aux, children):
    flags, dtype, dictionary = aux
    it = iter(children)
    data = next(it)
    validity = next(it) if flags[0] else None
    offsets = next(it) if flags[1] else None
    elem_validity = next(it) if flags[2] else None
    return Column(data, dtype, validity, dictionary, offsets=offsets,
                  elem_validity=elem_validity)


jax.tree_util.register_pytree_node(Column, _col_flatten, _col_unflatten)


class Batch:
    """An ordered set of equal-capacity Columns plus a row-selection mask.

    ``selection`` is a bool[capacity] array; None means all `capacity`
    rows are live. ``num_rows()`` is a traced scalar (selection.sum()).
    """

    __slots__ = ("columns", "selection")

    def __init__(self, columns: Dict[str, Column], selection=None):
        self.columns = dict(columns)
        self.selection = selection

    # -- construction -------------------------------------------------------

    @staticmethod
    def from_numpy(data: Dict[str, np.ndarray], num_rows: Optional[int] = None,
                   dtypes: Optional[Dict[str, T.DataType]] = None,
                   growth: float = 2.0) -> "Batch":
        cols = {}
        n = num_rows
        for name, arr in data.items():
            if n is None:
                n = len(arr)
            cap = bucket_capacity(n, growth)
            dt = (dtypes or {}).get(name) or _np_to_dtype(arr.dtype)
            padded = np.zeros(cap, dtype=dt.np_dtype)
            padded[:n] = arr[:n]
            cols[name] = Column(jnp.asarray(padded), dt)
        sel = jnp.arange(cap) < n
        return Batch(cols, sel)

    @staticmethod
    def from_arrow(table: pa.Table, growth: float = 2.0,
                   capacity: Optional[int] = None) -> "Batch":
        """Ingest a pyarrow table: dictionary-encode strings, pad to bucket.

        Replaces the reference's vectorized Parquet column readers
        (`VectorizedParquetRecordReader.java:54`) as the host->HBM edge.
        `capacity` forces a fixed padded size (chunked loads keep one
        compiled shape across chunks)."""
        n = table.num_rows
        cap = capacity if capacity is not None else bucket_capacity(n, growth)
        assert cap >= n, (cap, n)
        cols: Dict[str, Column] = {}
        for name, col in zip(table.column_names, table.columns):
            cols[name] = _arrow_to_column(name, col, n, cap)
        sel = jnp.arange(cap) < n
        return Batch(cols, sel)

    # -- shape/meta ---------------------------------------------------------

    @property
    def capacity(self) -> int:
        for c in self.columns.values():
            return c.capacity
        return 0

    @property
    def names(self) -> List[str]:
        return list(self.columns.keys())

    def column(self, name: str) -> Column:
        return self.columns[name]

    def num_rows(self):
        """Traced count of live rows."""
        if self.selection is None:
            return jnp.asarray(self.capacity, dtype=jnp.int32)
        return jnp.sum(self.selection).astype(jnp.int32)

    def schema(self) -> T.Schema:
        return T.Schema([T.Field(n, c.dtype, c.validity is not None)
                         for n, c in self.columns.items()])

    def selection_mask(self):
        if self.selection is None:
            return jnp.ones((self.capacity,), dtype=jnp.bool_)
        return self.selection

    # -- transforms ---------------------------------------------------------

    def with_selection(self, sel) -> "Batch":
        return Batch(self.columns, sel)

    def select(self, names: Sequence[str]) -> "Batch":
        return Batch({n: self.columns[n] for n in names}, self.selection)

    def with_column(self, name: str, col: Column) -> "Batch":
        cols = dict(self.columns)
        cols[name] = col
        return Batch(cols, self.selection)

    # -- egress -------------------------------------------------------------

    def to_arrow(self) -> pa.Table:
        """Compact (drop unselected rows), decode dictionaries, return
        host table. ALL device arrays leave in ONE `jax.device_get`
        call: each pull is a host sync of its own, and one batched
        transfer overlaps the copies instead of serializing them."""
        import jax
        pulls = []
        if self.selection is not None:
            pulls.append(self.selection)
        for col in self.columns.values():
            pulls.append(col.data)
            if col.validity is not None:
                pulls.append(col.validity)
            if col.offsets is not None:
                pulls.append(col.offsets)
            if col.elem_validity is not None:
                pulls.append(col.elem_validity)
        host = iter(jax.device_get(pulls))
        sel = next(host) if self.selection is not None else None
        arrays = []
        names = []
        for name, col in self.columns.items():
            data = next(host)
            valid = next(host) if col.validity is not None else None
            offsets = next(host) if col.offsets is not None else None
            evalid = next(host) if col.elem_validity is not None else None
            if offsets is not None:
                arrays.append(_list_to_arrow(col, data, valid, offsets,
                                             evalid, sel))
                names.append(name)
                continue
            if sel is not None:
                data = data[sel]
                if valid is not None:
                    valid = valid[sel]
            arrays.append(_column_to_arrow(col, data, valid))
            names.append(name)
        return pa.table(arrays, names=names)

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    def __repr__(self) -> str:
        return f"Batch(cap={self.capacity}, cols={self.columns!r})"


def _batch_flatten(b: Batch):
    names = tuple(b.columns.keys())
    has_sel = b.selection is not None
    children = tuple(b.columns[n] for n in names)
    if has_sel:
        children = children + (b.selection,)
    return children, (names, has_sel)


def _batch_unflatten(aux, children):
    names, has_sel = aux
    if has_sel:
        *cols, sel = children
    else:
        cols, sel = children, None
    return Batch({n: c for n, c in zip(names, cols)}, sel)


jax.tree_util.register_pytree_node(Batch, _batch_flatten, _batch_unflatten)


# ---------------------------------------------------------------------------
# Dictionary algebra (host-side, trace-time static)
#
# String columns carry host pyarrow dictionaries; device data is int32
# codes. Any transform that slices or combines dictionaries must keep the
# invariant "equal strings <=> equal codes *within one dictionary*", and
# any operator combining two columns must first remap both onto one shared
# dictionary. These helpers do that once on host; the resulting remap
# tables become jit constants (a gather on device).
# ---------------------------------------------------------------------------


def dedupe_dictionary(dictionary: pa.Array):
    """Collapse duplicate values in a dictionary.

    Returns (remap, deduped) where `remap` is a device int32 table mapping
    old code -> new code, or None when the dictionary was already unique.
    Needed after value transforms (e.g. substring) that can map distinct
    old values onto one new value — otherwise group-by/join on codes would
    treat equal strings as distinct (the reference gets this for free from
    UTF8String equality)."""
    import pyarrow.compute as pc
    arr = dictionary.combine_chunks() if isinstance(
        dictionary, pa.ChunkedArray) else dictionary
    uniq = pc.unique(arr)
    if len(uniq) == len(arr):
        return None, arr
    remap = pc.index_in(arr, value_set=uniq).cast(pa.int32())
    return jnp.asarray(remap.to_numpy(zero_copy_only=False)), uniq


def unify_dictionaries(da: pa.Array, db: pa.Array):
    """Merge two (internally unique) dictionaries into one shared one.

    Returns (remap_b, merged): `merged` extends `da` with values of `db`
    not already present (so codes into `da` stay valid), and `remap_b` is
    a device int32 table mapping b-codes -> merged codes (None when the
    dictionaries are identical). Mirrors the chunk-level DictUnifier in
    io/sources.py, but for two already-loaded columns."""
    import pyarrow.compute as pc
    da = da.combine_chunks() if isinstance(da, pa.ChunkedArray) else da
    db = db.combine_chunks() if isinstance(db, pa.ChunkedArray) else db
    if da.equals(db):
        return None, da
    present = pc.index_in(db, value_set=da)
    new_mask = pc.is_null(present)
    if pc.any(new_mask).as_py():
        new_vals = pc.filter(db, new_mask)
        merged = pa.concat_arrays([da.cast(pa.string()),
                                   new_vals.cast(pa.string())])
    else:
        merged = da
    remap = pc.index_in(db, value_set=merged).cast(pa.int32())
    return jnp.asarray(remap.to_numpy(zero_copy_only=False)), merged


def apply_code_remap(codes, remap):
    """Gather new codes through a remap table (identity when remap is None)."""
    if remap is None:
        return codes
    if remap.shape[0] == 0:
        # all-null column: the dictionary (and thus the remap) is
        # empty, no code is valid and validity masks every row — any
        # constant code works
        return jnp.zeros_like(codes)
    return jnp.take(remap, jnp.clip(codes, 0, remap.shape[0] - 1))


def unify_string_columns(l_data, l_dict: pa.Array, r_data, r_dict: pa.Array):
    """Re-encode two string code columns onto one shared dictionary.

    Dedupes each side, merges right values into the left dictionary, and
    remaps both code arrays. Returns (l_data, r_data, merged). After this,
    code equality <=> string equality across the two columns."""
    lmap, ld = dedupe_dictionary(l_dict)
    rmap, rd = dedupe_dictionary(r_dict)
    l_data = apply_code_remap(l_data, lmap)
    r_data = apply_code_remap(r_data, rmap)
    bmap, merged = unify_dictionaries(ld, rd)
    r_data = apply_code_remap(r_data, bmap)
    return l_data, r_data, merged


# ---------------------------------------------------------------------------
# Arrow conversion helpers
# ---------------------------------------------------------------------------

_ARROW_TO_DTYPE = {
    pa.bool_(): T.BOOLEAN,
    pa.int8(): T.BYTE,
    pa.int16(): T.SHORT,
    pa.int32(): T.INT,
    pa.int64(): T.LONG,
    pa.float32(): T.FLOAT,
    pa.float64(): T.DOUBLE,
    pa.date32(): T.DATE,
    pa.timestamp("us"): T.TIMESTAMP,
}


def _np_to_dtype(np_dtype) -> T.DataType:
    m = {np.dtype(np.bool_): T.BOOLEAN, np.dtype(np.int8): T.BYTE,
         np.dtype(np.int16): T.SHORT, np.dtype(np.int32): T.INT,
         np.dtype(np.int64): T.LONG, np.dtype(np.float32): T.FLOAT,
         np.dtype(np.float64): T.DOUBLE}
    if np_dtype not in m:
        raise TypeError(f"unsupported numpy dtype {np_dtype}")
    return m[np_dtype]


def _arrow_to_column(name: str, col: pa.ChunkedArray, n: int, cap: int) -> Column:
    """One Arrow column to a device Column. Inside a query it leaves
    two spans: `chunk.convert` (Arrow to the padded numpy buffer:
    combine, decimal limb copy, cast, pad) and `chunk.put` (the
    `jax.device_put` calls, i.e. staging: a put is not a sync)."""
    if pa.types.is_list(col.type) or pa.types.is_large_list(col.type):
        arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) \
            else col
        return _arrow_list_to_column(name, arr, n, cap)
    with span("chunk.convert", column=name):
        dt, padded, valid_np, dictionary = _arrow_to_padded(name, col, n, cap)
    nbytes = padded.nbytes + (valid_np.nbytes if valid_np is not None else 0)
    with span("chunk.put", column=name, bytes=nbytes):
        validity = jax.device_put(valid_np) if valid_np is not None else None
        # device_put is ~2x jnp.asarray for host->device of large buffers
        data = jax.device_put(padded)
    return Column(data, dt, validity, dictionary)


def _arrow_to_padded(name: str, col, n: int, cap: int):
    """(dtype, data padded to `cap`, validity padded to `cap` or None,
    dictionary or None) of a non-list Arrow column: all host work."""
    arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
    at = arr.type
    dictionary = None
    if pa.types.is_null(at):
        # an empty/all-None pandas object column infers arrow `null`
        # (e.g. a streaming schema df with pd.Series([], dtype=str)):
        # treat it as an all-NULL string column, the dtype the object
        # column would carry with any value present
        arr = arr.cast(pa.string())
        at = arr.type
    if pa.types.is_string(at) or pa.types.is_large_string(at):
        arr = arr.dictionary_encode()
        at = arr.type
    if pa.types.is_dictionary(at):
        dictionary = arr.dictionary
        codes = arr.indices.cast(pa.int32())
        np_data = codes.to_numpy(zero_copy_only=False)
        dt: T.DataType = T.STRING
    elif pa.types.is_decimal(at):
        dt = T.DecimalType(at.precision, at.scale)
        # exact unscaled int64: read the low 64-bit limb of the 128-bit
        # little-endian decimal buffer (two's complement reinterpret is
        # exact for values in int64 range, which our repr requires).
        # decimal128 shares one buffer layout for every precision, so no
        # cast is needed (the cast materialized a full copy — a third of
        # decimal ingest time at TPC-H scale)
        if arr.type.bit_width != 128:
            arr = arr.cast(pa.decimal128(38, at.scale))
        buf = arr.buffers()[1]
        raw = np.frombuffer(buf, dtype=np.int64,
                            count=2 * (arr.offset + len(arr)))
        lo = raw[2 * arr.offset::2]          # strided view, copied once
        if at.precision > 18:
            # only precision > 18 can exceed int64; cheaper columns
            # (TPC-H's (12,2)/(15,2)) skip the check entirely
            hi = raw[2 * arr.offset + 1::2]
            expect_hi = lo >> 63  # sign extension when value fits int64
            mism = hi != expect_hi
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
        dt = _ARROW_TO_DTYPE.get(at)
        if dt is None:
            raise TypeError(f"unsupported arrow type {at} for column {name}")
        np_data = arr.cast(pa.from_numpy_dtype(dt.np_dtype)).to_numpy(
            zero_copy_only=False)

    valid_np = None
    if arr.null_count > 0:
        valid_np = np.zeros(cap, dtype=np.bool_)
        valid_np[:n] = ~np.asarray(arr.is_null())
        np_data = np.where(valid_np[:n], np_data, np.zeros((), dtype=dt.np_dtype))

    padded = np.zeros(cap, dtype=dt.np_dtype)
    padded[:n] = np_data
    return dt, padded, valid_np, dictionary


def _arrow_list_to_column(name: str, arr, n: int, cap: int) -> Column:
    """pa.ListArray -> offsets-encoded list Column: FLATTENED element
    data + absolute int32 offsets [cap+1] (padding rows repeat the last
    offset, i.e. zero-length)."""
    if pa.types.is_large_list(arr.type):
        arr = arr.cast(pa.list_(arr.type.value_type))
    offs = arr.offsets.to_numpy(zero_copy_only=False).astype(np.int32)
    values = arr.values
    vcap = bucket_capacity(max(len(values), 1))
    elem = _arrow_to_column(f"{name}.element", values, len(values), vcap)
    padded_off = np.full(cap + 1, offs[n] if len(offs) > n else 0,
                         dtype=np.int32)
    padded_off[:n + 1] = offs[:n + 1]
    validity = None
    if arr.null_count > 0:
        valid_np = np.zeros(cap, dtype=np.bool_)
        valid_np[:n] = ~np.asarray(arr.is_null())
        validity = jax.device_put(valid_np)
    return Column(elem.data, T.ArrayType(elem.dtype), validity,
                  elem.dictionary, offsets=jax.device_put(padded_off),
                  elem_validity=elem.validity)


def _list_to_arrow(col: Column, data: np.ndarray,
                   valid: Optional[np.ndarray], offsets: np.ndarray,
                   elem_valid: Optional[np.ndarray],
                   sel: Optional[np.ndarray]) -> pa.Array:
    """Offsets-encoded list column -> pa.ListArray over the SELECTED
    rows (compaction happens here — per-row slices can't be gathered by
    the flat-column path)."""
    cap = len(offsets) - 1
    idx = np.nonzero(sel[:cap])[0] if sel is not None else np.arange(cap)
    starts = offsets[idx]
    lengths = (offsets[idx + 1] - starts).astype(np.int64)
    lengths = np.maximum(lengths, 0)
    new_off = np.zeros(len(idx) + 1, dtype=np.int32)
    np.cumsum(lengths, out=new_off[1:])
    total = int(new_off[-1])
    intra = np.arange(total, dtype=np.int64) - np.repeat(
        new_off[:-1].astype(np.int64), lengths)
    val_idx = np.repeat(starts.astype(np.int64), lengths) + intra
    vals = data[val_idx]
    ev = None if elem_valid is None else elem_valid[val_idx]
    elem_col = Column(None, col.dtype.element, None, col.dictionary)
    elem_arrow = _column_to_arrow(elem_col, vals, ev)
    off_mask = None
    if valid is not None:
        off_mask = np.zeros(len(idx) + 1, dtype=bool)
        off_mask[:len(idx)] = ~valid[idx]
    return pa.ListArray.from_arrays(
        pa.array(new_off, type=pa.int32(), mask=off_mask), elem_arrow)


def _column_to_arrow(col: Column, data: np.ndarray,
                     valid: Optional[np.ndarray]) -> pa.Array:
    dt = col.dtype
    mask = None if valid is None else ~valid
    if isinstance(dt, T.StringType):
        if col.dictionary is None:
            return pa.array(data.astype("U"), mask=mask)
        codes = np.clip(data, 0, len(col.dictionary) - 1)
        out = pa.DictionaryArray.from_arrays(
            pa.array(codes.astype(np.int32), mask=mask), col.dictionary)
        return out.cast(pa.string())
    if isinstance(dt, T.DecimalType):
        # inverse of ingest: place unscaled int64 into the low limb of a
        # little-endian 128-bit buffer with sign extension in the high limb
        lo = data.astype(np.int64)
        hi = lo >> 63
        raw = np.empty((len(lo), 2), dtype=np.int64)
        raw[:, 0] = lo
        raw[:, 1] = hi
        validity_buf = None
        if valid is not None:
            validity_buf = pa.array(valid.astype(np.bool_)).buffers()[1]
        return pa.Array.from_buffers(
            pa.decimal128(max(dt.precision, 19), dt.scale), len(lo),
            [validity_buf, pa.py_buffer(raw.tobytes())],
            null_count=int((~valid).sum()) if valid is not None else 0)
    if isinstance(dt, T.DateType):
        return pa.array(data.astype(np.int32), mask=mask).cast(pa.date32())
    if isinstance(dt, T.TimestampType):
        return pa.array(data.astype(np.int64), mask=mask).cast(pa.timestamp("us"))
    return pa.array(data, mask=mask)
