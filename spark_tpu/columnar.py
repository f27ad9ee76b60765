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
                   capacity: Optional[int] = None,
                   placement: Optional["ShardedPlacement"] = None
                   ) -> "Batch":
        """Ingest a pyarrow table: dictionary-encode strings, pad to bucket.

        Replaces the reference's vectorized Parquet column readers
        (`VectorizedParquetRecordReader.java:54`) as the host->HBM edge.
        `capacity` forces a fixed padded size (chunked loads keep one
        compiled shape across chunks). Under a `placement` the rows
        are dealt over a mesh's shards on the host and every array is
        put sharded: no device ever holds a whole column, and
        `placement.dealt` says what each shard got."""
        n = table.num_rows
        cap = capacity if capacity is not None else bucket_capacity(n, growth)
        if placement is not None:
            cap = placement.capacity(cap)
        assert cap >= n, (cap, n)
        cols: Dict[str, Column] = {}
        for name, col in zip(table.column_names, table.columns):
            cols[name] = _arrow_to_column(name, col, n, cap, placement)
        if placement is None:
            return Batch(cols, jnp.arange(cap) < n)
        stripes = placement.stripes(n, cap)
        mask = np.zeros(cap, dtype=np.bool_)
        for _src, count, dst in stripes:
            mask[dst:dst + count] = True
        placement.dealt = tuple(count for _, count, _ in stripes)
        return Batch(cols, placement.put(mask))

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


class ShardedPlacement:
    """Where a loaded table's device copy lies under a mesh: every
    array sharded on dim 0 over the mesh's data axis, the live rows
    dealt over the shards in order and evenly. `stripes` is the deal,
    made on the host before any put: shard i holds rows
    [src, src + count) of the table at [i * local, i * local + count)
    of the padded capacity, first rows on shard 0, counts a row apart
    at most, so position-dependent aggregates see the table's order
    and no shard waits for a fuller one. One is made for one load:
    `dealt` is the live rows `Batch.from_arrow` gave each shard under
    it (host knowledge, for the counters; None before)."""

    __slots__ = ("shards", "sharding", "dealt")

    def __init__(self, mesh, axis: str):
        from jax.sharding import NamedSharding, PartitionSpec
        self.shards = int(mesh.devices.size)
        self.sharding = NamedSharding(mesh, PartitionSpec(axis))
        self.dealt: Optional[Tuple[int, ...]] = None

    def capacity(self, cap: int) -> int:
        """`cap` made a multiple of the shards (a power-of-two bucket
        over a gang of three is not)."""
        return cap + (-cap) % self.shards

    def stripes(self, rows: int, cap: int) -> List[Tuple[int, int, int]]:
        """(src, count, dst) a shard."""
        local = cap // self.shards
        base, extra = divmod(rows, self.shards)
        out, src = [], 0
        for i in range(self.shards):
            count = base + (i < extra)
            out.append((src, count, i * local))
            src += count
        return out

    def put(self, host: np.ndarray):
        """Each device is sent its stripe of `host` from the host."""
        return jax.device_put(host, self.sharding)


def _arrow_to_column(name: str, col, n: int, cap: int,
                     placement: Optional[ShardedPlacement] = None
                     ) -> Column:
    """One Arrow column to a device Column. Inside a query it leaves
    two spans: `chunk.convert` (Arrow to the padded numpy buffer:
    decimal limb copy, cast, code remap, pad) and `chunk.put` (the
    `jax.device_put` calls, i.e. staging: a put is not a sync)."""
    if pa.types.is_list(col.type) or pa.types.is_large_list(col.type):
        # offsets are absolute into the flattened values: a list
        # column has no stripes (`io/device_cache.py::scan_mesh`)
        assert placement is None, name
        arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) \
            else col
        return _arrow_list_to_column(name, arr, n, cap)
    with span("chunk.convert", column=name):
        host = _arrow_to_padded(name, col, n, cap, placement)
    return host.put(placement)


def device_dtype(name: str, at: pa.DataType) -> T.DataType:
    """The engine type a non-list Arrow column is held in on the
    device (its `np_dtype` is the padded buffer's)."""
    if pa.types.is_null(at) or pa.types.is_string(at) \
            or pa.types.is_large_string(at) or pa.types.is_dictionary(at):
        # an empty/all-None pandas object column infers arrow `null`
        # (e.g. a streaming schema df with pd.Series([], dtype=str)):
        # an all-NULL string column, the dtype the object column
        # would carry with any value present
        return T.STRING
    if pa.types.is_decimal(at):
        return T.DecimalType(at.precision, at.scale)
    if pa.types.is_timestamp(at):
        return T.TIMESTAMP
    dt = _ARROW_TO_DTYPE.get(at)
    if dt is None:
        raise TypeError(f"unsupported arrow type {at} for column {name}")
    return dt


def _values_view(arr: pa.Array, np_dtype, width: int = 1) -> np.ndarray:
    """The fixed-width values of `arr` where they lie in its buffer,
    `width` items of `np_dtype` a value: no copy, and under a null
    whatever the writer left there."""
    buf = arr.buffers()[1]
    if buf is None:  # an all-null array may come without values
        return np.zeros(width * len(arr), dtype=np_dtype)
    return np.frombuffer(buf, dtype=np_dtype,
                         count=width * (arr.offset + len(arr))
                         )[width * arr.offset:]


def fill_padded(name: str, piece: pa.Array, out: np.ndarray, pos: int,
                code_map: Optional[np.ndarray] = None
                ) -> Optional[np.ndarray]:
    """Write one piece of a non-list Arrow column into
    `out[pos:pos + len(piece)]`, a buffer of the column's device
    dtype: the one copy a row makes on its way from the reader's
    record batch to the buffer `jax.device_put` is handed. A string
    piece comes dictionary-typed; `code_map` (int32) takes its codes
    to the column's dictionary, None where they are its codes.
    Returns the piece's validity, or None where it has no null; the
    rows under a null are written as zero."""
    m = len(piece)
    if m == 0:
        return None
    dst = out[pos:pos + m]
    at = piece.type
    if pa.types.is_null(at):
        dst[:] = 0
        return np.zeros(m, dtype=np.bool_)
    if pa.types.is_dictionary(at):
        codes = piece.indices
        src = _values_view(codes, codes.type.to_pandas_dtype())
        if code_map is None:
            dst[:] = src
        elif len(code_map):
            # clipped, not checked: a code under a null may be anything
            np.take(code_map, src, out=dst, mode="clip")
        else:
            dst[:] = 0  # no value at all: every row is null
    elif pa.types.is_decimal(at):
        # exact unscaled int64: the low 64-bit limb of the 128-bit
        # little-endian decimal buffer (two's complement reinterpret is
        # exact for values in int64 range, which our repr requires).
        # decimal128 shares one buffer layout for every precision, so
        # no cast is needed (the cast materialized a full copy: a third
        # of decimal ingest time at TPC-H scale)
        if at.bit_width != 128:
            piece = piece.cast(pa.decimal128(38, at.scale))
        raw = _values_view(piece, np.int64, width=2)
        lo = raw[::2]                        # strided view, copied once
        if at.precision > 18:
            # only precision > 18 can exceed int64; cheaper columns
            # (TPC-H's (12,2)/(15,2)) skip the check entirely
            mism = raw[1::2] != lo >> 63  # sign extension when it fits
            if piece.null_count:
                mism = mism & np.asarray(piece.is_valid())
            if mism.any():
                raise OverflowError(
                    f"decimal column {name} exceeds int64 unscaled range")
        dst[:] = lo
    elif at == pa.date32():
        dst[:] = _values_view(piece, np.int32)
    elif pa.types.is_timestamp(at):
        if at != pa.timestamp("us"):
            piece = piece.cast(pa.timestamp("us"))
        dst[:] = _values_view(piece, np.int64)
    elif pa.types.is_boolean(at):  # bit-packed: through Arrow
        import pyarrow.compute as pc
        dst[:] = (pc.fill_null(piece, False) if piece.null_count
                  else piece).to_numpy(zero_copy_only=False)
    else:
        dst[:] = _values_view(piece, at.to_pandas_dtype())
    if not piece.null_count:
        return None
    valid = np.asarray(piece.is_valid())
    dst[~valid] = 0
    return valid


def _same_dictionary(a: pa.Array, b: pa.Array) -> bool:
    """Whether two dictionaries hold the same values in the same
    order; batches cut from one row group share the very buffers."""
    if len(a) != len(b) or a.type != b.type:
        return False
    if a.offset == b.offset and [x and x.address for x in a.buffers()] \
            == [x and x.address for x in b.buffers()]:
        return True
    return a.equals(b)


def merge_dictionaries(dicts: Sequence[pa.Array]
                       ) -> Tuple[Optional[pa.Array], list]:
    """One dictionary for pieces that each came with their own:
    (merged, maps). `merged` holds every value once, in the order in
    which the dictionaries bring them; `maps[i]` is the int32 table
    from the codes of `dicts[i]` to the merged ones, or None where
    they are the merged ones. Work is per dictionary entry, never per
    row, and a map is made once while consecutive pieces share a
    dictionary."""
    import pyarrow.compute as pc
    merged, maps, last = None, [], None
    for d in dicts:
        if last is not None and _same_dictionary(d, last):
            maps.append(maps[-1])
            continue
        last = d
        if merged is None:
            merged = d
            maps.append(None)
            continue
        if d.type != merged.type:
            d = d.cast(merged.type)
        present = pc.index_in(d, value_set=merged)
        if present.null_count:
            merged = pa.concat_arrays([merged,
                                       d.filter(pc.is_null(present))])
            present = pc.index_in(d, value_set=merged)
        to_merged = present.to_numpy(zero_copy_only=False).astype(
            np.int32, copy=False)
        maps.append(None if np.array_equal(
            to_merged, np.arange(len(to_merged))) else to_merged)
    return merged, maps


def sort_dictionary(merged: pa.Array, maps: list) -> Tuple[pa.Array, list]:
    """`merge_dictionaries`' result with the values in sorted order:
    a table held whole (`_arrow_to_padded`; over a mesh since PR 36,
    on one device since PR 37) then carries the same dictionary
    whatever order its rows brought the values in, so a stage over it
    (whose program holds tables made from the dictionary: a sort's
    ranks, an equality's table by code) is the same program for every
    data set of the same values, and the compile caches find it again,
    as they found the streamed scan's programs, which carry codes
    only. Work is per dictionary entry."""
    import pyarrow.compute as pc
    if merged is None or len(merged) < 2:
        return merged, maps
    order = pc.sort_indices(merged).to_numpy(zero_copy_only=False)
    if np.array_equal(order, np.arange(len(order))):
        return merged, maps
    to_sorted = np.empty(len(order), dtype=np.int32)
    to_sorted[order] = np.arange(len(order), dtype=np.int32)
    made = {}   # consecutive pieces share one map
    for m in maps:
        if id(m) not in made:
            made[id(m)] = to_sorted if m is None else to_sorted[m]
    return merged.take(pa.array(order)), [made[id(m)] for m in maps]


class HostColumn:
    """The host half of a non-list Column while it is filled: `data`
    padded to the capacity in the device dtype, `validity` made when
    the first null shows (`new_mask`), `dictionary` for a string
    column, `rows` filled so far (`append` writes there: a caller
    that deals rows over stripes moves it). `data` and what `new_mask`
    returns are zero past `rows`, or the caller's to zero there: `put`
    hands both to `jax.device_put` as they are."""

    __slots__ = ("name", "dtype", "data", "validity", "dictionary",
                 "rows", "_new_mask")

    def __init__(self, name: str, dtype: T.DataType, data: np.ndarray,
                 new_mask, dictionary: Optional[pa.Array] = None):
        self.name = name
        self.dtype = dtype
        self.data = data
        self.validity: Optional[np.ndarray] = None
        self.dictionary = dictionary
        self.rows = 0
        self._new_mask = new_mask

    def append(self, piece: pa.Array,
               code_map: Optional[np.ndarray] = None) -> None:
        valid = fill_padded(self.name, piece, self.data, self.rows,
                            code_map)
        end = self.rows + len(piece)
        if valid is not None and self.validity is None:
            self.validity = self._new_mask()
            self.validity[:self.rows] = True
        if self.validity is not None:
            self.validity[self.rows:end] = True if valid is None else valid
        self.rows = end

    @property
    def nbytes(self) -> int:
        return self.data.nbytes + (self.validity.nbytes
                                   if self.validity is not None else 0)

    def put(self, placement: Optional[ShardedPlacement] = None) -> Column:
        """To the default device, or under a `placement` sharded over
        its mesh."""
        put = jax.device_put if placement is None else placement.put
        with span("chunk.put", column=self.name, bytes=self.nbytes):
            validity = put(self.validity) \
                if self.validity is not None else None
            # device_put is ~2x jnp.asarray for host->device of large
            # buffers
            data = put(self.data)
        dictionary = self.dictionary
        if dictionary is None and isinstance(self.dtype, T.StringType):
            # a `null` column, or one of no chunk: no value, no code
            dictionary = pa.array([], type=pa.string())
        return Column(data, self.dtype, validity, dictionary)


def as_dictionary_pieces(col) -> list:
    """The pieces of a string column as dictionary arrays: a plain
    column is hashed row by row, once and into one dictionary for all
    its pieces, in the order of first appearance."""
    if isinstance(col, pa.Array):
        col = pa.chunked_array([col])
    if not pa.types.is_dictionary(col.type):
        col = col.dictionary_encode()
    return col.chunks


def _arrow_to_padded(name: str, col, n: int, cap: int,
                     placement: Optional[ShardedPlacement] = None
                     ) -> HostColumn:
    """A non-list Arrow column in new buffers padded to `cap`: all
    host work. The column's chunks are filled in one after the other
    (`fill_padded`), never made one array first; under a `placement`
    each piece is cut where a shard's share ends and written where
    that shard's rows lie, which is still the one copy a row makes."""
    dt = device_dtype(name, col.type)
    host = HostColumn(name, dt, np.zeros(cap, dtype=dt.np_dtype),
                      lambda: np.zeros(cap, dtype=np.bool_))
    if isinstance(dt, T.StringType) and not pa.types.is_null(col.type):
        pieces = as_dictionary_pieces(col)
        host.dictionary, maps = merge_dictionaries(
            [p.dictionary for p in pieces])
        # a table held whole: the stage's program must not hold the
        # order this data set's rows brought the values in
        host.dictionary, maps = sort_dictionary(host.dictionary, maps)
    else:
        pieces = col.chunks if isinstance(col, pa.ChunkedArray) else [col]
        maps = [None] * len(pieces)
    # (src, count, dst) a shard; with no mesh the one stripe is the table
    stripes = [(0, n, 0)] if placement is None \
        else placement.stripes(n, cap)
    row, shard = 0, 0   # the table's row the next piece starts at
    for piece, code_map in zip(pieces, maps):
        done = 0
        while done < len(piece):
            src, count, dst = stripes[shard]
            take = min(len(piece) - done, src + count - row)
            if take == 0:
                shard += 1
                continue
            host.rows = dst + row - src
            host.append(piece.slice(done, take), code_map)
            done += take
            row += take
    assert row == n, (name, row, n)
    if host.validity is not None:
        # `append` reads what lies before its cursor as valid rows
        local = cap // len(stripes)
        for _src, count, dst in stripes:
            host.validity[dst + count:dst + local] = False
    return host


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
