"""Sort kernel: multi-key `lax.sort` with permutation payload.

Replaces the reference's Tungsten sort tier (`SortExec.scala:40`,
`UnsafeExternalSorter.java`, `RadixSort.java`): XLA's `lax.sort` is the
device sort; there is no spill tier because batches are HBM-resident and
statically shaped. Orders follow Spark semantics: ASC -> NULLS FIRST,
DESC -> NULLS LAST by default; DESC on strings sorts by host-computed
dictionary rank (a static lookup table), since codes are not ordered.
Unselected rows sort to the end, so a sort also compacts the selection.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from .. import types as T
from ..columnar import Batch, Column
from ..expr import SortOrder, Vec


def _rank_table(dictionary: pa.Array):
    """code -> lexicographic rank, computed once on host (static)."""
    order = pc.array_sort_indices(dictionary)
    ranks = np.empty(len(dictionary), dtype=np.int32)
    ranks[order.to_numpy(zero_copy_only=False)] = np.arange(
        len(dictionary), dtype=np.int32)
    return jnp.asarray(ranks)


def sort_carrying_positions(keys: Sequence) -> Tuple:
    """The key operands sorted lexicographically, each row's position
    before the sort carried along as the last array of the result: what
    a stable `lax.sort` of `keys` with an iota payload gives. Written
    as an UNSTABLE sort whose last key is the position, which breaks
    every tie the way a stable sort breaks it, so the result is the
    same array for array; XLA:TPU compiles this form in about half the
    time (sandbox, PR 37, for a described v5e: a join's build sort of
    1 Mi rows 156.8 s stable, 81.6 s so), and a join's stage is made of
    such sorts (PERF.md, PR 37)."""
    positions = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    operands = tuple(keys) + (positions,)
    return jax.lax.sort(operands, num_keys=len(operands), is_stable=False)


def sort_key_operand(vec: Vec, ascending: bool):
    """Map a key column to an ascending-sortable operand of its dtype."""
    data = vec.data
    if isinstance(vec.dtype, T.StringType):
        if vec.dictionary is None:
            raise ValueError("sort on string requires dictionary")
        table = _rank_table(vec.dictionary)
        if len(table) == 0:
            # all-null column: every row is masked by the null-rank
            # operand, so any constant key works
            data = jnp.zeros(data.shape, dtype=jnp.int32)
        else:
            data = jnp.take(table, jnp.clip(data, 0, len(table) - 1))
    if isinstance(vec.dtype, T.BooleanType):
        data = data.astype(jnp.int8)
    if not ascending:
        if jnp.issubdtype(data.dtype, jnp.floating):
            data = -data
        else:
            data = ~data  # bitwise complement reverses integer order, no overflow
    return data


def sort_operands(batch: Batch, orders: Sequence[SortOrder]) -> List:
    """Ascending-comparable operand arrays for the sort keys (null-rank
    int8 columns interleaved before nullable keys). Comparing two rows'
    operand tuples lexicographically == comparing them under `orders` —
    shared by the local sort and the range-partitioning exchange."""
    operands = []
    for o in orders:
        vec = o.eval(batch)
        if vec.validity is not None:
            nulls = (~vec.validity).astype(jnp.int8)
            # ASC+NULLS FIRST: null rank 0; NULLS LAST: null rank 1
            rank = nulls if not o.nulls_first else (1 - nulls)
            operands.append(rank.astype(jnp.int8))
        operands.append(sort_key_operand(vec, o.ascending))
    return operands


def sort_permutation(batch: Batch, orders: Sequence[SortOrder]):
    """Returns (perm, num_valid): perm puts rows in order with unselected
    rows last; gathering all columns by perm and selecting iota<num_valid
    yields the sorted, compacted batch."""
    cap = batch.capacity
    sel = batch.selection
    invalid = jnp.zeros((cap,), jnp.int8) if sel is None else (~sel).astype(jnp.int8)
    sorted_ops = sort_carrying_positions(
        [invalid] + sort_operands(batch, orders))
    perm = sorted_ops[-1]
    n_valid = jnp.sum((sorted_ops[0] == 0).astype(jnp.int32))
    return perm, n_valid


def apply_permutation(batch: Batch, perm, n_valid) -> Batch:
    """`batch`'s rows at the positions `perm`, the first `n_valid` of
    them live. A `perm` shorter than the batch cuts it to that many
    slots (a compacted runtime filter's output)."""
    cols = {}
    for name, col in batch.columns.items():
        if col.offsets is not None:
            cols[name] = _permute_list_column(col, perm)
            continue
        data = jnp.take(col.data, perm)
        validity = None if col.validity is None else jnp.take(col.validity, perm)
        cols[name] = Column(data, col.dtype, validity, col.dictionary)
    sel = jnp.arange(perm.shape[0]) < n_valid
    return Batch(cols, sel)


def _permute_list_column(col: Column, perm) -> Column:
    """Row-permute an offsets-encoded array column: rebuild offsets from
    the permuted row lengths, then gather each output value slot from
    its source slice — all static shapes (the flattened values array
    keeps its capacity), so arrays survive ORDER BY instead of being
    gathered as garbage scalars (code-review r5)."""
    old_off = col.offsets
    starts = jnp.take(old_off[:-1], perm)
    lengths = jnp.take(old_off[1:] - old_off[:-1], perm)
    new_off = jnp.concatenate(
        [jnp.zeros((1,), old_off.dtype), jnp.cumsum(lengths)]) \
        .astype(old_off.dtype)
    vcap = col.data.shape[0]
    iota = jnp.arange(vcap, dtype=jnp.int32)
    out_row = jnp.clip(
        jnp.searchsorted(new_off, iota, side="right") - 1, 0,
        len(lengths) - 1)
    intra = iota - jnp.take(new_off, out_row)
    src = jnp.clip(jnp.take(starts, out_row) + intra, 0, vcap - 1)
    data = jnp.take(col.data, src)
    ev = None if col.elem_validity is None else \
        jnp.take(col.elem_validity, src)
    validity = None if col.validity is None else \
        jnp.take(col.validity, perm)
    return Column(data, col.dtype, validity, col.dictionary,
                  offsets=new_off, elem_validity=ev)
