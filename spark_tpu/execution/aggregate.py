"""Group-by aggregation kernels.

Replaces the reference's Tungsten hash aggregation
(`HashAggregateExec.scala:46`, `TungstenAggregationIterator.scala:82`,
`UnsafeFixedWidthAggregationMap.java:39` on `BytesToBytesMap.java`) with
two TPU-native strategies chosen at trace time:

1. **direct**: when every group key has a statically known small integer
   domain (dictionary-encoded strings -> |dict|, `x % c` -> c, bool -> 2,
   byte -> 256), the combined domain is a dense table and aggregation is
   a scatter-add/min/max (segment reduce) — no hash table at all. This is
   the common case for TPC-H-style low-cardinality GROUP BYs and is the
   op the MXU/VPU executes at memory bandwidth.
2. **sort**: general exact fallback — multi-operand `lax.sort` on the key
   columns (the XLA analog of Tungsten's sort-based fallback path), group
   boundaries by adjacent-difference, then `jax.ops.segment_*`.

Both paths consume the declarative accumulator specs of
``expr_agg.AggregateFunction`` and produce a Batch of group keys +
accumulator columns with an `occupied` selection; merge across shards
re-reduces the same accumulators (associative + commutative), which is
what makes the partial/final split and mesh `psum` trees work unchanged.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar import Batch, Column, bucket_capacity
from ..expr import Alias, Expression, Literal, Mod, Pmod, Vec
from ..expr_agg import AccSpec, AggExpr
from .sort import sort_carrying_positions


def key_domain(expr: Expression, vec: Vec) -> Optional[Tuple[int, int]]:
    """Statically-known integer key range as (domain, lo) with
    value in [lo, lo+domain), or None (trace-time decision).

    `lo` matters for signed ranges: truncated `%` yields (-m, m) and BYTE
    is [-128, 128) — a [0, domain) assumption would silently merge
    negative keys into slot 0."""
    while isinstance(expr, Alias):
        expr = expr.child
    if vec.dictionary is not None:
        return len(vec.dictionary), 0
    if isinstance(vec.dtype, T.BooleanType):
        return 2, 0
    if isinstance(vec.dtype, T.ByteType):
        return 256, -128
    if isinstance(expr, Mod):
        div = expr.children[1]
        while hasattr(div, "child") and div.children:
            div = div.children[0]
        if isinstance(div, Literal) and isinstance(div.value, int) and div.value > 0:
            m = int(div.value)
            if isinstance(expr, Pmod):
                return m, 0
            # truncated %: result in (-m, m)
            return 2 * m - 1, -(m - 1)
    return None


def _key_index(vec: Vec, domain: int, lo: int):
    idx = vec.data.astype(jnp.int32) - jnp.int32(lo)
    return jnp.clip(idx, 0, domain - 1)


_SEGMENT_REDUCE = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}


def _sorted_segment_reduce(contrib_sorted, reduce: str, starts_rows,
                           start_pos, end_pos, present):
    """Per-segment reduce over rows SORTED by segment, without a
    colliding scatter (XLA scatter-add into shared slots serializes on
    TPU: ~300ms for 4M rows into 65k segments, measured on Q3).

    integer sum: prefix-sum difference csum[end] - csum[start-1] — int64
    wraps mod 2^64 so the difference is exact. float sum and min/max: a
    SEGMENTED associative scan (reset at `starts_rows` markers) read at
    segment ends — a global-prefix difference would put each segment's
    float error at the ulp of the whole-table running total instead of
    the segment's own magnitude. start_pos/end_pos index each segment's
    first/last sorted row; `present` masks empty segments."""
    is_int = jnp.issubdtype(contrib_sorted.dtype, jnp.integer)
    if reduce == "sum" and is_int:
        csum = jnp.cumsum(contrib_sorted)
        ex = csum - contrib_sorted  # exclusive prefix
        out = jnp.take(csum, end_pos) - jnp.take(ex, start_pos)
        return jnp.where(present, out, jnp.zeros_like(out))
    if reduce == "sum":
        op = jnp.add
    else:
        op = jnp.minimum if reduce == "min" else jnp.maximum

    def combine(a, b):
        va, fa = a
        vb, fb = b
        return (jnp.where(fb, vb, op(va, vb)), fa | fb)

    scanned, _ = jax.lax.associative_scan(
        combine, (contrib_sorted, starts_rows))
    out = jnp.take(scanned, end_pos)
    if reduce == "sum":
        out = jnp.where(present, out, jnp.zeros_like(out))
    return out


def key_spans(nullables: Sequence[bool],
              domains: Sequence[Tuple[int, int]]) -> List[int]:
    """Per-key slot count: the value domain plus one NULL slot for
    SCHEMA-nullable keys (SQL groups NULL keys together). Nullability
    comes from the schema, not a batch's concrete validity — chunked
    execution must keep ONE layout even when some chunks lack nulls."""
    return [d + (1 if nullable else 0)
            for nullable, (d, _lo) in zip(nullables, domains)]


def direct_index(key_vecs: Sequence[Vec], domains: Sequence[Tuple[int, int]],
                 spans: Sequence[int], sel):
    """Combined dense-domain index per row; unselected rows get an
    out-of-bounds index (scatter mode='drop' discards them); NULL key
    values map to the key's dedicated null slot.
    `domains` entries are (domain, lo) pairs from `key_domain`."""
    total = 1
    strides = []
    for span in spans:
        strides.append(total)
        total *= span
    idx = jnp.zeros((), jnp.int32)
    for vec, (d, lo), span, s in zip(key_vecs, domains, spans, strides):
        ki = _key_index(vec, d, lo)
        if vec.validity is not None and span > d:
            ki = jnp.where(vec.validity, ki, jnp.int32(d))  # null slot
        idx = idx + ki * s
    if sel is not None:
        idx = jnp.where(sel, idx, total)
    return idx, total, strides


def direct_init(spans: Sequence[int], specs: List[List[AccSpec]]):
    """Fresh accumulator tables: (occupied_cnt, [[acc,...],...]).
    `spans` are the per-key slot counts incl. null slots (key_spans)."""
    total = int(np.prod(list(spans) or [1]))
    cnt = jnp.zeros((total,), jnp.int64)
    accs = [[jnp.full((total,), spec.neutral) for spec in row]
            for row in specs]
    return cnt, accs


def direct_update(tables, idx, total, contribs: List[List],
                  specs: List[List[AccSpec]], kernel_mode: str = "auto",
                  merge: bool = False,
                  reuse_count: Optional[Tuple[int, int]] = None):
    """Merge one chunk's contributions into carried tables (associative).

    kernel_mode: 'auto' uses the Pallas MXU one-hot matmul kernel on TPU
    (XLA scatter-add with colliding indices is ~100x slower there) and
    plain scatter elsewhere; 'matmul'/'scatter' force a path ('matmul'
    off-TPU runs the kernel in interpret mode, for tests).

    merge=True means the contributions are PARTIAL ACCUMULATORS (a final
    -mode aggregate folding per-shard tables), not raw per-row values:
    AccSpec.width bounds only the raw update, so merge forces full
    64-bit limbs — a partial count easily exceeds 2^8.

    reuse_count=(i, j): the caller promises contribs[i][j] equals the
    selection indicator (a count over a never-null child), so the
    kernel's occupancy row rides that row's sums instead of adding its
    own — the MXU kernel cost is linear in limb rows, and a count-only
    aggregate (post RewriteGroupKeyAggregates) drops from 2 rows to 1.
    Ignored in merge mode (partial counts are not indicators).
    """
    cnt, accs = tables
    if np.ndim(idx) == 0:
        idx = jnp.broadcast_to(idx, contribs[0][0].shape if contribs
                               and contribs[0] else (1,))

    all_sum = all(spec.reduce == "sum" for row in specs for spec in row)
    backend = jax.default_backend()
    use_kernel = (kernel_mode == "matmul"
                  or (kernel_mode == "auto" and backend == "tpu"))
    has_float = any(np.issubdtype(spec.np_dtype, np.floating)
                    for row in specs for spec in row)
    if has_float and total > 512:
        # the VPU masked-reduce float path costs O(domain) per row; the
        # factorized MXU kernel is int-only — scatter instead
        use_kernel = False
    if all_sum and use_kernel and total <= (1 << 20) and \
            (idx.shape[0] >= 128 or kernel_mode == "matmul"):
        from .pallas_groupby import dense_groupby_sums
        reuse = reuse_count if not merge else None
        if reuse is not None:
            int_rows = []
            int_widths = []
        else:
            int_rows = [jnp.ones(idx.shape, jnp.int64)]
            int_widths = [8]  # the occupancy count contributes 0/1
        float_rows = []
        layout = []  # (row_kind, index) per (i, j)
        reuse_pos = None
        for i, (contrib_row, spec_row) in enumerate(zip(contribs, specs)):
            for j, (contrib, spec) in enumerate(zip(contrib_row, spec_row)):
                if np.issubdtype(spec.np_dtype, np.floating):
                    layout.append(("f", len(float_rows)))
                    float_rows.append(contrib)
                else:
                    layout.append(("i", len(int_rows)))
                    if reuse == (i, j):
                        reuse_pos = len(int_rows)
                    int_rows.append(contrib.astype(jnp.int64))
                    int_widths.append(64 if merge else spec.width)
        if reuse is not None and reuse_pos is None:
            # promised row turned out to be a float row: fall back
            int_rows = [jnp.ones(idx.shape, jnp.int64)] + int_rows
            int_widths = [8] + int_widths
            layout = [(k, p + 1) if k == "i" else (k, p)
                      for (k, p) in layout]
            reuse_pos = 0
        int_sums, float_sums = dense_groupby_sums(
            idx, int_rows, float_rows, total,
            interpret=(backend != "tpu"), int_widths=int_widths)
        cnt = cnt + int_sums[reuse_pos if reuse_pos is not None else 0]
        new_accs = []
        k = 0
        for table_row, spec_row in zip(accs, specs):
            new_row = []
            for table, spec in zip(table_row, spec_row):
                kind, pos = layout[k]
                k += 1
                if kind == "f":
                    new_row.append(table + float_sums[pos].astype(spec.np_dtype))
                else:
                    new_row.append(table + int_sums[pos].astype(spec.np_dtype))
            new_accs.append(new_row)
        return cnt, new_accs

    cnt = cnt.at[idx].add(jnp.ones(idx.shape, jnp.int64), mode="drop")
    new_accs = []
    for table_row, contrib_row, spec_row in zip(accs, contribs, specs):
        new_row = []
        for table, contrib, spec in zip(table_row, contrib_row, spec_row):
            if spec.reduce == "sum":
                new_row.append(table.at[idx].add(contrib, mode="drop"))
            elif spec.reduce == "min":
                new_row.append(table.at[idx].min(contrib, mode="drop"))
            else:
                new_row.append(table.at[idx].max(contrib, mode="drop"))
        new_accs.append(new_row)
    return cnt, new_accs


def direct_keys(domains: Sequence[Tuple[int, int]],
                spans: Sequence[int], strides: Sequence[int],
                key_dtypes: Sequence[T.DataType]) -> Tuple[List, List]:
    """Reconstruct key column (values, validities) from the dense domain
    index. A key's null slot (index == domain) decodes to validity False;
    keys without a null slot get validity None."""
    total = int(np.prod(list(spans) or [1]))
    out_idx = jnp.arange(total, dtype=jnp.int32)
    key_arrays = []
    key_valids = []
    rem = out_idx
    for (d, lo), span, s, dt in zip(reversed(list(domains)),
                                    reversed(list(spans)),
                                    reversed(strides),
                                    reversed(list(key_dtypes))):
        k = rem // s
        rem = rem - k * s
        if span > d:  # has a null slot
            key_valids.append(k != d)
            k = jnp.minimum(k, d - 1)
        else:
            key_valids.append(None)
        key_arrays.append((k + jnp.int32(lo)).astype(dt.np_dtype))
    key_arrays.reverse()
    key_valids.reverse()
    return key_arrays, key_valids


def direct_aggregate(key_vecs: Sequence[Vec],
                     domains: Sequence[Tuple[int, int]],
                     spans: Sequence[int],
                     contribs: List[List], specs: List[List[AccSpec]],
                     sel, kernel_mode: str = "auto",
                     merge: bool = False,
                     reuse_count: Optional[Tuple[int, int]] = None
                     ) -> Tuple[List, List, List, object]:
    """One-shot dense-domain aggregation.
    Returns (key_arrays, key_valids, acc_arrays, occupied)."""
    idx, total, strides = direct_index(key_vecs, domains, spans, sel)
    tables = direct_init(spans, specs)
    cnt, accs = direct_update(tables, idx, total, contribs, specs,
                              kernel_mode=kernel_mode, merge=merge,
                              reuse_count=reuse_count)
    key_arrays, key_valids = direct_keys(domains, spans, strides,
                                         [v.dtype for v in key_vecs])
    return key_arrays, key_valids, accs, cnt > 0


def sort_aggregate(key_vecs: Sequence[Vec],
                   contribs: List[List], specs: List[List[AccSpec]],
                   sel, capacity: int, num_segments: Optional[int] = None
                   ) -> Tuple[List, List, List, object, object]:
    """General sort-based aggregation.

    Returns (key_arrays, key_validities, acc_arrays, occupied,
    total_groups). Groups beyond `num_segments` are dropped — the caller
    must flag `total_groups > num_segments` and retry with capacity
    (the join/exchange AQE loop pattern).
    """
    num_segments = num_segments or capacity
    operands = []
    invalid = jnp.zeros((capacity,), jnp.int32) if sel is None else \
        (~sel).astype(jnp.int32)
    operands.append(invalid)
    for vec in key_vecs:
        data = vec.data
        if vec.validity is not None:
            operands.append((~vec.validity).astype(jnp.int8))
            # neutralize data under NULL: two NULL keys must land in ONE
            # group even when their dead payloads differ (e.g. after a
            # union's dictionary remap)
            data = jnp.where(vec.validity, data,
                             jnp.zeros((), data.dtype))
        operands.append(data)
    num_keys = len(operands)
    sorted_ops = sort_carrying_positions(operands)  # + the permutation
    perm = sorted_ops[-1]
    inv_sorted = sorted_ops[0].astype(jnp.bool_)
    valid_sorted = ~inv_sorted

    # group starts: first valid row, or any key component differing from prev
    diff = jnp.zeros((capacity,), jnp.bool_)
    for op in sorted_ops[1:num_keys]:
        shifted = jnp.roll(op, 1)
        diff = diff | (op != shifted)
    first = jnp.arange(capacity) == 0
    starts = (first | diff) & valid_sorted
    total_groups = jnp.sum(starts.astype(jnp.int32))
    gid = jnp.cumsum(starts.astype(jnp.int32)) - 1
    gid = jnp.where(valid_sorted & (gid < num_segments), gid,
                    num_segments)  # OOB -> dropped (flagged by caller)

    # per-segment first/last sorted-row positions via NON-colliding
    # scatters (each segment writes each exactly once); every reduce
    # below reads prefix scans at these bounds — colliding scatter-adds
    # serialize on TPU (~300ms for 4M rows into 65k segments)
    pos = jnp.arange(capacity, dtype=jnp.int32)
    in_seg = gid < num_segments
    sidx = jnp.where(starts & in_seg, gid, num_segments)
    nxt_gid = jnp.concatenate(
        [gid[1:], jnp.full((1,), num_segments, gid.dtype)])
    ends = in_seg & (nxt_gid != gid)
    eidx = jnp.where(ends, gid, num_segments)
    start_pos = jnp.zeros((num_segments,), jnp.int32).at[sidx].set(
        pos, mode="drop")
    end_pos = jnp.zeros((num_segments,), jnp.int32).at[eidx].set(
        pos, mode="drop")
    present = jnp.zeros((num_segments,), jnp.bool_).at[sidx].set(
        jnp.ones((capacity,), jnp.bool_), mode="drop")
    occupied_cnt = jnp.where(present, end_pos - start_pos + 1, 0)

    accs = []
    for row_contribs, row_specs in zip(contribs, specs):
        fn_accs = []
        for contrib, spec in zip(row_contribs, row_specs):
            contrib_sorted = jnp.take(contrib, perm)
            out = _sorted_segment_reduce(contrib_sorted, spec.reduce,
                                         starts, start_pos, end_pos,
                                         present)
            if spec.reduce != "sum":
                neutral = jnp.full((num_segments,), spec.neutral)
                out = jnp.where(occupied_cnt > 0, out, neutral)
            fn_accs.append(out.astype(spec.np_dtype))
        accs.append(fn_accs)

    # scatter first-of-group key values into the output slots
    key_arrays = []
    key_valids = []
    oi = 1
    for vec in key_vecs:
        if vec.validity is not None:
            null_sorted = sorted_ops[oi].astype(jnp.bool_)
            oi += 1
        else:
            null_sorted = None
        data_sorted = sorted_ops[oi]
        oi += 1
        out = jnp.zeros((num_segments,), data_sorted.dtype).at[
            jnp.where(starts, gid, num_segments)].set(data_sorted, mode="drop")
        key_arrays.append(out)
        if null_sorted is not None:
            kv = jnp.ones((num_segments,), jnp.bool_).at[
                jnp.where(starts, gid, num_segments)].set(
                    ~null_sorted, mode="drop")
            key_valids.append(kv)
        else:
            key_valids.append(None)
    return key_arrays, key_valids, accs, occupied_cnt > 0, total_groups


# ---------------------------------------------------------------------------
# Positional aggregates: percentile/median/collect_list/collect_set
# (reference: ApproximatePercentile.scala:1 / Percentile.scala /
# collect.scala — ObjectHashAggregate's serialized per-group state
# becomes ONE device sort by (group keys, value) + segmented positional
# gathers; list outputs compact into offsets-encoded array columns)
# ---------------------------------------------------------------------------

def positional_sort(key_vecs: Sequence[Vec], value_vec: Vec, sel,
                    capacity: int):
    """Sort rows by (liveness, group keys, value-null-last, value).
    Returns (values_sorted, value_valid_sorted, starts, gid, start_pos,
    total_groups, group_occupied). Group ORDER depends only on the keys,
    so several positional sorts (different value children) and a
    sort_aggregate over the same keys all align group-for-group."""
    operands = []
    invalid = jnp.zeros((capacity,), jnp.int32) if sel is None else \
        (~sel).astype(jnp.int32)
    operands.append(invalid)
    for vec in key_vecs:
        data = vec.data
        if vec.validity is not None:
            operands.append((~vec.validity).astype(jnp.int8))
            data = jnp.where(vec.validity, data,
                             jnp.zeros((), data.dtype))
        operands.append(data)
    vinvalid = jnp.zeros((capacity,), jnp.int8) \
        if value_vec.validity is None else \
        (~value_vec.validity).astype(jnp.int8)
    operands.append(vinvalid)  # null values sort to the group tail
    operands.append(value_vec.data)
    num_keys = len(operands)
    sorted_ops = sort_carrying_positions(operands)
    valid_sorted = sorted_ops[0] == 0
    values_sorted = sorted_ops[-2]
    vvalid_sorted = (sorted_ops[-3] == 0) & valid_sorted

    diff = jnp.zeros((capacity,), jnp.bool_)
    i = 1
    for vec in key_vecs:
        if vec.validity is not None:
            op = sorted_ops[i]
            diff = diff | (op != jnp.roll(op, 1))
            i += 1
        op = sorted_ops[i]
        diff = diff | (op != jnp.roll(op, 1))
        i += 1
    first = jnp.arange(capacity) == 0
    starts = (first | diff) & valid_sorted
    total_groups = jnp.sum(starts.astype(jnp.int32))
    gid = jnp.cumsum(starts.astype(jnp.int32)) - 1
    gid = jnp.where(valid_sorted, gid, capacity)

    pos = jnp.arange(capacity, dtype=jnp.int32)
    sidx = jnp.where(starts, jnp.clip(gid, 0, capacity), capacity)
    # GROUP-indexed first-row position (slot g -> group g's start)
    gstart = jnp.zeros((capacity,), jnp.int32).at[sidx].set(
        pos, mode="drop")
    # per-ROW segment-start position (running max of start markers)
    row_start = jax.lax.cummax(jnp.where(starts, pos, jnp.int32(0)))
    return (values_sorted, vvalid_sorted, starts, gid, gstart,
            row_start, total_groups, sorted_ops)


def positional_percentile(values_sorted, vvalid_sorted, gid, gstart,
                          num_segments: int, q: float, capacity: int):
    """Exact per-group percentile with linear interpolation (the
    reference's Percentile): values of each group sit contiguously with
    nulls at the tail, so the q-quantile is two gathers + a lerp.
    `gstart` is GROUP-indexed (slot g -> group g's first sorted row)."""
    cnt = jnp.zeros((num_segments + 1,), jnp.int32).at[
        jnp.clip(gid, 0, num_segments)].add(
        vvalid_sorted.astype(jnp.int32), mode="drop")[:num_segments]
    gstart = gstart[:num_segments]
    vals = values_sorted.astype(jnp.float64)
    idx = (cnt - 1).astype(jnp.float64) * q
    lo = jnp.clip(jnp.floor(idx).astype(jnp.int32), 0, None)
    hi = jnp.clip(jnp.ceil(idx).astype(jnp.int32), 0, None)
    safe = jnp.clip(gstart, 0, capacity - 1)
    v_lo = jnp.take(vals, jnp.clip(safe + lo, 0, capacity - 1))
    v_hi = jnp.take(vals, jnp.clip(safe + hi, 0, capacity - 1))
    frac = idx - lo.astype(jnp.float64)
    out = v_lo + (v_hi - v_lo) * frac
    return out, cnt > 0


def positional_collect(values_sorted, vvalid_sorted, gid, row_start,
                       num_segments: int, distinct: bool, capacity: int):
    """collect_list / collect_set: compact each group's (optionally
    deduplicated) valid values into an offsets-encoded list column.
    `row_start` is the PER-ROW segment-start position. Returns
    (data[cap], offsets[num_segments+1])."""
    keep = vvalid_sorted
    if distinct:
        same_prev = (jnp.roll(values_sorted, 1) == values_sorted) & \
            (jnp.roll(gid, 1) == gid) & \
            (jnp.arange(capacity) != 0)
        keep = keep & ~(same_prev & vvalid_sorted &
                        jnp.roll(vvalid_sorted, 1))
    kcnt = jnp.zeros((num_segments + 1,), jnp.int32).at[
        jnp.clip(gid, 0, num_segments)].add(
        keep.astype(jnp.int32), mode="drop")[:num_segments]
    new_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(kcnt)]).astype(jnp.int32)
    ck = jnp.cumsum(keep.astype(jnp.int32))
    # rank of each kept row within its group's kept values
    ck_at_start = jnp.take(ck, row_start) - jnp.take(
        keep.astype(jnp.int32), row_start)
    rank = ck - ck_at_start - 1
    target = jnp.where(
        keep,
        jnp.take(new_off, jnp.clip(gid, 0, num_segments)) + rank,
        capacity)
    data = jnp.zeros((capacity,), values_sorted.dtype).at[
        target].set(values_sorted, mode="drop")
    return data, new_off
