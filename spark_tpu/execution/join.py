"""Equi-join kernels: sorted-build binary-search with many-to-many expansion.

Replaces the reference's join tier (`SortMergeJoinExec.scala:36`,
`HashedRelation.scala:41`, `BroadcastHashJoinExec.scala:40`,
`ShuffledHashJoinExec.scala:37`) with a sort+searchsorted formulation that
XLA maps well onto TPU:

- the build side is sorted once (`lax.sort`);
- each probe key binary-searches its match *range* [lo, hi)
  (`jnp.searchsorted` left/right), so duplicate build keys are handled;
- output rows are produced by prefix-sum expansion into a statically
  shaped output: out row r maps back to probe row p via a second
  searchsorted over the row-offset array, and to build row lo[p]+(r-off[p]).

Output capacity is a static trace-time parameter. The executor seeds it
with the probe capacity (exact for FK joins, the TPC-H shape) and, when
the traced total exceeds it, reads the real total from a metric and
re-jits with a sufficient capacity — the host-side stats->re-plan loop of
the reference's AQE (`AdaptiveSparkPlanExec.scala:64`) in miniature.

All shapes are static; everything fuses into the enclosing stage.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar import Batch, Column
from ..expr import Vec
from .sort import sort_carrying_positions


def canon_key_data(data):
    """One representative per join-equal float key class: -0.0 -> +0.0
    and every NaN payload -> the canonical NaN. Join keys compare NaN
    equal to NaN (the reference's/pandas semantics), so the sort total
    order (NaN greatest), searchsorted tie-breaking and `==` must all
    see a single bit pattern per class — applied to BOTH sides before
    any sort/search/hash. Non-float keys pass through untouched."""
    if not jnp.issubdtype(data.dtype, jnp.floating):
        return data
    data = jnp.where(data == 0, jnp.zeros((), data.dtype), data)
    return jnp.where(jnp.isnan(data), jnp.asarray(np.nan, data.dtype),
                     data)


def build_sorted(key: Vec, sel) -> Tuple:
    """Sort build side by key; invalid rows pushed to the end.

    Returns (sorted_keys, perm, num_valid, valid_mask_sorted)."""
    from ..testing import faults
    faults.fire("join_build")  # chaos seam: fires at trace time
    cap = key.data.shape[0]
    invalid = jnp.zeros((cap,), jnp.int8)
    if sel is not None:
        invalid = (~sel).astype(jnp.int8)
    if key.validity is not None:
        invalid = invalid | (~key.validity).astype(jnp.int8)
    inv_s, keys_s, perm = sort_carrying_positions(
        (invalid, canon_key_data(key.data)))
    valid_s = inv_s == 0
    n_valid = jnp.sum(valid_s.astype(jnp.int32))
    # invalid slots carry arbitrary keys after the valid prefix;
    # overwrite with the sort order's +max so the array stays globally
    # sorted for binary search. For floats that is the canonical NaN
    # (valid NaN keys sort ABOVE +inf, so an inf sentinel would break
    # the order whenever the build has NaN keys and padding); sentinel
    # runs merging into a valid NaN run is fine — match ranges clip at
    # n_valid, exactly as they already do for valid +inf keys.
    if jnp.issubdtype(keys_s.dtype, jnp.floating):
        sentinel = jnp.asarray(np.nan, keys_s.dtype)
    else:
        sentinel = jnp.asarray(np.iinfo(np.dtype(keys_s.dtype)).max, keys_s.dtype)
    keys_s = jnp.where(valid_s, keys_s, sentinel)
    return keys_s, perm, n_valid, valid_s


def build_has_duplicates(sorted_keys, valid_sorted):
    """Traced bool: any two valid build rows share a key (adjacent
    check on the sorted keys). Drives the unique-build fast path's
    AQE fallback flag — a table-level property, conservatively True if
    ANY key repeats (even unmatched ones). NaN groups with NaN, as it
    does everywhere join keys compare (`==` alone would let duplicate
    NaN build keys slip past the many-to-many fallback and silently
    drop their extra matches)."""
    same = sorted_keys[1:] == sorted_keys[:-1]
    if jnp.issubdtype(sorted_keys.dtype, jnp.floating):
        same = same | (jnp.isnan(sorted_keys[1:])
                       & jnp.isnan(sorted_keys[:-1]))
    both = valid_sorted[1:] & valid_sorted[:-1]
    return jnp.any(same & both)


def search_sorted(sorted_keys, pk, side: str = "left"):
    """`jnp.searchsorted(sorted_keys, pk, side)` by ONE sort of the two
    arrays laid end to end (`sort_carrying_positions`: ties keep their
    order): a query's insertion point is the number of build keys that
    sort before it, which a running count over the sorted order gives,
    scattered back to the query's place by the position the sort
    carried along. `method="sort"` of
    `jnp.searchsorted` ranks the concatenation AND the queries, two
    64-bit argsorts and two scatters a call; XLA:TPU takes minutes to
    compile each such sort, and a join's stage is made of them (Q3's
    first request did not answer within 300 s on the chip; PERF.md,
    PR 37). Ties: for `left` the queries stand first, so a query
    sorts before the build keys equal to it; for `right` after them.
    NaN sorts last and equal to itself, as in `lax.sort`'s total
    order, which is the order `build_sorted` left the keys in."""
    n, m = sorted_keys.shape[0], pk.shape[0]
    dtype = jnp.promote_types(sorted_keys.dtype, pk.dtype)
    build, query = sorted_keys.astype(dtype), pk.astype(dtype)
    # where the queries stand in the concatenation: first or last
    first = 0 if side == "left" else n
    both = jnp.concatenate([query, build] if side == "left"
                           else [build, query])
    _, src = sort_carrying_positions((both,))
    query_here = (src >= first) & (src < first + m)
    builds_before = jnp.cumsum((~query_here).astype(jnp.int32))
    # a build key's count goes nowhere: past the end, each to a place
    # of its own, so that the indices are unique as declared (the
    # scatter then needs no sort of its own)
    return jnp.zeros((m,), jnp.int32).at[
        jnp.where(query_here, src - first,
                  m + jnp.arange(n + m, dtype=jnp.int32))].set(
            builds_before, mode="drop", unique_indices=True)


def match_unique(sorted_keys, n_valid, perm, probe_key: Vec, probe_sel):
    """Unique-build match: each probe row matches at most one build row
    (the FK->PK shape; reference: HashedRelation.scala keyIsUnique).
    ONE searchsorted + one build-sized gather; no expansion, no
    reindexing — probe columns pass through untouched.

    Returns (build_idx, found)."""
    pk = canon_key_data(probe_key.data)
    lo = search_sorted(sorted_keys, pk, side="left")
    lo = jnp.minimum(lo, sorted_keys.shape[0] - 1).astype(jnp.int32)
    hit = jnp.take(sorted_keys, lo)
    eq = hit == pk
    if jnp.issubdtype(sorted_keys.dtype, jnp.floating):
        # NaN keys join equal (the reference's NaN semantics): both
        # sides are canonicalized, so `lo` lands on the build's NaN run
        # and only the `NaN == NaN` comparison itself needs the assist
        eq = eq | (jnp.isnan(hit) & jnp.isnan(pk))
    found = eq & (lo < n_valid)
    if probe_key.validity is not None:
        found = found & probe_key.validity
    if probe_sel is not None:
        found = found & probe_sel
    build_idx = jnp.take(perm, lo)
    return build_idx, found


def match_ranges(sorted_keys, n_valid, probe_key: Vec, probe_sel):
    """Binary-search each probe key's build match range.

    Returns (lo, cnt): build rows [lo, lo+cnt) in sorted order match.
    cnt is 0 for unmatched/invalid/unselected probe rows.

    By sort, which matters on TPU: the default 'scan' binary search is
    log2(build) SEQUENTIAL whole-probe gathers (~1.4s for 8M probes,
    measured), while one extra lax.sort is ~100ms."""
    pk = canon_key_data(probe_key.data)
    lo = search_sorted(sorted_keys, pk, side="left")
    hi = search_sorted(sorted_keys, pk, side="right")
    lo = jnp.minimum(lo, n_valid).astype(jnp.int32)
    hi = jnp.minimum(hi, n_valid).astype(jnp.int32)
    found = hi > lo
    if probe_key.validity is not None:
        found = found & probe_key.validity
    if probe_sel is not None:
        found = found & probe_sel
    cnt = jnp.where(found, hi - lo, 0).astype(jnp.int32)
    return lo, cnt


def expand(lo, cnt_key, cnt_eff, perm, out_cap: int):
    """Prefix-sum expansion of match ranges into a static-capacity output.

    cnt_key[p] = number of key-matched build rows for probe row p;
    cnt_eff[p] = rows to emit for p (== cnt_key, or max(cnt_key,1) for
    outer joins that null-extend unmatched probe rows).

    Returns (p, build_idx, is_pair, valid, total):
      p[r]        probe row of output row r
      build_idx[r] build row (meaningful when is_pair[r])
      is_pair[r]  r is a key-matched pair (False => null-extension row)
      valid[r]    r < total emitted rows
      total       traced scalar: rows actually produced (host checks
                  against out_cap and re-jits on overflow)
    """
    cap = cnt_eff.shape[0]
    assert cap < (1 << 30) and perm.shape[0] < (1 << 30), \
        "expand packs (probe idx, lo) into one int64"
    off = jnp.cumsum(cnt_eff) - cnt_eff  # exclusive prefix sum
    total = off[-1] + cnt_eff[-1]
    r = jnp.arange(out_cap, dtype=jnp.int32)
    # Each emitting probe row owns a contiguous run of output rows
    # starting at off[p]; probe indices increase across runs. Pack
    # (probe idx, lo, cnt_key==0) into one int64, scatter it at each
    # run start (non-colliding) and forward-fill with a running max —
    # gathers (take(off/lo/cnt_key, p)) are ~10x slower than scans on
    # TPU and dominated the round-3 join profile (~1.7s of Q5).
    emitting = cnt_eff > 0
    pidx = jnp.arange(cap, dtype=jnp.int64)
    zflag = (cnt_key == 0).astype(jnp.int64)
    pack = (pidx << 32) | (lo.astype(jnp.int64) << 1) | zflag
    tgt = jnp.where(emitting, off, out_cap)
    packs = jnp.zeros((out_cap,), jnp.int64).at[tgt].set(pack, mode="drop")
    offm = jnp.zeros((out_cap,), jnp.int32).at[tgt].set(
        off.astype(jnp.int32), mode="drop")
    fill = jax.lax.cummax(packs)
    off_run = jax.lax.cummax(offm)  # start position of r's run
    p = (fill >> 32).astype(jnp.int32)
    lo_p = ((fill >> 1) & jnp.int64(0x3FFFFFFF)).astype(jnp.int32)
    j = r - off_run
    # j < cnt_key[p] <=> the run emits pairs (cnt_eff==cnt_key) and not
    # the cnt_key==0 null-extension run (cnt_eff=1, one row with j=0)
    is_pair = (fill & 1) == 0
    build_pos = jnp.clip(lo_p + j, 0, perm.shape[0] - 1)
    build_idx = jnp.take(perm, build_pos)
    valid = r < total
    return p, build_idx, is_pair & valid, valid, total


class RuntimeFilter:
    """A built runtime join filter: Bloom membership over hashed int64
    keys, plus [lo, hi] value bounds when the key dtype is ordered
    (numeric/date/timestamp/decimal) — the cheap range rejection that
    needs two compares instead of k hash probes."""

    def __init__(self, bloom, lo=None, hi=None):
        self.bloom = bloom
        self.lo = lo
        self.hi = hi


def _runtime_filter_key(vec: Vec):
    """(hashed int64 values, validity, ordered) for filter build/probe.

    Dictionary strings map through the per-dictionary VALUE hashes the
    shuffle uses, so build and probe sides with independently-built
    dictionaries hash equal strings equally (codes alone would not).
    `ordered` marks dtypes whose raw values support min/max bounds."""
    if vec.dictionary is not None:
        from ..parallel.shuffle import _dict_value_hashes
        table = _dict_value_hashes(vec.dictionary)
        if table.shape[0] == 0:
            # all-NULL / zero-row string column: a 0-entry dictionary
            # has nothing to take from; validity already masks every
            # row, so any constant hash is correct
            return jnp.zeros(vec.data.shape, jnp.int64), vec.validity, \
                False
        idx = jnp.clip(vec.data.astype(jnp.int32), 0, table.shape[0] - 1)
        return jnp.take(table, idx), vec.validity, False
    ordered = not isinstance(vec.dtype, (T.StringType, T.BooleanType))
    return vec.data.astype(jnp.int64), vec.validity, ordered


def build_runtime_filter(build_batch: Batch, key_expr, ctx,
                         expected_items: int, fpp: float = 0.03
                         ) -> RuntimeFilter:
    """Build a RuntimeFilter from the build-side key column. NULL keys
    are excluded (they never equi-match). Inside shard_map the per-shard
    Bloom bits pmax-combine (bitwise OR over the build's one-bit-a-byte
    staging array, packed to words after it) and the bounds pmin/pmax,
    so the filter covers every shard's build rows while staying
    replicated."""
    from ..sketch import BloomFilter
    vec = key_expr.eval(build_batch)
    hashed, validity, ordered = _runtime_filter_key(vec)
    mask = build_batch.selection_mask()
    if validity is not None:
        mask = mask & validity
    sharded = ctx.axis_name is not None and ctx.n_shards > 1
    # the engine's pmax/pmin, not lax's: see parallel/mesh.py for what
    # XLA:TPU does to narrow and to 64-bit operands
    from ..parallel.mesh import pmax, pmin
    bloom = BloomFilter.build(
        hashed, expected_items=expected_items, fpp=fpp, mask=mask,
        combine=(lambda staged: pmax(staged, ctx.axis_name))
        if sharded else None)
    lo = hi = None
    if ordered:
        raw = vec.data
        bmask = mask
        if jnp.issubdtype(raw.dtype, jnp.floating):
            pos = jnp.asarray(np.inf, raw.dtype)
            neg = jnp.asarray(-np.inf, raw.dtype)
            # a valid NaN build key would poison the bounds (NaN
            # propagates through min/max and every probe compare goes
            # False — an empty join). NaN never equi-matches anyway
            # (IEEE), so exclude it from the bounds; NaN probe keys
            # fail the range compare and prune, consistently with the
            # join's own equality.
            bmask = bmask & ~jnp.isnan(raw)
        else:
            info = np.iinfo(np.dtype(raw.dtype))
            pos = jnp.asarray(info.max, raw.dtype)
            neg = jnp.asarray(info.min, raw.dtype)
        lo = jnp.min(jnp.where(bmask, raw, pos))
        hi = jnp.max(jnp.where(bmask, raw, neg))
    if sharded and lo is not None:
        lo = pmin(lo, ctx.axis_name)
        hi = pmax(hi, ctx.axis_name)
    return RuntimeFilter(bloom, lo, hi)


def apply_runtime_filter(filt: RuntimeFilter, probe_batch: Batch,
                         key_expr):
    """Per-probe-row keep mask: False is a definite non-match (prune),
    True is probabilistic (the join still decides). NULL probe keys are
    pruned — an equi-join never matches them."""
    vec = key_expr.eval(probe_batch)
    hashed, validity, ordered = _runtime_filter_key(vec)
    keep = filt.bloom.might_contain(hashed)
    if filt.lo is not None and ordered:
        # range rejection on raw values: an empty build side leaves
        # lo > hi (the sentinels), which prunes everything — correct
        # for inner/semi joins
        keep = keep & (vec.data >= filt.lo) & (vec.data <= filt.hi)
    if validity is not None:
        keep = keep & validity
    return keep


def gather_columns(batch: Batch, idx, present,
                   name_map: Sequence[Tuple[str, str]]
                   ) -> List[Tuple[str, Column]]:
    """Gather columns at idx; validity &= present (rows where the side
    contributes no value — null-extensions — become NULL).

    Columns carrying provenance compose indices (``base[idx0[idx]]``)
    instead of gathering already-gathered data: the index composition is
    ONE gather shared by every column from the same origin (XLA CSE),
    and the upstream per-column gathers die by DCE unless something else
    consumes them. This is what makes a chain of N joins cost one
    payload gather per column instead of N (Q5's profile was dominated
    by per-join payload gathers)."""
    out = []
    for src_name, out_name in name_map:
        col = batch.columns[src_name]
        if col.prov is not None:
            base_data, base_valid, idx0, present0 = col.prov
            idx2 = jnp.take(idx0, idx)
            data = jnp.take(base_data, idx2)
            new_present = present if present0 is None else \
                (jnp.take(present0, idx) & present)
            if base_valid is not None:
                validity = jnp.take(base_valid, idx2) & new_present
            else:
                validity = new_present
            out.append((out_name, Column(
                data, col.dtype, validity, col.dictionary,
                prov=(base_data, base_valid, idx2, new_present))))
            continue
        data = jnp.take(col.data, idx)
        if col.validity is not None:
            validity = jnp.take(col.validity, idx) & present
        else:
            validity = present
        out.append((out_name, Column(data, col.dtype, validity,
                                     col.dictionary,
                                     prov=(col.data, col.validity, idx,
                                           present))))
    return out
