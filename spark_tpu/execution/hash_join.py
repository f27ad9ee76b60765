"""Hash build/probe equi-join kernel: open-addressing table over the
sorted build side.

The sort kernel (execution/join.py) binary-searches each probe key with
``jnp.searchsorted(..., method='sort')`` — correct and fast for small
probes, but each searchsorted call SORTS the probe side (two calls per
join), so on the join-bound TPC-H shapes (Q3/Q5: 6M-60M probe rows
against sub-million builds) the probe-side sorts dominate the profile.
This module is the ``BytesToBytesMap.java`` seat retold for XLA: build
a power-of-two open-addressing table (linear probing, murmur-mixed
int64 keys) over the build side's DISTINCT keys as device arrays, then
probe with a fixed-bound vectorized loop — O(expected cluster length)
small-table gathers per probe row instead of O(P log P) sort work.

Design notes:

- The build side is still sorted once (``join.build_sorted`` — the
  build is the small side, and sorting groups duplicate keys into
  runs). The table stores, per distinct key, the POSITION of its run
  start in the sorted array; run lengths come from a per-run count.
  The probe therefore returns the exact ``(lo, cnt)`` pair the sort
  kernel's ``match_ranges`` returns, so the many-to-many prefix-sum
  expansion (``join.expand``), the unique-build FK->PK fast path and
  every downstream gather are SHARED between kernels and the two
  paths produce byte-identical output (same rows, same order).
- Table capacity is a static power of two derived from the (already
  bucketed) build capacity and ``join.hashLoadFactor``, clamped by
  ``join.hashMaxTableSlots`` — stage keys stay stable per capacity
  bucket. A clamp that would push the load factor past
  ``_FALLBACK_LOAD_FACTOR`` falls back to the sort kernel at trace
  time (the analyzer's JOIN_HASH_TABLE_PRESSURE finding predicts
  this).
- Inserts claim vacant slots with a scatter-min among the round's
  contenders (occupied slots are never stolen, preserving the linear-
  probing invariant the probe's early-exit relies on); both loops are
  ``lax.while_loop``s bounded by ``join.hashMaxProbe`` with an
  all-done early exit. A build whose longest cluster exceeds the
  bound raises the ``join_hashsat_<tag>`` flag and the executor's AQE
  loop re-jits that join on the sort kernel — correctness never
  depends on the probe bound.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..expr import Vec

KERNEL_MODE_KEY = "spark_tpu.sql.join.kernelMode"
LOAD_FACTOR_KEY = "spark_tpu.sql.join.hashLoadFactor"
MAX_PROBE_KEY = "spark_tpu.sql.join.hashMaxProbe"
MAX_SLOTS_KEY = "spark_tpu.sql.join.hashMaxTableSlots"
MIN_PROBE_ROWS_KEY = "spark_tpu.sql.join.hashMinProbeRows"
PROBE_BUILD_RATIO_KEY = "spark_tpu.sql.join.hashProbeBuildRatio"

#: effective load factor past which a (maxTableSlots-clamped) table
#: degrades to long clusters: fall back to the sort kernel instead
_FALLBACK_LOAD_FACTOR = 0.7

#: bytes per table slot (int32 position) + the per-position run-count
#: array the probe gathers through — the analyzer's HBM estimate
SLOT_BYTES = 16


def _want_slots(build_cap: int, conf) -> int:
    """Unclamped table capacity: smallest power of two holding
    `build_cap` distinct keys at `hashLoadFactor`."""
    load = float(conf.get(LOAD_FACTOR_KEY))
    want = max(int(np.ceil(max(int(build_cap), 1) / load)), 16)
    return 1 << int(np.ceil(np.log2(want)))


def table_slots(build_cap: int, conf) -> int:
    """Static table capacity: `_want_slots` clamped by
    `hashMaxTableSlots`. `build_cap` is already bucketed (batch
    capacities always are), so the result is stable per capacity
    bucket."""
    # floor the clamp to a power of two: slot indexing masks with
    # `& (slots - 1)`, so a non-power-of-two conf value would leave
    # every slot above the highest mask bit unreachable
    max_slots = int(conf.get(MAX_SLOTS_KEY))
    return min(_want_slots(build_cap, conf),
               1 << (max_slots.bit_length() - 1))


def kernel_choice(conf, probe_cap: int, build_cap: int,
                  hash_fallback=None) -> Tuple[str, str]:
    """('hash'|'sort', reason) for one join instance, decided at trace
    time from static capacities — the ONE decision procedure, shared
    with the analyzer's JOIN_HASH_TABLE_PRESSURE prediction so the two
    can't drift. `hash_fallback` is the per-join AQE state: False means
    a previous attempt saturated the table (or the planner persisted
    that outcome) — stay on sort.

    Reasons: 'pinned' (AQE saturation pin), 'forced' (kernelMode said
    so), 'tpu' (auto never picks hash on a TPU: `_auto_keeps_sort`),
    'small-probe'/'ratio' (auto heuristics keep sort), 'clamp'
    (the mode WANTED hash but the maxTableSlots clamp pushes the load
    factor past the fallback bound — the degraded case the analyzer
    reports), 'auto' (auto picked hash)."""
    if hash_fallback is False:
        return "sort", "pinned"
    mode = str(conf.get(KERNEL_MODE_KEY))
    if mode == "sort":
        return "sort", "forced"
    if mode == "auto":
        if _auto_keeps_sort():
            return "sort", "tpu"
        # the table build amortizes only over large, probe-heavy joins
        if int(probe_cap) < int(conf.get(MIN_PROBE_ROWS_KEY)):
            return "sort", "small-probe"
        if int(probe_cap) < float(conf.get(PROBE_BUILD_RATIO_KEY)) \
                * int(build_cap):
            return "sort", "ratio"
    slots = table_slots(build_cap, conf)
    # the fallback bound applies only when the maxTableSlots clamp
    # actually reduced the table: an UNCLAMPED table honors the
    # configured hashLoadFactor by construction (power-of-two rounding
    # only lowers the effective load), and a user-chosen loadFactor in
    # (0.7, 0.9] is their call — saturation + the AQE sort pin still
    # backstop pathological clusters
    if slots < _want_slots(build_cap, conf) \
            and int(build_cap) > _FALLBACK_LOAD_FACTOR * slots:
        return "sort", "clamp"  # maxTableSlots: load factor too high
    return "hash", ("forced" if mode == "hash" else "auto")


def _auto_keeps_sort() -> bool:
    """True where `auto` must not pick the hash kernel whatever the
    capacities: on a TPU. Each step of the probe loop is three gathers
    over EVERY probe row, some 10 ns a row each on a v5e, and the loop
    runs as far as the data's longest cluster of keys; the sort
    kernel's whole search is one sort of probe and build together at
    under 3 ns a row. TPC-H Q3 at SF1 on one v5e: 3.9-4.8 s a request
    on the hash kernel, moving with the seed's values, 1.4 s on the
    sort kernel whatever they are (PERF.md, PR 37). `hashMinProbeRows`
    and `hashProbeBuildRatio` were set on CPU counts and decide there.
    `kernelMode=hash` still forces the kernel on any backend."""
    return jax.default_backend() == "tpu"


def resolve_kernel(conf, probe_cap: int, build_cap: int,
                   hash_fallback=None) -> str:
    return kernel_choice(conf, probe_cap, build_cap, hash_fallback)[0]


#: splitmix64-style finalizer seed (shared by build and probe — the
#: ONE requirement; value mirrors murmur3's c1 for no deeper reason)
_HASH_SEED = 0xCC9E2D51


def _hash_keys(keys, hash_dtype=None) -> jnp.ndarray:
    """Murmur-mixed int64 hash of a key column. Floats hash by BIT
    PATTERN (truncation to int would fold [0,1) onto one slot), with
    +-0.0 and NaN payloads canonicalized so keys the join treats as
    equal hash equal; collisions only cost probe steps — the table
    compares true key values.

    `hash_dtype` is the PROMOTED common dtype of the two key sides
    (jnp.promote_types): build and probe must hash under one dtype, or
    numerically equal mixed-precision keys (float32 probe vs float64
    build) hash different bit patterns and every match is silently
    missed. The cast mirrors the numeric promotion `==` applies in the
    probe's hit test and searchsorted applies in the sort kernel."""
    from ..sketch import _mix64
    from .join import canon_key_data
    if hash_dtype is not None and keys.dtype != hash_dtype:
        keys = keys.astype(hash_dtype)
    if jnp.issubdtype(keys.dtype, jnp.floating):
        keys = canon_key_data(keys)
        width = keys.dtype.itemsize * 8
        keys = jax.lax.bitcast_convert_type(
            keys, jnp.int32 if width == 32 else jnp.int64)
    return _mix64(keys.astype(jnp.int64), _HASH_SEED).astype(jnp.int64)


def _keys_equal(a, b):
    """Join-key equality, matching the sort kernel's searchsorted TOTAL
    order: NaN groups with NaN (the reference joins NaN keys equal,
    and `match_ranges` already does via sort order); +-0.0 compare
    equal under IEEE `==` as they do under sorting."""
    eq = a == b
    if jnp.issubdtype(a.dtype, jnp.floating):
        eq = eq | (jnp.isnan(a) & jnp.isnan(b))
    return eq


def build_table(keys_s, valid_s, slots: int, max_probe: int,
                hash_dtype=None) -> Tuple:
    """Insert each distinct valid build key into the open table.

    `keys_s`/`valid_s` come from ``join.build_sorted`` (valid prefix,
    invalid slots overwritten with a +max sentinel). Returns
    ``(t_pos, cnt_all, saturated)``:

      t_pos[s]    sorted-array position of the run START of the key
                  stored in slot s, or `cap` (empty)
      cnt_all[p]  number of VALID rows in position p's key run (valid
                  rows of a run are contiguous from its start, so
                  [start, start+cnt) are exactly the matches)
      saturated   traced bool: some key failed to claim a slot within
                  `max_probe` steps — the caller flags it and the AQE
                  loop re-jits on the sort kernel
    """
    cap = keys_s.shape[0]
    i32 = jnp.int32
    pos = jnp.arange(cap, dtype=i32)
    prev_same = jnp.concatenate(
        [jnp.zeros((1,), jnp.bool_), _keys_equal(keys_s[1:], keys_s[:-1])])
    is_start = (~prev_same) & valid_s
    # per-run valid-row counts: one scatter-add over the (small) build
    run_id = jnp.cumsum(is_start.astype(i32)) - 1
    counts = jnp.zeros((cap,), i32).at[
        jnp.where(valid_s, run_id, cap)].add(1, mode="drop")
    cnt_all = jnp.take(counts, jnp.clip(run_id, 0, cap - 1))

    h = (_hash_keys(keys_s, hash_dtype) & (slots - 1)).astype(i32)
    t_pos0 = jnp.full((slots,), cap, i32)

    def cond(state):
        d, _t, claimed = state
        return (d < max_probe) & ~jnp.all(claimed | ~is_start)

    def body(state):
        d, t_pos, claimed = state
        want = is_start & ~claimed
        s = (h + d) & (slots - 1)
        # min contender per slot this round, merged only into VACANT
        # slots: an occupied slot is never stolen, so the linear-
        # probing invariant (no vacancy between h(K) and K's slot)
        # holds and the probe may stop at the first vacancy
        scratch = jnp.full((slots,), cap, i32).at[
            jnp.where(want, s, slots)].min(pos, mode="drop")
        vacant = t_pos == cap
        t_new = jnp.where(vacant & (scratch < cap), scratch, t_pos)
        claimed = claimed | (want & (jnp.take(t_new, s) == pos))
        return d + 1, t_new, claimed

    _d, t_pos, claimed = jax.lax.while_loop(
        cond, body, (jnp.zeros((), i32), t_pos0,
                     jnp.zeros((cap,), jnp.bool_)))
    saturated = jnp.any(is_start & ~claimed)
    return t_pos, cnt_all, saturated


def probe_table(t_pos, cnt_all, keys_s, probe_key: Vec, probe_sel,
                slots: int, max_probe: int, hash_dtype=None) -> Tuple:
    """Vectorized fixed-bound probe: returns the sort kernel's
    ``(lo, cnt)`` contract (``join.match_ranges``) — build rows
    [lo, lo+cnt) in sorted order match; cnt is 0 for unmatched,
    NULL-key or unselected probe rows.

    Every inserted key sits within `max_probe` steps of its home slot
    with no vacancy before it, so a probe that hits a vacant slot (or
    exhausts the bound against a table built without saturation) has
    PROVEN a miss — no false negatives."""
    cap = keys_s.shape[0]
    i32 = jnp.int32
    pk = probe_key.data  # raw values: IEEE == already treats +-0 equal
    ph = (_hash_keys(probe_key.data, hash_dtype) & (slots - 1)).astype(i32)
    n = pk.shape[0]
    lo0 = jnp.zeros((n,), i32)
    cnt0 = jnp.zeros((n,), i32)
    done0 = jnp.zeros((n,), jnp.bool_)

    def cond(state):
        d, _lo, _cnt, done = state
        return (d < max_probe) & ~jnp.all(done)

    def body(state):
        d, lo, cnt, done = state
        s = (ph + d) & (slots - 1)
        tp = jnp.take(t_pos, s)
        occupied = tp < cap
        tpc = jnp.minimum(tp, cap - 1)
        hit = occupied & _keys_equal(jnp.take(keys_s, tpc), pk) & ~done
        lo = jnp.where(hit, tp, lo)
        cnt = jnp.where(hit, jnp.take(cnt_all, tpc), cnt)
        done = done | hit | ~occupied
        return d + 1, lo, cnt, done

    _d, lo, cnt, _done = jax.lax.while_loop(
        cond, body, (jnp.zeros((), i32), lo0, cnt0, done0))
    found = cnt > 0
    if probe_key.validity is not None:
        found = found & probe_key.validity
    if probe_sel is not None:
        found = found & probe_sel
    cnt = jnp.where(found, cnt, 0).astype(i32)
    return lo, cnt
