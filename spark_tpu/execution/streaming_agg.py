"""Streaming (chunked) aggregation driver.

The reference streams rows through operator iterators so working sets
never materialize (`WholeStageCodegenExec`'s produce/consume loop,
`TungstenAggregationIterator.scala:82`); a naive XLA translation instead
materializes the whole scan in HBM and dies on inputs larger than device
memory. This driver restores the streaming discipline at batch
granularity: a jitted `update(tables, chunk) -> tables` step is compiled
once and driven over input chunks (device-synthesized range chunks, or
host-ingested scan chunks), with accumulator tables donated across steps.
Narrow ops (project/filter) replay inside the update step, so XLA still
fuses scan->filter->aggregate into one kernel per chunk.

Streaming applies when the aggregate takes the dense-domain direct path
(statically-bounded group count). The sort-based general path falls back
to whole-input execution.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar import Batch, Column, bucket_capacity
from ..observability.spans import span
from ..plan import physical as P
from .chunk_stream import Carry, drive, run_through_joins
from .recovery import CHECKPOINT_EVERY_KEY

CHUNK_ROWS_KEY = "spark_tpu.sql.execution.streamingChunkRows"


def conf_compile_suffix(conf) -> str:
    """Conf values baked into traced programs but absent from plan
    describe() strings. Every compiled-stage cache key (executor stages
    and the chunk drivers below) appends this, so one stage cache
    shared across sessions with different overlays — or one session
    mutating conf between runs — can never serve a program compiled
    under other settings."""
    return (f"#k{conf.get('spark_tpu.sql.aggregate.kernelMode')}"
            f"#d{conf.get('spark_tpu.sql.aggregate.maxDirectDomain')}"
            f"#g{conf.get('spark_tpu.sql.execution.bucketGrowth')}"
            # mesh composition: shard_map closes over the Mesh object,
            # so a decommission that changed the device pool (same n,
            # different devices) must not reuse a program compiled
            # over a mesh containing the drained device
            f"#x{conf.get('spark_tpu.sql.mesh.excludeDevices')}")


#: join types where per-probe-chunk execution is sound: each probe row's
#: output is independent of other probe rows (right/full append
#: build-side rows once globally, so chunking the probe would emit them
#: per chunk)
_CHUNKABLE_JOINS = ("inner", "left", "left_semi", "left_anti")


def walk_chain(node: P.PhysicalPlan, allow_joins: bool = True
               ) -> Tuple[List, P.PhysicalPlan]:
    """The chain of Project/Filter — and, when `allow_joins`,
    probe-side-chunkable joins (the build side is an independent
    subtree, materialized once) — from `node` down, and the node under
    it."""
    chain = []
    while True:
        if isinstance(node, (P.ProjectExec, P.FilterExec)):
            chain.append(node)
            node = node.children[0]
        elif isinstance(node, P.RuntimeFilterExec):
            # a runtime filter is a pure pruning optimization: the join
            # it guards re-checks every key, so the streamed replay can
            # drop it (chunking already bounds residency)
            node = node.children[0]
        elif allow_joins and isinstance(node, P.JoinExec) \
                and node.how in _CHUNKABLE_JOINS:
            chain.append(node)
            node = node.children[0]  # continue down the probe side
        else:
            return chain, node


def find_streamable_chain(agg: "P.HashAggregateExec",
                          allow_joins: bool = True
                          ) -> Optional[Tuple[List, P.LeafExec]]:
    """agg.child must be a `walk_chain` over a single leaf."""
    chain, leaf = walk_chain(agg.child, allow_joins)
    if isinstance(leaf, (P.RangeExec, P.ScanExec)):
        return chain, leaf
    return None


def _replay_chain(chain: List, ctx, batch: Batch,
                  builds: Optional[dict] = None) -> Batch:
    for op in reversed(chain):
        if isinstance(op, P.JoinExec):
            batch = op.compute(ctx, [batch, builds[op.tag]])
        else:
            batch = op.compute(ctx, [batch])
    return batch


def prepare_chunk_joins(chain: List, conf, first_cap: int, recovery=None):
    """Shared chunk-driver setup: materialize each probe-side join's
    build subtree once (QueryStageExec role) and seed missing output
    capacities with the CHUNK capacity. Returns (joins, builds,
    saved_caps); learned caps stay on the plan nodes afterwards so the
    AQE cap harvest persists them — callers restore `saved_caps` only
    when aborting before any chunk ran."""
    joins = [op for op in chain if isinstance(op, P.JoinExec)]
    builds = {j.tag: _materialize_subtree(j.children[1], conf, recovery)
              for j in joins}
    saved_caps = {j.tag: j.out_cap for j in joins}
    for j in joins:
        if j.out_cap is None:
            j.out_cap = first_cap
    return joins, builds, saved_caps


def _materialize_subtree(root: P.PhysicalPlan, conf, recovery=None) -> Batch:
    """Compile + run an independent subtree (a join's build side) with
    its own AQE capacity-retry loop — a stage materialization, like the
    reference's QueryStageExec. Completed materializations land in the
    recovery stage-output memo (the surviving-shuffle-file analog), so
    a downstream failure's re-execution replays them instead of
    re-running."""
    if recovery is not None:
        hit = recovery.memo_get(("build", id(root)),
                                label=root.simple_string())
        if hit is not None:
            return hit
    scans: List[P.LeafExec] = []

    def collect(n):
        if getattr(n, "needs_input", False):
            scans.append(n)
        for c in n.children:
            collect(c)

    collect(root)
    from ..io.device_cache import load_scan
    # the subtree's program below is jitted for one device (no
    # shard_map), so it asks for the one-device copy whatever mesh the
    # query runs under, as it always has
    inputs = [load_scan(s, conf, None)[0] if isinstance(s, P.ScanExec)
              else s.load() for s in scans]
    # the executor's capacity setters, so every overflow family the main
    # AQE loop knows (join/exchange/aggregate) retries here too
    from .executor import QueryExecution
    adaptive = bool(conf.get("spark_tpu.sql.adaptive.enabled"))

    for _attempt in range(8):
        def run(ins):
            ctx = P.ExecContext(conf)
            counter = [0]

            def replay(n):
                if getattr(n, "needs_input", False):
                    b = ins[counter[0]]
                    counter[0] += 1
                    return b
                return n.compute(ctx, [replay(c) for c in n.children])

            out = replay(root)
            return out, ctx.flags, ctx.metrics

        batch, flags, metrics = jax.jit(run)(inputs)
        flags, metrics = jax.device_get((flags, metrics))
        overflow = [k for k, v in flags.items()
                    if k.startswith(("join_overflow_", "join_nonunique_",
                                     "exch_overflow_", "agg_overflow_"))
                    and bool(v)]
        if not overflow:
            if recovery is not None:
                recovery.memo_put(("build", id(root)), batch)
            return batch
        if not adaptive and any(
                not k.startswith("join_nonunique_") for k in overflow):
            raise RuntimeError(
                f"build-side capacity overflow in {overflow} with "
                f"adaptive re-planning disabled")
        for k in overflow:
            if k.startswith("join_nonunique_"):
                QueryExecution._set_join_nonunique(
                    root, k[len("join_nonunique_"):])
            elif k.startswith("join_overflow_"):
                tag = k[len("join_overflow_"):]
                total = int(metrics[f"join_rows_{tag}"])
                QueryExecution._set_join_cap(
                    root, tag, bucket_capacity(max(total, 8)))
            elif k.startswith("exch_overflow_"):
                tag = k[len("exch_overflow_"):]
                mx = int(metrics[f"exch_max_{tag}"])
                QueryExecution._set_exchange_cap(
                    root, tag, bucket_capacity(max(mx, 8)))
            else:
                tag = k[len("agg_overflow_"):]
                total = int(metrics[f"agg_groups_{tag}"])
                QueryExecution._set_agg_groups(root, tag, max(total, 8))
    raise RuntimeError("build-side capacity did not converge")


def _range_chunk(leaf: P.RangeExec, start, chunk_rows: int,
                 rows_total: int) -> Batch:
    """Synthesize one chunk of a Range in-trace; `start` is a traced row
    offset so one compiled step serves every chunk."""
    ids = leaf.start + leaf.step * (start + jnp.arange(chunk_rows,
                                                      dtype=jnp.int64))
    sel = (start + jnp.arange(chunk_rows, dtype=jnp.int64)) < rows_total
    return Batch({"id": Column(ids, T.LONG, bits=leaf._id_bits())}, sel)


def stream_range_aggregate(agg: "P.HashAggregateExec", chain: List,
                           leaf: P.RangeExec, conf,
                           cache: Optional[dict] = None) -> Optional[Batch]:
    """Run agg over a big Range in chunks. Returns the result batch, or
    None when the direct path doesn't apply. `cache` (the session stage
    cache) persists the compiled update step across executions — the
    analog of the reference's Janino codegen cache."""
    chunk_rows = int(conf.get(CHUNK_ROWS_KEY))
    rows_total = leaf.num_rows()
    # this stream has no host loop (`chunk_stream.drive`): its two
    # acts stand under `streaming` by the names a driven stream's have
    with span("stream.begin"):
        run = _range_program(agg, chain, leaf, conf, cache, chunk_rows,
                             rows_total)
    if run is None:
        return None
    with span("chunk.launch"):  # an enqueue: the one dispatch returning
        return run()


def _range_program(agg, chain, leaf, conf, cache, chunk_rows, rows_total):
    """The fused chunk loop over a Range, found in `cache` or built and
    kept there; None when the direct path does not apply."""
    key = (f"stream_range:{agg.describe()}:{chunk_rows}:{rows_total}"
           + conf_compile_suffix(conf))
    run = cache.get(key) if cache is not None else None
    if run is None:
        ctx = P.ExecContext(conf)
        probe = _replay_chain(chain, ctx,
                              _range_chunk(leaf, jnp.int64(0), 8, rows_total))
        prep = agg.prepare_direct(probe, conf)
        if prep is None:
            return None
        n_chunks = -(-rows_total // chunk_rows)

        # the source is device-synthesized, so the whole chunk loop fuses
        # into ONE dispatch (a lax.fori_loop with carried tables) — no
        # host round-trip per chunk
        if any(a.func.uses_row_base for a in agg.agg_exprs) \
                and n_chunks * chunk_rows >= (1 << 30):
            raise RuntimeError(
                "first/last over a streamed range exceeds the 2^30 "
                f"packed-position bound ({rows_total} rows)")

        @jax.jit
        def run():
            def body(i, tables):
                ctx = P.ExecContext(conf)
                b = _replay_chain(
                    chain, ctx,
                    _range_chunk(leaf, i.astype(jnp.int64) * chunk_rows,
                                 chunk_rows, rows_total))
                return agg.direct_update_tables(
                    tables, b, prep, conf,
                    row_base=i.astype(jnp.int64) * chunk_rows)

            tables = jax.lax.fori_loop(0, n_chunks, body,
                                       agg.direct_init_tables(prep))
            return agg.direct_finalize_tables(tables, prep)

        if cache is not None:
            cache[key] = run
    return run


def stream_scan_aggregate(agg: "P.HashAggregateExec", chain: List,
                          leaf: P.ScanExec, conf,
                          cache: Optional[dict] = None,
                          recovery=None) -> Optional[Batch]:
    """Run agg over a chunked Scan: host ingests record-batch chunks
    (uniform bucketed capacity so the update step compiles once) while the
    device reduces — the double-buffered host->HBM pipeline of SURVEY.md
    section 2.5 'Async/overlap' (io/sources.py PrefetchChunkIterator
    decodes chunk N+1 on a background thread while chunk N computes).
    The carry: accumulator tables on the device."""
    chunk_rows = int(conf.get(CHUNK_ROWS_KEY))

    def begin(first, dictionaries):
        joins, builds, saved_caps = prepare_chunk_joins(
            chain, conf, first.capacity, recovery)

        def make_update():
            key = (f"stream_scan:{agg.describe()}:{chunk_rows}"
                   + conf_compile_suffix(conf))
            bundle = cache.get(key) if cache is not None else None
            if bundle is None:
                ctx = P.ExecContext(conf)
                probe = _replay_chain(chain, ctx, first, builds)
                prep0 = agg.prepare_direct(probe, conf)
                if prep0 is None:
                    return None

                if joins:
                    def update(tables, b, bb, row_base):
                        ctx = P.ExecContext(conf)
                        b = _replay_chain(chain, ctx, b, bb)
                        new = agg.direct_update_tables(
                            tables, b, prep0, conf, row_base=row_base)
                        return new, ctx.flags, ctx.metrics

                    # no donation: a join-capacity overflow must re-run
                    # the SAME chunk against the pre-update tables
                    bundle = (prep0, jax.jit(update))
                else:
                    def update(tables, b, row_base):
                        ctx = P.ExecContext(conf)
                        b = _replay_chain(chain, ctx, b)
                        return agg.direct_update_tables(
                            tables, b, prep0, conf, row_base=row_base)

                    # join-free hot path: donate tables, no per-chunk
                    # host sync — the double-buffered host->HBM overlap
                    bundle = (prep0, jax.jit(update, donate_argnums=(0,)))
                if cache is not None:
                    cache[key] = bundle
            return bundle

        bundle = make_update()
        if bundle is None:
            for j in joins:  # leave the whole-input fallback's caps alone
                j.out_cap = saved_caps[j.tag]
            return None
        prep, update_fn = bundle
        tables = agg.direct_init_tables(prep)

        # running row base for position-packed aggregates: each chunk's
        # stride covers the largest post-replay capacity (join out_caps
        # only grow, so bases stay collision-free even across mid-run
        # re-jits)
        row_base = 0

        def chunk_stride(b):
            return max([b.capacity] + [j.out_cap or 0 for j in joins])

        def check_bound(b):
            if row_base + chunk_stride(b) >= (1 << 30) and \
                    any(a.func.uses_row_base for a in agg.agg_exprs):
                raise RuntimeError(
                    "first/last over a streamed scan exceeds the 2^30 "
                    "packed-position bound")

        def regrow(b):
            # out_cap is part of describe(): re-jit under the new key
            # (the grown out_cap widens the position stride — re-check)
            nonlocal update_fn
            _prep2, update_fn = make_update()
            check_bound(b)

        def fold(b, ci):
            nonlocal tables
            check_bound(b)
            base = jnp.asarray(row_base, jnp.int64)
            if not joins:
                new = update_fn(tables, b, base)
            else:
                new = run_through_joins(
                    lambda: update_fn(tables, b, builds, base),
                    lambda: regrow(b), joins, "stream_scan")
            # the step's last act, so still inside chunk.launch: letting
            # go of the donated tables gives up the interpreter lock, and
            # beside the filling threads it is a while in coming back
            tables = new

        def took(_new, b, ci):
            nonlocal row_base
            row_base += chunk_stride(b)

        def finish(drain):
            with drain:
                return jax.block_until_ready(agg.direct_finalize_tables(
                    tables, prep, dictionaries() or None))

        return Carry(fold, took, finish,
                     check=_dict_growth_guard(agg, prep))

    return drive(leaf, chunk_rows, conf, recovery, begin)


def drive_host_partials(leaf, conf, cache, recovery, driver: str, node,
                        chain: List, tail, finish, *, seed=(), start=0,
                        stop_rows=None):
    """`drive` with the carry of the spill aggregate and of external
    collect: each chunk's output pulled to host Arrow buffers (host RAM
    as the spill tier), concatenated at the end. They differ by what the
    chunk program does after the chain, `tail(ctx, b)`; by `finish`,
    what is made of the concatenated table under the drain; by the
    `seed` partials a resume from `start` prepends; and by the plain
    LIMIT's `stop_rows`. The program stands in the stage cache under
    `driver` and `node.describe()`, which moves with grown join caps."""
    import pyarrow as pa
    chunk_rows = int(conf.get(CHUNK_ROWS_KEY))
    spilled: List = list(seed)

    def concat():
        return pa.concat_tables(spilled, promote_options="permissive")

    def begin(first, dictionaries):
        joins, builds, _saved = prepare_chunk_joins(
            chain, conf, first.capacity, recovery)
        update_fn = None
        rows = 0

        def make_update():
            nonlocal update_fn
            key = (f"{driver}:{node.describe()}:{chunk_rows}"
                   + conf_compile_suffix(conf))
            update_fn = cache.get(key) if cache is not None else None
            if update_fn is None:
                # a NEW function for each program: jax.jit keeps its
                # trace by the function's identity, and a join's grown
                # out_cap is read only while tracing
                def update(b, bb):
                    ctx = P.ExecContext(conf)
                    out = tail(ctx, _replay_chain(chain, ctx, b, bb))
                    return out, ctx.flags, ctx.metrics

                update_fn = jax.jit(update)
                if cache is not None:
                    cache[key] = update_fn

        def fold(b, ci):
            # dictionary-encoded columns decode to strings in to_arrow, so
            # per-chunk dictionaries unify value-wise in the concat. The
            # host pull rides inside the retried step: a flake during
            # to_arrow replays only this chunk (not yet spilled)
            return run_through_joins(lambda: update_fn(b, builds),
                                     make_update, joins, driver).to_arrow()

        def took(table, b, ci):
            nonlocal rows
            spilled.append(table)
            rows += table.num_rows
            return stop_rows is not None and rows >= stop_rows

        def drained(drain):
            with drain:
                return finish(concat())

        make_update()
        return Carry(fold, took, drained)

    # empty: a resume that landed exactly at end-of-stream — the seed
    # checkpoint already covers every chunk
    return drive(leaf, chunk_rows, conf, recovery, begin,
                 lambda: concat() if spilled else None, start=start)


def stream_scan_aggregate_spill(agg: "P.HashAggregateExec", chain: List,
                                leaf: P.ScanExec, conf,
                                cache: Optional[dict] = None,
                                recovery=None, skip_chunks: int = 0,
                                seed_partials: Optional[List] = None):
    """Out-of-core aggregation for UNBOUNDED group keys (no static
    domain — e.g. TPC-H Q3's l_orderkey): stream probe chunks through
    device-resident build sides, reduce each chunk with a PARTIAL-mode
    sort aggregate (num_segments = chunk capacity, so per-chunk overflow
    is impossible), and spill the compacted partial batches to host
    Arrow buffers — host RAM plays the role the reference's executor
    disk plays for `UnsafeExternalSorter.java:1` /
    `ExternalAppendOnlyMap.scala:55`. Returns (concatenated host partial
    table, partial node) for the caller to re-reduce with a FINAL
    aggregate; None when the shape doesn't apply.

    The checkpoint-restore path reuses this driver to RESUME a failed
    mesh stream single-device: `skip_chunks` advances the chunk cursor
    past what the checkpoint already covers, and `seed_partials`
    prepends the checkpointed partial tables to the spill list."""
    import copy
    partial = copy.copy(agg)
    partial.mode = "partial"
    # num_segments falls back to the post-replay batch capacity: a chunk
    # can never have more groups than rows, so the per-chunk partial
    # needs no overflow retry of its own
    partial.est_groups = None
    # the join capacities the plan came with go back on at the end
    planned = [(j, j.out_cap) for j in chain
               if isinstance(j, P.JoinExec) and j.out_cap is not None]

    def finish(table):
        for j, cap in planned:
            j.out_cap = cap
        return table

    table = drive_host_partials(
        leaf, conf, cache, recovery, "stream_spill", agg, chain,
        lambda ctx, b: partial.compute(ctx, [b]), finish,
        seed=seed_partials or (), start=int(skip_chunks))
    return None if table is None else (table, partial)


def _spillable_scan(agg: "P.HashAggregateExec"):
    """(chain, leaf) when `agg` can stream through the partial-spill
    driver: a complete-mode aggregate whose accumulators decompose, over
    a chain down to a chunkable scan; else None."""
    if agg.mode != "complete":
        return None
    if any(a.func.uses_row_base for a in agg.agg_exprs):
        return None  # packed-position aggs need whole-input row order
    if any(getattr(a.func, "positional", False) for a in agg.agg_exprs):
        return None  # no accumulator decomposition: whole-input only
    found = find_streamable_chain(agg)
    if found is None or not isinstance(found[1], P.ScanExec) or \
            not hasattr(found[1].source, "load_chunks"):
        return None
    return found


def try_stream_aggregate_spill(agg: "P.HashAggregateExec", conf,
                               cache: Optional[dict] = None,
                               recovery=None):
    """Device-budget gate for the out-of-core partial-spill path:
    engages when the probe scan's working set cannot stay resident —
    its estimated footprint exceeds the per-query
    `spark_tpu.sql.memory.deviceBudget`, or the cross-query arbiter
    (service/arbiter.py) denied the residency lease from the shared
    HBM pool (UnifiedMemoryManager.scala:49's execution-pool analog,
    now genuinely shared across concurrent queries)."""
    from ..service.arbiter import admit_scan_resident, out_of_core_active
    if not out_of_core_active(conf):
        return None
    found = _spillable_scan(agg)
    if found is None or admit_scan_resident(conf, found[1], None):
        return None  # (the executor comes here with no mesh only)
    return stream_scan_aggregate_spill(agg, *found, conf, cache, recovery)


def _dict_growth_guard(agg: "P.HashAggregateExec", prep):
    """Guard: a chunk whose dictionary outgrows the padded direct domain
    would silently alias groups; fail loudly instead (shared by the
    single-chip and mesh streaming drivers)."""
    dict_limits = {}
    for g, (dom, _lo), dic in zip(agg.group_exprs, prep.domains,
                                  prep.key_dicts):
        if dic is not None and len(g.references()) == 1:
            dict_limits[next(iter(g.references()))] = dom

    def check_dicts(b: Batch):
        for name, limit in dict_limits.items():
            col = b.columns.get(name)
            if col is not None and col.dictionary is not None \
                    and len(col.dictionary) > limit:
                raise RuntimeError(
                    f"dictionary of {name!r} grew past the padded direct "
                    f"domain ({len(col.dictionary)} > {limit}); raise "
                    f"spark_tpu.sql.aggregate.maxDirectDomain or disable "
                    f"streaming")

    return check_dicts


def checkpoint_key(agg: "P.HashAggregateExec", leaf: P.ScanExec,
                   chunk_rows: int) -> str:
    """Plan-independent identity of a resumable stream: the mesh
    partial aggregate that SAVES a checkpoint and the single-device
    complete aggregate that RESTORES it are different physical nodes
    from different plans, but stream the same source rows under the
    same chunk boundaries into the same aggregation. Source identity
    (cache token), pruned columns, pushed-filter count, group/agg
    names and the chunk size pin all of that; any mismatch (e.g. the
    OOM ladder shrank streamingChunkRows) makes the checkpoint
    unmatchable and the fallback safely restarts from chunk 0."""
    token = leaf.source.cache_token()
    src = repr(token) if token is not None else f"name:{leaf.source.name}"
    cols = sorted(leaf.required_columns or [])
    # filter VALUES, not count: two same-shaped aggregates over the
    # same source differing only in predicate literals must not share
    # a checkpoint slot (name() renders literals: "(l_shipdate <= N)")
    filters = sorted(f.name() for f in (leaf.pushed_filters or ()))
    groups = [g.name() for g in agg.group_exprs]
    aggs = [f"{type(a.func).__name__}:{a.out_name}" for a in agg.agg_exprs]
    return (f"{src}|cols{cols}|f{filters}"
            f"|g{groups}|a{aggs}|c{chunk_rows}")


def _with_dict_overrides(batch: Batch, dict_overrides: dict) -> Batch:
    """Swap grown global dictionaries into a partial/final batch's
    dictionary-encoded columns (codes handed out earlier stay valid —
    DictUnifier grows append-only)."""
    if not dict_overrides:
        return batch
    cols = dict(batch.columns)
    for name, dic in dict_overrides.items():
        if name in cols and cols[name].dictionary is not None:
            c = cols[name]
            cols[name] = type(c)(c.data, c.dtype, c.validity, dic)
    return Batch(cols, batch.selection)


def _record_restore(recovery, ck, **extra) -> None:
    """A resume from checkpoint `ck` is running: record it, with the
    chunks it replays."""
    replayed = recovery.restore_replayed(ck.key, ck.cursor)
    recovery.record("checkpoint_restore", None, cursor=int(ck.cursor),
                    ckpt_rows=int(ck.table.num_rows),
                    chunks_replayed=replayed, **extra)


def resume_from_mesh_checkpoint(agg: "P.HashAggregateExec", conf,
                                cache: Optional[dict] = None,
                                recovery=None):
    """Mesh-fallback restore: when the failed mesh stream left a
    checkpoint matching this (single-device, complete-mode) aggregate,
    resume at the checkpointed chunk cursor — stream the REMAINING
    chunks through the partial-spill driver with the checkpointed
    partial rows prepended, for the caller to re-reduce with a FINAL
    aggregate. Returns (partial table, partial node) like
    stream_scan_aggregate_spill, or None when no checkpoint applies."""
    if recovery is None or not recovery.checkpoints:
        return None
    found = _spillable_scan(agg)
    if found is None:
        return None
    chunk_rows = int(conf.get(CHUNK_ROWS_KEY))
    ck = recovery.get_checkpoint(checkpoint_key(agg, found[1], chunk_rows))
    if ck is None:
        return None
    out = stream_scan_aggregate_spill(agg, *found, conf, cache,
                                      recovery=recovery,
                                      skip_chunks=ck.cursor,
                                      seed_partials=[ck.table])
    if out is not None:
        _record_restore(recovery, ck)
    return out


def _streamable_string_keys(agg, child_schema) -> bool:
    """Only bare string column references stream (their dictionary grows
    append-only via DictUnifier); derived string keys rebuild per-chunk
    dictionaries with unstable codes."""
    from ..expr import Alias, ColumnRef
    for g in agg.group_exprs:
        e = g
        while isinstance(e, Alias):
            e = e.child
        if not isinstance(e, ColumnRef) and \
                isinstance(e.dtype(child_schema), T.StringType):
            return False
    return True


def stream_scan_aggregate_mesh(agg: "P.HashAggregateExec", mesh, conf,
                               cache: Optional[dict] = None,
                               recovery=None) -> Optional[Batch]:
    """Chunked host ingest under a mesh: each chunk is sharded over the
    data axis and folded into PER-SHARD accumulator tables by a jitted
    shard_map step; the final step emits each shard's partial batch, so
    the (already planned) exchange + final aggregate run unchanged.

    This is the round-2 gap VERDICT weak #7: distributed runs used to
    materialize entire scans. The carry: partial tables, [n, total]-shaped
    arrays sharded on dim 0 — only accumulator-table bytes stay resident
    between chunks — with what a gang needs at the chunk boundary."""
    if agg.mode != "partial":
        return None
    if any(getattr(a.func, "positional", False) for a in agg.agg_exprs):
        return None  # no accumulator decomposition: whole-input only
    # mesh streaming is unary-only: a streamed join would need the build
    # replicated per shard — future work
    found = find_streamable_chain(agg, allow_joins=False)
    if found is None:
        return None
    chain, leaf = found
    if not isinstance(leaf, P.ScanExec):
        return None  # Range synthesizes in-trace; nothing to stream
    if not _streamable_string_keys(agg, agg.child.schema()):
        return None
    if not hasattr(leaf.source, "load_chunks"):
        return None
    chunk_rows = int(conf.get(CHUNK_ROWS_KEY))
    est = leaf.source.estimated_rows()
    if est is not None and est <= chunk_rows:
        return None
    from ..io.device_cache import scan_mesh
    if _prefer_resident(leaf, conf, scan_mesh(leaf, mesh),
                        getattr(recovery, "metrics", None)):
        return None

    import contextlib
    import time as _time
    import pyarrow as pa
    from jax.sharding import PartitionSpec as Psp
    from ..observability.spans import current_shard_telemetry
    from ..parallel import elastic as EL
    from ..parallel.mesh import AXIS, shard_map
    n = int(mesh.devices.size)
    telem = current_shard_telemetry()
    needs_base = any(a.func.uses_row_base for a in agg.agg_exprs)
    every = int(conf.get(CHECKPOINT_EVERY_KEY))
    # position-packed aggregates are excluded from checkpoint/resume
    # AND rebalance — their packed row bases encode assignment order
    ck_key = checkpoint_key(agg, leaf, chunk_rows) \
        if recovery is not None and not needs_base else None
    save_key = ck_key if every > 0 else None
    # elastic resume: a gang restart (or decommission re-execution)
    # re-enters this driver with the failed stream's checkpoint intact
    # — skip the covered chunks and merge the checkpointed partial
    # rows at emit, so the recovery replays at most everyChunks chunks
    # ON the mesh (the mesh-side analog of resume_from_mesh_checkpoint)
    ck = recovery.get_checkpoint(ck_key) if ck_key is not None else None
    # straggler rebalancing (parallel/elastic.py): inert until the
    # ElasticRebalancer flags a shard via on_straggler, then each
    # chunk's rows skew away from it. Position-packed aggregates keep
    # the even split (their packed bases encode assignment).
    rebal = EL.RebalanceState(n, conf, recovery=recovery) \
        if not needs_base else None
    rebalancing = contextlib.ExitStack()  # open for the chunk loop alone

    def empty():
        if ck is None:
            return None
        # resume landed exactly at end-of-stream: the checkpoint
        # already covers every chunk — its partial rows ARE the
        # stream's result (the exchange + final above re-reduce)
        _record_restore(recovery, ck, driver="mesh")
        return Batch.from_arrow(ck.table)

    def begin(first, dictionaries):
        key = (f"stream_mesh:{agg.describe()}:{chunk_rows}:{n}"
               + conf_compile_suffix(conf))
        bundle = cache.get(key) if cache is not None else None
        if bundle is None:
            ctx = P.ExecContext(conf)
            probe = _replay_chain(chain, ctx, first)
            prep = agg.prepare_direct(probe, conf)
            if prep is None:
                return None

            def update(tables, b, chunk_base):
                t = jax.tree_util.tree_map(lambda x: x[0], tables)
                ctx = P.ExecContext(conf)
                local = _replay_chain(chain, ctx, b)
                # unique packed positions: chunks stride the full chunk
                # capacity (host counter), shards stride the local
                # capacity
                base = chunk_base + jax.lax.axis_index(AXIS) \
                    .astype(jnp.int64) * local.capacity
                new = agg.direct_update_tables(t, local, prep, conf,
                                               row_base=base)
                # per-shard telemetry channel: this shard's live rows
                # this chunk, shape [1] so the sharded stack is [n] with
                # one device-resident slot per shard
                # (spans.ShardStreamTelemetry times per-shard readiness
                # off exactly this array)
                live = jnp.sum(
                    local.selection_mask().astype(jnp.int64))[None]
                return jax.tree_util.tree_map(lambda x: x[None], new), live

            def emit(tables):
                t = jax.tree_util.tree_map(lambda x: x[0], tables)
                return agg.direct_partial_batch(t, prep)

            update_step = jax.jit(shard_map(
                update, mesh=mesh, in_specs=(Psp(AXIS), Psp(AXIS), Psp()),
                out_specs=(Psp(AXIS), Psp(AXIS)), check_vma=False),
                donate_argnums=(0,))
            emit_step = jax.jit(shard_map(
                emit, mesh=mesh, in_specs=(Psp(AXIS),),
                out_specs=Psp(AXIS), check_vma=False))
            # prep MUST live in the bundle: the jitted closures capture
            # it, so a cache hit with a fresh prep would silently mix
            # layouts
            bundle = (prep, update_step, emit_step)
            if cache is not None:
                cache[key] = bundle
        prep, update_step, emit_step = bundle

        # per-shard neutral tables, [n, total] sharded on dim 0
        cnt0, accs0 = agg.direct_init_tables(prep)
        tables = (jnp.broadcast_to(cnt0, (n,) + cnt0.shape),
                  [[jnp.broadcast_to(a, (n,) + a.shape) for a in row]
                   for row in accs0])
        chunk_base = 0
        # each folded chunk's per-shard live rows, [n] on the device:
        # pulled once, after the drain, into shard_rows_max / _total
        folded = []

        def row_width(b):
            return sum(c.data.dtype.itemsize
                       + (1 if c.validity is not None else 0)
                       for c in b.columns.values())

        def emit_rows():
            # the per-shard partial rows (the exact shape a FINAL
            # aggregate consumes) against the dictionaries grown so far —
            # every code folded so far is covered (append-only)
            return _with_dict_overrides(emit_step(tables), dictionaries())

        def with_seed(t: pa.Table) -> pa.Table:
            # a RESUMED stream's accumulators only cover the post-cursor
            # chunks: the seed checkpoint's rows go first, so neither a
            # later restore nor the FINAL aggregate above loses the head
            # of the stream
            return t if ck is None else pa.concat_tables(
                [ck.table, t], promote_options="permissive")

        def snapshot():  # device->host checkpoint of the accumulators
            return with_seed(emit_rows().to_arrow())

        def between(ci, b, t_in0, t_in1):
            # graceful decommission: a pending drain request applies at
            # the chunk boundary — checkpoint forced at the current
            # cursor so the reduced gang resumes here, then the request
            # surfaces to the executor, which excludes the draining
            # devices and re-executes. The `decommission` seam fires
            # FIRST: a fault injected there models the drain machinery
            # dying, and rides the normal mesh ladder.
            drain, drain_ids = EL.pending_decommission(conf, mesh)
            if drain:
                from ..testing import faults
                faults.fire("decommission")
                if save_key is not None and ci > 0:
                    recovery.save_checkpoint(save_key, ci, snapshot)
                raise EL.MeshDecommissionRequest(drain, drain_ids)
            if telem is not None:
                telem.chunk_ingested(ci, b.capacity,
                                     b.capacity * row_width(b),
                                     t_in0, t_in1)

        def fold(b, ci):
            nonlocal tables, chunk_base
            padded = EL.pad_chunk_for_shards(b, n, rebal)
            if needs_base and chunk_base + padded.capacity >= (1 << 30):
                raise RuntimeError(
                    "first/last over a streamed mesh scan exceeds the "
                    "2^30 packed-position bound")
            t_disp = _time.perf_counter()
            out, shard_rows = update_step(
                tables, padded, jnp.asarray(chunk_base, jnp.int64))
            if telem is not None:
                # hot path stays sync-free: the device array is buffered;
                # the PREVIOUS chunk's buffer flushes inside this call
                telem.chunk_dispatched(ci, shard_rows, row_width(b),
                                       t_disp)
            chunk_base += padded.capacity
            folded.append(shard_rows)
            tables = out  # the step's last act: see stream_scan_aggregate

        def took(_out, b, ci):
            if ck_key is not None:
                # consumed-chunk watermark: bounds the replay a later
                # checkpoint restore reports (restore_replayed)
                recovery.note_progress(ck_key, ci + 1)
            if save_key is not None and (ci + 1) % every == 0:
                recovery.save_checkpoint(save_key, ci + 1, snapshot)

        def finish(drain):
            rebalancing.close()
            if telem is not None:
                telem.finish()  # flush the last chunk's buffered records
            with drain:
                out = jax.block_until_ready(emit_rows())
            registry = getattr(recovery, "metrics", None)
            if registry is not None:
                # ready since the drain: a pull, not a wait
                registry.count_shard_rows(
                    np.sum(jax.device_get(folded), axis=0))
            return out if ck is None \
                else Batch.from_arrow(with_seed(out.to_arrow()))

        if ck is not None:
            # the bundle exists and the cursor was skipped: the resume is
            # definitely running — record it (with its bounded replay)
            _record_restore(recovery, ck, driver="mesh")
        rebalancing.enter_context(EL.use_rebalance(rebal))
        return Carry(fold, took, finish, _dict_growth_guard(agg, prep),
                     between)

    with rebalancing:
        return drive(leaf, chunk_rows, conf, recovery, begin, empty,
                     start=ck.cursor if ck is not None else 0)


def _prefer_resident(leaf: "P.ScanExec", conf, mesh,
                     metrics=None) -> bool:
    """True when the scan should load whole and ride the device-table
    cache instead of streaming: it's already cached, or its estimated
    footprint fits in half the cache budget (so repeated queries skip
    host ingest entirely — the round-3 headline perf fix). The budget
    is a chip's: under a `mesh` (what `scan_mesh` gave for this scan;
    None: one device) the scan is laid over its shards and what one
    of them must hold is the estimate's share. The verdict counts into `scans_resident` /
    `scans_streamed` of `metrics`: a streamed scan asks the cache only
    `contains()`, which counts neither a hit nor a miss."""
    resident = _resident_verdict(leaf, conf, mesh)
    if metrics is not None:
        metrics.counter("scans_resident" if resident
                        else "scans_streamed").inc()
    return resident


def _resident_verdict(leaf: "P.ScanExec", conf, mesh) -> bool:
    from ..io.device_cache import (CACHE_BYTES_KEY, chip_share,
                                   estimated_scan_bytes, is_cached,
                                   scan_cache_key)
    from ..service.arbiter import admit_scan_resident
    # cheap disqualifiers FIRST: admit_scan_resident takes a
    # full-estimate lease from the shared pool, and leases are held to
    # query end — a scan that was never going to ride the cache must
    # not reserve est-sized headroom while it streams chunk-sized
    budget = int(conf.get(CACHE_BYTES_KEY))
    if budget <= 0:
        return False
    if scan_cache_key(leaf, mesh) is None:
        return False  # uncacheable source: residency would re-ingest
    if not is_cached(leaf, mesh):
        est_b = chip_share(estimated_scan_bytes(leaf), mesh)
        if est_b is None or est_b > budget // 2:
            return False
    return admit_scan_resident(conf, leaf, mesh)
    # False = over the per-query budget, or the shared-pool lease was
    # denied (arbiter): must stream


def try_stream_aggregate(agg: "P.HashAggregateExec", conf,
                         cache: Optional[dict] = None,
                         recovery=None) -> Optional[Batch]:
    if agg.mode != "complete":
        return None
    if any(getattr(a.func, "positional", False) for a in agg.agg_exprs):
        return None  # no accumulator decomposition: whole-input only
    found = find_streamable_chain(agg)
    if found is None:
        return None
    if not _streamable_string_keys(agg, agg.child.schema()):
        return None
    chain, leaf = found
    chunk_rows = int(conf.get(CHUNK_ROWS_KEY))
    if isinstance(leaf, P.RangeExec):
        if any(isinstance(op, P.JoinExec) for op in chain):
            return None  # joined Range: whole-input execution
        if leaf.num_rows() <= chunk_rows:
            return None
        return stream_range_aggregate(agg, chain, leaf, conf, cache)
    est = leaf.source.estimated_rows()
    if est is not None and est <= chunk_rows:
        return None
    if not hasattr(leaf.source, "load_chunks"):
        return None
    if _prefer_resident(leaf, conf, None,
                        getattr(recovery, "metrics", None)):
        return None
    return stream_scan_aggregate(agg, chain, leaf, conf, cache, recovery)
