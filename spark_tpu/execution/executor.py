"""Query execution driver.

Mirrors the reference's `execution/QueryExecution.scala` phase pipeline
(analyzed -> optimizedPlan -> sparkPlan -> executedPlan -> toRdd), except
the terminal artifact is a single jitted stage function over columnar
Batches instead of an RDD DAG: XLA compilation replaces both Janino
whole-stage codegen and task scheduling for the single-chip path. The
compiled-stage cache keyed on the physical plan fingerprint is the analog
of `CodeGenerator.compile:1435`'s Janino cache.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from ..columnar import Batch
from ..config import Conf
from ..plan import logical as L
from ..plan import physical as P
from ..plan.optimizer import default_optimizer
from ..plan.planner import plan_physical


class _ReplanRequest(Exception):
    """Internal: restart execution after a strategy re-plan."""


DISPATCH_POLL_KEY = "spark_tpu.execution.dispatchPollMs"


#: When a runtime filter's output is worth compacting
#: (`QueryExecution._learn_filter_caps`): the probe has at least
#: MIN_SLOTS slots, and the bucket of the rows the filter kept, times
#: SHRINK, fits them. Constants, set by a sweep on one TPU v5e (my chip
#: run, PR 38, call 2: a probe of N slots compacted to N/4, four
#: columns, against one N-row gather above the filter run at N/4
#: instead): at a quarter, the compaction costs 17.3 ms at 1 Mi slots
#: and one gather saved gives back 12.5, so two of the eight gathers
#: and nine scatters a join and an aggregate hold above a filter pay
#: for it (at half, a compaction gathers twice the rows to save a
#: third less); at 65,536 slots it costs 1.86 ms and a gather saved
#: gives 0.73, so all seventeen passes together save some 10 ms an
#: execution, under which a second compile of the stage (minutes cold,
#: seconds from the compile cache) is not paid back in a process's
#: life; at 4,096 a gather saved gives nothing
FILTER_COMPACT_MIN_SLOTS = 1 << 16
FILTER_COMPACT_SHRINK = 4

#: When a hash or range exchange's block is learned from the bucket it
#: filled (`QueryExecution._learn_exchange_caps`): a shard receives at
#: least MIN_SLOTS slots (n x the block), and the block the fullest
#: bucket needs is at most half the seeded one. The join's gathers
#: above an exchange cost some 8 ns a slot on one TPU v5e whatever the
#: row (PERF.md section 7), so halving 1 Mi receive slots saves a few
#: ms of gathers an execution, which a second compile of the stage
#: (minutes cold, seconds from the compile cache) does not repay in a
#: process's life; Q5 at SF10 receives 32 Mi slots of LINEITEM a shard
EXCHANGE_SHRINK_MIN_SLOTS = 1 << 20


def _filter_kept(metrics: Dict, tag: str) -> int:
    """The rows runtime filter `tag` kept, from its two counts."""
    return int(metrics[f"rtf_tested_{tag}"]) \
        - int(metrics[f"rtf_pruned_{tag}"])


def _tag_attr(span, record: Dict, prefix: str, attr: str) -> int:
    """`attr=<tag>=<value>,...` on `span` from `record`'s
    `<prefix><tag>` keys, in tag order; how many there were."""
    found = sorted((k[len(prefix):], v) for k, v in record.items()
                   if k.startswith(prefix))
    if found:
        span.attrs[attr] = ",".join(f"{tag}={v}" for tag, v in found)
    return len(found)


#: name of the short-lived thread a sync that has to wait starts
#: (`LockWatch.assert_no_thread_leak` finds a leak by this prefix)
SYNC_WAITER_THREAD = "spark-tpu-dispatch-sync"


def _await_ready(leaves, done):
    """The waiter thread's whole life: block until the device is done
    with `leaves`, then wake the query's thread. It only waits: a
    device error is swallowed here and raised by the pull, on the
    thread that runs the query."""
    try:
        jax.block_until_ready(leaves)
    except BaseException:
        pass
    finally:
        done.set()


def _sync_dispatched(outs, conf, span=None):
    """Host-sync a dispatched stage's stats channel, cancellably.

    `jax.device_get` blocks until the device computation completes, so
    a cancel of a DISPATCHED stage used to land only when the stage
    finished. With a cancel token installed and dispatchPollMs > 0 the
    wait is for the device and not for a clock: a stage that is ready
    already is pulled at once; otherwise one daemon thread
    (`SYNC_WAITER_THREAD`, one a sync and never shared: a waiter
    blocked on one session's stage would find another's late) blocks
    on the output arrays and sets an event when they are ready, so the
    query's thread wakes a thread's hand-over after the device ends,
    however long the stage ran. It waits on that event in slices of at
    most dispatchPollMs and at most the deadline's remaining budget,
    and checks the token after each (`CancelToken.wait(on=)`): a
    DELETE /queries/<id> (which sets the same event and so wakes the
    wait at once) or a blown
    queryDeadlineMs raises the structured lifecycle error within one
    slice (the device compute keeps running in the background, XLA
    offers no kill, and the waiter ends with it, holding nothing of the
    query but the arrays; the host thread, its leases and its session
    lease are released promptly). Checks the token DIRECTLY rather
    than through lifecycle.checkpoint: how many slices a stage takes
    is timing-dependent, and routing them through the `cancel_point`
    chaos seam would make the cancel matrix's nth-boundary targeting
    nondeterministic.

    The pull stays the last act on the query's thread, so a device
    error surfaces there, in `dispatch.sync`, under _execute_recover.

    `span` (the caller's `dispatch.sync`) gets the attributes `ticks`,
    the slices slept through with the stage still running (0 for a
    stage shorter than dispatchPollMs, and where the sync blocked
    straight through), and `waited`, 1 where the stage was not ready
    at the call and a waiter was started."""
    from . import lifecycle
    tok = lifecycle.current_token()
    poll_s = float(conf.get(DISPATCH_POLL_KEY) or 0) / 1e3
    attrs = {} if span is None else span.attrs
    attrs["ticks"] = attrs["waited"] = 0
    if tok is not None and poll_s > 0:
        leaves = [a for a in jax.tree_util.tree_leaves(outs)
                  if hasattr(a, "is_ready")]
        if not all(a.is_ready() for a in leaves):
            attrs["waited"] = 1
            done = threading.Event()
            threading.Thread(
                target=_await_ready, args=(leaves, done),
                name=SYNC_WAITER_THREAD, daemon=True).start()
            while not tok.wait(poll_s, on=done):
                attrs["ticks"] += 1
    return jax.device_get(outs)


class QueryExecution:
    def __init__(self, session, logical: L.LogicalPlan, spans=None):
        from ..observability import SpanRecorder
        self.session = session
        self.logical = logical
        self._analyzed: Optional[L.LogicalPlan] = None
        self._optimized: Optional[L.LogicalPlan] = None
        self._executed: Optional[P.PhysicalPlan] = None
        self.phase_times: Dict[str, float] = {}
        self.last_metrics: Dict[str, float] = {}  # ints except the *_ms_* keys
        #: the largest `join_rows_*` of this query's stages so far
        #: (`_note_joins`: what `join_widest_rows` has counted of it)
        self._join_widest = 0
        # observability: lifecycle identity + per-phase spans (Chrome
        # -trace exportable) + the XLA cost/memory analysis of every
        # stage this execution compiled or reused (observability/)
        self.query_id: int = session._next_query_id()
        # `spans`: the recorder a served request was born with
        # (service/server.py), which this query adopts, so that its
        # `t0_ms` count from the request's first instant and the front
        # end's spans stand in the same tree
        self.spans = spans if spans is not None else SpanRecorder()
        self.spans.query_id = self.query_id
        self.spans.max_spans = int(session.conf.get(
            "spark_tpu.sql.observability.maxSpans"))
        self.spans.max_shard_records = int(session.conf.get(
            "spark_tpu.sql.observability.maxShardRecords"))
        self.stage_costs: Dict[str, dict] = {}
        # capacity/size predictions harvested from the planned tree
        # (analysis/predictions.py) — graded against observed metrics
        # by history.prediction_report / grade_predictions
        self.plan_predictions: Optional[list] = None
        # cost-based join-reorder decisions (plan/join_reorder.py);
        # None until the optimizer ran for this execution
        self.reorder_decisions: Optional[list] = None
        # per-(batch, rule) application records from the plan-change
        # tracer (analysis/plan_integrity.py): the event-log rule_trace
        # payload + explain(rules=True); None until the optimizer ran
        self.rule_trace: Optional[list] = None
        # lite-mode plan-integrity findings, merged into
        # analysis_findings by _analyze_plan_phase (full mode raises
        # PlanIntegrityError from inside the optimizer instead)
        self._integrity_findings: list = []
        # set per execute_batch: False keeps event construction off the
        # hot path when nothing is listening
        self._observe_events = False
        self.spilled_partial_rows: Optional[int] = None
        # adaptive strategy re-plans (DynamicJoinSelection.scala:1):
        # {join_tag: strategy}, applied by executed_plan on re-plan
        self._join_overrides: Dict[str, str] = {}
        # failure handling (execution/failures.py): a degraded rerun
        # overlays conf (mesh fallback / spill reroute) without mutating
        # the session; counters feed the event log's fault_summary
        self._exec_conf = None  # Conf overlay, or None = session conf
        self._mesh_fallback = False
        self._oom_rung = 0
        self._retry_policy = None
        # elastic-mesh gang-restart budget (parallel/elastic.py),
        # created per execute_batch like the retry policy
        self._elastic = None
        self._last_stage_key: Optional[str] = None
        self.fault_summary: Dict[str, object] = {}
        self.fault_events: list = []
        # partial-progress recovery (execution/recovery.py): chunk
        # retrier conf + stage-output memo + mesh checkpoints, created
        # per execute_batch / external collect
        self._recovery = None
        # pre-compile static analysis (spark_tpu/analysis/): typed
        # findings from the plan walk + (gated) jaxpr walk; None until
        # the analyzer ran for this execution
        self.analysis_findings: Optional[list] = None
        self._analysis_posted = False
        # python-UDF evaluation summary (execution/python_eval.py):
        # the event-log `udf` record — mode, batch/row totals, worker
        # restarts; None when the query had no UDFs
        self.udf_summary: Optional[Dict] = None

    @property
    def _conf(self):
        """Effective conf for planning/execution: the session conf, or a
        degraded-mode overlay (mesh fallback pins mesh.size=0, the OOM
        ladder's spill rung pins a 1-byte device budget)."""
        return self._exec_conf if self._exec_conf is not None \
            else self.session.conf

    def _activate_conf(self) -> None:
        """Apply session conf to analysis-time context (the reference's
        SQLConf thread-activation — ContextVar-backed so concurrent
        service queries on other threads keep their own value)."""
        from ..expr import set_case_sensitive
        set_case_sensitive(bool(
            self.session.conf.get("spark_tpu.sql.caseSensitive")))

    @property
    def analyzed(self) -> L.LogicalPlan:
        if self._analyzed is None:
            t0 = time.perf_counter()
            self._activate_conf()
            self.logical.schema()  # eager name/type resolution raises here
            self._analyzed = self.logical
            t1 = time.perf_counter()
            self.phase_times["analysis"] = t1 - t0
            self.spans.record("analysis", t0, t1)
        return self._analyzed

    def _fingerprint(self, plan: L.LogicalPlan) -> str:
        """`session._plan_fingerprint` as a query pays for it: the
        tree's string and a `cache_token` of every scan's source."""
        with self.spans.span("plan.fingerprint"):
            return self.session._plan_fingerprint(plan)

    def _apply_cache(self, plan: L.LogicalPlan) -> L.LogicalPlan:
        """Substitute cached subtrees with scans over their materialized
        tables (reference: CacheManager.useCachedData). A MARKED but
        not-yet-materialized subtree appearing in any query materializes
        on first use, like the reference's InMemoryRelation. Matching is
        on the pre-optimization plan fingerprint."""
        session = self.session
        if not session._data_cache and not session._cache_requests:
            return plan
        root_fp = self._fingerprint(plan)

        def f(node):
            fp = self._fingerprint(node)
            table = session._data_cache.get(fp)
            if table is not None:
                # shared (service) or per-session result-cache hit: the
                # subtree replays from the materialized Arrow table
                session.metrics.counter("result_cache_hits").inc()
            if table is None and fp in session._cache_requests \
                    and fp != root_fp:
                # first use inside a larger query: materialize now (the
                # fp != root_fp guard leaves root execution to the
                # normal path, which fills the cache afterwards)
                sub = QueryExecution(session, session._cache_requests[fp])
                table = sub.collect()
                session._data_cache[fp] = table
            if table is not None:
                from ..io.sources import ArrowTableSource
                return L.Scan(ArrowTableSource("__cached__", table))
            return None

        # top-down so the largest cached subtree wins
        return plan.transform_down(f)

    def _resolve_scalar_subqueries(self, plan: L.LogicalPlan
                                   ) -> L.LogicalPlan:
        """Execute uncorrelated scalar subqueries and substitute their
        single value as a Literal — BEFORE optimization so the literal
        participates in pushdown (reference: PlanSubqueries +
        ScalarSubquery execution)."""
        from ..expr import Literal

        def expr_has(e) -> bool:
            if isinstance(e, L.ScalarSubqueryExpr):
                return True
            return any(expr_has(c) for c in e.children)

        if not any(expr_has(e) for e in L.iter_expressions(plan)):
            return plan  # skip the rebuild on the no-subquery hot path

        def fix(e):
            def f(node):
                if isinstance(node, L.ScalarSubqueryExpr):
                    if len(node.plan.schema().fields) != 1:
                        raise RuntimeError(
                            "scalar subquery must return exactly one "
                            "column")
                    table = QueryExecution(self.session,
                                           node.plan).collect()
                    if table.num_rows > 1:
                        raise RuntimeError(
                            "scalar subquery returned more than one row")
                    dt = node.plan.schema().fields[0].dtype
                    val = None if table.num_rows == 0 else \
                        table.column(0)[0].as_py()
                    return Literal(val, dt)
                return node
            return e.transform_up(f)

        return L.map_expressions(plan, fix)

    @property
    def optimized_plan(self) -> L.LogicalPlan:
        if self._optimized is None:
            t0 = time.perf_counter()
            plan = self._apply_cache(self.analyzed)
            plan = self._resolve_scalar_subqueries(plan)
            log: list = []
            from ..analysis.plan_integrity import (PlanChangeTracer,
                                                   PlanIntegrityValidator)
            mode = str(self._conf.get(
                "spark_tpu.sql.planChangeValidation"))
            validator = PlanIntegrityValidator(mode) \
                if mode in ("lite", "full") else None
            tracer = PlanChangeTracer(diffs=bool(self._conf.get(
                "spark_tpu.sql.planChangeLog")))
            self._optimized = default_optimizer(
                self._conf, reorder_log=log, validator=validator,
                tracer=tracer).execute(plan)
            # cost-based join-reorder decisions (plan/join_reorder.py):
            # one record per eligible region, into the event log and
            # the explain()/history API "reorder: yes/no" annotation
            self.reorder_decisions = log
            self.rule_trace = tracer.records
            if validator is not None:
                self._integrity_findings = validator.findings
            t1 = time.perf_counter()
            self.phase_times["optimization"] = t1 - t0
            self.spans.record("optimize", t0, t1)
        return self._optimized

    @property
    def executed_plan(self) -> P.PhysicalPlan:
        if self._executed is None:
            t0 = time.perf_counter()
            self._executed = plan_physical(
                self.optimized_plan, self._conf,
                join_strategy_overrides=self._join_overrides or None)
            t1 = time.perf_counter()
            self.phase_times["planning"] = t1 - t0
            self.spans.record("plan", t0, t1)
        return self._executed

    def explain(self, extended: bool = False, runtime: bool = False,
                analysis: bool = False, rules: bool = False) -> str:
        out = []
        if extended:
            out += ["== Logical Plan ==", self.logical.tree_string(),
                    "== Optimized Logical Plan ==",
                    self.optimized_plan.tree_string()]
        if runtime and self.last_metrics:
            out.append("== Physical Plan (runtime metrics) ==")
            out.append(self._runtime_tree(self.executed_plan))
            if self.stage_costs:
                out.append("== Stage cost (XLA) ==")
                for info in self.stage_costs.values():
                    bits = [f"stage {info.get('key_hash', '?')}"]
                    for k, label in (("flops", "flops"),
                                     ("bytes_accessed", "bytes"),
                                     ("peak_hbm_bytes", "peak HBM")):
                        if info.get(k) is not None:
                            bits.append(f"{label}={info[k]:,}")
                    if info.get("analysis_ms") is not None:
                        bits.append(f"analysis={info['analysis_ms']}ms")
                    out.append("  " + " ".join(bits))
        else:
            out += ["== Physical Plan ==",
                    self.executed_plan.tree_string()]
        out += ["== Join Reorder =="] + self._reorder_lines()
        if rules:
            # per-rule effectiveness trace from the plan-change tracer
            # (optionally with before/after diffs under planChangeLog)
            from ..analysis.plan_integrity import render_trace
            self.optimized_plan  # ensure the optimizer (and tracer) ran
            out.append("== Rule Trace ==")
            out += render_trace(self.rule_trace or []) or \
                ["  no rules applied"]
        if analysis:
            out.append("== Static Analysis ==")
            findings = self.analysis_findings
            if findings is None:
                # not executed yet: run the (pure, host-side) plan walk
                # on demand — the jaxpr half needs loaded inputs and
                # only rides an actual execution
                from ..analysis import analyze_plan
                mesh_n = max(1, int(self._conf.get(
                    "spark_tpu.sql.mesh.size")))
                findings = analyze_plan(self.executed_plan, self._conf,
                                        mesh_n)
            if findings:
                out += ["  " + f.render() for f in findings]
            else:
                out.append("  no findings")
        return "\n".join(out)

    def _reorder_lines(self) -> List[str]:
        """Human-readable cost-based join-reorder annotation for
        explain(): 'reorder: yes/no' plus, per region, the frontend
        order, the chosen order, and the per-join estimated rows."""
        self.executed_plan  # ensure the optimizer (and its log) ran
        decisions = self.reorder_decisions or []
        changed = [d for d in decisions if d.get("changed")]
        lines = [f"  reorder: {'yes' if changed else 'no'}"
                 + (f" ({len(changed)}/{len(decisions)} regions)"
                    if decisions else "")]
        for d in decisions:
            if not d.get("changed"):
                arrow = " (kept)"
            elif d.get("kind") == "orientation":
                arrow = " -> same order, probe/build orientation flipped"
            else:
                arrow = " -> " + " * ".join(d["order"])
            est = d.get("est_rows") or []
            lines.append("  " + " * ".join(d["relations"]) + arrow
                         + (f"  est rows/join: {est}" if est else ""))
        return lines

    def _runtime_tree(self, node: P.PhysicalPlan, depth: int = 0) -> str:
        """Tree annotated with per-operator runtime observables (the
        SQL-UI plan graph analog of `metric/SQLMetrics.scala:40`):
        output rows everywhere, plus join actual-vs-capacity, exchange
        max-bucket-vs-capacity, and runtime-filter pruned/tested."""
        m = self.last_metrics
        notes = []
        rows = m.get(f"rows_{getattr(node, 'op_tag', '')}")
        if rows is not None:
            notes.append(f"rows out: {rows:,}")
        tag = getattr(node, "tag", None)
        if isinstance(node, P.JoinExec):
            jr = m.get(f"join_rows_{tag}")
            if jr is not None:
                cap = node.out_cap
                notes.append(f"join rows: {jr:,}"
                             + (f"/{cap:,} cap" if cap else ""))
            if getattr(node, "cbo_est_rows", None) is not None:
                # the reorder cost model's output estimate, next to the
                # observed rows it is graded against
                notes.append(f"cbo est: {node.cbo_est_rows:,}")
        elif isinstance(node, P.ExchangeExec):
            mx = m.get(f"exch_max_{tag}")
            if mx is not None:
                cap = node.block_cap
                notes.append(f"exch max: {mx:,}"
                             + (f"/{cap:,} cap" if cap else ""))
            er = m.get(f"exch_rows_{tag}")
            if er is not None:
                notes.append(f"exch rows: {er:,}")
        elif isinstance(node, P.RuntimeFilterExec):
            tested = m.get(f"rtf_tested_{tag}")
            pruned = m.get(f"rtf_pruned_{tag}")
            if tested is not None and pruned is not None:
                notes.append(f"rtf pruned: {pruned:,}/{tested:,}")
            slots = m.get(f"rtf_slots_{tag}")
            if slots is not None:
                notes.append(f"slots out: {slots:,}")
        note = f"   [{'; '.join(notes)}]" if notes else ""
        line = "  " * depth + node.simple_string() + note
        return "\n".join([line] + [self._runtime_tree(c, depth + 1)
                                   for c in node.children])

    # -- execution ----------------------------------------------------------

    def _collect_scans(self, node: P.PhysicalPlan,
                       out: List[P.LeafExec]) -> None:
        if getattr(node, "needs_input", False):
            out.append(node)
        for c in node.children:
            self._collect_scans(c, out)

    def _splice_stream(self, node: P.PhysicalPlan, tagged):
        """Splice one streamed-aggregate result back into the plan.
        `tagged` is ("direct", Batch) / ("mesh", partial Batch) /
        ("spill", (host partial table, partial node)) — the same tagged
        value the recovery stage-output memo retains, so a recovery
        re-execution rebuilds the splice without re-streaming."""
        kind, result = tagged
        if kind == "direct":
            return P.InputExec(result, node.schema(), label="streamed_agg")
        if kind == "mesh":
            spliced = P.InputExec(result, node.schema(),
                                  label="streamed_partial_agg")
            # the final aggregate above resolves its functions
            # against the PRE-aggregation schema
            spliced._agg_base_schema = node._base_schema()
            return spliced
        # "spill": host-spilled partials re-reduce in a FINAL aggregate
        # (the partial -> exchange -> final split of AggUtils.scala,
        # with host Arrow buffers in the exchange's seat)
        from ..columnar import bucket_capacity
        from ..expr import ColumnRef
        partial_table, partial_node = result
        inp = P.InputExec(Batch.from_arrow(partial_table),
                          partial_node.schema(),
                          label="spilled_partials")
        inp._agg_base_schema = node._base_schema()
        final_groups = [ColumnRef(g.name()) for g in node.group_exprs]
        final = P.HashAggregateExec(
            inp, final_groups, node.agg_exprs, mode="final",
            est_groups=bucket_capacity(max(partial_table.num_rows, 8)))
        final.tag = node.tag
        self.spilled_partial_rows = partial_table.num_rows
        return final

    def _materialize_streaming(self, node: P.PhysicalPlan,
                               mesh=None) -> P.PhysicalPlan:
        """Execute streamable aggregates eagerly (chunked, accumulator
        carry) and splice their results back as InputExec leaves. Under a
        mesh, PARTIAL aggregates over chunked scans stream with per-shard
        tables (the exchange + final stages above run unchanged).

        Completed streams land in the recovery stage-output memo (the
        surviving-shuffle-file analog): when a downstream failure
        re-executes the query, the splice replays from the memo instead
        of re-ingesting the stream. After a mesh failure, a matching
        mesh checkpoint resumes the stream at its chunk cursor."""
        from .streaming_agg import (resume_from_mesh_checkpoint,
                                    stream_scan_aggregate_mesh,
                                    try_stream_aggregate,
                                    try_stream_aggregate_spill)
        rec = self._recovery
        cache = self.session._stage_cache
        if isinstance(node, P.HashAggregateExec):
            # the chunk drivers jit on their own, outside
            # _compile_stage: name the stage for _handle_failure's
            # diagnostics (never a cache key, so evicting it is a no-op)
            self._last_stage_key = f"stream:{node.simple_string()}"
        if mesh is None and isinstance(node, P.HashAggregateExec):
            memo_key = ("stream", id(node))
            if rec is not None:
                hit = rec.memo_get(memo_key, label=node.simple_string())
                if hit is not None:
                    return self._splice_stream(node, hit)
                if self._mesh_fallback:
                    resumed = resume_from_mesh_checkpoint(
                        node, self._conf, cache, rec)
                    if resumed is not None:
                        rec.memo_put(memo_key, ("spill", resumed))
                        return self._splice_stream(node,
                                                   ("spill", resumed))
            result = try_stream_aggregate(node, self._conf, cache, rec)
            if result is not None:
                if rec is not None:
                    rec.memo_put(memo_key, ("direct", result))
                return self._splice_stream(node, ("direct", result))
            spill = try_stream_aggregate_spill(node, self._conf, cache,
                                               rec)
            if spill is not None:
                if rec is not None:
                    rec.memo_put(memo_key, ("spill", spill))
                return self._splice_stream(node, ("spill", spill))
        if mesh is not None and isinstance(node, P.HashAggregateExec) \
                and node.mode == "partial":
            memo_key = ("stream_mesh", id(node))
            if rec is not None:
                hit = rec.memo_get(memo_key, label=node.simple_string())
                if hit is not None:
                    return self._splice_stream(node, hit)
            result = stream_scan_aggregate_mesh(
                node, mesh, self._conf, cache, rec)
            if result is not None:
                if rec is not None:
                    rec.memo_put(memo_key, ("mesh", result))
                return self._splice_stream(node, ("mesh", result))
        new_children = tuple(self._materialize_streaming(c, mesh)
                             for c in node.children)
        if new_children != node.children:
            import copy
            node = copy.copy(node)
            node.children = new_children
        return node

    def _materialize_generates(self, node: P.PhysicalPlan
                               ) -> P.PhysicalPlan:
        """Mesh runs: offsets-encoded list columns cannot shard (their
        offsets are absolute into the flattened values), so explode
        subtrees materialize single-device and the FLAT exploded result
        shards as an InputExec — the stage cut the reference makes at
        GenerateExec.scala:1, with the generate on the driver device."""
        new_children = tuple(self._materialize_generates(c)
                             for c in node.children)
        if new_children != node.children:
            import copy
            node = copy.copy(node)
            node.children = new_children
        if isinstance(node, P.GenerateExec):
            from .streaming_agg import _materialize_subtree
            b = _materialize_subtree(node, self._conf, self._recovery)
            return P.InputExec(b, node.schema(), label="generated")
        return node

    def _stage_key(self, root: P.PhysicalPlan, mesh=None) -> str:
        from .streaming_agg import conf_compile_suffix
        conf = self._conf
        n = int(mesh.devices.size) if mesh is not None else 1
        metrics_on = bool(conf.get("spark_tpu.sql.metrics.enabled"))
        return (root.describe()
                + (f"#mesh{n}" if mesh is not None else "")
                + f"#m{int(metrics_on)}"
                + conf_compile_suffix(conf))

    def _events_enabled(self) -> bool:
        """Whether lifecycle events are worth constructing at all: an
        observability output is configured, or a non-built-in listener
        is registered. With neither, posting would render plan strings
        and span dicts per query for three subscribers that each check
        conf and do nothing — pure hot-path waste."""
        conf = self.session.conf
        if str(conf.get("spark_tpu.sql.eventLog.dir")) \
                or str(conf.get("spark_tpu.sql.trace.dir")) \
                or str(conf.get("spark_tpu.sql.metrics.sink")):
            return True
        return any(not getattr(li, "_builtin", False)
                   for li in self.session.listeners.listeners)

    def _shard_obs_on(self) -> bool:
        """Gate for per-shard telemetry (mesh runs only): 'on' always,
        'off' never, 'auto' whenever lifecycle events are observed —
        the same discipline as xlaCost, so a service/event-logged mesh
        query gets its flight-recorder records and a bare CLI run pays
        nothing."""
        mode = str(self._conf.get(
            "spark_tpu.sql.observability.shardSpans"))
        if mode == "off":
            return False
        if mode == "on":
            return True
        return self._observe_events

    def _observe_cost(self) -> bool:
        """Gate for XLA cost/memory capture (it costs a second compile
        of the stage): 'on' always, 'off' never, 'auto' only when an
        observability output is configured or the OOM ladder is
        descending (the rung-3 diagnostic cites measured HBM)."""
        conf = self._conf
        mode = str(conf.get("spark_tpu.sql.observability.xlaCost"))
        if mode == "off":
            return False
        if mode == "on":
            return True
        return bool(str(self.session.conf.get("spark_tpu.sql.eventLog.dir"))
                    or str(self.session.conf.get("spark_tpu.sql.trace.dir"))
                    or str(self.session.conf.get(
                        "spark_tpu.sql.metrics.sink"))
                    or self._oom_rung > 0)

    def _capture_stage_cost(self, fn, key: str, args,
                            compiled=None) -> Optional[dict]:
        """cost_analysis()/memory_analysis() per stage key, memoized on
        the session (a stage recompiles only when its key changes, so
        the analysis stays valid). Fault injection is suppressed around
        the analysis lowering: it re-traces the stage, and trace-time
        chaos sites must count once per REAL compile. When a `Compiled`
        is already in hand (the AOT compile-cache path, or a wrapper
        holding one for these args), it is analyzed directly — no
        second analysis compile."""
        import hashlib
        from ..observability import xla_cost
        from ..testing import faults
        from . import compile_cache as CC
        info = self.session._stage_costs.get(key)
        if info is None and args is not None and self._observe_cost():
            if compiled is None and isinstance(fn, CC.CachedStageFn):
                compiled = fn.compiled_for(args)
            t0 = time.perf_counter()
            if compiled is not None:
                info = xla_cost.analyze_compiled(compiled)
            else:
                with faults.suppressed():
                    info = xla_cost.analyze_jit(fn, args)
            info["analysis_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 1)
            info["key_hash"] = hashlib.md5(
                key.encode()).hexdigest()[:10]
            info["stage"] = key[:160]
            if "error" not in info:
                # memoize successes only: a failed analysis (e.g. the
                # analysis compile itself OOMed mid-ladder) must retry
                # next time instead of pinning the error forever
                store = self.session._stage_costs
                store[key] = info
                while len(store) > 512:
                    store.pop(next(iter(store)))
        if info is not None:
            self.stage_costs[key] = info
        return info

    def _build_stage_fn(self, root: P.PhysicalPlan, mesh=None):
        """Construct the stage callable (pre-jit): the replay of the
        operator tree over input batches, shard_map-wrapped under a
        mesh. One builder serves both consumers — `_compile_stage` jits
        exactly this, and the jaxpr analyzer abstractly evaluates
        exactly this — so the analysis can never drift from the
        compiled program."""
        conf = self._conf
        per_op = bool(conf.get("spark_tpu.sql.metrics.enabled"))
        # what the operators learn while this stage is traced and keep
        # off the program (ExecContext.host): beside the stage-cache
        # entry, under its key, so a later hit still finds it
        host = self.session._stage_host.setdefault(
            self._stage_key(root, mesh), {})

        def replay_root(ctx, inputs):
            counter = [0]

            def replay(node: P.PhysicalPlan) -> Batch:
                if getattr(node, "needs_input", False):
                    b = inputs[counter[0]]
                    counter[0] += 1
                    return b
                child_batches = [replay(c) for c in node.children]
                out = node.compute(ctx, child_batches)
                if per_op:
                    # rows-out per operator, psum'd across shards — the
                    # SQLMetrics.scala:40 analog, shown by
                    # explain(runtime=True)
                    ctx.add_metric(
                        f"rows_{getattr(node, 'op_tag', 'op?')}",
                        jnp.sum(out.selection_mask().astype(jnp.int64)))
                return out

            return replay(root)

        if mesh is None:
            def run(inputs):
                ctx = P.ExecContext(conf, host=host)
                out = replay_root(ctx, inputs)
                return out, ctx.flags, ctx.metrics

            return run
        else:
            from jax.sharding import PartitionSpec as Psp
            from ..parallel.mesh import shard_map
            from ..parallel import stripe_batch
            from ..parallel.mesh import AXIS, pmax

            n = int(mesh.devices.size)

            # sorted/limited/global-agg results are replicated on every
            # shard; each shard emits its contiguous stripe so the
            # out_spec reassembles the full (ordered) result exactly once
            replicated_out = isinstance(
                root.output_partitioning(),
                (P.SinglePartition, P.Replicated))

            def run_shard(inputs, _token):
                ctx = P.ExecContext(conf, axis_name=AXIS, n_shards=n,
                                    host=host)
                out = replay_root(ctx, inputs)
                if replicated_out:
                    out = stripe_batch(out, ctx)
                # AQE stats channel: reduce flags/metrics to replicated
                # scalars (pmax for per-shard capacity stats, psum else)
                flags = {k: jax.lax.psum(
                    jnp.asarray(v).astype(jnp.int32), AXIS)
                    for k, v in ctx.flags.items()}
                metrics = {}
                for k, v in ctx.metrics.items():
                    # capacity-sizing stats take the worst shard (pmax);
                    # row counts sum across shards
                    red = pmax if k.startswith(
                        ("join_rows_", "exch_max_", "agg_groups_")) \
                        else jax.lax.psum
                    metrics[k] = red(jnp.asarray(v), AXIS)
                    if k.startswith("join_rows_"):
                        # and the whole mesh's, which the counters take
                        metrics["join_mesh_rows_" + k[len("join_rows_"):]] \
                            = jax.lax.psum(jnp.asarray(v), AXIS)
                return out, flags, metrics

            return shard_map(
                run_shard, mesh=mesh,
                in_specs=(Psp(AXIS), Psp(AXIS)),
                out_specs=(Psp(AXIS), Psp(), Psp()),
                check_vma=False)

    def _find_stage(self, root: P.PhysicalPlan, mesh, args):
        """The `stage.lookup` span's body: the stage's key, the disk
        cache that applies, and what the stage cache holds under the
        key: (key, cc, fn, partial), `fn` None on a miss, `partial` a
        wrapper whose key is warm and whose call signature is not."""
        from ..testing import faults
        from . import compile_cache as CC
        key = self._stage_key(root, mesh)
        self._last_stage_key = key  # recovery evicts exactly this entry
        cc = CC.get_cache(self._conf) if args is not None else None
        if cc is not None:
            plan = faults.active()
            if plan is not None and any(
                    r.site in faults.TRACE_TIME_SITES
                    for r in plan.rules):
                # trace-time chaos seams fire once per (re)compile; a
                # deserialized executable involves no trace, so the
                # armed rule's nth hit would silently never arrive
                # (and a transient-retry eviction would stop forcing
                # the re-trace the seam contract documents). Chaos
                # determinism wins: bypass the disk cache while such
                # rules are armed.
                cc = None
        fn = self.session._stage_cache.get(key)
        partial = None
        if fn is not None and isinstance(fn, CC.CachedStageFn):
            if not fn.has_builder:
                # warm-start entries arrive builder-less; bind the jit
                # fallback here (only the executor owns the plan) so a
                # novel call signature can still compile. The thunk
                # closes over the PRE-BUILT stage fn (conf + plan
                # only) — never `self`: these wrappers live in the
                # session-lifetime shared stage cache, and capturing
                # the QueryExecution would pin its recovery memo's
                # materialized batches per cached key
                stage_fn = self._build_stage_fn(root, mesh)
                fn.bind_builder(lambda: jax.jit(stage_fn))
            if cc is not None and fn.compiled_for(args) is None:
                # the KEY is warm but THIS call signature is not
                # (another dictionary encoding / batch shape): the
                # disk may already hold its executable from another
                # process or an earlier run — fall through to fill
                # the existing wrapper, so the "never jit a known
                # shape twice" contract holds per SIGNATURE, not
                # merely per key (and a fresh compile here gets
                # persisted instead of hiding in the jit fallback)
                partial = fn
                fn = None
        if fn is not None:
            self.session.metrics.counter("compile_cache_hits").inc()
            self._capture_stage_cost(fn, key, args)
        return key, cc, fn, partial

    def _compile_stage(self, root: P.PhysicalPlan, mesh=None, args=None):
        from ..observability.listener import StageCompiledEvent
        from ..testing import faults
        from . import compile_cache as CC
        from . import lifecycle
        # cooperative boundary before paying (or re-paying) a compile
        lifecycle.checkpoint("compile")
        # once a dispatch attempt; on a miss it ends where `compile`
        # begins
        with self.spans.span("stage.lookup"):
            key, cc, fn, partial = self._find_stage(root, mesh, args)
        if fn is not None:
            return fn
        self.session.metrics.counter("compile_cache_misses").inc()
        t_compile = time.perf_counter()
        faults.fire("stage_compile")  # chaos seam: pre-jit, cache miss
        if mesh is not None:
            faults.fire("mesh")  # chaos seam: mesh/shard_map lowering
        compiled = None
        disk_hit = False
        if cc is not None:
            # persistent cross-process seat: deserialize instead of
            # compiling when a matching executable is on disk
            t_deser = time.perf_counter()
            compiled = cc.load(key, mesh, args,
                               metrics=self.session.metrics)
            if compiled is not None:
                disk_hit = True
                self.spans.record("deserialize", t_deser,
                                  time.perf_counter())
        if compiled is not None:
            if partial is not None:
                fn = partial
            else:
                # builder closes over the pre-built stage fn only (see
                # the warm-start bind above for why `self` must not
                # leak in)
                stage_fn = self._build_stage_fn(root, mesh)
                fn = CC.CachedStageFn(lambda: jax.jit(stage_fn))
            fn.add(CC.call_signature(args), compiled)
        elif cc is not None:
            # AOT path: pay trace + backend compile NOW (the lazy jit
            # would pay the same at first dispatch) so the executable
            # can be serialized for the next process
            jitted = jax.jit(self._build_stage_fn(root, mesh))
            compiled = jitted.lower(*args).compile()
            cc.store(key, mesh, args, compiled,
                     metrics=self.session.metrics)
            fn = partial if partial is not None \
                else CC.CachedStageFn(lambda: jitted)
            fn.add(CC.call_signature(args), compiled)
        else:
            fn = jax.jit(self._build_stage_fn(root, mesh))
        self.session._stage_cache[key] = fn
        cost = self._capture_stage_cost(fn, key, args, compiled=compiled)
        t1 = time.perf_counter()
        # honesty note: jax.jit is lazy — the EXECUTING program's XLA
        # compile happens inside the first dispatch (its
        # `dispatch.launch`). Under the compile cache
        # the AOT path is EAGER, so this span carries the true compile
        # (or deserialize) cost. Without it, the span covers stage
        # setup plus, when capture is on, the AOT analysis compile
        # (whose wall-clock rides in the analysis_ms attr).
        attrs = {"stage": (cost or {}).get("key_hash", key[:60])}
        if cc is not None:
            attrs["disk_hit"] = disk_hit
        if cost and cost.get("analysis_ms") is not None:
            attrs["analysis_ms"] = cost["analysis_ms"]
        self.spans.record("compile", t_compile, t1, **attrs)
        if self._observe_events:
            self.session.listeners.post(
                "on_stage_compiled", StageCompiledEvent(
                    query_id=self.query_id, ts=time.time(), stage_key=key,
                    key_hash=(cost or {}).get("key_hash", ""),
                    mesh_n=int(mesh.devices.size) if mesh is not None else 1,
                    cost=cost))
        return fn

    # -- pre-compile static analysis (spark_tpu/analysis/) ------------------

    def _analysis_conf(self):
        conf = self._conf
        return (bool(conf.get("spark_tpu.sql.analysis.enabled")),
                bool(conf.get("spark_tpu.sql.analysis.strict")))

    def _jaxpr_analysis_on(self, strict: bool) -> bool:
        """Gate for the jaxpr half (one extra abstract trace per unique
        stage key, memoized): mirrors the xlaCost 'auto' discipline."""
        mode = str(self._conf.get("spark_tpu.sql.analysis.jaxpr"))
        if mode == "off":
            return False
        if mode == "on":
            return True
        return strict or self._events_enabled()

    def _post_analysis(self, strict: bool) -> None:
        """Publish findings on the bus (once per execution) and raise
        pre-compile under strict when any is error-severity."""
        from ..analysis import AnalysisFindingError, errors_of
        from ..observability.listener import AnalysisEvent
        findings = self.analysis_findings or []
        if findings and self._observe_events and not self._analysis_posted:
            self._analysis_posted = True
            self.session.listeners.post("on_analysis", AnalysisEvent(
                query_id=self.query_id, ts=time.time(),
                findings=[f.to_dict() for f in findings]))
        if strict and errors_of(findings):
            raise AnalysisFindingError(findings)

    def _analyze_plan_phase(self) -> None:
        """Plan-level walk of the planned tree — BEFORE streaming
        splices/UDF extraction execute anything, so strict mode rejects
        a hazardous plan with zero device work done."""
        enabled, strict = self._analysis_conf()
        if not enabled:
            # leave None ("never analyzed"), NOT [] ("analyzed clean"):
            # explain(analysis=True) runs its on-demand walk off the
            # None sentinel, so a disabled execution can't print a
            # false clean bill. Lite-mode plan-integrity findings still
            # surface — validation ran regardless of the analyzer gate.
            self.executed_plan  # ensure the optimizer (validator) ran
            self.analysis_findings = \
                list(self._integrity_findings) or None
            if self.analysis_findings:
                self._post_analysis(strict=False)
            return
        from ..analysis import analyze_plan
        t0 = time.perf_counter()
        mesh_n = max(1, int(self._conf.get("spark_tpu.sql.mesh.size")))
        # lite-mode plan-integrity findings (collected while the
        # optimizer ran, triggered via executed_plan below) join the
        # analyzer's findings in the same flow
        self.analysis_findings = analyze_plan(self.executed_plan,
                                              self._conf, mesh_n) \
            + list(self._integrity_findings)
        self.spans.record("analyze", t0, time.perf_counter(),
                          findings=len(self.analysis_findings))
        if strict:
            self._post_analysis(strict)

    def _analyze_jaxpr_phase(self, root: P.PhysicalPlan, mesh,
                             args) -> None:
        """Jaxpr-level walk of the exact callable about to be jitted,
        memoized per stage key next to the XLA cost analyses. Appends to
        the plan-phase findings, then publishes the combined set."""
        enabled, strict = self._analysis_conf()
        if not enabled:
            return
        if self._jaxpr_analysis_on(strict):
            from ..analysis import analyze_jaxpr, trace_stage
            from ..testing import faults
            # the memo's key is the stage's, a rendering of the whole
            # tree: a hit pays it too, so the interval is recorded
            # either way
            t0 = time.perf_counter()
            key = "jaxpr#" + self._stage_key(root, mesh)
            memo = self.session._analysis_memo
            found = memo.get(key)
            if found is None:
                try:
                    # suppressed(): abstract evaluation re-traces the
                    # stage; trace-time chaos sites must count once per
                    # REAL compile only
                    with faults.suppressed():
                        jaxpr = trace_stage(
                            self._build_stage_fn(root, mesh), args)
                    n = int(mesh.devices.size) if mesh is not None else 1
                    found = analyze_jaxpr(jaxpr, mesh_n=n)
                except Exception as e:  # noqa: BLE001 — advisory only
                    import warnings
                    warnings.warn(f"jaxpr analysis failed (skipped): "
                                  f"{type(e).__name__}: {e}")
                    found = []
                else:
                    memo[key] = found
                    while len(memo) > 512:
                        memo.pop(next(iter(memo)))
            self.spans.record("analyze_jaxpr", t0, time.perf_counter(),
                              findings=len(found))
            if found:
                known = {(f.code, f.op) for f in
                         (self.analysis_findings or [])}
                self.analysis_findings = (self.analysis_findings or []) \
                    + [f for f in found if (f.code, f.op) not in known]
        self._post_analysis(strict)

    def _aqe_cache_key(self, mesh) -> Optional[str]:
        """Plan + data-identity key for persisted AQE capacities; None
        (uncacheable) when any scan's source has no identity stamp."""
        tokens = [s.source.cache_token()
                  for s in L.iter_scans(self.optimized_plan)]
        if any(t is None for t in tokens):
            return None
        n = int(mesh.devices.size) if mesh is not None else 1
        return (self.optimized_plan.tree_string()
                + f"#mesh{n}#src{tokens!r}")

    @staticmethod
    def _collect_caps(root: P.PhysicalPlan, out: Dict[str, int]) -> None:
        """Harvest every AQE-discovered static capacity from a converged
        plan, keyed `kind:tag` (the persistence side of the stats
        channel: the reference re-learns MapOutputStatistics per query,
        but its shuffle files are sized dynamically — XLA's static
        shapes make remembering converged capacities the difference
        between one compile and a compile per retry per execution)."""
        for c in root.children:
            QueryExecution._collect_caps(c, out)
        if isinstance(root, P.JoinExec):
            if root.out_cap is not None:
                out[f"join:{root.tag}"] = root.out_cap
            if root.unique_build is False:
                out[f"uniq:{root.tag}"] = 0
        elif isinstance(root, P.ExchangeExec) and root.block_cap is not None:
            out[f"exch:{root.tag}"] = root.block_cap
        elif isinstance(root, P.HashAggregateExec) and root.est_groups:
            out[f"agg:{root.tag}"] = root.est_groups
        elif isinstance(root, P.RuntimeFilterExec) \
                and root.out_cap is not None:
            out[f"rtf:{root.tag}"] = root.out_cap

    def _apply_saved_caps(self, root: P.PhysicalPlan, caps: Dict[str, int]
                          ) -> None:
        for key, cap in caps.items():
            kind, tag = key.split(":", 1)
            if kind == "join":
                self._set_join_cap(root, tag, cap)
            elif kind == "uniq":
                self._set_join_nonunique(root, tag)
            elif kind == "exch":
                self._set_exchange_cap(root, tag, cap)
            elif kind == "rtf":
                self._set_filter_cap(root, tag, cap)
            else:
                self._set_agg_groups(root, tag, cap)

    @staticmethod
    def _set_join_cap(root: P.PhysicalPlan, tag: str, cap: int) -> None:
        for c in root.children:
            QueryExecution._set_join_cap(c, tag, cap)
        if isinstance(root, P.JoinExec) and root.tag == tag:
            root.out_cap = cap

    @staticmethod
    def _set_join_nonunique(root: P.PhysicalPlan, tag: str) -> None:
        for c in root.children:
            QueryExecution._set_join_nonunique(c, tag)
        if isinstance(root, P.JoinExec) and root.tag == tag:
            root.unique_build = False

    @staticmethod
    def _set_exchange_cap(root: P.PhysicalPlan, tag: str, cap: int) -> None:
        for c in root.children:
            QueryExecution._set_exchange_cap(c, tag, cap)
        if isinstance(root, P.ExchangeExec) and root.tag == tag:
            root.block_cap = cap

    @staticmethod
    def _set_agg_groups(root: P.PhysicalPlan, tag: str, est: int) -> None:
        for c in root.children:
            QueryExecution._set_agg_groups(c, tag, est)
        if isinstance(root, P.HashAggregateExec) and root.tag == tag:
            root.est_groups = est

    @staticmethod
    def _set_filter_cap(root: P.PhysicalPlan, tag: str, cap: int) -> None:
        for c in root.children:
            QueryExecution._set_filter_cap(c, tag, cap)
        if isinstance(root, P.RuntimeFilterExec) and root.tag == tag:
            root.out_cap = cap

    def execute_batch(self, t_begin: Optional[float] = None
                      ) -> Tuple[Batch, Dict, Dict]:
        """Run the query, returning (device Batch, flags, metrics).
        `t_begin`: where the span `query.begin` starts, `collect`'s
        entry when it is the caller.

        Joins whose many-to-many expansion overflows the seeded output
        capacity surface a `join_overflow_<tag>` flag plus the true row
        total in `join_rows_<tag>`; the loop below re-jits those joins
        with a sufficient static capacity (the AQE-style stats->re-plan
        host loop, `AdaptiveSparkPlanExec.scala:64`). A skewed shuffle
        join raises _ReplanRequest instead: the physical plan rebuilds
        with the join forced to broadcast and execution restarts.

        Failures flow through the structured taxonomy
        (execution/failures.py): transient flakes and stage timeouts
        retry with backoff, RESOURCE_EXHAUSTED descends the degradation
        ladder, mesh failures re-plan single-device — all recorded in
        `fault_summary` and the event log."""
        from ..observability.listener import QueryStartEvent
        from ..parallel.elastic import ElasticMeshState
        from ..service import arbiter as res_arbiter
        from ..testing import faults
        from .failures import RetryPolicy
        from .recovery import RecoveryContext
        from . import lifecycle
        t_begin = t_begin or time.perf_counter()
        self._activate_conf()
        # degraded-mode state was sticky across executions of one
        # QueryExecution: a warm-loop re-execution after a transient
        # mesh failure stayed pinned single-device (and an OOM reroute
        # stayed spill-routed) forever. Every execution starts
        # optimistic — the ladder re-derives whatever it still needs.
        # A plan built under the old overlay must be rebuilt.
        if self._exec_conf is not None or self._mesh_fallback:
            self._executed = None
        self._exec_conf = None
        self._mesh_fallback = False
        faults.arm(self.session.conf)
        # query lifecycle scope (execution/lifecycle.py): install a
        # cancel token (deadline armed from queryDeadlineMs) unless an
        # outer scope — the SQL service, or an enclosing execution
        # whose subquery this is — already did, and register it for
        # session.cancel(query_id)
        lc_scope = lifecycle.enter_query_scope(
            self.session.app_id, self.query_id, self.session.conf)
        # cross-query arbiter lease scope (service/arbiter.py): scans
        # this execution keeps resident lease from the shared HBM pool;
        # everything leased is released when the execution ends. None
        # (free) when no arbiter is installed.
        arb_token = res_arbiter.enter_query(
            f"{self.session.app_id}:q{self.query_id}")
        conf = self._conf
        self.fault_summary = {}
        self.fault_events = []
        self.udf_summary = None
        self._recovery = RecoveryContext(metrics=self.session.metrics,
                                         record=self._record_fault)
        # NOTE: _analysis_posted is NOT reset here — it is
        # per-QueryExecution, so an external-collect attempt that falls
        # through to execute_batch (or a re-executed qe) posts the
        # on_analysis event exactly once
        self.analysis_findings = None
        self._oom_rung = 0
        self._retry_policy = RetryPolicy(
            max_retries=self._max_retries(conf),
            backoff_ms=float(conf.get("spark_tpu.execution.backoffMs")))
        self._elastic = ElasticMeshState(conf)
        self._observe_events = self._events_enabled()
        if self._observe_events:
            self.session.listeners.post("on_query_start", QueryStartEvent(
                query_id=self.query_id, ts=time.time(),
                plan=self.logical.tree_string()))
        self.session._exec_depth += 1
        # `collect`'s scopes and the prologue above (fault arming, the
        # lifecycle and arbiter scopes, the recovery context, the
        # retry and elastic state, the start event), handed over: it
        # begins in `collect`
        self.spans.record("query.begin", t_begin, time.perf_counter())
        try:
            for _replan in range(4):
                try:
                    return self._execute_recover()
                except _ReplanRequest:
                    self._executed = None  # re-plan with _join_overrides
                    # the rebuilt plan has fresh node identities and
                    # different shapes: memoized stage outputs no
                    # longer splice (epoch bump)
                    self._recovery.invalidate()
                    self.spans.mark("aqe_replan", kind="join_strategy")
            # replan budget exhausted: finish with capacity growth only
            self._no_more_replans = True
            return self._execute_recover()
        except _ReplanRequest:
            raise
        except (lifecycle.QueryCancelledError,
                lifecycle.QueryDeadlineError) as e:
            self._observe_cancel(e)
            raise
        except Exception as e:  # noqa: BLE001 — observe, then surface
            self._post_query_end(None, status="error", error=e)
            self._flightrec_dump(e)
            raise
        finally:
            res_arbiter.exit_query(arb_token)
            lifecycle.exit_query_scope(lc_scope)
            self.session._exec_depth -= 1
            if self._recovery is not None:
                # the memo spans recovery loops, not executions: drop
                # retained device batches / checkpoint tables now
                self._recovery.release()
            if self.session._exec_depth == 0:
                # implicit (WITH-clause) materializations are statement
                # -scoped: evict when the outermost execution finishes
                self.session._evict_implicit_caches()

    @staticmethod
    def _max_retries(conf) -> int:
        """spark_tpu.execution.maxRetries, unless the deprecated
        spark_tpu.sql.execution.maxTaskFailures was explicitly set (its
        registry default must not shadow the new key)."""
        legacy = "spark_tpu.sql.execution.maxTaskFailures"
        if conf.is_explicitly_set(legacy):
            return int(conf.get(legacy))
        return int(conf.get("spark_tpu.execution.maxRetries"))

    # -- failure recovery ---------------------------------------------------

    def _record_fault(self, action: str, exc=None, **extra) -> None:
        """Count one recovery action into fault_summary, append a
        bounded event record (both land in the event log), post the
        typed FaultEvent, and mark the retry on the span trace."""
        from ..observability.listener import FaultEvent
        self.fault_summary[action] = int(self.fault_summary.get(action, 0)) + 1
        error = "" if exc is None else f"{type(exc).__name__}: {exc}"[:200]
        site = getattr(exc, "site", None)
        if len(self.fault_events) < 32:
            ev = {"action": action}
            if exc is not None:
                ev["error"] = error
                if site is not None:
                    ev["site"] = site
            ev.update(extra)
            self.fault_events.append(ev)
        else:
            # the 32-entry cap used to drop later events SILENTLY —
            # count the truncation so history/event-log consumers can
            # see the record list is incomplete (the action counters
            # above still count everything)
            self.fault_summary["events_dropped"] = int(
                self.fault_summary.get("events_dropped", 0)) + 1
        self.spans.mark(f"retry:{action}", error=error[:120])
        if self._observe_events:
            self.session.listeners.post("on_fault", FaultEvent(
                query_id=self.query_id, ts=time.time(), action=action,
                error=error, site=site))

    def _observe_cancel(self, e: Exception) -> None:
        """Observability for a cancelled/deadlined execution: the
        lifecycle counter, a `cancel` action in fault_summary (history
        FAULT_ACTIONS), a `cancelled` instant span in the Chrome
        trace, and a query-end event whose status ("cancelled" /
        "deadline_exceeded") flows into the event log and the
        service's query-history store."""
        from .lifecycle import QueryCancelledError
        cancelled = isinstance(e, QueryCancelledError)
        status = "cancelled" if cancelled else "deadline_exceeded"
        self.session.metrics.counter(
            "query_cancelled" if cancelled
            else "query_deadline_exceeded").inc()
        self._record_fault("cancel", e)
        self.spans.mark("cancelled",
                        reason="cancel" if cancelled else "deadline")
        # the no-orphan contract holds wherever the cancel lands: even
        # when it hits outside the UDF lane (scan, exchange, a chunked
        # aggregate), no pooled UDF worker survives the query — idle
        # workers respawn on demand, so this only costs a warm start
        pool = getattr(self.session, "_udf_pool", None)
        if pool is not None:
            pool.shutdown()
        self._post_query_end(None, status=status, error=e)

    def _flightrec_dump(self, e: Exception) -> None:
        """Crash-time diagnostics for a SURFACED failure (the recovery
        ladder gave up): classify the terminal error and ask the
        session's flight recorder for a bundle. Cancels/deadlines take
        the `_observe_cancel` path and deliberately never dump —
        stopping a query is lifecycle, not a crash. Never raises, and
        works with events off: the recorder's rings may be sparse then,
        but plan + fault summary ride along in `extra`."""
        try:
            from ..observability.flight_recorder import FlightRecorder
            rec = FlightRecorder.of(self.session)
            if rec is None:
                return
            from .failures import StageOOMError
            if isinstance(e, StageOOMError):
                reason = "oom"
            elif ("recovery did not converge" in str(e)
                  and isinstance(e, RuntimeError)):
                reason = "recovery_nonconvergent"
            else:
                reason = "fatal"
            rec.dump(reason, extra={
                "query_id": self.query_id,
                "plan": self.logical.tree_string()[:2000],
                "fault_summary": {
                    k: v for k, v in self.fault_summary.items()
                    if k != "events"},
            }, error=e)
        except Exception as dump_err:  # noqa: BLE001 — diagnostics only
            import warnings
            warnings.warn(f"flight-recorder trigger failed: {dump_err}")

    def _mesh_replan(self, mesh_size: Optional[int] = None) -> None:
        """Shared reset for the elastic-ladder rungs that change the
        gang's shape (drain, shrink-on-restart, single-device
        fallback): memoized stage outputs can no longer splice
        (checkpoints survive — the next stream resumes from them), and
        the plan rebuilds — under a mesh.size overlay when given, else
        against the conf whose device exclusions just changed."""
        if self._recovery is not None:
            self._recovery.invalidate()
        if mesh_size is not None:
            overlay = Conf(parent=self._conf)
            overlay.set("spark_tpu.sql.mesh.size", mesh_size)
            self._exec_conf = overlay
        self._executed = None

    def _execute_recover(self) -> Tuple[Batch, Dict, Dict]:
        """Run `_execute_batch_inner` under the failure taxonomy: each
        iteration either returns, re-raises (_ReplanRequest, FATAL,
        exhausted budgets), or applies one recovery action and loops."""
        from ..observability.spans import use_recorder
        from . import lifecycle
        last: Optional[Exception] = None
        for _ in range(32):  # every action below consumes a bounded budget
            # cooperative boundary at every stage-attempt entry: a
            # cancel/deadline delivered mid-recovery stops the ladder
            # here instead of burning another recovery action
            lifecycle.checkpoint("stage_attempt")
            try:
                # code below that has no handle on the query (columnar
                # ingest, the chunk drivers) opens its spans on this
                # execution's recorder
                with use_recorder(self.spans):
                    return self._execute_batch_inner()
            except _ReplanRequest:
                raise
            except Exception as e:  # noqa: BLE001
                last = e
                self._handle_failure(e)  # raises when unrecoverable
        raise RuntimeError(
            f"stage failure recovery did not converge after 32 recovery "
            f"actions; fault_summary={self.fault_summary}; last error: "
            + ("<none>" if last is None
               else f"{type(last).__name__}: {str(last)[:300]}"))

    def _handle_failure(self, e: Exception) -> None:
        """One step of the recovery ladder. Returns after applying a
        recovery action (caller re-executes); raises when the failure is
        fatal or every applicable budget is exhausted."""
        import warnings
        from .failures import (FailureClass, StageCompileError,
                               StageOOMError, StageTimeoutError, classify,
                               is_compile_refusal, is_mesh_failure)
        conf = self._conf
        cls = classify(e)
        msg = f"{type(e).__name__}: {e}"

        # lifecycle control outranks every recovery rung: a cancelled
        # or deadlined query surfaces unchanged — no retry, no
        # degraded re-plan, no gang restart (execution/lifecycle.py)
        if cls is FailureClass.CANCELLED:
            raise

        # a program the device compiler refused fails as that, before
        # any rung can read its text as something curable: a kernel
        # over its VMEM limit says RESOURCE_EXHAUSTED (the OOM ladder's
        # token) and, under a mesh, carries shard_map in its op name
        # (the gang-restart ladder's)
        if is_compile_refusal(e):
            raise StageCompileError(
                f"the device compiler refused a program of stage "
                f"{(self._last_stage_key or '<uncompiled>')[:400]}: "
                f"{msg}") from e

        # graceful decommission (parallel/elastic.py): a drain request
        # surfaced at a chunk boundary — a planned transition, not a
        # failure. Exclude the draining devices at SESSION level (the
        # decommission outlives this query), clear the one-shot
        # request, and re-execute on the reduced gang, which resumes
        # from the checkpoint the drain just forced.
        from ..parallel import elastic as EL
        mesh_on = int(conf.get("spark_tpu.sql.mesh.size")) > 1
        if mesh_on and isinstance(e, EL.MeshDecommissionRequest):
            warnings.warn(
                f"decommissioning mesh shard(s) {sorted(e.shards)} "
                f"(device ids {sorted(e.device_ids)}): draining at the "
                f"chunk boundary and continuing on the reduced gang")
            self._record_fault("decommission", None,
                               shards=sorted(e.shards),
                               devices=sorted(e.device_ids))
            EL.apply_decommission(self.session.conf, e.device_ids)
            if self._recovery is not None:
                self._recovery.begin_recovery_attempt()
            self._mesh_replan()  # the gang shrank: [n, ...] shapes differ
            return

        # mesh/collective failure ladder: gang restart first — the
        # mesh streaming driver resumes at its last checkpoint ON the
        # mesh — and only past the restart budget the single-device
        # fallback (degraded but correct), the final rung. Each rung
        # is gated by its OWN conf: meshFallback.enabled=false still
        # restarts (mesh-or-fail), it just removes the degrade rung.
        if mesh_on and not self._mesh_fallback and is_mesh_failure(e):
            # a pool of <= 1 survivors cannot host a gang: skip the
            # restart rung (a re-mesh would be single-device anyway —
            # that is exactly what the fallback rung below does)
            healthy = EL.healthy_device_count(conf)
            restartable = healthy is None or healthy > 1
            slept = self._elastic.try_restart(self._record_fault) \
                if restartable and self._elastic is not None else None
            if slept is not None:
                warnings.warn(
                    f"mesh stage failure, gang-restarting the mesh "
                    f"(attempt {self._elastic.restarts}/"
                    f"{self._elastic.max_restarts}, backoff "
                    f"{slept:.0f}ms): {msg[:160]}")
                self._record_fault("mesh_restart", e,
                                   attempt=self._elastic.restarts,
                                   backoff_ms=round(slept, 1))
                self.session.metrics.counter("mesh_restart_attempts").inc()
                if self._recovery is not None:
                    self._recovery.begin_recovery_attempt()
                # re-probe the healthy pool: a genuinely lost host
                # shrinks the gang instead of failing the re-mesh —
                # smaller n changes shapes, so memoized outputs drop
                n_conf = int(conf.get("spark_tpu.sql.mesh.size"))
                if healthy is not None and 1 < healthy < n_conf:
                    self._mesh_replan(mesh_size=healthy)
                return
            if bool(conf.get(
                    "spark_tpu.execution.meshFallback.enabled")):
                warnings.warn(
                    f"mesh stage failure, re-planning single-device "
                    f"(mesh_fallback): {msg[:160]}")
                self._record_fault("mesh_fallback", e)
                self._mesh_fallback = True
                if self._recovery is not None:
                    self._recovery.begin_recovery_attempt()
                self._mesh_replan(mesh_size=0)  # no exchanges/sharding
                return
            # no degrade rung (meshFallback.enabled=false): the
            # classification rungs below decide, like pre-elastic

        if cls in (FailureClass.TRANSIENT, FailureClass.TIMEOUT):
            slept = self._retry_policy.attempt_retry()
            if slept is None:
                if cls is FailureClass.TIMEOUT:
                    raise StageTimeoutError(
                        f"stage still over stageTimeoutMs after "
                        f"{self._retry_policy.attempts} retries: "
                        f"{msg[:200]}") from e
                raise  # transient budget exhausted: surface the original
            action = "stage_timeout" if cls is FailureClass.TIMEOUT \
                else "transient_retry"
            # "transient stage failure" prefix is load-bearing: the
            # pre-taxonomy retry loop warned with it and tests match it
            kind = "stage timeout" if cls is FailureClass.TIMEOUT \
                else "transient stage failure"
            warnings.warn(
                f"{kind}, retrying "
                f"({self._retry_policy.remaining} left, "
                f"backoff {slept:.0f}ms): {msg[:160]}")
            self._record_fault(action, e, backoff_ms=round(slept, 1))
            if self._recovery is not None:
                # shapes unchanged: completed upstream stage outputs
                # replay from the memo on the re-execution
                self._recovery.begin_recovery_attempt()
            # drop only THIS stage's compiled entry so the retry
            # recompiles (and trace-time injection sites re-fire
            # deterministically) — except on TIMEOUT: the program was
            # fine, just slow; recompiling the identical stage would
            # re-pay compile inside the next deadline window
            if cls is FailureClass.TRANSIENT \
                    and self._last_stage_key is not None:
                self.session._stage_cache.pop(self._last_stage_key, None)
            return

        if cls is FailureClass.OOM:
            self._oom_rung += 1
            # release this query's arbiter leases before any degraded
            # retry: a genuine RESOURCE_EXHAUSTED means the estimate
            # that backed them was wrong, and the retry's admit
            # decisions must start from a clean slate (the shared pool
            # must not stay pinned by a query that just OOMed)
            from ..service.arbiter import release_current
            release_current()
            if self._oom_rung == 1:
                # rung 1: evict the device-resident table cache (the
                # storage pool) and retry — the UnifiedMemoryManager
                # storage-eviction move
                from ..io.device_cache import CACHE
                # release_current() above dropped THIS query's pins;
                # any still-pinned entries are other running queries'
                # working sets — evicting those frees no HBM (their
                # references stay live) while zeroing the storage
                # accounting they're counted under
                freed = CACHE.evict_bytes(CACHE.nbytes)
                if self._last_stage_key is not None:
                    self.session._stage_cache.pop(self._last_stage_key, None)
                import gc
                gc.collect()
                warnings.warn(f"RESOURCE_EXHAUSTED: evicted device cache "
                              f"({freed} bytes) and retrying: {msg[:160]}")
                self._record_fault("oom_cache_evict", e, freed_bytes=freed)
                if self._recovery is not None:
                    # the memo pins device-resident stage outputs
                    # (build sides, streamed splices): under memory
                    # pressure they are part of the storage pool this
                    # rung exists to evict — drop them so the retry
                    # runs unpinned (reuse is lost, memory is freed)
                    self._recovery.invalidate()
                    self._recovery.begin_recovery_attempt()
                return
            if self._oom_rung == 2 and bool(conf.get(
                    "spark_tpu.execution.oom.spillOnExhausted")):
                # rung 2: re-plan under a 1-byte device budget so the
                # host-spill chunked paths (streaming partial spill /
                # external collect) take over — host RAM as spill tier
                warnings.warn(f"RESOURCE_EXHAUSTED persists: re-routing "
                              f"through the host-spill chunked path: "
                              f"{msg[:160]}")
                self._record_fault("oom_spill_reroute", e)
                if self._recovery is not None:
                    # the deviceBudget re-plan changes streaming shapes
                    self._recovery.invalidate()
                    self._recovery.begin_recovery_attempt()
                overlay = Conf(parent=conf)
                overlay.set("spark_tpu.sql.memory.deviceBudget", 1)
                chunk = int(conf.get(
                    "spark_tpu.sql.execution.streamingChunkRows"))
                overlay.set("spark_tpu.sql.execution.streamingChunkRows",
                            min(chunk, 1 << 22))
                self._exec_conf = overlay
                self._executed = None
                return
            # rung 3: out of moves — diagnostic naming the stage and its
            # capacity stats (issue acceptance: fail with a diagnostic)
            raise StageOOMError(self._oom_diagnostic(e)) from e

        raise  # FATAL: surface unchanged

    def _oom_diagnostic(self, e: Exception) -> str:
        caps: Dict[str, int] = {}
        try:
            if self._executed is not None:
                self._collect_caps(self._executed, caps)
        except Exception:  # noqa: BLE001 — best-effort diagnostics only
            pass
        from ..io.device_cache import CACHE
        from ..observability import xla_cost
        conf = self._conf
        stage = (self._last_stage_key or "<uncompiled>")[:400]
        # measured HBM demand (memory_analysis of the failing stage) vs
        # device capacity — the blind spot this layer exists to close:
        # the ladder's rung order can now be tuned against numbers
        hbm = "n/a (enable spark_tpu.sql.observability.xlaCost)"
        cost = self.session._stage_costs.get(self._last_stage_key or "") \
            or self.stage_costs.get(self._last_stage_key or "")
        if cost and cost.get("peak_hbm_bytes") is None:
            err = cost.get("error") or cost.get("memory_error")
            if err:
                hbm = f"capture failed: {err}"
        if cost and cost.get("peak_hbm_bytes") is not None:
            cap = xla_cost.device_hbm_capacity()
            hbm = (f"measured peak HBM demand "
                   f"{cost['peak_hbm_bytes']:,} bytes "
                   f"(args={cost.get('argument_bytes', 0):,}, "
                   f"temps={cost.get('temp_bytes', 0):,}, "
                   f"out={cost.get('output_bytes', 0):,}) vs "
                   f"device capacity "
                   + (f"{cap:,} bytes" if cap else "unknown"))
        return (
            f"RESOURCE_EXHAUSTED survived the degradation ladder "
            f"(device-cache evict -> host-spill reroute): "
            f"{type(e).__name__}: {str(e)[:200]}\n"
            f"  stage: {stage}\n"
            f"  hbm: {hbm}\n"
            f"  capacity stats (kind:tag -> rows): {caps or 'n/a'}\n"
            f"  deviceCacheBytes={CACHE.nbytes}, "
            f"deviceBudget={conf.get('spark_tpu.sql.memory.deviceBudget')}, "
            f"streamingChunkRows="
            f"{conf.get('spark_tpu.sql.execution.streamingChunkRows')}, "
            f"mesh.size={conf.get('spark_tpu.sql.mesh.size')}")

    def _execute_batch_inner(self) -> Tuple[Batch, Dict, Dict]:
        from ..parallel.mesh import get_mesh
        mesh = get_mesh(self._conf)
        if mesh is not None:
            # a drain request no gang this size can ever apply must
            # not stay armed for a future larger mesh
            from ..parallel.elastic import discard_stale_decommission
            discard_stale_decommission(self.session.conf, mesh)
        # seed capacities a previous execution of this plan discovered,
        # so repeated queries skip the overflow->re-jit ramp entirely.
        # The key includes every scan's source identity stamp: caps
        # learned on old data must not seed (possibly too small) after a
        # table is re-registered or a file rewritten.
        # planned before the span opens, in the phases' own order (each
        # is timed where it is first asked for: `analysis`, `optimize`,
        # then `plan`), so that `replan.key` holds none of them
        self.optimized_plan
        planned = self.executed_plan
        with self.spans.span("replan.key"):
            aqe_key = self._aqe_cache_key(mesh)
            saved_caps = self.session._aqe_caps.get(aqe_key) \
                if aqe_key is not None else None
            if saved_caps:
                self._apply_saved_caps(planned, saved_caps)
        # static analysis, plan half: after planning (with persisted AQE
        # caps applied — they are part of the stage key the recompile
        # check audits), before any streaming splice or compile. Strict
        # mode raises here, pre-compile.
        self._analyze_plan_phase()
        # size/capacity predictions off the planned tree (pure host
        # walk, microseconds): graded post-run against observed metrics
        # — the analyzer-self-grading loop (history.prediction_report)
        try:
            from ..analysis.predictions import predict_plan
            with self.spans.span("predict"):
                self.plan_predictions = predict_plan(
                    self.executed_plan, self._conf,
                    int(mesh.devices.size) if mesh is not None else 1)
        except Exception as e:  # noqa: BLE001 — predictions are advisory
            import warnings
            warnings.warn(f"plan prediction walk failed (skipped): "
                          f"{type(e).__name__}: {e}")
            self.plan_predictions = None
        root0 = self.executed_plan
        from .python_eval import extract_python_udfs, plan_has_udfs
        if plan_has_udfs(root0):
            t0 = time.perf_counter()
            root0 = extract_python_udfs(root0, self.session.conf,
                                        qe=self)
            self.phase_times["python_udfs"] = time.perf_counter() - t0
        if mesh is not None:
            root0 = self._materialize_generates(root0)
        # spark_tpu.sql.profile.dir: one trace of everything from here
        # on (streaming splice, ingest, dispatch), so a streamed
        # query's chunks and their spark_tpu.* annotations stand in it;
        # planning above may run nested executions, which have theirs
        profile_dir = str(self._conf.get("spark_tpu.sql.profile.dir"))
        with jax.profiler.trace(profile_dir) if profile_dir \
                else contextlib.nullcontext():
            return self._run_planned(root0, mesh, aqe_key)

    def _run_planned(self, root0: P.PhysicalPlan, mesh, aqe_key
                     ) -> Tuple[Batch, Dict, Dict]:
        """The planned tree to its result batch: streaming splice,
        ingest of what stays resident, the whole-stage dispatch with
        its capacity re-plans, and the end event."""
        from ..columnar import bucket_capacity
        from ..testing import faults
        from .failures import StageTimeoutError
        # per-shard flight recorder (observability/spans.py): the mesh
        # chunk drivers pick the telemetry up from the context var so
        # their signatures stay stable; records land on self.spans
        from ..observability.spans import (ShardStreamTelemetry,
                                           use_shard_telemetry)
        telem = None
        if mesh is not None and self._shard_obs_on():
            telem = ShardStreamTelemetry(
                recorder=self.spans, mesh=mesh, query_id=self.query_id,
                bus=self.session.listeners)
        # the chunk pipeline's spans (chunk.*, stream.drain) nest
        # under this one; a plan with nothing to stream leaves none
        with self.spans.span("streaming") as sp, \
                use_shard_telemetry(telem):
            root = self._materialize_streaming(root0, mesh)
            if root is root0:
                self.spans.discard(sp)
        if root is not root0:
            # chunked ingest + chunk compute happen inside the splice
            self.phase_times["streaming"] = sp.t1 - sp.t0
        else:
            # nothing streams: the walk that found so (a scan's
            # `estimated_rows`, the device-table cache's key and the
            # residency verdict) stands as a leaf of its own
            self.spans.record("stream.verdict", sp.t0, sp.t1)
        scans: List[P.LeafExec] = []
        self._collect_scans(root, scans)

        from . import lifecycle
        from ..io.device_cache import load_scan, scan_mesh
        # a scan that loads (a cache miss, an in-memory table) leaves
        # its columns' chunk.convert / chunk.put spans under this one
        with self.spans.span("ingest", scans=len(scans)) as sp:
            # cooperative boundary before host ingest loads the scans
            lifecycle.checkpoint("scan")
            # dedupe by node identity: a runtime filter's creation chain
            # shares its leaf with the join build side (the documented
            # DAG), so the same scan appears twice in `scans` — load and
            # pad it once, feed the same Batch to both input slots
            loaded: Dict[int, Batch] = {}
            for s in scans:
                if id(s) in loaded:
                    continue
                b, dealt = load_scan(s, self._conf, scan_mesh(s, mesh)) \
                    if isinstance(s, P.ScanExec) else (s.load(), None)
                if mesh is not None:
                    from ..parallel import pad_batch_to_multiple
                    if dealt is not None:
                        # a scan laid over the mesh at its load: the
                        # stage finds its rows in place, and the host
                        # knows how it dealt them
                        sp.attrs["mesh"] = int(mesh.devices.size)
                        self.session.metrics.count_shard_rows(dealt)
                    b = pad_batch_to_multiple(b, int(mesh.devices.size))
                loaded[id(s)] = b
            scan_batches = [loaded[id(s)] for s in scans]
        self.phase_times["ingest"] = sp.t1 - sp.t0

        t0 = time.perf_counter()
        token = None
        if mesh is not None:
            from ..parallel.mesh import stage_token
            token = stage_token(mesh)
        # static analysis, jaxpr half: abstract-eval the exact stage
        # callable about to be jitted (gated; memoized per stage key),
        # then publish the combined findings on the bus
        self._analyze_jaxpr_phase(
            root, mesh,
            (scan_batches,) if mesh is None else (scan_batches, token))
        adaptive = bool(self._conf.get("spark_tpu.sql.adaptive.enabled"))
        timeout_ms = int(self._conf.get(
            "spark_tpu.execution.stageTimeoutMs"))
        overflow: List[str] = []
        for _attempt in range(8):
            # failures here (compile, dispatch, trace-time injected
            # faults) propagate to _execute_recover, which classifies
            # them (execution/failures.py) and retries/degrades —
            # the unified spark.task.maxFailures seat
            t_att = time.perf_counter()
            args = (scan_batches,) if mesh is None \
                else (scan_batches, token)
            fn = self._compile_stage(root, mesh, args)
            # one per dispatch span, capacity re-plans' attempts included
            self.session.metrics.counter("stage_dispatches").inc()
            with self.spans.span("dispatch", attempt=_attempt) as disp:
                if mesh is not None:
                    disp.attrs["mesh"] = int(mesh.devices.size)
                faults.fire("stage_run")  # chaos seam: pre-dispatch
                # the call returning: trace + compile on a miss,
                # else the enqueue of the stage's program
                with self.spans.span("dispatch.launch"):
                    batch, flags, metrics = fn(*args)
                # ONE batched host pull for the whole stats channel
                # — per-scalar np.asarray is a host sync each (the
                # pull also syncs the attempt, making the wall-clock
                # deadline check below honest). The pull is
                # cancellable: it is woken when the device is done,
                # and waits for that in slices of dispatchPollMs, so
                # a cancel/deadline lands within one slice instead
                # of at stage completion
                with self.spans.span("dispatch.sync") as sync:
                    try:
                        flags, metrics = _sync_dispatched(
                            (flags, metrics), self._conf, sync)
                    finally:  # a cancel mid-wait counts them too
                        for attr, counter in (
                                ("ticks", "dispatch_sync_ticks"),
                                ("waited", "dispatch_sync_waits")):
                            self.session.metrics.counter(counter).inc(
                                sync.attrs.get(attr, 0))
                self._note_joins(disp, root, metrics)
                if mesh is not None:
                    self._count_mesh_stage(disp, metrics)
            # deadline BEFORE the stage-timeout check: an attempt
            # that outran the end-to-end budget raises the
            # lifecycle error (ladder stops), never a retryable
            # StageTimeoutError — queryDeadlineMs < stageTimeoutMs
            # must not retry through the recovery ladder
            lifecycle.checkpoint("post_dispatch")
            if timeout_ms > 0:
                att_ms = (time.perf_counter() - t_att) * 1e3
                if att_ms > timeout_ms:
                    raise StageTimeoutError(
                        f"stage attempt took {att_ms:.0f}ms > "
                        f"stageTimeoutMs={timeout_ms}: "
                        f"{root.simple_string()}")
            overflow = [k for k, v in flags.items()
                        if k.startswith(("join_overflow_",
                                         "join_nonunique_",
                                         "exch_overflow_",
                                         "agg_overflow_",
                                         "rtf_overflow_"))
                        and bool(v)]
            self._post_stage_completed(_attempt, t_att, metrics,
                                       overflow)
            if not overflow:
                break
            self.spans.mark("aqe_overflow", flags=overflow[:8])
            # unique-build fallbacks are correctness re-plans, not
            # capacity growth — never gated by the adaptive conf
            if not adaptive and any(
                    not k.startswith("join_nonunique_")
                    for k in overflow):
                raise RuntimeError(
                    f"capacity overflow in {overflow} with adaptive "
                    f"re-planning disabled "
                    f"(spark_tpu.sql.adaptive.enabled=false)")
            for k in overflow:
                if k.startswith("join_nonunique_"):
                    self._set_join_nonunique(
                        root, k[len("join_nonunique_"):])
                elif k.startswith("join_overflow_"):
                    tag = k[len("join_overflow_"):]
                    total = int(metrics[f"join_rows_{tag}"])
                    self._set_join_cap(root, tag,
                                       bucket_capacity(max(total, 8)))
                elif k.startswith("exch_overflow_"):
                    tag = k[len("exch_overflow_"):]
                    mx = int(metrics[f"exch_max_{tag}"])
                    if self._maybe_skew_replan(root, tag, metrics,
                                               mesh):
                        raise _ReplanRequest()
                    self._set_exchange_cap(root, tag,
                                           bucket_capacity(max(mx, 8)))
                elif k.startswith("rtf_overflow_"):
                    # a compacted filter kept more rows than its
                    # capacity holds: grow it to what it kept
                    tag = k[len("rtf_overflow_"):]
                    self._set_filter_cap(
                        root, tag,
                        bucket_capacity(_filter_kept(metrics, tag)))
                else:
                    tag = k[len("agg_overflow_"):]
                    total = int(metrics[f"agg_groups_{tag}"])
                    # bucketed like every other learned capacity:
                    # compute re-buckets before use, and a raw count
                    # in the stage key recompiles per exact total
                    self._set_agg_groups(root, tag,
                                         bucket_capacity(max(total, 8)))
        else:
            raise RuntimeError(
                f"capacity retries did not converge; still "
                f"overflowing: {overflow}")
        batch = jax.block_until_ready(batch)
        self.phase_times["execution"] = time.perf_counter() - t0
        if aqe_key is not None:
            # harvest from the UNSPLICED plan: streamed-aggregate joins
            # mutated their caps on the original nodes, which the
            # spliced `root` no longer contains. Merge (don't replace)
            # so a streamed run doesn't drop caps a whole-input run
            # learned, and bound the cache (plan strings are big).
            converged: Dict[str, int] = {}
            self._collect_caps(self.executed_plan, converged)
            self._collect_caps(root, converged)
            if mesh is None and adaptive:
                self._learn_filter_caps(root, metrics, converged)
            elif adaptive:
                self._learn_exchange_caps(root, metrics, converged,
                                          int(mesh.devices.size))
            if converged:
                store = self.session._aqe_caps
                store.setdefault(aqe_key, {}).update(converged)
                while len(store) > 256:
                    store.pop(next(iter(store)))
        # per-shard exchange vectors ([n] arrays riding the metrics
        # channel) unpack into transfer-phase flight-recorder records;
        # they never enter last_metrics (scalar columns only)
        if mesh is not None and self._shard_obs_on():
            self._record_exchange_shards(metrics, mesh)
        self.last_metrics = {k: int(v) for k, v in metrics.items()
                             if not k.startswith("shard_")}
        # the *_ms_* keys (rtf_build_ms_*): trace-time costs from the
        # host's record of the stage, floats (sub-ms builds are the
        # common case); and
        # its shapes (rtf_slots_*: what each filter handed on;
        # rtf_build_indices_*: what each filter's build scattered;
        # exch_slot_bytes_*: what each exchange's collective carried)
        self.last_metrics.update(
            (k, round(v, 3) if isinstance(v, float) else v)
            for k, v in self._stage_host().items()
            if isinstance(v, float)
            or k.startswith(("rtf_slots_", "rtf_build_indices_",
                             "exch_slot_bytes_")))
        if self._mesh_fallback:
            # degraded single-device result of a mesh-planned query:
            # visible next to the device metrics and in the event log
            self.last_metrics["mesh_fallback"] = 1
        # fill the data cache on the first action over a marked plan
        fp = self._fingerprint(self.logical)
        if fp in self.session._cache_requests and \
                fp not in self.session._data_cache:
            self.session._data_cache[fp] = batch.to_arrow()
        self._log_event(root)
        return batch, flags, metrics

    def _maybe_skew_replan(self, root: P.PhysicalPlan, exch_tag: str,
                           metrics: Dict, mesh) -> bool:
        """On a skewed shuffle-join exchange (max bucket > factor x mean
        rows/shard), force the join to broadcast and request a re-plan
        — the `OptimizeSkewedJoin.scala:56` / `DynamicJoinSelection`
        move, expressed as strategy re-selection. Returns True when an
        override was recorded."""
        conf = self._conf
        if getattr(self, "_no_more_replans", False):
            return False  # budget exhausted: capacity growth only
        if mesh is None or not bool(conf.get(
                "spark_tpu.sql.adaptive.skewJoin.enabled")):
            return False
        n = int(mesh.devices.size)
        factor = float(conf.get("spark_tpu.sql.adaptive.skewJoin.factor"))
        limit = int(conf.get(
            "spark_tpu.sql.adaptive.skewJoin.broadcastThreshold"))
        mx = int(metrics.get(f"exch_max_{exch_tag}", 0))
        rows = int(metrics.get(f"exch_rows_{exch_tag}", 0))
        # exch_max is the max per-(src,dst) bucket count; a uniform
        # spread puts rows/n^2 in each bucket
        if rows <= 0 or mx * n * n <= factor * rows:
            return False  # overflow without skew: capacity growth wins

        # find the join fed by this exchange
        hit = []

        def walk(node, parent):
            for c in node.children:
                walk(c, node)
            if isinstance(node, P.ExchangeExec) and node.tag == exch_tag \
                    and isinstance(parent, P.JoinExec):
                hit.append(parent)

        walk(root, None)
        if not hit:
            return False
        join = hit[0]
        if join.strategy != "shuffle" or join.how in ("right", "full") \
                or join.tag in self._join_overrides:
            return False
        # measured build-side size: its own exchange's routed rows
        build = join.children[1]
        build_rows = None
        if isinstance(build, P.ExchangeExec):
            build_rows = metrics.get(f"exch_rows_{build.tag}")
        if build_rows is None:
            return False  # no measurement -> keep capacity growth
        width = 8 * max(1, len(build.schema().fields))
        if int(build_rows) * width > limit:
            return False
        self._join_overrides[join.tag] = "broadcast"
        return True

    def _note_joins(self, disp, root: P.PhysicalPlan,
                    metrics: Dict) -> None:
        """What a dispatched stage's joins did, into the process
        counter `/metrics` serves, from the stats channel
        `dispatch.sync` has just pulled (no sync of its own), whatever
        the conf: `join_output_rows`, the sum of the stage's
        `join_rows_*` (under a mesh `join_mesh_rows_*`, the whole
        mesh's: `join_rows_*` is the fullest shard's there, a
        capacity), and `join_widest_rows`, the largest of the
        query's (it grows by what a stage's largest passes the query's
        so far: the widest join the chosen order made). The filters'
        `rtf_tested` / `rtf_pruned` are the metrics sink's, folded at a
        query's end. The `dispatch` span
        gets `joins=<n>`, the stage's join operators, how each
        join on several columns packed them into one key
        (`join_keys`, `<tag>=exact|hashed`: `hashed` re-verifies every
        column of a match), under a mesh how each met its build
        (`join_strategies`, `<tag>=shuffle|broadcast`), and
        `rtf_caps=<tag>=<K>,...` where a runtime filter of `root`
        hands on a compacted batch of K slots, and `rtf_build=<tag>=<n>,...`:
        the indices each filter's Bloom build scattered. A stage
        without a join touches nothing; one
        deserialized from the engine's own compile cache
        (`compileCache.enabled`) was not traced in this process and has
        no host record, so its span keeps only `joins` and `rtf_caps`
        and its `last_metrics` go without."""
        rows_of = "join_mesh_rows_" if any(
            k.startswith("join_mesh_rows_") for k in metrics) \
            else "join_rows_"
        joined = [int(v) for k, v in metrics.items()
                  if k.startswith(rows_of)]
        if joined:
            self.session.metrics.counter("join_output_rows").inc(
                sum(joined))
            widest = max(joined)
            if widest > self._join_widest:
                self.session.metrics.counter("join_widest_rows").inc(
                    widest - self._join_widest)
                self._join_widest = widest
        joins = self._nodes(root, P.JoinExec)
        if joins:
            disp.attrs["joins"] = len(joins)
        host = self._stage_host()
        for prefix, attr in (("join_keys_", "join_keys"),
                             ("join_strategy_", "join_strategies"),
                             ("rtf_build_indices_", "rtf_build")):
            _tag_attr(disp, host, prefix, attr)
        rtf_caps = sorted((f.tag, f.out_cap)
                          for f in self._nodes(root, P.RuntimeFilterExec)
                          if f.out_cap is not None)
        if rtf_caps:
            disp.attrs["rtf_caps"] = ",".join(
                f"{tag}={cap}" for tag, cap in rtf_caps)

    @staticmethod
    def _nodes(root: P.PhysicalPlan, kind: type) -> List[P.PhysicalPlan]:
        """`root`'s operators of `kind`, each once (runtime filters'
        creation chains are shared under them: the tree is a DAG)."""
        found: List[P.PhysicalPlan] = []
        seen = set()

        def walk(node):
            if id(node) in seen:
                return
            seen.add(id(node))
            for c in node.children:
                walk(c)
            if isinstance(node, kind):
                found.append(node)

        walk(root)
        return found

    def _learn_filter_caps(self, root: P.PhysicalPlan, metrics: Dict,
                           converged: Dict[str, int]) -> None:
        """After a converged attempt on one device: a runtime filter
        that handed on its probe's slots and kept few of them gets a
        capacity among the converged ones, `rtf:<tag>`, from its own
        counts in the stats channel `dispatch.sync` has pulled. This
        execution's result stands; the next execution of the same text
        on the same data applies the capacity and compiles the
        compacted stage once (a first submission's compile is most of
        what a request may take, PERF.md, and two do not fit), and
        after that the capacity only grows (`rtf_overflow_<tag>`)."""
        from ..columnar import bucket_capacity
        host = self._stage_host()
        for node in self._nodes(root, P.RuntimeFilterExec):
            # the probe's slots, which a masked filter hands on: from
            # the host's record of the trace (none: nothing is learned)
            slots = host.get(f"rtf_slots_{node.tag}")
            if node.out_cap is not None or slots is None \
                    or f"rtf_tested_{node.tag}" not in metrics:
                continue
            cap = bucket_capacity(_filter_kept(metrics, node.tag))
            if slots >= FILTER_COMPACT_MIN_SLOTS \
                    and cap * FILTER_COMPACT_SHRINK <= slots:
                converged[f"rtf:{node.tag}"] = cap

    def _learn_exchange_caps(self, root: P.PhysicalPlan, metrics: Dict,
                             converged: Dict[str, int], n: int) -> None:
        """After a converged attempt over a mesh of `n`: a hash or range
        exchange still on its seeded block (2x a uniform spread of its
        input) whose fullest bucket, `exch_max_<tag>` in the stats
        channel `dispatch.sync` has pulled, fits half of it gets the
        block that bucket fills among the converged capacities,
        `exch:<tag>`, when a shard receives EXCHANGE_SHRINK_MIN_SLOTS
        slots or more. This execution's result stands; the next
        execution of the same text on the same data applies the block
        and compiles the smaller stage once, and after that the block
        only grows (`exch_overflow_<tag>`). Each block learned counts
        in `exchange_blocks_learned`, and the stage that ran the seeded
        blocks leaves the stage cache."""
        from ..columnar import bucket_capacity
        host = self._stage_host()
        learned = 0
        for node in self._nodes(root, P.ExchangeExec):
            # the block it ran with, from the host's record of the trace
            # (none: a broadcast, or an exchange the trace never reached)
            block = host.get(f"exch_block_{node.tag}")
            fullest = metrics.get(f"exch_max_{node.tag}")
            if node.block_cap is not None or block is None \
                    or fullest is None:
                continue
            cap = bucket_capacity(max(int(fullest), 8))
            if n * block >= EXCHANGE_SHRINK_MIN_SLOTS and 2 * cap <= block:
                converged[f"exch:{node.tag}"] = cap
                learned += 1
        if learned:
            self.session.metrics.counter("exchange_blocks_learned").inc(
                learned)
            # the next execution runs the learned blocks: this stage's
            # program, kept loaded, would hold its code in the chip's
            # memory beside the learned one's for nothing (on a TPU a
            # loaded program's code counts in `bytes_in_use`)
            self.session._stage_cache.pop(self._last_stage_key, None)

    def _stage_host(self) -> Dict[str, object]:
        """The host's record of the stage last compiled or found."""
        return self.session._stage_host.get(self._last_stage_key or "", {})

    def _count_mesh_stage(self, disp, metrics: Dict) -> None:
        """What a dispatched mesh stage did, into the process counters
        `/metrics` serves, from the stats channel `dispatch.sync` has
        just pulled (no sync of its own), whatever the conf:
        `mesh_stage_dispatches`, the exchanges' routed rows and bytes,
        and of each exchange's per-shard row vector the fullest
        shard's rows and all shards'. From the host's record of the
        stage: `exchange_slot_bytes`, what the exchanges' collectives
        carry, padding included (`exch_slot_bytes_*`), and on the
        `dispatch` span `exch_blocks=<tag>=<slots>,...`, each
        exchange's slots a (source, destination) pair."""
        registry = self.session.metrics
        registry.counter("mesh_stage_dispatches").inc()
        for k, v in metrics.items():
            if k.startswith("exch_rows_"):
                registry.counter("exchange_rows").inc(int(v))
            elif k.startswith("exch_bytes_"):
                registry.counter("exchange_bytes").inc(int(v))
            elif k.startswith("shard_rows_"):
                registry.count_shard_rows(v.reshape(-1))
        host = self._stage_host()
        slot_bytes = sum(v for k, v in host.items()
                         if k.startswith("exch_slot_bytes_"))
        if slot_bytes:
            registry.counter("exchange_slot_bytes").inc(slot_bytes)
        _tag_attr(disp, host, "exch_block_", "exch_blocks")

    def _record_exchange_shards(self, metrics: Dict, mesh) -> None:
        """Unpack the exchanges' per-shard row/byte vectors (emitted as
        one-hot psums by parallel/shuffle.py) into transfer-phase shard
        records on the span recorder — the exchange half of the flight
        recorder, next to the chunk drivers' compute/ingest records."""
        from ..parallel.mesh import shard_hosts
        import numpy as np
        hosts = shard_hosts(mesh)
        for k, v in metrics.items():
            if not k.startswith("shard_rows_"):
                continue
            tag = k[len("shard_rows_"):]
            rows = np.asarray(v).reshape(-1)
            nbytes = metrics.get(f"shard_bytes_{tag}")
            nbytes = np.asarray(nbytes).reshape(-1) \
                if nbytes is not None else None
            self.spans.add_shard_records([{
                "shard": i, "host": hosts[i] if i < len(hosts) else 0,
                "chunk": None, "phase": "transfer", "rows": int(rows[i]),
                "bytes": int(nbytes[i]) if nbytes is not None else None,
                "source": f"exchange:{tag}",
            } for i in range(len(rows))])

    def _post_stage_completed(self, attempt: int, t_att: float,
                              metrics: Dict, overflow: List[str]) -> None:
        from ..observability.listener import StageCompletedEvent
        if not self._observe_events:
            return
        cost = self.stage_costs.get(self._last_stage_key or "")
        with self.spans.span("stage_event"):
            self.session.listeners.post(
                "on_stage_completed", StageCompletedEvent(
                    query_id=self.query_id, ts=time.time(),
                    stage_key=self._last_stage_key or "",
                    key_hash=(cost or {}).get("key_hash", ""),
                    attempt=attempt,
                    elapsed_ms=round(
                        (time.perf_counter() - t_att) * 1e3, 2),
                    metrics=metrics, overflow=list(overflow)))

    def _build_event(self, root: Optional[P.PhysicalPlan],
                     status: str = "ok", error=None) -> Dict:
        """The event-log record for this execution: one dict, JSON-line
        serializable (sinks.json_default covers numpy/JAX scalars)."""
        from ..observability import xla_cost
        from ..observability.sinks import EVENT_LOG_SCHEMA_VERSION
        event = {
            "schema_version": EVENT_LOG_SCHEMA_VERSION,
            "query_id": self.query_id,
            "ts": time.time(),
            "status": status,
            "plan": root.describe() if root is not None else
            self.logical.tree_string(),
            "phase_times_s": {k: round(v, 6)
                              for k, v in self.phase_times.items()},
            "metrics": self.last_metrics,
        }
        if error is not None:
            event["error"] = f"{type(error).__name__}: {error}"[:300]
        if root is not None:
            try:
                # runtime-annotated physical tree (rows/caps/hbm notes)
                # — the GET /queries/<id>/plan payload
                event["plan_tree"] = self._runtime_tree(root)
            except Exception:  # noqa: BLE001 — annotation is best-effort
                pass
        if self.spans.spans:
            event["spans"] = self.spans.to_dicts()
            if self.spans.dropped:
                event["spans_dropped"] = self.spans.dropped
        if self.spans.shard_records:
            # per-shard flight-recorder records (schema v3): mesh chunk
            # drivers' ingest/compute waits + exchange transfer vectors
            event["shards"] = list(self.spans.shard_records)
            if self.spans.shard_dropped:
                event["shards_dropped"] = self.spans.shard_dropped
        if self.plan_predictions:
            # planner/AQE size predictions, graded post-hoc against the
            # metrics in this same record (history.prediction_report)
            event["predictions"] = list(self.plan_predictions)
        if self.reorder_decisions is not None:
            # cost-based join-reorder decisions (plan/join_reorder.py):
            # per-region frontend order vs chosen order + estimates,
            # served by GET /queries/<id>/plan
            event["reorder"] = {
                "enabled": bool(self.session.conf.get(
                    "spark_tpu.sql.cbo.joinReorder")),
                "changed": any(d.get("changed")
                               for d in self.reorder_decisions),
                "regions": list(self.reorder_decisions)}
        if self.rule_trace:
            # per-rule optimizer application records (schema v7,
            # analysis/plan_integrity.py PlanChangeTracer): batch, rule,
            # invocations, effective count, ms, optional first-effective
            # tree diff — history.rule_report / GET /queries/<id>/plan
            event["rule_trace"] = [dict(r) for r in self.rule_trace]
        if self.stage_costs:
            # per-stage XLA cost/memory accounting (history.hbm_summary
            # / compile_summary read these)
            event["stages"] = list(self.stage_costs.values())
            cap = xla_cost.device_hbm_capacity()
            if cap is not None:
                event["device_hbm_capacity_bytes"] = cap
        if self.analysis_findings:
            # pre-compile analyzer findings (read back via
            # history.read_event_log; bench counts them per query)
            event["analysis_findings"] = [
                f.to_dict() for f in self.analysis_findings]
        if self.udf_summary:
            # python-UDF lane record (schema v5): mode + batch/row
            # totals + worker restarts (history.prediction_report
            # grades udf_batches/udf_rows predictions against these)
            event["udf"] = dict(self.udf_summary)
        if self.fault_summary:
            # every retry/eviction/degradation/fallback this
            # execution survived (history.fault_summary reads these)
            event["fault_summary"] = dict(
                self.fault_summary,
                retry_backoff_ms=round(
                    self._retry_policy.total_sleep_ms, 1)
                if self._retry_policy is not None else 0.0,
                events=self.fault_events)
        return event

    def _post_query_end(self, root: Optional[P.PhysicalPlan],
                        status: str = "ok", error=None) -> None:
        from ..observability.listener import QueryEndEvent
        if not self._observe_events:
            return
        # the event's build, the listener bus, the sinks, the status
        # store; the event's own copy of the spans is taken inside, so
        # it holds neither this span nor `egress`
        with self.spans.span("end_event"):
            try:
                event = self._build_event(root, status, error)
            except Exception as e:  # noqa: BLE001 — observability only
                import warnings
                warnings.warn(f"event build failed: {e}")
                return
            self.session.listeners.post("on_query_end", QueryEndEvent(
                query_id=self.query_id, ts=event["ts"], status=status,
                event=event, spans=self.spans))

    def _log_event(self, root: P.PhysicalPlan) -> None:
        """Publish the execution's event record on the listener bus
        (the `EventLoggingListener.scala:50` event-stream analog — the
        JSONL writer, Chrome-trace writer, and metrics sinks are all
        subscribers; replay with spark_tpu.history.read_event_log)."""
        self._post_query_end(root, status="ok")

    def collect(self) -> pa.Table:
        # ONE arbiter lease scope spans the external-collect gate AND
        # the execute_batch that runs when the gate says "fits
        # resident": the residency lease granted during the gate check
        # must stay held while the resident execution actually uses the
        # bytes (the inner enter_query calls nest onto this owner).
        from ..service import arbiter as res_arbiter
        from . import lifecycle
        t_begin = time.perf_counter()
        arb_token = res_arbiter.enter_query(
            f"{self.session.app_id}:q{self.query_id}")
        # lifecycle scope spans the external-collect gate too, so a
        # cancel lands between chunks of the out-of-core egress path
        # (execute_batch nests inside this scope, sharing the token)
        lc_scope = lifecycle.enter_query_scope(
            self.session.app_id, self.query_id, self.session.conf)
        try:
            try:
                ext = self._try_external_collect()
            except (lifecycle.QueryCancelledError,
                    lifecycle.QueryDeadlineError) as e:
                # the external path never reaches execute_batch's
                # except: observe here (counter + fault record + event)
                self._observe_cancel(e)
                raise
            if ext is not None:
                return ext
            batch, _, _ = self.execute_batch(t_begin)
            # after the end event: the device-to-host pull and the
            # Arrow build stand in `self.spans` (and the service's
            # timeline), not in the event log's record
            with self.spans.span("egress"):
                return batch.to_arrow()
        finally:
            lifecycle.exit_query_scope(lc_scope)
            res_arbiter.exit_query(arb_token)

    def _try_external_collect(self) -> Optional[pa.Table]:
        """Out-of-core host egress (execution/external.py): ORDER BY /
        LIMIT / plain materialization over scans past the device budget
        — per-query deviceBudget, or the shared arbiter pool when the
        service installed one — stream chunk-wise and spill to host
        Arrow, never resident."""
        from ..service import arbiter as res_arbiter
        if not res_arbiter.out_of_core_active(self.session.conf):
            return None
        import warnings
        from ..testing import faults
        from .external import try_external_collect
        from .failures import FailureClass, RetryPolicy, classify
        from .python_eval import plan_has_udfs
        from .recovery import RecoveryContext
        from ..observability.spans import use_recorder
        self._activate_conf()
        if plan_has_udfs(self.executed_plan):
            return None  # UDF stages evaluate through execute_batch
        # the out-of-core egress path never reaches execute_batch, but
        # it is exactly where the host-spill findings live — analyze
        # (and strict-gate) here too
        self._observe_events = self._events_enabled()
        self._analyze_plan_phase()
        self._post_analysis(self._analysis_conf()[1])
        # chunk-granular retry covers this path too: arm conf-driven
        # injection and record chunk_retry actions on THIS execution
        # (counters reset like execute_batch — repeated collects must
        # not accumulate stale actions)
        faults.arm(self.session.conf)
        self.fault_summary = {}
        self.fault_events = []
        self._recovery = RecoveryContext(metrics=self.session.metrics,
                                         record=self._record_fault)
        t0 = time.perf_counter()
        conf = self.session.conf
        # transient rung for the egress path (the execute_batch ladder
        # never sees these streams): a flake that exhausts the
        # per-chunk budget restarts the whole external stream under
        # the same maxRetries/backoff budget instead of aborting
        policy = RetryPolicy(
            max_retries=self._max_retries(conf),
            backoff_ms=float(conf.get("spark_tpu.execution.backoffMs")))
        arb_token = res_arbiter.enter_query(
            f"{self.session.app_id}:q{self.query_id}:ext")
        try:
            while True:
                try:
                    with use_recorder(self.spans), \
                            self.spans.span("external") as sp:
                        out = try_external_collect(
                            self.session, self.executed_plan, conf,
                            self.session._stage_cache, self._recovery)
                        if out is None:
                            self.spans.discard(sp)
                    break
                except Exception as e:  # noqa: BLE001 — classified below
                    if classify(e) not in (FailureClass.TRANSIENT,
                                           FailureClass.TIMEOUT):
                        raise
                    slept = policy.attempt_retry()
                    if slept is None:
                        raise
                    warnings.warn(
                        f"transient stage failure, retrying external "
                        f"collect ({policy.remaining} left, backoff "
                        f"{slept:.0f}ms): {type(e).__name__}: "
                        f"{str(e)[:160]}")
                    self._record_fault("transient_retry", e,
                                       backoff_ms=round(slept, 1))
                    self._recovery.begin_recovery_attempt()
        finally:
            self._recovery.release()
            res_arbiter.exit_query(arb_token)
        if out is not None:
            self.phase_times["external"] = time.perf_counter() - t0
        return out
