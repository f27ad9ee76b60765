"""Physical plan: partitioning algebra + executable operators.

The partitioning algebra mirrors the reference's
`plans/physical/partitioning.scala` (`Distribution:31`,
`HashPartitioning:212`); operators mirror `execution/SparkPlan.scala`
(`requiredChildDistribution`, `outputPartitioning`) but `compute` builds a
*traced* jnp program over whole Batches instead of an RDD of row
iterators — the executor jits the composed tree, so XLA fusion plays the
role of `WholeStageCodegenExec.scala:626`.
"""

from __future__ import annotations

import copy
import dataclasses
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar import Batch, Column, bucket_capacity
from ..config import Conf
from ..expr import (Alias, AnalysisError, Expression, SortOrder, Vec)
from ..expr_agg import AggExpr
from ..execution import aggregate as agg_kernels
from ..execution import join as join_kernels
from ..execution import sort as sort_kernels


# ---------------------------------------------------------------------------
# Partitioning algebra (reference: partitioning.scala)
# ---------------------------------------------------------------------------

class Distribution:
    pass


@dataclass(frozen=True)
class UnspecifiedDistribution(Distribution):
    pass


@dataclass(frozen=True)
class AllTuples(Distribution):
    """All rows co-located in one logical partition."""


@dataclass(frozen=True)
class ClusteredDistribution(Distribution):
    keys: Tuple[str, ...]


@dataclass(frozen=True)
class BroadcastDistribution(Distribution):
    """Full copy on every shard."""


@dataclass(frozen=True)
class OrderedDistribution(Distribution):
    """Rows range-partitioned by sort key: shard i's keys all <= shard
    i+1's (reference: OrderedDistribution in partitioning.scala:79)."""

    order_key: Tuple[str, ...]  # repr of the SortOrders (equality basis)


class Partitioning:
    num_partitions: int = 1

    def satisfies(self, dist: Distribution) -> bool:
        if isinstance(dist, UnspecifiedDistribution):
            return True
        return False


@dataclass(frozen=True)
class SinglePartition(Partitioning):
    num_partitions: int = 1

    def satisfies(self, dist):
        return not isinstance(dist, BroadcastDistribution)


@dataclass(frozen=True)
class HashPartitioning(Partitioning):
    keys: Tuple[str, ...] = ()
    num_partitions: int = 1

    def satisfies(self, dist):
        if isinstance(dist, UnspecifiedDistribution):
            return True
        if isinstance(dist, ClusteredDistribution):
            return set(self.keys).issubset(set(dist.keys)) and len(self.keys) > 0
        return False


@dataclass(frozen=True)
class Replicated(Partitioning):
    num_partitions: int = 1

    def satisfies(self, dist):
        return isinstance(dist, (UnspecifiedDistribution, BroadcastDistribution))


@dataclass(frozen=True)
class RangePartitioning(Partitioning):
    """Contiguous key ranges over the mesh axis in shard order
    (reference: RangePartitioning, partitioning.scala:255). `orders`
    carries the actual SortOrder objects for the exchange lowering;
    equality/hashing uses their repr (SortOrder overloads no __eq__)."""

    order_key: Tuple[str, ...] = ()
    num_partitions: int = 1
    orders: Tuple = dataclasses.field(default=(), compare=False, hash=False)

    def satisfies(self, dist):
        if isinstance(dist, UnspecifiedDistribution):
            return True
        if isinstance(dist, OrderedDistribution):
            return self.order_key == dist.order_key
        return False


@dataclass(frozen=True)
class UnknownPartitioning(Partitioning):
    num_partitions: int = 1


# ---------------------------------------------------------------------------
# Execution context
# ---------------------------------------------------------------------------

class ExecContext:
    """Per-execution state threaded through `compute` calls: conf, runtime
    flags (traced scalars surfaced to the host, e.g. join-capacity
    overflow), and per-operator metrics (the SQLMetrics analog).

    When running inside `shard_map` over a mesh, `axis_name`/`n_shards`
    identify the data axis: leaves synthesize only their stripe and
    ExchangeExec lowers to collectives (parallel/shuffle.py).

    `host` is what an operator learns while it is TRACED and the host
    alone may know (the milliseconds a join's or a runtime filter's
    construction took, the kernel a join resolved to). It never enters
    the program: a clock's reading as a constant would make the lowered
    text differ in every process. The executor keeps the record beside
    the stage-cache entry, so a stage-cache hit still reports the
    build cost of the trace that made the stage."""

    def __init__(self, conf: Conf, axis_name: Optional[str] = None,
                 n_shards: int = 1, host: Optional[Dict[str, object]] = None):
        self.conf = conf
        self.axis_name = axis_name
        self.n_shards = n_shards
        self.flags: Dict[str, object] = {}
        self.metrics: Dict[str, object] = {}
        self.host: Dict[str, object] = {} if host is None else host

    def add_flag(self, name: str, value) -> None:
        if name in self.flags:
            self.flags[name] = self.flags[name] | value
        else:
            self.flags[name] = value

    def add_metric(self, name: str, value) -> None:
        # registered prefixes only (observability/metrics.py): an
        # unregistered name would flow into the event log but silently
        # miss every history summary — fail at trace time instead.
        # The metric-prefix lint pass enforces the same statically.
        from ..observability.metrics import is_registered_metric
        if not is_registered_metric(name):
            raise ValueError(
                f"unregistered metric name {name!r}: add its prefix to "
                f"observability.metrics.METRIC_PREFIXES and a history "
                f"summary consumer")
        self.metrics[name] = value

    def add_host_ms(self, name: str, t0: float) -> None:
        """Milliseconds since `t0` (`time.perf_counter`) under a
        registered metric name, into the host's record and no
        program."""
        from ..observability.metrics import is_registered_metric
        if not is_registered_metric(name):
            raise ValueError(f"unregistered metric name {name!r}")
        self.host[name] = (time.perf_counter() - t0) * 1e3


# ---------------------------------------------------------------------------
# Operators
# ---------------------------------------------------------------------------

class PhysicalPlan:
    children: Tuple["PhysicalPlan", ...] = ()

    def schema(self) -> T.Schema:
        raise NotImplementedError

    def output_partitioning(self) -> Partitioning:
        return SinglePartition()

    def required_child_distributions(self) -> List[Distribution]:
        return [UnspecifiedDistribution() for _ in self.children]

    def compute(self, ctx: ExecContext, inputs: List[Batch]) -> Batch:
        raise NotImplementedError

    def describe(self) -> str:
        """Stable structural fingerprint for the compiled-stage cache
        (plays the role of the Janino cache key in CodeGenerator.scala:1435)."""
        parts = [self.simple_string()]
        for c in self.children:
            parts.append(c.describe())
        return "(" + " ".join(parts) + ")"

    def simple_string(self) -> str:
        return type(self).__name__

    def tree_string(self, depth: int = 0) -> str:
        line = "  " * depth + self.simple_string()
        return "\n".join([line] + [c.tree_string(depth + 1)
                                   for c in self.children])

    def __repr__(self):
        return self.tree_string()


class LeafExec(PhysicalPlan):
    """Leaves either synthesize data in-trace (Range) or consume a host
    -loaded Batch passed as a jit argument (Scan)."""

    #: True when the executor must load and pass a Batch argument
    needs_input = False

    #: mesh data-axis size the planner targeted (1 = single chip). When
    #: >1, the leaf's rows are sharded over the axis, so its output
    #: partitioning is unknown and exchanges get inserted above it.
    dist_n: int = 1

    def output_partitioning(self):
        if self.dist_n > 1:
            return UnknownPartitioning(self.dist_n)
        return SinglePartition()

    def load(self):  # host side
        raise NotImplementedError


class RangeExec(LeafExec):
    def __init__(self, start: int, end: int, step: int = 1):
        self.start, self.end, self.step = start, end, step
        self.children = ()

    def schema(self):
        return T.Schema([T.Field("id", T.LONG, nullable=False)])

    def num_rows(self) -> int:
        return max(0, -(-(self.end - self.start) // self.step))

    def compute(self, ctx, inputs):
        n = self.num_rows()
        cap = bucket_capacity(n)
        bits = self._id_bits()
        if ctx.axis_name is not None:
            # synthesize only this shard's contiguous stripe
            shards = ctx.n_shards
            cap += (-cap) % shards
            local = cap // shards
            i = jax.lax.axis_index(ctx.axis_name)
            base = i.astype(jnp.int64) * local
            offs = base + jnp.arange(local, dtype=jnp.int64)
            ids = self.start + self.step * offs
            return Batch({"id": Column(ids, T.LONG, bits=bits)}, offs < n)
        ids = self.start + self.step * jnp.arange(cap, dtype=jnp.int64)
        sel = jnp.arange(cap) < n
        return Batch({"id": Column(ids, T.LONG, bits=bits)}, sel)

    def _id_bits(self) -> Optional[int]:
        """Static id bound: values in [0, 2^bits) when the range is
        non-negative. Capacity padding (bucket rounding, chunk tails,
        and shard-multiple rounding) can synthesize ids past `end`;
        2x num_rows plus a generous shard-rounding slack bounds every
        padding scheme used."""
        if self.start < 0 or self.step < 0:
            return None
        hi = self.start + self.step * (2 * max(self.num_rows(), 8) + 8192)
        return max(1, int(np.ceil(np.log2(max(hi, 2)))))

    def simple_string(self):
        return f"RangeExec({self.start},{self.end},{self.step})"


class ScanExec(LeafExec):
    needs_input = True

    def __init__(self, source, required_columns, pushed_filters):
        self.source = source
        self.required_columns = required_columns
        self.pushed_filters = tuple(pushed_filters)
        self.children = ()

    def schema(self):
        full = self.source.schema()
        if self.required_columns is None:
            return full
        return T.Schema([full.field(n) for n in self.required_columns])

    def load(self, placement=None) -> Batch:
        if placement is None:  # a source written before placements
            return self.source.load(self.required_columns,
                                    self.pushed_filters)
        return self.source.load(self.required_columns, self.pushed_filters,
                                placement)

    def compute(self, ctx, inputs):
        # the executor substitutes the loaded batch
        raise RuntimeError("ScanExec.compute is handled by the executor")

    def simple_string(self):
        cols = "*" if self.required_columns is None else \
            ",".join(self.required_columns)
        return (f"ScanExec({self.source.name},[{cols}],"
                f"pushed={[repr(f) for f in self.pushed_filters]})")


class InputExec(LeafExec):
    """A leaf holding an already-computed device Batch (e.g. the result of
    a streamed aggregation) — the analog of a materialized QueryStageExec
    in the reference's AQE loop (`AdaptiveSparkPlanExec.scala:64`)."""

    needs_input = True

    def __init__(self, batch: Batch, schema: T.Schema, label: str = "input"):
        self._batch = batch
        self._schema = schema
        self.label = label
        self.children = ()

    def schema(self):
        return self._schema

    def load(self) -> Batch:
        return self._batch

    def compute(self, ctx, inputs):
        raise RuntimeError("InputExec.compute is handled by the executor")

    def simple_string(self):
        return f"InputExec({self.label},{self._schema!r})"


class UnaryExec(PhysicalPlan):
    @property
    def child(self) -> PhysicalPlan:
        return self.children[0]

    def output_partitioning(self):
        return self.child.output_partitioning()


class ProjectExec(UnaryExec):
    def __init__(self, child: PhysicalPlan, exprs: Sequence[Expression]):
        self.children = (child,)
        self.exprs = tuple(exprs)

    def schema(self):
        cs = self.child.schema()
        return T.Schema([T.Field(e.name(), e.dtype(cs), e.nullable(cs))
                         for e in self.exprs])

    def compute(self, ctx, inputs):
        batch = inputs[0]
        cap = batch.capacity
        cols = {}
        for e in self.exprs:
            v = e.eval(batch)
            data = v.data
            if data is None:
                raise AnalysisError(f"cannot project host-only value {e!r}")
            if np.ndim(data) == 0:
                data = jnp.broadcast_to(data, (cap,))
            validity = v.validity
            if validity is not None and np.ndim(validity) == 0:
                validity = jnp.broadcast_to(validity, (cap,))
            cols[e.name()] = Column(data, v.dtype, validity, v.dictionary,
                                    offsets=v.offsets,
                                    elem_validity=v.elem_validity)
        return Batch(cols, batch.selection)

    def simple_string(self):
        return f"ProjectExec({[repr(e) for e in self.exprs]})"


class FilterExec(UnaryExec):
    def __init__(self, child: PhysicalPlan, condition: Expression):
        self.children = (child,)
        self.condition = condition

    def schema(self):
        return self.child.schema()

    def compute(self, ctx, inputs):
        batch = inputs[0]
        v = self.condition.eval(batch)
        keep = v.data
        if v.validity is not None:
            keep = keep & v.validity  # NULL predicate -> drop row
        if np.ndim(keep) == 0:
            keep = jnp.broadcast_to(keep, (batch.capacity,))
        sel = keep if batch.selection is None else (batch.selection & keep)
        return batch.with_selection(sel)

    def simple_string(self):
        return f"FilterExec({self.condition!r})"


class HashAggregateExec(UnaryExec):
    """Trace-time choice between dense-domain scatter aggregation and the
    sort-based general path (see execution/aggregate.py). `mode` follows
    the reference's partial/final split (`AggUtils.scala`):

    - complete: update + reduce + finalize in one node;
    - partial:  update + reduce, outputs raw accumulator columns;
    - final:    re-reduces accumulator columns by key, then finalizes.
    """

    def __init__(self, child: PhysicalPlan, group_exprs: Sequence[Expression],
                 agg_exprs: Sequence[AggExpr], mode: str = "complete",
                 est_groups: Optional[int] = None):
        assert mode in ("complete", "partial", "final")
        self.children = (child,)
        self.group_exprs = tuple(group_exprs)
        self.agg_exprs = tuple(agg_exprs)
        self.mode = mode
        self.est_groups = est_groups
        self.tag = "a0"

    def _child_schema_for_types(self) -> T.Schema:
        cs = self.child.schema()
        if self.mode == "final":
            # accumulator dtypes are schema-independent; group types come
            # from the partial output schema
            return cs
        return cs

    def _acc_col_name(self, i: int, j: int, spec) -> str:
        return f"__acc_{i}_{j}_{spec.suffix}"

    def schema(self):
        cs = self.child.schema()
        fields = [T.Field(g.name(), g.dtype(cs), g.nullable(cs))
                  for g in self.group_exprs]
        if self.mode == "partial":
            base = self._base_schema()
            for i, a in enumerate(self.agg_exprs):
                for j, spec in enumerate(a.func.accumulators(base)):
                    fields.append(T.Field(
                        self._acc_col_name(i, j, spec),
                        _np_to_logical(spec.np_dtype), False))
        else:
            base = self._base_schema()
            for a in self.agg_exprs:
                fields.append(T.Field(a.out_name, a.func.result_type(base),
                                      a.func.result_nullable(base)))
        return T.Schema(fields)

    def _base_schema(self) -> T.Schema:
        """Schema the aggregate functions' children resolve against: the
        pre-aggregation input schema. A FINAL stage looks through its
        exchange to its own partial stage (or a spliced InputExec's
        stashed schema); complete/partial stages resolve against their
        direct child — which may itself be an INDEPENDENT aggregate
        (nested aggregation, e.g. max over a grouped subquery) whose
        OUTPUT schema is exactly the right base."""
        node: PhysicalPlan = self.children[0]
        while isinstance(node, ExchangeExec):
            node = node.children[0]
        if self.mode == "final":
            if isinstance(node, HashAggregateExec):
                return node._base_schema()
            stashed = getattr(node, "_agg_base_schema", None)
            if stashed is not None:
                return stashed
        return node.schema()

    def compute(self, ctx, inputs):
        batch = inputs[0]
        base = self._base_schema()
        sel = batch.selection

        if any(getattr(a.func, "positional", False)
               for a in self.agg_exprs):
            return self._compute_positional(ctx, batch, base)

        key_vecs = [g.eval(batch) for g in self.group_exprs]
        if self.mode == "final":
            specs = [a.func.accumulators(base) for a in self.agg_exprs]
            contribs = []
            for i, a in enumerate(self.agg_exprs):
                row = []
                for j, spec in enumerate(specs[i]):
                    col = batch.columns[self._acc_col_name(i, j, spec)]
                    data = col.data
                    if sel is not None:
                        data = jnp.where(sel, data, jnp.asarray(spec.neutral))
                    row.append(data)
                contribs.append(row)
        else:
            specs = [a.func.accumulators(base) for a in self.agg_exprs]
            contribs = self._updates(batch, sel, ctx)

        domains = [agg_kernels.key_domain(g, v)
                   for g, v in zip(self.group_exprs, key_vecs)]
        max_domain = int(ctx.conf.get("spark_tpu.sql.aggregate.maxDirectDomain"))
        cs = self.child.schema()
        nullables = [g.nullable(cs) for g in self.group_exprs]
        spans = agg_kernels.key_spans(
            nullables, [d for d in domains if d is not None])
        use_direct = (all(d is not None for d in domains)
                      and int(np.prod(list(spans) or [1])) <= max_domain)

        if use_direct:
            key_arrays, key_valids, accs, occupied = \
                agg_kernels.direct_aggregate(
                    key_vecs, domains, spans, contribs, specs, sel,
                    kernel_mode=str(ctx.conf.get(
                        "spark_tpu.sql.aggregate.kernelMode")),
                    merge=(self.mode == "final"),
                    reuse_count=None if self.mode == "final"
                    else self._occupancy_reuse(batch))
        else:
            num_segments = batch.capacity
            if self.est_groups and self.group_exprs:
                num_segments = min(batch.capacity,
                                   bucket_capacity(self.est_groups))
            (key_arrays, key_valids, accs, occupied,
             total_groups) = agg_kernels.sort_aggregate(
                key_vecs, contribs, specs, sel, batch.capacity,
                num_segments=num_segments)
            if num_segments < batch.capacity:
                # sized-down output: surface the real group count so the
                # executor can re-jit bigger on overflow (AQE loop)
                ctx.add_metric(f"agg_groups_{self.tag}", total_groups)
                ctx.add_flag(f"agg_overflow_{self.tag}",
                             total_groups > num_segments)

        if not self.group_exprs:
            # global aggregate: exactly one output row, always present
            occupied = jnp.ones((1,), jnp.bool_)
            key_arrays = []
            key_valids = []
            accs = [[acc[:1] for acc in row] for row in accs]

        cols: Dict[str, Column] = {}
        for g, vec, arr, kv in zip(self.group_exprs, key_vecs, key_arrays,
                                   key_valids):
            cols[g.name()] = Column(arr, vec.dtype, kv, vec.dictionary)

        if self.mode == "partial":
            for i, a in enumerate(self.agg_exprs):
                for j, spec in enumerate(specs[i]):
                    cols[self._acc_col_name(i, j, spec)] = Column(
                        accs[i][j], _np_to_logical(spec.np_dtype))
        else:
            for i, a in enumerate(self.agg_exprs):
                data, validity = a.func.device_finalize(accs[i], base)
                cols[a.out_name] = Column(
                    data, a.func.result_type(base), validity,
                    getattr(a.func, "output_dictionary", None))
        ctx.add_metric(f"agg_groups", jnp.sum(occupied.astype(jnp.int32)))
        return Batch(cols, occupied)

    def _compute_positional(self, ctx, batch: Batch, base) -> Batch:
        """Aggregates with positional functions (percentile/median/
        collect_list/collect_set — ApproximatePercentile.scala:1,
        collect.scala): one complete pass over a (group keys, value)
        sort per distinct value child. Regular functions in the same
        SELECT ride a sort_aggregate over the SAME key order, so all
        output columns align group-for-group."""
        from ..expr import cast_vec
        if self.mode != "complete":
            raise AnalysisError(
                "positional aggregates (percentile/median/collect_*) "
                "have no partial/final decomposition")
        sel = batch.selection
        cap = batch.capacity
        key_vecs = [g.eval(batch) for g in self.group_exprs]
        num_segments = cap

        regular = [(i, a) for i, a in enumerate(self.agg_exprs)
                   if not getattr(a.func, "positional", False)]
        specs = [a.func.accumulators(base) for _, a in regular]
        contribs = [a.func.update(batch, sel) for _, a in regular]
        (key_arrays, key_valids, accs, occupied,
         _total) = agg_kernels.sort_aggregate(
            key_vecs, contribs, specs, sel, cap,
            num_segments=num_segments)
        if not self.group_exprs:
            occupied = jnp.ones((1,), jnp.bool_) \
                if num_segments == 1 else \
                jnp.arange(num_segments) < 1
            key_arrays, key_valids = [], []

        out_cols: Dict[str, Column] = {}
        for g, vec, arr, kv in zip(self.group_exprs, key_vecs,
                                   key_arrays, key_valids):
            out_cols[g.name()] = Column(arr, vec.dtype, kv,
                                        vec.dictionary)

        results: Dict[int, Column] = {}
        for j, (_, a) in enumerate(regular):
            data, validity = a.func.device_finalize(accs[j], base)
            results[regular[j][0]] = Column(
                data, a.func.result_type(base), validity,
                getattr(a.func, "output_dictionary", None))

        from ..expr_agg import CollectList, Percentile
        sorts = {}  # child repr -> positional_sort outputs
        for i, a in enumerate(self.agg_exprs):
            if not getattr(a.func, "positional", False):
                continue
            f = a.func
            vec = f.child.eval(batch)
            if isinstance(f, Percentile):
                vec = cast_vec(vec, T.DOUBLE)
            skey = (repr(f.child), isinstance(f, Percentile))
            if skey not in sorts:
                sorts[skey] = agg_kernels.positional_sort(
                    key_vecs, vec, sel, cap)
            (vals_s, vvalid_s, _starts, gid, gstart, row_start, _tg,
             _ops) = sorts[skey]
            if isinstance(f, Percentile):
                out, ok = agg_kernels.positional_percentile(
                    vals_s, vvalid_s, gid, gstart, num_segments,
                    f.q, cap)
                results[i] = Column(out, T.DOUBLE, ok & occupied)
            else:
                data, offs = agg_kernels.positional_collect(
                    vals_s, vvalid_s, gid, row_start, num_segments,
                    f.distinct, cap)
                results[i] = Column(
                    data, T.ArrayType(vec.dtype), occupied,
                    vec.dictionary, offsets=offs)

        for i, a in enumerate(self.agg_exprs):
            out_cols[a.out_name] = results[i]
        ctx.add_metric("agg_groups",
                       jnp.sum(occupied.astype(jnp.int32)))
        return Batch(out_cols, occupied)

    def _occupancy_reuse(self, batch) -> Optional[Tuple[int, int]]:
        """(i, j) of an accumulator whose contribution equals the
        selection indicator (a count over a trace-time-never-null
        child): the MXU kernel rides it for occupancy instead of adding
        its own ones row. Trace-time validity (`v.validity is None`) is
        the exact gate — static nullability over-approximates (e.g.
        `pmod(x, const)` is schema-nullable but runtime-valid)."""
        from ..expr_agg import Avg, Count, Sum
        for i, a in enumerate(self.agg_exprs):
            f = a.func
            if isinstance(f, Count) and f.child is None:
                return (i, 0)
            if isinstance(f, (Count, Sum, Avg)) and f.child is not None:
                if f.child.eval(batch).validity is None:
                    return (i, 0 if isinstance(f, Count) else 1)
        return None

    # -- reusable direct-path steps (shared with the streaming driver) ------

    def prepare_direct(self, probe_batch: Batch, conf,
                       pad_dict: bool = True) -> Optional["DirectAggPlan"]:
        """Trace-time check + static metadata for the dense-domain path.
        Returns None when any key lacks a static domain (sort path)."""
        base = self._base_schema()
        cs = self.child.schema()
        key_vecs = [g.eval(probe_batch) for g in self.group_exprs]
        domains = []
        for g, v in zip(self.group_exprs, key_vecs):
            dom = agg_kernels.key_domain(g, v)
            if dom is None:
                return None
            d, lo = dom
            if pad_dict and v.dictionary is not None:
                # headroom for dictionaries that grow across chunks
                d = bucket_capacity(max(16, 2 * d))
            domains.append((d, lo))
        spans = agg_kernels.key_spans(
            [g.nullable(cs) for g in self.group_exprs], domains)
        total = int(np.prod(list(spans) or [1]))
        if total > int(conf.get("spark_tpu.sql.aggregate.maxDirectDomain")):
            return None
        strides = []
        t = 1
        for span in spans:
            strides.append(t)
            t *= span
        specs = [a.func.accumulators(base) for a in self.agg_exprs]
        return DirectAggPlan(
            domains=domains, spans=spans, strides=strides, total=total,
            key_dtypes=[v.dtype for v in key_vecs],
            key_dicts=[v.dictionary for v in key_vecs], specs=specs)

    def direct_init_tables(self, prep: "DirectAggPlan"):
        return agg_kernels.direct_init(prep.spans, prep.specs)

    def _updates(self, batch: Batch, sel, ctx=None, row_base=None):
        """Per-row accumulator contributions. Position-packed aggregates
        (First/Last/AnyValue, `uses_row_base`) receive a globally unique
        row base: `row_base` spaces host-driven chunks and the shard
        index spaces mesh shards, so accumulator merges never tie on
        in-chunk position (a tie would let the two word accumulators of
        one 64-bit value each pick a different row)."""
        if any(a.func.uses_row_base for a in self.agg_exprs):
            base = jnp.asarray(0 if row_base is None else row_base,
                               jnp.int64)
            if ctx is not None and ctx.axis_name is not None \
                    and ctx.n_shards > 1:
                if ctx.n_shards * batch.capacity >= (1 << 30):
                    raise RuntimeError(
                        "first/last aggregation input exceeds the 2^30 "
                        "packed-position bound "
                        f"({ctx.n_shards} shards x {batch.capacity} rows)")
                base = base + jax.lax.axis_index(ctx.axis_name) \
                    .astype(jnp.int64) * batch.capacity
            return [a.func.update(batch, sel, row_base=base)
                    if a.func.uses_row_base else a.func.update(batch, sel)
                    for a in self.agg_exprs]
        return [a.func.update(batch, sel) for a in self.agg_exprs]

    def direct_update_tables(self, tables, batch: Batch,
                             prep: "DirectAggPlan", conf=None,
                             row_base=None):
        sel = batch.selection
        key_vecs = [g.eval(batch) for g in self.group_exprs]
        idx, _, _ = agg_kernels.direct_index(key_vecs, prep.domains,
                                             prep.spans, sel)
        contribs = self._updates(batch, sel, row_base=row_base)
        mode = str(conf.get("spark_tpu.sql.aggregate.kernelMode")) \
            if conf is not None else "auto"
        return agg_kernels.direct_update(tables, idx, prep.total, contribs,
                                         prep.specs, kernel_mode=mode,
                                         reuse_count=self._occupancy_reuse(
                                             batch))

    def direct_finalize_tables(self, tables, prep: "DirectAggPlan",
                               dict_overrides: Optional[Dict] = None) -> Batch:
        cnt, accs = tables
        base = self._base_schema()
        occupied = cnt > 0
        key_arrays, key_valids = agg_kernels.direct_keys(
            prep.domains, prep.spans, prep.strides, prep.key_dtypes)
        if not self.group_exprs:
            occupied = jnp.ones((1,), jnp.bool_)
        cols: Dict[str, Column] = {}
        for g, arr, kv, dt, dic in zip(self.group_exprs, key_arrays,
                                       key_valids, prep.key_dtypes,
                                       prep.key_dicts):
            if dict_overrides and g.name() in dict_overrides:
                dic = dict_overrides[g.name()]
            cols[g.name()] = Column(arr, dt, kv, dic)
        for i, a in enumerate(self.agg_exprs):
            data, validity = a.func.device_finalize(accs[i], base)
            cols[a.out_name] = Column(
                data, a.func.result_type(base), validity,
                getattr(a.func, "output_dictionary", None))
        return Batch(cols, occupied)

    def direct_partial_batch(self, tables, prep: "DirectAggPlan",
                             dict_overrides: Optional[Dict] = None) -> Batch:
        """Partial-mode output batch from carried accumulator tables:
        group keys + RAW accumulator columns + occupancy selection (the
        shape the exchange+final stages consume)."""
        cnt, accs = tables
        base = self._base_schema()
        key_arrays, key_valids = agg_kernels.direct_keys(
            prep.domains, prep.spans, prep.strides, prep.key_dtypes)
        cols: Dict[str, Column] = {}
        for g, arr, kv, dt, dic in zip(self.group_exprs, key_arrays,
                                       key_valids, prep.key_dtypes,
                                       prep.key_dicts):
            if dict_overrides and g.name() in dict_overrides:
                dic = dict_overrides[g.name()]
            cols[g.name()] = Column(arr, dt, kv, dic)
        for i, a in enumerate(self.agg_exprs):
            for j, spec in enumerate(prep.specs[i]):
                cols[self._acc_col_name(i, j, spec)] = Column(
                    accs[i][j], _np_to_logical(spec.np_dtype))
        return Batch(cols, cnt > 0)

    def output_partitioning(self):
        if self.mode == "partial":
            # per-shard accumulator tables: rows for one key exist on
            # every shard, so nothing stronger than the child's layout
            # (claiming SinglePartition here would suppress the exchange
            # the final aggregate depends on)
            return self.child.output_partitioning()
        if not self.group_exprs:
            return SinglePartition()
        return self.child.output_partitioning()

    def required_child_distributions(self):
        if self.mode in ("complete", "final"):
            if not self.group_exprs:
                return [AllTuples()]
            names = []
            for g in self.group_exprs:
                e = g
                while isinstance(e, Alias):
                    e = e.child
                from ..expr import ColumnRef
                if not isinstance(e, ColumnRef):
                    # a computed group key has no child column to hash
                    # (mesh positional aggregates reach complete mode
                    # directly): gather instead of a broken exchange
                    return [AllTuples()]
                names.append(e.name())
            return [ClusteredDistribution(tuple(names))]
        return [UnspecifiedDistribution()]

    def simple_string(self):
        return (f"HashAggregateExec(mode={self.mode}, "
                f"groups={[repr(g) for g in self.group_exprs]}, "
                f"aggs={[repr(a) for a in self.agg_exprs]}, "
                f"est={self.est_groups})")


@dataclass
class DirectAggPlan:
    """Static (trace-time) metadata for the dense-domain aggregate path.
    `domains` entries are (domain, lo) pairs — see `aggregate.key_domain`."""

    domains: List[Tuple[int, int]]
    spans: List[int]  # domain + null slot for schema-nullable keys
    strides: List[int]
    total: int
    key_dtypes: List[T.DataType]
    key_dicts: List
    specs: List


def _np_to_logical(np_dtype) -> T.DataType:
    m = {np.dtype(np.int64): T.LONG, np.dtype(np.float64): T.DOUBLE,
         np.dtype(np.int32): T.INT, np.dtype(np.float32): T.FLOAT,
         np.dtype(np.bool_): T.BOOLEAN, np.dtype(np.int16): T.SHORT,
         np.dtype(np.int8): T.BYTE}
    return m[np.dtype(np_dtype)]


class SortExec(UnaryExec):
    """Global sort: range-partition over the mesh (sampled bounds +
    all_to_all), then sort locally — shard i's keys <= shard i+1's, so
    the ordered shard concat IS the global order (reference:
    SortExec.scala:40 + RangePartitioning). Single-chip, the requirement
    is trivially satisfied and this is just the local sort."""

    def __init__(self, child: PhysicalPlan, orders: Sequence[SortOrder]):
        self.children = (child,)
        self.orders = tuple(orders)

    def schema(self):
        return self.child.schema()

    def order_key(self) -> Tuple[str, ...]:
        return tuple(repr(o) for o in self.orders)

    def required_child_distributions(self):
        return [OrderedDistribution(self.order_key())]

    def output_partitioning(self):
        return self.child.output_partitioning()

    def compute(self, ctx, inputs):
        batch = inputs[0]
        perm, n_valid = sort_kernels.sort_permutation(batch, self.orders)
        return sort_kernels.apply_permutation(batch, perm, n_valid)

    def simple_string(self):
        return f"SortExec({[repr(o) for o in self.orders]})"


class WindowExec(UnaryExec):
    """All window functions of one spec over one sorted permutation
    (reference: execution/window/WindowExec.scala — frame processors
    become segmented scans, execution/window.py). Partitions co-locate
    via a hash exchange; an empty PARTITION BY needs all rows together."""

    def __init__(self, child: PhysicalPlan, wexprs: Sequence[Tuple],
                 out_schema: T.Schema):
        self.children = (child,)
        self.wexprs = tuple(wexprs)
        self._schema = out_schema

    def schema(self):
        return self._schema

    def _spec(self):
        return self.wexprs[0][0].spec

    def required_child_distributions(self):
        from ..expr import ColumnRef
        spec = self._spec()
        if not spec._partition:
            return [AllTuples()]
        names = []
        for p in spec._partition:
            e = p
            while isinstance(e, Alias):
                e = e.child
            if not isinstance(e, ColumnRef):
                return [AllTuples()]
            names.append(e.name())
        return [ClusteredDistribution(tuple(names))]

    def output_partitioning(self):
        return self.child.output_partitioning()

    def compute(self, ctx, inputs):
        from ..execution import window as win
        from ..execution.sort import sort_operands
        batch = inputs[0]
        cap = batch.capacity
        sel = batch.selection_mask()
        spec = self._spec()

        p_orders = [SortOrder(p, ascending=True) for p in spec._partition]
        p_ops = sort_operands(batch, p_orders)
        o_ops = sort_operands(batch, list(spec._order))

        operands = [(~sel).astype(jnp.int8)] + p_ops + o_ops
        num_keys = len(operands)
        sorted_ops = sort_kernels.sort_carrying_positions(operands)
        perm = sorted_ops[-1]
        valid_sorted = sorted_ops[0] == 0
        sp_ops = list(sorted_ops[1:1 + len(p_ops)])
        so_ops = list(sorted_ops[1 + len(p_ops):num_keys])

        starts = win._segment_starts(sp_ops, cap, valid_sorted)
        gid = jnp.cumsum(starts.astype(jnp.int32)) - 1
        gid = jnp.where(valid_sorted, gid, cap)
        change = win._peer_change(starts, so_ops, cap)
        base = self.child.schema()

        new_cols: Dict[str, Column] = dict(batch.columns)
        for w, name in self.wexprs:
            out_dtype = w.dtype(base)
            validity_sorted = None
            if w.kind == "row_number":
                vals = win.row_number(starts, cap)
            elif w.kind == "rank":
                vals = win.rank(starts, change, cap)
            elif w.kind == "dense_rank":
                vals = win.dense_rank(starts, change, cap)
            elif w.kind in ("lag", "lead"):
                v = w.arg.eval(batch)
                if v.dictionary is not None and w.default is not None:
                    raise AnalysisError(
                        "lag/lead with a default on a string column is "
                        "not supported (the default would be written in "
                        "dictionary-code space)")
                data_s = jnp.take(v.data, perm)
                val_s = None if v.validity is None else \
                    jnp.take(v.validity, perm)
                vals, validity_sorted = win.shift_in_segment(
                    data_s, val_s, gid, w.offset, w.default, cap)
            else:
                if w.arg is None:  # count(*) over (...)
                    data_s = jnp.ones((cap,), jnp.int64)
                    val_s = None
                else:
                    v = w.arg.eval(batch)
                    if v.dictionary is not None and w.kind != "count":
                        # codes are insertion-ordered, not lexicographic:
                        # min/max/sum over codes would silently corrupt
                        raise AnalysisError(
                            f"window {w.kind} over a string column is "
                            f"not supported")
                    from ..expr import cast_vec
                    acc_t = out_dtype if w.kind in ("sum",) else v.dtype
                    if w.kind == "avg":
                        from ..expr_agg import Sum
                        acc_t = Sum(w.arg).result_type(base)
                    vv = cast_vec(v, acc_t)
                    data_s = jnp.take(vv.data, perm)
                    val_s = None if vv.validity is None else \
                        jnp.take(vv.validity, perm)
                if val_s is not None:
                    val_s = val_s & valid_sorted
                else:
                    val_s = valid_sorted
                frame = w.spec._frame
                if frame is not None and frame[0] == "range":
                    from ..window import UNBOUNDED_PRECEDING as _UP
                    if frame[1] <= _UP and frame[2] == 0:
                        # RANGE UNBOUNDED PRECEDING .. CURRENT ROW is
                        # exactly the default peer frame: no value
                        # arithmetic, so any order keys are fine
                        frame = None
                if frame is None:
                    out, cnt = win.windowed_agg(
                        "sum" if w.kind == "avg" else w.kind, data_s,
                        val_s, gid, cap, starts, change,
                        bool(spec._order), cap)
                else:
                    # ROWS/RANGE BETWEEN (WindowExec.scala:36 frames)
                    if not spec._order:
                        raise AnalysisError(
                            "a window frame requires an ORDER BY in "
                            "its window specification")
                    range_key = range_key_valid = None
                    if frame[0] == "range":
                        range_key, range_key_valid = \
                            self._range_frame_key(batch, spec, frame,
                                                  base, perm,
                                                  valid_sorted)
                    lo, hi = win.frame_bounds(
                        frame, starts, change, cap, bool(spec._order),
                        n_valid=jnp.sum(valid_sorted.astype(jnp.int32)),
                        range_key=range_key,
                        range_key_valid=range_key_valid)
                    max_len = None
                    if frame[0] == "rows":
                        from ..window import (UNBOUNDED_FOLLOWING as _UF,
                                              UNBOUNDED_PRECEDING as _UP2)
                        if frame[1] > _UP2 and frame[2] < _UF:
                            max_len = min(cap, frame[2] - frame[1] + 1)
                    out, cnt = win.framed_agg(
                        "sum" if w.kind == "avg" else w.kind, data_s,
                        val_s, lo, hi, cap, max_len=max_len)
                if w.kind == "avg":
                    safe = jnp.maximum(cnt, 1)
                    if isinstance(out_dtype, T.DecimalType):
                        from ..expr_agg import decimal_avg_halfup
                        arg_t = w.arg.dtype(base)
                        vals = decimal_avg_halfup(
                            out.astype(jnp.int64), safe,
                            10 ** (out_dtype.scale - arg_t.scale))
                    else:
                        vals = out.astype(jnp.float64) / safe
                elif w.kind == "count":
                    vals = cnt
                else:
                    vals = out
                if w.kind != "count":
                    validity_sorted = cnt > 0
            # scatter back to input row order
            unsorted = jnp.zeros((cap,), vals.dtype).at[perm].set(vals)
            validity = None
            if validity_sorted is not None:
                validity = jnp.zeros((cap,), jnp.bool_).at[perm].set(
                    validity_sorted)
            src_dict = None
            if w.kind in ("lag", "lead"):
                src = w.arg.eval(batch)
                src_dict = src.dictionary
            new_cols[name] = Column(unsorted.astype(out_dtype.np_dtype),
                                    out_dtype, validity, src_dict)
        return Batch(new_cols, batch.selection)

    def _range_frame_key(self, batch, spec, frame, base, perm,
                         valid_sorted):
        """Sorted order-key values for a RANGE frame with value-space
        offsets: exactly one ascending numeric/date order key (the
        reference's RangeFrame constraint). Keys are sanitized so NULL
        and filtered rows carry monotone sentinels (see
        win.sanitize_range_key)."""
        from ..execution import window as win
        from ..window import UNBOUNDED_FOLLOWING, UNBOUNDED_PRECEDING
        _, a, b = frame
        if a <= UNBOUNDED_PRECEDING and b >= UNBOUNDED_FOLLOWING:
            return None, None
        if len(spec._order) != 1:
            raise AnalysisError(
                "RANGE BETWEEN with offsets requires exactly one ORDER "
                "BY key")
        o = spec._order[0]
        if not o.ascending:
            raise AnalysisError(
                "RANGE BETWEEN with offsets supports ascending order "
                "keys only")
        v = o.child.eval(batch)
        if v.dictionary is not None or isinstance(
                v.dtype, (T.StringType, T.BooleanType)):
            raise AnalysisError(
                "RANGE BETWEEN needs a numeric or date order key")
        key = jnp.take(v.data, perm)
        kv = None if v.validity is None else jnp.take(v.validity, perm)
        key = win.sanitize_range_key(key, kv, valid_sorted,
                                     o.nulls_first)
        return key, kv

    def simple_string(self):
        # the FULL spec must be in the fingerprint: the compiled-stage
        # cache keys on describe(), and two windows differing only in
        # partition/order would otherwise collide
        return f"WindowExec({[(repr(w), n) for w, n in self.wexprs]})"


class GenerateExec(UnaryExec):
    """explode: one output row per flattened array element. Output
    capacity is the VALUES capacity — a static shape (the flattened
    element array's padded length), so unlike the reference's
    `GenerateExec.scala:1` row iterator no AQE sizing is needed: element
    slots map back to their rows via one searchsorted over offsets and
    every child column gathers by that segment id. `outer` appends one
    slot per input row for empty/NULL arrays (explode_outer)."""

    def __init__(self, child: PhysicalPlan, gen_expr, out_name: str,
                 out_schema: T.Schema, outer: bool = False):
        self.children = (child,)
        self.gen_expr = gen_expr
        self.out_name = out_name
        self._schema = out_schema
        self.outer = outer

    def schema(self):
        return self._schema

    def compute(self, ctx, inputs):
        batch = inputs[0]
        cap = batch.capacity
        v = self.gen_expr.eval(batch)
        if v.offsets is None:
            raise AnalysisError(
                f"explode() needs an array, got {v.dtype!r}")
        vcap = max(int(v.data.shape[0]), 1)
        iota = jnp.arange(vcap, dtype=jnp.int32)
        seg = jnp.searchsorted(v.offsets, iota, side="right") - 1
        seg_c = jnp.clip(seg, 0, cap - 1)
        total = v.offsets[-1]
        row_live = batch.selection_mask()
        live = (iota < total) & jnp.take(row_live, seg_c)
        if v.validity is not None:
            live = live & jnp.take(v.validity, seg_c)

        def replicate(col: Column, idx):
            data = jnp.take(col.data, idx)
            valid = None if col.validity is None else \
                jnp.take(col.validity, idx)
            return data, valid

        elem_t = v.dtype.element
        parts = {}
        for name, col in batch.columns.items():
            if col.offsets is not None:
                continue  # array columns do not replicate (see schema)
            parts[name] = replicate(col, seg_c)
        elem_data = v.data
        elem_valid = v.elem_validity
        sel = live
        if self.outer:
            # one extra slot per input row, live only for empty/NULL
            # arrays; its element is NULL (explode_outer semantics)
            lens = v.offsets[1:] - v.offsets[:-1]
            empty = lens == 0
            if v.validity is not None:
                empty = empty | ~v.validity
            extra_live = row_live & empty
            for name, col in batch.columns.items():
                if name not in parts:
                    continue
                d, va = parts[name]
                d2 = jnp.concatenate([d, col.data])
                va2 = None
                if va is not None:
                    va2 = jnp.concatenate([va, col.validity])
                parts[name] = (d2, va2)
            elem_data = jnp.concatenate(
                [elem_data, jnp.zeros((cap,), elem_data.dtype)])
            ev_main = elem_valid if elem_valid is not None else \
                jnp.ones((vcap,), jnp.bool_)
            elem_valid = jnp.concatenate(
                [ev_main, jnp.zeros((cap,), jnp.bool_)])
            sel = jnp.concatenate([live, extra_live])

        cols = {n: Column(d, batch.columns[n].dtype, va,
                          batch.columns[n].dictionary)
                for n, (d, va) in parts.items()}
        cols[self.out_name] = Column(elem_data, elem_t, elem_valid,
                                     v.dictionary)
        ctx.add_metric(f"gen_rows_{self.out_name}",
                       jnp.sum(sel.astype(jnp.int64)))
        return Batch(cols, sel)

    def simple_string(self):
        return (f"GenerateExec(explode{'_outer' if self.outer else ''}"
                f"({self.gen_expr!r}) AS {self.out_name})")


class LimitExec(UnaryExec):
    """First-n. Over a range-partitioned (sorted) child it stays
    distributed: shard i keeps rows whose global rank — its local rank
    plus the psum'd count on lower shards — is under n, with no gather of
    the dataset (reference: the GlobalLimit/LocalLimit split in
    limit.scala). Otherwise it collapses to one logical partition."""

    def __init__(self, child: PhysicalPlan, n: int):
        self.children = (child,)
        self.n = n

    def schema(self):
        return self.child.schema()

    def required_child_distributions(self):
        if isinstance(self.child.output_partitioning(), RangePartitioning):
            return [UnspecifiedDistribution()]
        return [AllTuples()]

    def output_partitioning(self):
        return self.child.output_partitioning()

    def compute(self, ctx, inputs):
        batch = inputs[0]
        sel = batch.selection_mask()
        local_rank = jnp.cumsum(sel.astype(jnp.int32)) - sel.astype(jnp.int32)
        if ctx.axis_name is not None and ctx.n_shards > 1 and \
                isinstance(self.child.output_partitioning(),
                           RangePartitioning):
            n_shards = ctx.n_shards
            local_count = jnp.sum(sel.astype(jnp.int32))
            counts = jax.lax.all_gather(local_count, ctx.axis_name)
            i = jax.lax.axis_index(ctx.axis_name)
            offset = jnp.sum(jnp.where(
                jnp.arange(n_shards) < i, counts, 0))
            keep = local_rank < jnp.maximum(self.n - offset, 0)
            return batch.with_selection(sel & keep)
        keep = local_rank < self.n
        return batch.with_selection(sel & keep)

    def simple_string(self):
        return f"LimitExec({self.n})"


class JoinExec(PhysicalPlan):
    """General equi-join: sorted-build binary-search with prefix-sum
    expansion (execution/join.py). Build side = right child. Supports
    many-to-many matches, inner/left/right/full outer, semi/anti, and
    residual (non-equi) conditions for every join type.

    `out_cap` is the static capacity of the expanded-pair block; None
    defaults to the probe capacity (exact for FK joins). When the traced
    row total overflows it, the executor reads the real total from the
    `join_rows_<tag>` metric and re-jits with a larger capacity — the
    stats->re-plan loop of the reference's AQE
    (`AdaptiveSparkPlanExec.scala:64`)."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 left_keys: Sequence[Expression], right_keys: Sequence[Expression],
                 how: str, condition: Optional[Expression],
                 out_schema: T.Schema, out_cap: Optional[int] = None,
                 tag: str = "j0", strategy: str = "shuffle"):
        self.children = (left, right)
        self.left_keys = tuple(left_keys)
        self.right_keys = tuple(right_keys)
        self.how = how
        self.condition = condition
        self._schema = out_schema
        self.out_cap = out_cap
        # unique-build fast path (HashedRelation.scala keyIsUnique
        # analog): assume each probe row matches <=1 build row — the
        # FK->PK shape — and emit probe-layout output with zero
        # expansion. A duplicate build key raises the
        # join_nonunique_<tag> flag and the AQE loop re-jits with the
        # general expansion path (False). None/True = try it.
        self.unique_build: Optional[bool] = None
        # True for left_semi joins SYNTHESIZED by the runtime-filter
        # rule to narrow a creation chain (plan/runtime_filter.py):
        # tagged from a separate counter (cj<n>) so real joins keep
        # their tag numbering across the strategy-override path
        self.creation_side = False
        # SQL NOT IN null-aware anti-join (left_anti only)
        self.null_aware = False
        # reorder cost-model output estimate (plan/join_reorder.py),
        # advisory: graded as a join_rows prediction, shown in the
        # runtime tree — never in simple_string (stage keys must not
        # vary with estimates)
        self.cbo_est_rows: Optional[int] = None
        self.tag = tag
        # "shuffle": co-partition both sides (ShuffledHashJoinExec.scala:37
        # analog); "broadcast": replicate the small build side via
        # all_gather and leave the probe side in place
        # (BroadcastHashJoinExec.scala:40 analog). Picked by the planner
        # from source row estimates vs autoBroadcastJoinThreshold.
        self.strategy = strategy

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]

    def schema(self):
        return self._schema

    def _clusterable_key_names(self):
        """Key positions usable for hash partitioning: both sides must be
        plain column references (the exchange hashes child columns by
        name; a computed key has no column to hash)."""
        from ..expr import ColumnRef
        lk, rk = [], []
        for l, r in zip(self.left_keys, self.right_keys):
            le, re = l, r
            while isinstance(le, Alias):
                le = le.child
            while isinstance(re, Alias):
                re = re.child
            if isinstance(le, ColumnRef) and isinstance(re, ColumnRef):
                lk.append(le.name())
                rk.append(re.name())
        return tuple(lk), tuple(rk)

    def required_child_distributions(self):
        if self.strategy == "broadcast":
            return [UnspecifiedDistribution(), BroadcastDistribution()]
        lk, rk = self._clusterable_key_names()
        if not lk:
            if self.how in ("right", "full"):
                # a replicated build would append its locally-unmatched
                # rows on EVERY shard (n-fold duplication); with no key
                # columns to hash-partition on, co-locate everything and
                # let the striped SinglePartition output dedupe
                return [AllTuples(), AllTuples()]
            # no hashable key columns (e.g. cross join's literal keys):
            # every probe row must see every build row -> replicate build
            return [UnspecifiedDistribution(), BroadcastDistribution()]
        return [ClusteredDistribution(lk), ClusteredDistribution(rk)]

    def output_partitioning(self):
        lp = self.left.output_partitioning()
        rp = self.right.output_partitioning()
        if isinstance(lp, SinglePartition) and \
                isinstance(rp, (SinglePartition, Replicated)):
            # both sides fully co-located: every shard computed the same
            # complete result (valid for every join type incl. outer)
            return SinglePartition()
        if self.how in ("right", "full"):
            # appended null-extended rows carry NULL left keys on whatever
            # shard held the unmatched build row — no layout guarantee
            # (the reference returns UnknownPartitioning here too)
            return UnknownPartitioning()
        return lp

    def _eval_keys(self, probe_batch, build_batch):
        def bcast(v: Vec, cap: int) -> Vec:
            # literal keys (cross join lowers to a constant-key equi-join)
            if v.data is not None and np.ndim(v.data) == 0:
                return Vec(jnp.broadcast_to(v.data, (cap,)), v.dtype,
                           v.validity, v.dictionary)
            return v

        lvecs = [bcast(k.eval(probe_batch), probe_batch.capacity)
                 for k in self.left_keys]
        rvecs = [bcast(k.eval(build_batch), build_batch.capacity)
                 for k in self.right_keys]
        lvecs, rvecs = _unify_key_dictionaries(lvecs, rvecs)
        if len(lvecs) != 1:
            lk, rk, exact = _pack_key_pair(lvecs, rvecs)
        else:
            lk, rk = lvecs[0], rvecs[0]
            exact = True
        return lvecs, rvecs, lk, rk, exact

    def _build_name_map(self, probe_batch, build_batch):
        """(left_names, out_names) with the `_r` collision suffix shared
        by every join type so one condition expression works for all."""
        left_names = list(probe_batch.columns.keys())
        if self.how in ("left_semi", "left_anti"):
            taken = set(left_names)
            out_names = list(left_names)
            for n in build_batch.columns.keys():
                name = n
                while name in taken:
                    name = name + "_r"
                out_names.append(name)
                taken.add(name)
        else:
            out_names = self._schema.names
        return left_names, out_names

    def _compute_unique(self, ctx, probe_batch, build_batch,
                        lvecs, rvecs, lk, keys_s, perm, n_valid, valid_s):
        """Unique-build fast path: probe-layout output, zero expansion
        (HashedRelation keyIsUnique analog). Raises join_nonunique_<tag>
        when the build side has duplicate keys; the AQE loop then
        re-jits with unique_build=False."""
        ctx.add_flag(f"join_nonunique_{self.tag}",
                     join_kernels.build_has_duplicates(keys_s, valid_s))
        build_idx, found = join_kernels.match_unique(
            keys_s, n_valid, perm, lk, probe_batch.selection)
        psel = probe_batch.selection_mask()
        exact = len(lvecs) == 1
        if not exact:
            # packed keys: verify true equality (a pack collision pair
            # in the build would have raised the nonunique flag, so the
            # single candidate is the only possible match)
            for lvec, rvec in zip(lvecs, rvecs):
                eq = lvec.data == jnp.take(rvec.data, build_idx)
                if lvec.validity is not None:
                    eq = eq & lvec.validity
                if rvec.validity is not None:
                    eq = eq & jnp.take(rvec.validity, build_idx)
                found = found & eq

        left_names, out_names = self._build_name_map(probe_batch,
                                                     build_batch)
        n_left = len(left_names)
        cols: Dict[str, Column] = {}
        for name, out_name in zip(left_names, out_names[:n_left]):
            cols[out_name] = probe_batch.columns[name]  # no gather
        build_name_map = list(zip(build_batch.columns.keys(),
                                  out_names[n_left:]))
        for (out_name, col) in join_kernels.gather_columns(
                build_batch, build_idx, found, build_name_map):
            cols[out_name] = col

        if self.condition is not None:
            out_probe = Batch(cols, psel & found)
            v = self.condition.eval(out_probe)
            keep = v.data if v.validity is None else (v.data & v.validity)
            found = found & keep
            for out_name, col in join_kernels.gather_columns(
                    build_batch, build_idx, found, build_name_map):
                cols[out_name] = col

        ctx.add_metric(f"join_rows_{self.tag}",
                       jnp.sum((psel & found).astype(jnp.int64)))
        if self.how == "left_semi":
            return probe_batch.with_selection(psel & found)
        if self.how == "left_anti":
            sel = psel & ~found
            if self.null_aware:
                sel = sel & self._null_aware_mask(ctx, lvecs[0],
                                                  build_batch, rvecs[0])
            return probe_batch.with_selection(sel)
        if self.how == "left":
            return Batch(cols, psel)
        return Batch(cols, psel & found)

    def _null_aware_mask(self, ctx, probe_key_vec, build_batch,
                         build_key_vec):
        """Per-probe-row NOT IN adjustment (SQL three-valued logic):
        a NULL anywhere in the build keys empties the result; a NULL
        probe key survives only when the build side is empty. Scalars
        reduce over the mesh axis — NULL build rows hash to ONE shard
        but empty every shard's output."""
        bsel = build_batch.selection_mask()
        if build_key_vec.validity is not None:
            has_null = jnp.sum((bsel & ~build_key_vec.validity)
                               .astype(jnp.int32))
        else:
            has_null = jnp.zeros((), jnp.int32)
        nonempty = jnp.sum(bsel.astype(jnp.int32))
        if ctx.axis_name is not None:
            has_null = jax.lax.psum(has_null, ctx.axis_name)
            nonempty = jax.lax.psum(nonempty, ctx.axis_name)
        mask = jnp.broadcast_to(has_null == 0,
                                (probe_key_vec.data.shape[0],))
        if probe_key_vec.validity is not None:
            mask = mask & (probe_key_vec.validity | (nonempty == 0))
        return mask

    def compute(self, ctx, inputs):
        probe_batch, build_batch = inputs
        lvecs, rvecs, lk, rk, exact = self._eval_keys(probe_batch, build_batch)
        keys_s, perm, n_valid, _valid_s = join_kernels.build_sorted(
            rk, build_batch.selection)
        if len(lvecs) > 1:  # how it packed a key of several columns:
            # the `dispatch` span's `join_keys` attribute
            ctx.host[f"join_keys_{self.tag}"] = \
                "exact" if exact else "hashed"
        if ctx.axis_name is not None:  # and, across chips, how it met
            # its build: hashed to the shards or replicated on each
            ctx.host[f"join_strategy_{self.tag}"] = self.strategy
        if (self.unique_build is not False
                and self.how in ("inner", "left", "left_semi",
                                 "left_anti")):
            return self._compute_unique(ctx, probe_batch, build_batch,
                                        lvecs, rvecs, lk, keys_s, perm,
                                        n_valid, _valid_s)
        lo, cnt = join_kernels.match_ranges(keys_s, n_valid, lk,
                                            probe_batch.selection)
        psel = probe_batch.selection_mask()
        semi_anti = self.how in ("left_semi", "left_anti")

        if semi_anti and exact and self.condition is None:
            found = cnt > 0
            if self.how == "left_semi":
                return probe_batch.with_selection(psel & found)
            sel = psel & ~found
            if self.null_aware:
                sel = sel & self._null_aware_mask(ctx, lvecs[0],
                                                  build_batch, rvecs[0])
            return probe_batch.with_selection(sel)

        probe_cap = probe_batch.capacity
        build_cap = build_batch.capacity
        out_cap = self.out_cap if self.out_cap is not None else probe_cap
        outer_probe = self.how in ("left", "full")
        if outer_probe:
            cnt_eff = jnp.where(psel, jnp.maximum(cnt, 1), 0)
        else:
            cnt_eff = jnp.where(psel, cnt, 0)
        p, build_idx, is_pair, valid, total = join_kernels.expand(
            lo, cnt, cnt_eff, perm, out_cap)
        ctx.add_metric(f"join_rows_{self.tag}", total)
        ctx.add_flag(f"join_overflow_{self.tag}", total > out_cap)

        pair_pass = is_pair
        if not exact:
            # hashed key pack: verify true per-key equality on each pair
            for lvec, rvec in zip(lvecs, rvecs):
                eq = jnp.take(lvec.data, p) == jnp.take(rvec.data, build_idx)
                if lvec.validity is not None:
                    eq = eq & jnp.take(lvec.validity, p)
                if rvec.validity is not None:
                    eq = eq & jnp.take(rvec.validity, build_idx)
                pair_pass = pair_pass & eq

        # assemble the expanded block: probe columns at p, build at build_idx
        left_names, out_names = self._build_name_map(probe_batch,
                                                     build_batch)
        n_left = len(left_names)
        cols: Dict[str, Column] = {}
        for (out_name, col) in join_kernels.gather_columns(
                probe_batch, p, valid,
                list(zip(left_names, out_names[:n_left]))):
            cols[out_name] = col
        build_name_map = list(zip(build_batch.columns.keys(),
                                  out_names[n_left:]))
        for (out_name, col) in join_kernels.gather_columns(
                build_batch, build_idx, pair_pass, build_name_map):
            cols[out_name] = col

        if self.condition is not None:
            out_probe = Batch(cols, valid & pair_pass)
            v = self.condition.eval(out_probe)
            keep = v.data if v.validity is None else (v.data & v.validity)
            pair_pass = pair_pass & keep
            # pairs dropped by the residual must also null the build side
            for out_name, col in join_kernels.gather_columns(
                    build_batch, build_idx, pair_pass, build_name_map):
                cols[out_name] = col

        # per-probe-row "any pair survived" (drives null-extension +
        # semi/anti). p is non-decreasing (output rows are emitted in
        # probe order), so count survivors per p-run with a prefix-sum
        # difference at run bounds — a colliding scatter-max serializes
        # on TPU (~90ms/4M rows, Q3 profile)
        m = (valid & pair_pass).astype(jnp.int32)
        csum_m = jnp.cumsum(m)
        ex_m = csum_m - m
        rpos = jnp.arange(out_cap, dtype=jnp.int32)
        run_start = (rpos == 0) | (p != jnp.roll(p, 1))
        nxt_p = jnp.concatenate([p[1:], jnp.full((1,), probe_cap, p.dtype)])
        run_end = nxt_p != p
        # no `valid` mask: tail rows (r >= total) share the last emitting
        # row's p (clipped), so they extend its run with m=0 — harmless —
        # while masking would lose that run's end marker entirely
        sidx_p = jnp.where(run_start, p, probe_cap)
        eidx_p = jnp.where(run_end, p, probe_cap)
        pstart = jnp.zeros((probe_cap,), jnp.int32).at[sidx_p].set(
            rpos, mode="drop")
        pend = jnp.zeros((probe_cap,), jnp.int32).at[eidx_p].set(
            rpos, mode="drop")
        ppresent = jnp.zeros((probe_cap,), jnp.bool_).at[sidx_p].set(
            jnp.ones((out_cap,), jnp.bool_), mode="drop")
        any_pass = ppresent & (
            (jnp.take(csum_m, pend) - jnp.take(ex_m, pstart)) > 0)

        if semi_anti:
            if self.how == "left_semi":
                return probe_batch.with_selection(psel & any_pass)
            sel = psel & ~any_pass
            if self.null_aware:
                sel = sel & self._null_aware_mask(ctx, lvecs[0],
                                                  build_batch, rvecs[0])
            return probe_batch.with_selection(sel)

        if outer_probe:
            # keep surviving pairs; for probe rows with none, keep exactly
            # the first emitted row as a null-extended row
            off_p = jnp.take(
                jnp.cumsum(cnt_eff) - cnt_eff, p)
            is_first = jnp.arange(out_cap, dtype=jnp.int32) == off_p
            null_ext = is_first & ~jnp.take(any_pass, p)
            sel = valid & (pair_pass | null_ext)
            # null-extended rows must show NULL build columns even when
            # they reused a failed pair slot
            for out_name, col in join_kernels.gather_columns(
                    build_batch, build_idx, pair_pass & ~null_ext,
                    build_name_map):
                cols[out_name] = col
        else:
            sel = valid & pair_pass

        if self.how in ("right", "full"):
            # append build rows no surviving pair touched, null-extended left
            scatter_b = jnp.where(valid & pair_pass, build_idx, build_cap)
            matched_b = jnp.zeros((build_cap,), jnp.bool_).at[scatter_b].max(
                jnp.ones_like(pair_pass), mode="drop")
            bsel = build_batch.selection_mask()
            app_sel = bsel & ~matched_b
            app_cols: Dict[str, Column] = {}
            for name, out_name in zip(left_names, out_names[:n_left]):
                src = probe_batch.columns[name]
                app_cols[out_name] = Column(
                    jnp.zeros((build_cap,), src.data.dtype), src.dtype,
                    jnp.zeros((build_cap,), jnp.bool_), src.dictionary)
            for name, out_name in build_name_map:
                src = build_batch.columns[name]
                app_cols[out_name] = Column(src.data, src.dtype,
                                            src.validity, src.dictionary)
            merged: Dict[str, Column] = {}
            for out_name in cols:
                a, b = cols[out_name], app_cols[out_name]
                av = a.validity if a.validity is not None else \
                    jnp.ones((out_cap,), jnp.bool_)
                bv = b.validity if b.validity is not None else \
                    jnp.ones((build_cap,), jnp.bool_)
                merged[out_name] = Column(
                    jnp.concatenate([a.data, b.data.astype(a.data.dtype)]),
                    a.dtype, jnp.concatenate([av, bv]), a.dictionary)
            return Batch(merged, jnp.concatenate([sel, app_sel]))

        return Batch(cols, sel)

    def simple_string(self):
        return (f"JoinExec({self.how}, {[repr(k) for k in self.left_keys]} = "
                f"{[repr(k) for k in self.right_keys]}, "
                f"cond={self.condition!r}, cap={self.out_cap}, "
                f"uniq={self.unique_build}, "
                + ("null_aware, " if self.null_aware else "")
                + f"strategy={self.strategy})")


class RuntimeFilterExec(PhysicalPlan):
    """Probe-side runtime join filter (reference: the exec side of
    `InjectRuntimeFilter.scala:1`, with `common/sketch/BloomFilter.java`
    replaced by the device kernels in sketch.py).

    children = (probe_child, creation_plan). The creation plan is the
    join build side's cheap Project/Filter-over-leaf chain (the same
    node objects — the tree becomes a DAG; the duplicate computation is
    bounded by runtimeFilter.creationSideThreshold, mirroring the
    reference's duplicated creation-side subquery). compute() builds a
    Bloom filter + min/max bounds from the creation keys in-stage,
    pmax/pmin-combines them across the mesh axis, and narrows the probe
    batch's selection mask — placed BELOW the probe-side exchange, so
    pruned rows never radix-partition or cross ICI.

    Dropping this node never changes results (the join re-checks every
    key): streamed/out-of-core chain matchers skip it.

    `out_cap` None hands on the probe's own slots with a narrower
    selection. With a capacity K (learned by the executor's capacity
    loop from this filter's own counts, `_learn_filter_caps`) the kept
    rows are moved to the front in their order and the batch is cut to
    K slots, so every operator above runs over K slots and not the
    probe's; `rtf_overflow_<tag>` says that more than K rows were kept,
    and the capacity loop then grows K and runs the stage again. Under
    a mesh the node keeps the masked output (its counts are sums over
    the shards, and a shard's capacity wants a shard's maximum)."""

    def __init__(self, child: PhysicalPlan, creation: PhysicalPlan,
                 probe_key: Expression, build_key: Expression,
                 est_items: Optional[int] = None, fpp: float = 0.03,
                 out_cap: Optional[int] = None):
        self.children = (child, creation)
        self.probe_key = probe_key
        self.build_key = build_key
        self.est_items = est_items
        self.fpp = fpp
        self.out_cap = out_cap
        self.tag = "rf0"

    @property
    def creation(self) -> PhysicalPlan:
        return self.children[1]

    def schema(self):
        return self.children[0].schema()

    def output_partitioning(self):
        return self.children[0].output_partitioning()

    def compute(self, ctx, inputs):
        probe, build = inputs
        n_items = self.est_items
        global_cap = build.capacity * max(1, ctx.n_shards)
        if n_items is None:
            n_items = global_cap
        # the planner estimate is pre-filter; the batch capacity is a
        # tighter static bound on insertable rows — don't size the
        # (replicated) bit array past it
        n_items = min(n_items, global_cap)
        t0 = time.perf_counter()
        filt = join_kernels.build_runtime_filter(
            build, self.build_key, ctx, expected_items=max(int(n_items), 8),
            fpp=self.fpp)
        # host time spent CONSTRUCTING the filter program (trace time):
        # the build itself fuses into the stage, so this is the honest
        # per-filter build-cost observable; kept on the host
        ctx.add_host_ms(f"rtf_build_ms_{self.tag}", t0)
        keep = join_kernels.apply_runtime_filter(filt, probe,
                                                 self.probe_key)
        psel = probe.selection_mask()
        ctx.add_metric(f"rtf_tested_{self.tag}",
                       jnp.sum(psel.astype(jnp.int64)))
        ctx.add_metric(f"rtf_pruned_{self.tag}",
                       jnp.sum((psel & ~keep).astype(jnp.int64)))
        sel = psel & keep
        cap = self.out_cap
        if cap is None or ctx.n_shards > 1 or cap >= probe.capacity:
            out = probe.with_selection(sel)
        else:
            # the kept rows' positions to the front, in their order
            # (a sort whose one key is "not kept" and whose ties the
            # position breaks), then every column gathered at the
            # first K of them: on its live rows every array above is
            # the array it was
            _, perm = sort_kernels.sort_carrying_positions(
                ((~sel).astype(jnp.int8),))
            kept = jnp.sum(sel.astype(jnp.int32))
            ctx.add_flag(f"rtf_overflow_{self.tag}", kept > cap)
            out = sort_kernels.apply_permutation(probe, perm[:cap], kept)
        # the slots handed on, beside rtf_tested / rtf_pruned: a shape,
        # known while the stage is traced, so the host's record has it
        # and the program does not
        ctx.host[f"rtf_slots_{self.tag}"] = \
            out.capacity * max(1, ctx.n_shards)
        # and the indices the Bloom build scattered, one a creation slot
        # whatever k is
        ctx.host[f"rtf_build_indices_{self.tag}"] = \
            filt.bloom.indices * max(1, ctx.n_shards)
        return out

    def simple_string(self):
        # `cap` only when set: a filter that hands on its probe's slots
        # keeps the text, and so the stage key and the compiled
        # program, it always had
        return (f"RuntimeFilterExec({self.probe_key!r} IN "
                f"bloom({self.build_key!r}), est={self.est_items}, "
                + (f"cap={self.out_cap}, " if self.out_cap is not None
                   else "")
                + f"fpp={self.fpp})")


def _unify_key_dictionaries(lvecs: List[Vec], rvecs: List[Vec]
                            ) -> Tuple[List[Vec], List[Vec]]:
    """Re-encode string join keys onto one shared dictionary per key pair.

    Two independently-encoded string columns assign codes independently, so
    comparing raw codes is meaningless (round-1 high-severity bug). The
    merge happens on host at trace time; codes are remapped with a device
    gather. Non-string keys pass through."""
    from ..columnar import unify_string_columns
    out_l, out_r = [], []
    for lv, rv in zip(lvecs, rvecs):
        if not isinstance(lv.dtype, T.StringType) and \
                not isinstance(rv.dtype, T.StringType):
            out_l.append(lv)
            out_r.append(rv)
            continue
        if lv.dictionary is None or rv.dictionary is None:
            raise AnalysisError(
                "string join keys require dictionary-encoded columns")
        l_data, r_data, merged = unify_string_columns(
            lv.data, lv.dictionary, rv.data, rv.dictionary)
        out_l.append(Vec(l_data, T.STRING, lv.validity, merged))
        out_r.append(Vec(r_data, T.STRING, rv.validity, merged))
    return out_l, out_r


def _key_width(v: Vec) -> Optional[int]:
    """Bits needed to represent the key's domain, or None when unbounded."""
    if v.dictionary is not None:
        n = len(v.dictionary)
        return max(1, (n - 1).bit_length()) if n > 1 else 1
    if isinstance(v.dtype, T.BooleanType):
        return 1
    if isinstance(v.dtype, T.ByteType):
        return 8
    if isinstance(v.dtype, T.ShortType):
        return 16
    if isinstance(v.dtype, (T.IntegerType, T.DateType)):
        return 32
    return None  # int64/timestamp: full range, cannot pack with others


def _unsigned_key(v: Vec, width: int):
    """Map key values to [0, 2^width) preserving distinctness (bias the
    sign bit for signed dtypes; dictionary codes are already unsigned)."""
    data = v.data.astype(jnp.int64)
    if v.dictionary is None and not isinstance(v.dtype, T.BooleanType):
        data = data + jnp.int64(1 << (width - 1))
    return data


_MIX_MUL = np.uint64(0xBF58476D1CE4E5B9)
_MIX_MUL2 = np.uint64(0x94D049BB133111EB)


def _mix64(x):
    """splitmix64 finalizer (wrapping uint64 arithmetic)."""
    u = x.astype(jnp.uint64)
    u = (u ^ (u >> 30)) * _MIX_MUL
    u = (u ^ (u >> 27)) * _MIX_MUL2
    u = u ^ (u >> 31)
    return u.astype(jnp.int64)


def _pack_key_pair(lvecs: List[Vec], rvecs: List[Vec]
                   ) -> Tuple[Vec, Vec, bool]:
    """Combine multi-key join keys into one int64 key per side.

    Widths are derived JOINTLY per key position (max of the two sides) so
    both sides share one bit layout. Returns (lk, rk, exact): when the
    combined widths fit in 63 bits the packing is collision-free
    (exact=True); otherwise both sides are hash-mixed and the caller MUST
    re-verify per-key equality on matches (round-1 packed lossily and
    joined silently wrong)."""
    validity_l = None
    validity_r = None
    for lv, rv in zip(lvecs, rvecs):
        for v in (lv, rv):
            if not isinstance(v.dtype, (T.IntegralType, T.StringType,
                                        T.DateType, T.BooleanType,
                                        T.TimestampType)):
                raise AnalysisError(
                    f"multi-key join on {v.dtype!r} unsupported")
        if lv.validity is not None:
            validity_l = lv.validity if validity_l is None else \
                (validity_l & lv.validity)
        if rv.validity is not None:
            validity_r = rv.validity if validity_r is None else \
                (validity_r & rv.validity)
    def kind(v):
        if v.dictionary is not None:
            return "dict"
        return "bool" if isinstance(v.dtype, T.BooleanType) else "int"

    widths = []
    for lv, rv in zip(lvecs, rvecs):
        wl, wr = _key_width(lv), _key_width(rv)
        if wl is None or wr is None or kind(lv) != kind(rv):
            widths.append(None)  # hash path (+ per-key re-verify)
        else:
            widths.append(max(wl, wr))
    if all(w is not None for w in widths) and sum(widths) <= 63:
        acc_l = jnp.zeros((), jnp.int64)
        acc_r = jnp.zeros((), jnp.int64)
        for lv, rv, w in zip(lvecs, rvecs, widths):
            acc_l = (acc_l << w) | _unsigned_key(lv, w)
            acc_r = (acc_r << w) | _unsigned_key(rv, w)
        return (Vec(acc_l, T.LONG, validity_l),
                Vec(acc_r, T.LONG, validity_r), True)
    hl = jnp.zeros((), jnp.int64)
    hr = jnp.zeros((), jnp.int64)
    for lv, rv in zip(lvecs, rvecs):
        hl = _mix64(hl ^ _mix64(lv.data.astype(jnp.int64)))
        hr = _mix64(hr ^ _mix64(rv.data.astype(jnp.int64)))
    return Vec(hl, T.LONG, validity_l), Vec(hr, T.LONG, validity_r), False


class ExchangeExec(UnaryExec):
    """Repartitioning boundary (reference: ShuffleExchangeExec.scala:115
    for the hash case, BroadcastExchangeExec.scala:78 for Replicated).

    On a single chip this is the identity; inside a `shard_map` over the
    mesh it lowers to collectives (parallel/shuffle.py):
      HashPartitioning           -> radix-partition + all_to_all
      SinglePartition/Replicated -> all_gather"""

    def __init__(self, child: PhysicalPlan, partitioning: Partitioning):
        self.children = (child,)
        self.partitioning = partitioning
        #: per-(src,dst) receive block size; None = seeded from the input
        #: capacity (2x uniform spread). The executor learns it from the
        #: fullest bucket a converged execution filled (a large block
        #: that would at least halve) and grows it on overflow
        self.block_cap: Optional[int] = None
        self.tag = "e0"

    def schema(self):
        return self.child.schema()

    def output_partitioning(self):
        return self.partitioning

    def compute(self, ctx, inputs):
        if ctx.axis_name is None or ctx.n_shards <= 1:
            return inputs[0]
        from ..parallel import shuffle
        if isinstance(self.partitioning, HashPartitioning):
            return shuffle.exchange_hash(inputs[0], self.partitioning.keys,
                                         ctx, block_cap=self.block_cap,
                                         tag=self.tag)
        if isinstance(self.partitioning, RangePartitioning):
            return shuffle.exchange_range(inputs[0],
                                          self.partitioning.orders, ctx,
                                          block_cap=self.block_cap,
                                          tag=self.tag)
        if isinstance(self.partitioning, (SinglePartition, Replicated)):
            return shuffle.all_gather_batch(inputs[0], ctx)
        raise AnalysisError(
            f"no collective lowering for {self.partitioning!r}")

    def simple_string(self):
        return f"ExchangeExec({self.partitioning!r}, block={self.block_cap})"


class UnionExec(PhysicalPlan):
    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 out_schema: T.Schema):
        self.children = (left, right)
        self._schema = out_schema

    def schema(self):
        return self._schema

    def output_partitioning(self):
        # per-shard concatenation of sharded children is NOT a single
        # partition: inheriting the base SinglePartition would both skip
        # needed exchanges above and make the executor stripe the
        # (distinct) per-shard output (round-2 high-severity bug)
        lp = self.children[0].output_partitioning()
        rp = self.children[1].output_partitioning()
        if isinstance(lp, SinglePartition) and isinstance(rp, SinglePartition):
            return SinglePartition()
        return UnknownPartitioning(
            max(lp.num_partitions, rp.num_partitions))

    def compute(self, ctx, inputs):
        from ..columnar import unify_string_columns
        lb, rb = inputs
        if ctx.axis_name is not None and ctx.n_shards > 1:
            # a SinglePartition child is physically replicated on every
            # shard; concatenated as-is it would appear n times in the
            # gathered output — take this shard's stripe so the union
            # totals exactly one copy per side
            from ..parallel.shuffle import stripe_batch
            parts = [c.output_partitioning() for c in self.children]
            if not all(isinstance(p, SinglePartition) for p in parts):
                if isinstance(parts[0], (SinglePartition, Replicated)):
                    lb = stripe_batch(lb, ctx)
                if isinstance(parts[1], (SinglePartition, Replicated)):
                    rb = stripe_batch(rb, ctx)
        cols = {}
        for out_f, ln, rn in zip(self._schema.fields, lb.names, rb.names):
            lc, rc = lb.columns[ln], rb.columns[rn]
            l_data, r_data = lc.data, rc.data
            dictionary = None
            if isinstance(out_f.dtype, T.StringType):
                # merge the two dictionaries and remap right codes — raw
                # right codes under the left dictionary decode to wrong
                # strings (round-1 high-severity bug)
                if lc.dictionary is None or rc.dictionary is None:
                    raise AnalysisError(
                        "UNION of string columns requires dictionaries")
                l_data, r_data, dictionary = unify_string_columns(
                    l_data, lc.dictionary, r_data, rc.dictionary)
            data = jnp.concatenate([
                l_data.astype(out_f.dtype.np_dtype),
                r_data.astype(out_f.dtype.np_dtype)])
            if lc.validity is None and rc.validity is None:
                validity = None
            else:
                lv = lc.validity if lc.validity is not None else \
                    jnp.ones((lb.capacity,), jnp.bool_)
                rv = rc.validity if rc.validity is not None else \
                    jnp.ones((rb.capacity,), jnp.bool_)
                validity = jnp.concatenate([lv, rv])
            cols[out_f.name] = Column(data, out_f.dtype, validity, dictionary)
        sel = jnp.concatenate([lb.selection_mask(), rb.selection_mask()])
        return Batch(cols, sel)
