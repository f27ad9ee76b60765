"""SparkSession analog: catalog + conf + entry points.

Reference: `sql/core/src/main/scala/org/apache/spark/sql/SparkSession.scala:83`
(builder, per-session conf/catalog/state) and `DataFrameReader`.
"""

from __future__ import annotations

import contextlib
from contextvars import ContextVar
from typing import Dict, Optional

import pandas as pd
import pyarrow as pa

from .columnar import Batch
from .config import Conf
from .dataframe import DataFrame
from .io.sources import ArrowTableSource, ParquetSource, TableSource
from .plan import logical as L

#: context-local active session (the SQL service pins one per worker
#: thread with `session.as_active()`); falls back to the process-global
#: singleton below, preserving the historical single-caller behavior
_ACTIVE: ContextVar[Optional["SparkTpuSession"]] = ContextVar(
    "spark_tpu_active_session", default=None)


class _ActiveSessionMeta(type):
    """`SparkTpuSession._active` used to be a process-global class
    attribute; under the concurrent SQL service it resolves per context
    (each worker thread sees the session it activated) with the global
    as fallback. Reads and writes of the class attribute keep working
    unchanged — tests assign `SparkTpuSession._active = None` and the
    builder reads it — via this metaclass property."""

    @property
    def _active(cls) -> Optional["SparkTpuSession"]:
        s = _ACTIVE.get()
        return s if s is not None else cls._global_active

    @_active.setter
    def _active(cls, value: Optional["SparkTpuSession"]) -> None:
        cls._global_active = value
        _ACTIVE.set(value)


class SparkTpuSession(metaclass=_ActiveSessionMeta):
    _global_active: Optional["SparkTpuSession"] = None

    def __init__(self, conf: Optional[Conf] = None,
                 register_active: bool = True):
        self.conf = conf or Conf()
        from .catalog import Catalog
        self.catalog: Catalog = Catalog(self)
        self._stage_cache: Dict[str, object] = {}
        # beside it, under the same keys: what a stage's operators
        # learned on the host while it was traced (ExecContext.host)
        self._stage_host: Dict[str, Dict[str, object]] = {}
        # observability spine (observability/): the listener bus every
        # event-log line / trace file / metrics flush hangs off, the
        # process metrics registry, XLA stage-cost memo, and the
        # session-unique event-log identity + query-id sequence
        from .observability import ListenerBus, MetricsRegistry
        from .observability.sinks import (install_default_listeners,
                                          make_app_id)
        self.listeners = ListenerBus()
        self.metrics = MetricsRegistry()
        self.app_id = make_app_id()
        self._stage_costs: Dict[str, dict] = {}
        # memoized jaxpr-analysis findings per stage key (analysis/)
        self._analysis_memo: Dict[str, list] = {}
        self._query_seq = 0
        install_default_listeners(self)
        # plan-fingerprint data cache (reference: CacheManager.scala):
        # requested marks fill with materialized Arrow tables on first
        # action; later plans substitute equal subtrees with cached scans
        self._cache_requests: Dict[str, object] = {}  # fp -> LogicalPlan
        from .service.arbiter import RESULT_CACHE_BYTES_KEY, ResultCache
        # standalone sessions keep the pre-service unbounded cache
        # unless the bound is explicitly configured: a cache()-marked
        # table larger than a default bound would silently recompute
        # per reference. Pooled sessions get this replaced by the
        # arbiter's shared, conf-bounded cache (service/pool.py).
        self._data_cache = ResultCache(
            max_bytes=(int(self.conf.get(RESULT_CACHE_BYTES_KEY))
                       if self.conf.is_explicitly_set(RESULT_CACHE_BYTES_KEY)
                       else 0),
            metrics=self.metrics)
        self._implicit_cache_fps: set = set()
        self._exec_depth = 0  # outermost-execution tracking for eviction
        # plan-fingerprint -> {kind:tag -> capacity} discovered by the
        # AQE overflow loop; repeated executions seed these and skip the
        # overflow->re-jit ramp
        self._aqe_caps: Dict[str, Dict[str, int]] = {}
        from .udf import UDFRegistration
        self.udf = UDFRegistration(self)
        # out-of-process UDF worker pool (udf_worker/pool.py): created
        # eagerly (a pool object spawns nothing until first checkout)
        # so lockwatch can wrap its cv at session install time; bounds
        # are refreshed from conf at each worker-mode evaluation.
        # Workers are reused across this session's queries; idle ones
        # reap after udf.pool.idleTimeoutMs, and a worker's stdin EOF
        # on process exit ends the child, so none outlives the engine.
        from .udf_worker.pool import UdfWorkerPool
        self._udf_pool = UdfWorkerPool(
            int(self.conf.get("spark_tpu.sql.udf.pool.maxWorkers")),
            float(self.conf.get("spark_tpu.sql.udf.pool.idleTimeoutMs")),
            metrics=self.metrics)
        if register_active:
            SparkTpuSession._active = self

    @contextlib.contextmanager
    def as_active(self):
        """Pin this session as the context-local active session (what
        `builder().get_or_create()` returns) for the enclosed block —
        the SQL service wraps each query execution in this so pooled
        sessions never stomp the process-global singleton or each
        other."""
        token = _ACTIVE.set(self)
        try:
            yield self
        finally:
            _ACTIVE.reset(token)

    # -- observability ------------------------------------------------------

    def _next_query_id(self) -> int:
        self._query_seq += 1
        return self._query_seq

    def add_listener(self, listener) -> None:
        """Register a QueryListener on the session bus (the
        SparkContext.addSparkListener seat)."""
        self.listeners.register(listener)

    def remove_listener(self, listener) -> None:
        self.listeners.unregister(listener)

    addListener = add_listener
    removeListener = remove_listener

    def warmup(self) -> int:
        """Warm-start the in-memory stage cache from the persistent
        compile cache (execution/compile_cache.py): replay the
        manifest of recently-seen stage keys, deserializing each
        entry whose environment fingerprint matches this process —
        deserialization only, no compiles. Returns entries installed
        (0 when spark_tpu.sql.compileCache.enabled is off). The
        SQL service calls the pooled equivalent at startup
        (compileCache.warmStart)."""
        from .execution.compile_cache import warm_start
        return warm_start(self._stage_cache, self.conf, self.metrics)

    def cancel(self, query_id: int) -> bool:
        """Request cooperative cancellation of a query currently
        executing on this session (the SparkContext.cancelJobGroup
        seat, execution/lifecycle.py): the running execution raises a
        structured QueryCancelledError at its next boundary — chunk,
        stage attempt, retry backoff, queue/lease wait — releasing
        every lease/worker/checkpoint it holds. Returns False when no
        execution with that query_id is registered (already finished,
        or never started). Callable from any thread."""
        from .execution import lifecycle
        return lifecycle.cancel(self.app_id, query_id)

    def decommission_shards(self, shards) -> None:
        """Gracefully drain the given mesh positions (elastic mesh,
        parallel/elastic.py): a running mesh stream checkpoints at its
        next chunk boundary and continues on the reduced gang; the
        drained devices stay excluded for later queries. The
        BlockManagerDecommissioner seat."""
        from .parallel.elastic import decommission_shards
        decommission_shards(self, shards)

    # -- data cache ---------------------------------------------------------

    @staticmethod
    def _plan_fingerprint(plan) -> str:
        """tree_string + each scan source's identity stamp: a Parquet
        rewrite or table re-registration changes the fingerprint, so a
        cached materialization can never match stale data (round-3
        ADVICE medium)."""
        tokens = [s.source.cache_token() for s in L.iter_scans(plan)]
        return plan.tree_string() + f"#src{tokens!r}"

    def mark_cache(self, plan, implicit: bool = False) -> None:
        fp = self._plan_fingerprint(plan)
        self._cache_requests[fp] = plan
        if implicit:
            # statement-scoped (e.g. WITH-clause views): evicted when the
            # outermost execution finishes, so implicit materializations
            # neither go stale nor grow session memory unboundedly
            self._implicit_cache_fps.add(fp)

    def uncache(self, plan) -> None:
        fp = self._plan_fingerprint(plan)
        self._cache_requests.pop(fp, None)
        self._data_cache.pop(fp, None)
        self._implicit_cache_fps.discard(fp)

    def _evict_implicit_caches(self) -> None:
        """Statement-scoped DATA lifetime: drop materialized tables but
        KEEP the requests/marks, so re-executing the same statement
        still dedupes a multiply-referenced CTE within that execution."""
        for fp in self._implicit_cache_fps:
            self._data_cache.pop(fp, None)

    # -- builder ------------------------------------------------------------

    class Builder:
        def __init__(self):
            self._conf = Conf()

        def config(self, key: str, value) -> "SparkTpuSession.Builder":
            self._conf.set(key, value)
            return self

        def get_or_create(self) -> "SparkTpuSession":
            if SparkTpuSession._active is not None:
                return SparkTpuSession._active
            return SparkTpuSession(self._conf)

        getOrCreate = get_or_create

    @classmethod
    def builder(cls) -> "SparkTpuSession.Builder":
        return cls.Builder()

    # -- entry points -------------------------------------------------------

    def range(self, start: int, end: Optional[int] = None,
              step: int = 1) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(self, L.Range(int(start), int(end), int(step)))

    def create_dataframe(self, data, name: str = "df") -> DataFrame:
        if isinstance(data, pd.DataFrame):
            table = pa.Table.from_pandas(data, preserve_index=False)
        elif isinstance(data, pa.Table):
            table = data
        elif isinstance(data, dict):
            table = pa.table(data)
        else:
            raise TypeError(f"cannot create DataFrame from {type(data)}")
        source = ArrowTableSource(name, table)
        return DataFrame(self, L.Scan(source))

    createDataFrame = create_dataframe

    def register_table(self, name: str, source_or_table) -> None:
        # invalidate cached materializations referencing this name (a
        # re-registered table must never serve stale cached results)
        stale = [fp for fp, plan in self._cache_requests.items()
                 if any(s.source.name == name for s in L.iter_scans(plan))]
        for fp in stale:
            self._cache_requests.pop(fp, None)
            self._data_cache.pop(fp, None)
            self._implicit_cache_fps.discard(fp)
        # free the replaced source's device-resident batches (they are
        # unreachable under the new token and would pin HBM until LRU
        # pressure evicted them)
        old = self.catalog.get(name)
        if old is not None:
            token = old.cache_token()
            if token is not None:
                from .io.device_cache import CACHE
                CACHE.invalidate_token(token)
        if isinstance(source_or_table, TableSource):
            self.catalog[name] = source_or_table
        elif isinstance(source_or_table, pa.Table):
            self.catalog[name] = ArrowTableSource(name, source_or_table)
        elif isinstance(source_or_table, pd.DataFrame):
            self.catalog[name] = ArrowTableSource(
                name, pa.Table.from_pandas(source_or_table,
                                           preserve_index=False))
        elif isinstance(source_or_table, DataFrame):
            self.catalog[name] = ArrowTableSource(
                name, source_or_table.collect())
        else:
            raise TypeError(f"cannot register {type(source_or_table)}")

    def table(self, name: str) -> DataFrame:
        if name not in self.catalog:
            raise KeyError(f"table {name!r} not found; "
                           f"known: {sorted(self.catalog)}")
        return DataFrame(self, L.Scan(self.catalog[name]))

    def read_parquet(self, path: str, name: Optional[str] = None) -> DataFrame:
        return DataFrame(self, L.Scan(ParquetSource(path, name)))

    def read_csv(self, path: str, name: Optional[str] = None,
                 **options) -> DataFrame:
        from .io.sources import CsvSource
        return DataFrame(self, L.Scan(CsvSource(path, name, **options)))

    def read_json(self, path: str, name: Optional[str] = None) -> DataFrame:
        from .io.sources import JsonSource
        return DataFrame(self, L.Scan(JsonSource(path, name)))

    def file_stream(self, path: str, schema_df=None,
                    format: str = "parquet"):
        """Directory-tailing streaming source (the readStream analog):
        returns a FileStreamSource whose `.to_df()` feeds
        `DataFrame.write_stream`. Offsets are a persisted seen-file
        log under the query's checkpoint; corrupt files quarantine
        instead of wedging the stream (see
        spark_tpu.streaming.source.file.strict)."""
        from .streaming import FileStreamSource
        return FileStreamSource(self, path, schema_df=schema_df,
                                format=format)

    def network_stream(self, host: str, port: int, schema_df):
        """Socket streaming source (io/network_source.py): length-
        framed Arrow-IPC record batches over TCP, each frame persisted
        under the query's checkpoint BEFORE it becomes a visible
        offset, with a reconnect/backoff ladder (see the
        spark_tpu.streaming.source.network.* keys). Returns a
        NetworkStreamSource whose `.to_df()` feeds
        `DataFrame.write_stream`."""
        from .io.network_source import NetworkStreamSource
        return NetworkStreamSource(self, host, port, schema_df)

    def long_accumulator(self, name: str = "acc") -> "Accumulator":
        return Accumulator(name, 0)

    def double_accumulator(self, name: str = "acc") -> "Accumulator":
        return Accumulator(name, 0.0)

    longAccumulator = long_accumulator
    doubleAccumulator = double_accumulator

    def sql(self, query: str) -> DataFrame:
        from .sql.parser import parse_sql
        plan = parse_sql(query, self)
        return DataFrame(self, plan)


class Accumulator:
    """Driver-side mergeable counter (reference: AccumulatorV2.scala:44).
    Python UDFs and grouped-map functions run host-side, so updates are
    plain in-process adds — the task->driver merge protocol collapses
    away; per-operator engine metrics ride the psum'd stats channel
    instead (metric/SQLMetrics.scala:40 analog in ExecContext)."""

    def __init__(self, name: str, value=0):
        self.name = name
        self._value = value

    def add(self, v) -> None:
        self._value += v

    @property
    def value(self):
        return self._value

    def reset(self) -> None:
        self._value = type(self._value)()

    def __repr__(self):
        return f"Accumulator({self.name}={self._value!r})"
