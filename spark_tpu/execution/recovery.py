"""Partial-progress recovery: chunk-granular retry, stage-output
reuse, and checkpoint/restore for streaming + mesh execution.

The PR-2 recovery layer is whole-query granular: `_execute_recover`
loops the entire `_execute_batch_inner`, so a fault in chunk 37 of a
streaming aggregate re-ingests from chunk 0, and a lost mesh host
throws away all accumulated state. The reference's resilience story is
*granular* — lineage + task-level retry re-runs one partition, and
completed shuffle files survive downstream failures (the RDD lineage
model of Zaharia et al., NSDI'12). This module restores that
granularity at the three seams this engine has:

- **ChunkRetrier** — per-chunk retry inside the one chunk driver
  (`chunk_stream.drive`, under the `streaming_agg.py` scan/spill/mesh
  variants and `external.py`).
  The carry state (accumulator tables, chunk cursor) is only advanced
  after a chunk succeeds, so a TRANSIENT/UNAVAILABLE fault replays
  exactly the failed chunk —
  `spark_tpu.execution.chunkRetry.{enabled,maxRetries}`. The
  `stream_chunk` fault seam fires once per chunk attempt here. The
  `load_chunks` ingest edge is NOT retried: a reader failure poisons
  the ChunkIterator (io/sources.py) and surfaces to the whole-query
  ladder, which restarts the stream against a fresh iterator.
- **StageOutputMemo** (inside RecoveryContext) — a per-query memo of
  completed stage outputs (streamed-aggregate splices, join build
  sides, generate materializations), the analog of shuffle files
  surviving a downstream task failure. When `_handle_failure`
  re-executes the query, completed upstream stages replay from the
  memo instead of re-running. Invalidated by epoch bump whenever a
  re-plan changes shapes (_ReplanRequest, mesh fallback, the OOM
  ladder's deviceBudget re-plan).
- **MeshCheckpoint** — every `checkpoint.everyChunks` chunks the mesh
  streaming driver snapshots its accumulator state device->host (as a
  partial-aggregate Arrow table, the exact shape a FINAL aggregate
  consumes); on mesh failure the single-device fallback resumes at the
  checkpointed chunk cursor instead of chunk 0. The `mesh_checkpoint`
  fault seam fires at each snapshot point.

All recovery actions flow through the executor's `_record_fault`
(`chunk_retry`, `stage_reuse`, `checkpoint_restore`) into
fault_summary, the event log and history; the process metrics registry
counts `rec_chunks_replayed`, `rec_stages_reused`, `rec_ckpt_bytes`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

from .failures import FailureClass, RetryPolicy, classify

CHUNK_RETRY_ENABLED_KEY = "spark_tpu.execution.chunkRetry.enabled"
CHUNK_RETRY_MAX_KEY = "spark_tpu.execution.chunkRetry.maxRetries"
CHECKPOINT_EVERY_KEY = "spark_tpu.execution.checkpoint.everyChunks"
BACKOFF_KEY = "spark_tpu.execution.backoffMs"

#: failure classes a single chunk replay can recover (OOM descends the
#: executor ladder instead — replaying the same chunk into the same
#: exhausted HBM would spin the per-chunk budget for nothing)
_RETRYABLE = (FailureClass.TRANSIENT, FailureClass.TIMEOUT)


@dataclass
class MeshCheckpoint:
    """Device->host snapshot of a mesh stream's accumulator state:
    the partial-aggregate rows covering the first `cursor` chunks."""

    key: str
    cursor: int  # chunks folded into `table` (resume skips these)
    table: Any  # pyarrow.Table of partial-aggregate rows


class ChunkRetrier:
    """Per-chunk retry policy for the streaming drivers' COMPUTE steps.

    `run(step)` fires the `stream_chunk` chaos seam, executes the
    step, and — when chunk retry is enabled — replays the step on
    TRANSIENT/TIMEOUT failures under a fresh per-chunk RetryPolicy
    (the spark.task.maxFailures discipline: the budget is per task
    attempt, not per stream). The caller's carry state must only
    advance on success, so the pre-chunk state is the implicit
    snapshot the replay runs against.

    INGEST (`next(chunks)`) is deliberately NOT retried: a reader
    failure poisons the ChunkIterator (io/sources.py), so a replay
    could never succeed — and a post-cursor failure replayed on a
    single-pass iterator would silently skip rows. Ingest failures
    surface to the whole-query ladder, which restarts the stream
    against a fresh iterator.

    Donation caveat: the hot-path update steps donate their carried
    tables; a REAL mid-dispatch failure may have consumed them, in
    which case the replay itself fails — the original transient error
    is re-raised so the outer whole-query ladder still classifies the
    failure as retryable (degraded to PR-2 whole-stream granularity,
    never worse).
    """

    def __init__(self, conf, recovery: Optional["RecoveryContext"] = None,
                 site: str = "stream_chunk"):
        self.enabled = bool(conf.get(CHUNK_RETRY_ENABLED_KEY))
        self.max_retries = int(conf.get(CHUNK_RETRY_MAX_KEY))
        self.backoff_ms = float(conf.get(BACKOFF_KEY))
        self.recovery = recovery
        # chaos seam fired per attempt: "stream_chunk" for the compute
        # steps, "ingest_prefetch" for the prefetcher's host-decode step
        # (io/sources.py) — same retry policy, same recovery recording
        self.site = site

    def run(self, step, chunk: int = 0):
        from ..testing import faults
        from .lifecycle import checkpoint
        # cooperative cancellation boundary: every chunk of every
        # driver (streaming direct/spill/mesh + external collect)
        # passes through here, so a cancel/deadline lands within one
        # chunk of delivery (execution/lifecycle.py)
        checkpoint("chunk")
        policy: Optional[RetryPolicy] = None
        orig: Optional[Exception] = None
        while True:
            try:
                # chaos seam: one hit per chunk attempt (replays
                # re-fire, so multi-fault rules can target retries).
                # Literal site strings: the fault-site lint statically
                # proves each KNOWN_SITE has a wired fire() seam.
                if self.site == "ingest_prefetch":
                    faults.fire("ingest_prefetch")
                elif self.site == "udf_batch":
                    # seam fires INSIDE the step (python_eval's worker
                    # lane): the step must kill the in-flight worker
                    # before the injected error surfaces, so the
                    # fatal rule models a real SIGKILL mid-batch
                    pass
                else:
                    faults.fire("stream_chunk")
                return step()
            except Exception as e:  # noqa: BLE001 — classified below
                if not self.enabled or self.max_retries <= 0:
                    raise
                cls = classify(e)
                if cls is FailureClass.CANCELLED:
                    # lifecycle control, not a fault: never replayed,
                    # and never laundered into a saved `orig` transient
                    raise
                if cls not in _RETRYABLE:
                    if orig is not None:
                        # the replay hit a secondary non-retryable error
                        # (e.g. a donated buffer consumed by the failed
                        # dispatch): surface the ORIGINAL transient so
                        # the outer ladder still retries the stream
                        raise orig from e
                    raise
                if policy is None:
                    policy = RetryPolicy(self.max_retries, self.backoff_ms)
                slept = policy.attempt_retry()
                if slept is None:
                    raise  # per-chunk budget exhausted: outer ladder
                orig = e
                if self.recovery is not None:
                    self.recovery.chunk_replayed(e, chunk=chunk,
                                                 backoff_ms=slept)


class RecoveryContext:
    """Per-query-execution recovery state, created by the executor at
    every `execute_batch` / external-collect entry and threaded through
    the streaming drivers: the fault recorder, the stage-output memo,
    and the mesh checkpoint store."""

    def __init__(self, metrics=None, record=None):
        self.metrics = metrics  # session MetricsRegistry (or None)
        self._record = record   # QueryExecution._record_fault (or None)
        # stage-output memo: key -> (epoch, attempt, value). Keys are
        # (kind, id(node)) — node identities are stable across
        # recovery re-executions (the physical plan is only rebuilt on
        # re-plan, which bumps the epoch and orphans the old ids).
        self._memo: Dict[Tuple, Tuple[int, int, Any]] = {}
        self.epoch = 0
        self.checkpoints: Dict[str, MeshCheckpoint] = {}
        # per-stream progress watermark (checkpoint key -> chunks
        # consumed): lets a checkpoint restore report exactly how many
        # chunks the replay re-covers (progress - cursor), bounding the
        # elastic-mesh replay proof. Survives invalidate() like the
        # checkpoints it measures against.
        self._progress: Dict[str, int] = {}
        # set by _handle_failure once any recovery action was applied:
        # memo hits before the first failure are intra-attempt dedup,
        # not recovery, and must not pollute fault_summary
        self.in_recovery = False
        # recovery-attempt ordinal + per-(attempt, key) reuse dedup: a
        # re-execution may consult the same memo entry several times
        # (direct probe, then spill fallback), but that is ONE stage
        # replayed from the memo, not several
        self.attempt = 0
        self._reuse_logged: set = set()

    # -- recording ----------------------------------------------------------

    def record(self, action: str, exc=None, **extra) -> None:
        if self._record is not None:
            self._record(action, exc, **extra)

    def chunk_replayed(self, exc, chunk: int, backoff_ms: float) -> None:
        self.record("chunk_retry", exc, chunk=int(chunk),
                    backoff_ms=round(float(backoff_ms), 1))
        if self.metrics is not None:
            self.metrics.counter("rec_chunks_replayed").inc()

    # -- stage-output memo --------------------------------------------------

    def begin_recovery_attempt(self) -> None:
        """Called by the executor whenever a recovery action was
        applied and the query will re-execute: memo hits from here on
        are genuine stage reuse (and count once per attempt per key)."""
        self.in_recovery = True
        self.attempt += 1

    def memo_get(self, key: Tuple, label: str = ""):
        hit = self._memo.get(key)
        if hit is None or hit[0] != self.epoch:
            return None
        epoch, put_attempt, value = hit
        # "stage reuse" = an output from a PREVIOUS attempt survived
        # this re-execution; hits on entries put within the current
        # attempt are intra-attempt dedup (direct probe then spill
        # fallback touching the same build side), not recovery
        if self.in_recovery and put_attempt < self.attempt \
                and (self.attempt, key) not in self._reuse_logged:
            self._reuse_logged.add((self.attempt, key))
            self.record("stage_reuse", None, stage=str(label)[:120])
            if self.metrics is not None:
                self.metrics.counter("rec_stages_reused").inc()
        return value

    def memo_put(self, key: Tuple, value) -> None:
        self._memo[key] = (self.epoch, self.attempt, value)

    def invalidate(self) -> None:
        """A re-plan changed shapes (join strategy, mesh fallback, OOM
        deviceBudget reroute): memoized outputs no longer splice into
        the new plan. Checkpoints survive — they are host Arrow data
        validated by a plan-independent key."""
        self.epoch += 1
        self._memo.clear()

    # -- mesh checkpoints ---------------------------------------------------

    def save_checkpoint(self, key: str, cursor: int, snapshot) -> None:
        """Snapshot the mesh stream's accumulator state at `cursor`
        consumed chunks. `snapshot` is a thunk producing the host Arrow
        partial table (called AFTER the chaos seam, so an injected
        `mesh_checkpoint` fault models a failure at the snapshot point
        and leaves the PREVIOUS checkpoint intact)."""
        from ..testing import faults
        faults.fire("mesh_checkpoint")
        table = snapshot()
        self.checkpoints[key] = MeshCheckpoint(key=key, cursor=int(cursor),
                                               table=table)
        if self.metrics is not None:
            self.metrics.counter("rec_ckpt_bytes").inc(int(table.nbytes))

    def get_checkpoint(self, key: str) -> Optional[MeshCheckpoint]:
        return self.checkpoints.get(key)

    def note_progress(self, key: str, chunks: int) -> None:
        """Advance the stream's consumed-chunk watermark (monotone)."""
        if int(chunks) > self._progress.get(key, 0):
            self._progress[key] = int(chunks)

    def progress(self, key: str) -> int:
        return self._progress.get(key, 0)

    def restore_replayed(self, key: str, cursor: int) -> int:
        """Chunks the resume at `cursor` re-covers (the failed attempt
        had consumed up to the watermark): counted into
        rec_chunks_replayed so the bounded-replay proof — at most
        checkpoint.everyChunks chunks per mesh recovery — is a metric,
        not an inference."""
        replayed = max(0, self.progress(key) - int(cursor))
        if replayed and self.metrics is not None:
            self.metrics.counter("rec_chunks_replayed").inc(replayed)
        return replayed

    def release(self) -> None:
        """Drop retained stage outputs (device batches) and checkpoint
        tables when the execution finishes — the memo exists to span
        recovery loops, not executions."""
        self._memo.clear()
        self.checkpoints.clear()
        self._progress.clear()
