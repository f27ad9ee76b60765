"""Structured failure taxonomy + retry policy for stage execution.

The reference's TaskScheduler distinguishes failure kinds and reacts per
kind — transient task failures retry (`TaskSetManager.scala:1`,
spark.task.maxFailures), fetch failures resubmit the parent stage
(`DAGScheduler.scala:1`), OOM kills spill and re-execute. XLA collapses
all of that into one opaque exception channel; this module restores the
structure:

- TRANSIENT: infra flakes (UNAVAILABLE, DEADLINE_EXCEEDED, channel
  resets) — retried with exponential backoff + jitter
  (`spark_tpu.execution.{maxRetries,backoffMs}`).
- TIMEOUT: a stage blew its wall-clock deadline
  (`spark_tpu.execution.stageTimeoutMs`) — retried like TRANSIENT
  (a fresh compile/run often clears a wedged runtime).
- OOM: HBM RESOURCE_EXHAUSTED — handled by the executor's degradation
  ladder (evict device cache -> reroute through the host-spill chunked
  path -> diagnostic raise), the UnifiedMemoryManager
  evict-then-spill discipline with host RAM as the spill tier. HBM
  only: the device compiler refusing a program (a kernel over its
  scoped-VMEM limit also says RESOURCE_EXHAUSTED) is FATAL — no
  eviction or re-plan changes what the compiler accepts.
- OVERFLOW: static-capacity overflow. Never an exception — it flows as
  flags through the stats channel into the AQE re-jit loop; listed here
  so the taxonomy is total.
- CANCELLED: lifecycle control (execution/lifecycle.py) — the query
  was cancelled or blew its end-to-end queryDeadlineMs. NEVER retried,
  never degraded: the recovery ladder re-raises immediately (a
  deadline blown mid-recovery must stop the ladder, not retry through
  it).
- FATAL: everything else — surfaces immediately. A compiler refusal
  surfaces as `StageCompileError`, naming the stage; the compiler's
  own text names the kernel.

Synthetic faults from `spark_tpu.testing.faults` carry their class on
the exception; real errors classify by message tokens, so both flow
through one path.
"""

from __future__ import annotations

import random
from enum import Enum
from typing import Optional


class FailureClass(Enum):
    TRANSIENT = "transient"
    TIMEOUT = "timeout"
    OOM = "oom"
    OVERFLOW = "overflow"
    CANCELLED = "cancelled"
    FATAL = "fatal"


class StageTimeoutError(RuntimeError):
    """A stage attempt exceeded spark_tpu.execution.stageTimeoutMs."""


class StageOOMError(RuntimeError):
    """RESOURCE_EXHAUSTED survived the whole degradation ladder; the
    message names the stage and its capacity stats."""


class StageCompileError(RuntimeError):
    """The device compiler refused a stage's program: a Pallas kernel
    over its scoped-VMEM limit, an unaligned slice, an op Mosaic does
    not lower. Deterministic, so never retried and never degraded; the
    message names the stage and carries the compiler's text."""


#: message tokens marking retryable infra flakes (gRPC channel errors);
#: DEADLINE_EXCEEDED is the runtime's own deadline, distinct from our
#: stage wall-clock TIMEOUT
_TRANSIENT_TOKENS = (
    "UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED",
    "Connection reset", "Socket closed", "connection attempt",
)

#: tokens of a program the device COMPILER refused. Checked before the
#: OOM tokens: a kernel over its VMEM limit reports RESOURCE_EXHAUSTED
#: "in memory space vmem", which the HBM ladder cannot cure. Exhausted
#: HBM ("memory space hbm", allocator messages) stays OOM.
_COMPILE_REFUSAL_TOKENS = (
    "memory space vmem", "memory space smem", "scoped vmem",
    "Mosaic", "tpu_custom_call",
)

_OOM_TOKENS = (
    "RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
    "Allocator ran out", "OOM while allocating",
)

#: tokens that mark a failure as coming from the COLLECTIVE path at
#: run/trace time — with meshFallback.enabled the executor re-plans
#: single-device. Deliberately narrow: a bare "mesh" token would also
#: swallow get_mesh's pre-dispatch misconfiguration diagnostic
#: ("mesh.size=N but only M devices visible"), silently degrading a
#: setup error the user needs to see.
_MESH_TOKENS = (
    "shard_map", "all_to_all", "all_gather", "collective", "axis_index",
    "NCCL",
)


def is_compile_refusal(exc: BaseException) -> bool:
    """True when the device compiler rejected the program itself (see
    `_COMPILE_REFUSAL_TOKENS`) — as opposed to the device running out
    of HBM, or the infrastructure flaking."""
    msg = f"{type(exc).__name__}: {exc}"
    return any(t in msg for t in _COMPILE_REFUSAL_TOKENS)


def classify(exc: BaseException) -> FailureClass:
    """Map an exception to its failure class. Synthetic faults classify
    by their carried class; real errors by message tokens."""
    from ..testing.faults import FaultInjected
    from .lifecycle import QueryCancelledError, QueryDeadlineError
    if isinstance(exc, (QueryCancelledError, QueryDeadlineError)):
        return FailureClass.CANCELLED
    if isinstance(exc, StageTimeoutError):
        return FailureClass.TIMEOUT
    if isinstance(exc, FaultInjected):
        if exc.fault == "resource_exhausted":
            return FailureClass.OOM
        if exc.fault in ("unavailable", "deadline"):
            return FailureClass.TRANSIENT
        return FailureClass.FATAL
    if isinstance(exc, MemoryError):
        return FailureClass.OOM
    if is_compile_refusal(exc):
        return FailureClass.FATAL
    msg = f"{type(exc).__name__}: {exc}"
    if any(t in msg for t in _OOM_TOKENS):
        return FailureClass.OOM
    if any(t in msg for t in _TRANSIENT_TOKENS):
        return FailureClass.TRANSIENT
    return FailureClass.FATAL


def is_mesh_failure(exc: BaseException) -> bool:
    """True when the failure points at the mesh/collective path (or a
    synthetic fault at the `mesh` / `mesh_checkpoint` / `decommission`
    sites — mesh_checkpoint models a host lost mid-stream at a
    snapshot point, decommission a drain that died at its boundary):
    the candidate set for the elastic recovery ladder (gang restart ->
    single-device fallback)."""
    from ..testing.faults import FaultInjected
    if isinstance(exc, FaultInjected):
        return exc.site in ("mesh", "mesh_checkpoint", "decommission")
    msg = f"{type(exc).__name__}: {exc}"
    return any(t in msg for t in _MESH_TOKENS)


class RetryPolicy:
    """One retry budget per query execution, shared by every failure
    class that retries (TRANSIENT and TIMEOUT): exponential backoff with
    jitter, the unified replacement for the ad-hoc fixed-count transient
    loop (spark.task.maxFailures seat).

    delay_n = backoff_ms * 2^n * uniform(0.5, 1.0)

    The default sleep is the INTERRUPTIBLE lifecycle wait
    (execution/lifecycle.py): a backoff wakes immediately when the
    query is cancelled and is capped by the remaining queryDeadlineMs
    budget — raising the structured lifecycle error instead of
    sleeping into a dead query. Pass an explicit `sleep` to opt out
    (tests that count slept milliseconds do).
    """

    def __init__(self, max_retries: int, backoff_ms: float,
                 sleep=None, rng: Optional[random.Random] = None):
        self.max_retries = max(0, int(max_retries))
        self.remaining = self.max_retries
        self.backoff_ms = max(0.0, float(backoff_ms))
        self.attempts = 0
        self.total_sleep_ms = 0.0
        self._sleep = sleep
        self._rng = rng or random.Random()

    def attempt_retry(self) -> Optional[float]:
        """Consume one retry and sleep the backoff. Returns the slept
        milliseconds, or None when the budget is exhausted (caller must
        surface the error). Raises the structured lifecycle error when
        the query was cancelled / deadlined — a retry of a dead query
        must not consume budget or sleep."""
        if self.remaining <= 0:
            return None
        from .lifecycle import checkpoint, sleep as _lc_sleep
        # cooperative boundary BEFORE paying the backoff: the chaos
        # matrix's retry-backoff delivery point
        checkpoint("retry_backoff")
        if self._sleep is None:
            self._sleep = _lc_sleep
        delay_ms = self.backoff_ms * (2 ** self.attempts)
        delay_ms *= 0.5 + self._rng.random() * 0.5
        if delay_ms > 0:
            self._sleep(delay_ms / 1e3)
        self.attempts += 1
        self.remaining -= 1
        self.total_sleep_ms += delay_ms
        return delay_ms
