"""Query lifecycle control: cooperative cancellation + end-to-end
deadlines.

The reference can KILL work: `SparkContext.cancelJobGroup` /
`cancelStage` propagate interrupts down to running tasks, and every
scheduler wait is interruptible, so a runaway query cannot hold the
cluster. An XLA engine has no task boundaries to interrupt — a
dispatched stage runs to completion — but it does own a set of HOST
boundaries: chunk loops, stage-attempt entries, retry backoffs,
admission-queue and arbiter-lease waits, streaming trigger
iterations. This module plants ONE cooperative token at those
boundaries:

- ``CancelToken`` — a thread-safe cancel flag plus an optional
  monotonic deadline (``spark_tpu.execution.queryDeadlineMs``). It is
  ContextVar-installed per query execution (the ShardStreamTelemetry
  pattern), so the deep drivers need no signature changes.
- ``checkpoint(where)`` — the boundary call: fires the ``cancel_point``
  chaos seam, then raises ``QueryCancelledError`` /
  ``QueryDeadlineError`` when the installed token says stop. Wired at
  chunk boundaries (ChunkRetrier), stage-attempt entry
  (_execute_recover), compile entry, scan ingest, retry-backoff entry
  (RetryPolicy), admission queue waits, arbiter lease waits, and the
  streaming trigger loop.
- ``sleep(seconds)`` — the interruptible replacement for every
  ``time.sleep`` on a cancellable path (RetryPolicy backoff, the
  ``slow`` chaos fault): wakes immediately on cancel, caps at the
  remaining deadline budget, and raises the structured error instead
  of returning into a dead query.
- ``wait_slice(remaining_s)`` — condition-variable wait capping: with
  a token installed, cv waits (admission queue, arbiter lease pool)
  wait in short slices bounded by the remaining deadline budget so
  cancellation lands within ~one poll interval instead of after
  queueTimeoutMs.

Both errors classify as ``FailureClass.CANCELLED``
(execution/failures.py): the recovery ladder re-raises them
immediately — a deadline blown mid-recovery stops the ladder, it does
not retry through it.

Token registry: ``enter_query_scope`` (called by the executor at every
execute_batch / collect entry) registers the token under
``(app_id, query_id)`` so ``session.cancel(query_id)`` can reach a
query running on another thread; the SQL service keeps its own map
keyed by service query id for ``DELETE /queries/<id>``. A nested
execution (scalar subquery, cached-subtree materialization) shares the
outer token, so cancelling the outer query stops its subqueries too.

The hard contract (chaos-proven by the cancel-point matrix in
tests/test_lifecycle.py): a cancelled/deadlined query releases every
resource it holds — arbiter leases drained, prefetch workers joined,
mesh/stream checkpoints left committed, no daemon outliving the query
— and an identical query run immediately after is byte-identical to an
uninterrupted run.
"""

from __future__ import annotations

import threading
import time
from contextvars import ContextVar
from typing import Dict, Optional, Tuple

from ..testing import faults

DEADLINE_KEY = "spark_tpu.execution.queryDeadlineMs"


class QueryCancelledError(RuntimeError):
    """The query was cancelled (session.cancel / DELETE /queries/<id>)
    and stopped at the next cooperative boundary."""

    code = "QUERY_CANCELLED"


class QueryDeadlineError(RuntimeError):
    """The query exceeded its end-to-end deadline
    (spark_tpu.execution.queryDeadlineMs). Distinct from the per-stage
    TIMEOUT class: a blown deadline stops the recovery ladder instead
    of retrying through it."""

    code = "QUERY_DEADLINE_EXCEEDED"


class CancelToken:
    """Thread-safe cancel flag + optional monotonic deadline. `cancel()`
    may be called from any thread (HTTP handler, another session);
    `check()` runs on the query thread at every cooperative boundary."""

    def __init__(self, deadline_ms: Optional[float] = None):
        self._event = threading.Event()
        # the events that `wait(on=)` calls are parked on right now:
        # cancel() sets them too. append / remove / the copy in
        # cancel() are each atomic, so no lock
        self._wakers: list = []
        self.deadline_ms = float(deadline_ms) if deadline_ms else None
        self.deadline = (time.monotonic() + self.deadline_ms / 1e3
                         if self.deadline_ms else None)

    def cancel(self) -> None:
        """Idempotent: the query stops at its next boundary; waiters
        parked in `wait()` wake immediately, on whichever event."""
        self._event.set()
        for ev in list(self._wakers):
            ev.set()

    @property
    def cancelled(self) -> bool:
        return self._event.is_set()

    def remaining_s(self) -> Optional[float]:
        """Deadline budget left (negative = blown); None = no deadline."""
        if self.deadline is None:
            return None
        return self.deadline - time.monotonic()

    def expired(self) -> bool:
        rem = self.remaining_s()
        return rem is not None and rem <= 0

    def check(self, where: str = "") -> None:
        """Raise the structured error when this query must stop."""
        at = f" at {where}" if where else ""
        if self._event.is_set():
            raise QueryCancelledError(f"query cancelled{at}")
        if self.expired():
            raise QueryDeadlineError(
                f"query exceeded queryDeadlineMs="
                f"{self.deadline_ms:g}{at}")

    def wait(self, seconds: float,
             on: Optional[threading.Event] = None) -> bool:
        """Interruptible bounded sleep: wakes on cancel, caps at the
        remaining deadline budget, raises on either. A capped wait
        raises QueryDeadlineError — the caller's full sleep would have
        outrun the budget, so sleeping the remainder then resuming
        work would just blow the deadline one boundary later.

        With `on`, the sleep is parked on the caller's event, which
        someone else sets (the device ending a dispatched stage) and a
        cancel sets too: True as soon as it is set, False where the
        slice passed and it is not: a wait for that event in slices of
        at most `seconds`, with the token checked after each."""
        s = max(0.0, float(seconds))
        rem = self.remaining_s()
        capped = rem is not None and rem < s
        if capped:
            s = max(rem, 0.0)
        ev = self._event if on is None else on
        if on is not None:
            self._wakers.append(on)
        try:
            # cancelled before `on` was listed: cancel() did not set it
            if s > 0 and not self._event.is_set():
                ev.wait(s)
        finally:
            if on is not None:
                self._wakers.remove(on)
        if self._event.is_set():
            raise QueryCancelledError("query cancelled during wait")
        if on is not None and on.is_set():
            return True
        if capped or self.expired():
            raise QueryDeadlineError(
                f"query exceeded queryDeadlineMs={self.deadline_ms:g} "
                f"during wait")
        return False


#: the token of the query execution running in the current context;
#: installed by the executor (or the SQL service, one layer out so
#: admission/session waits count against the deadline too)
_TOKEN: ContextVar[Optional[CancelToken]] = ContextVar(
    "spark_tpu_cancel_token", default=None)


def install(token: CancelToken):
    """Install `token` for the current context; returns the ContextVar
    reset token for `uninstall`."""
    return _TOKEN.set(token)


def uninstall(ctx_token) -> None:
    _TOKEN.reset(ctx_token)


def current_token() -> Optional[CancelToken]:
    return _TOKEN.get()


def checkpoint(where: str = "") -> None:
    """The cooperative boundary: fire the `cancel_point` chaos seam
    (the cancel-matrix delivery vehicle — a `cancel_point:cancel:n`
    rule cancels the installed token at the nth boundary), then raise
    if the installed token says stop. One None check when idle — cheap
    enough for chunk loops."""
    faults.fire("cancel_point")
    tok = _TOKEN.get()
    if tok is not None:
        tok.check(where)


def sleep(seconds: float) -> None:
    """Interruptible sleep for cancellable paths (RetryPolicy backoff,
    the `slow` chaos fault): plain time.sleep without a token."""
    tok = _TOKEN.get()
    if tok is None:
        time.sleep(seconds)
    else:
        tok.wait(seconds)


def wait_slice(remaining_s: Optional[float],
               poll_s: float = 0.05) -> Optional[float]:
    """Cap one condition-variable wait: without a token, the caller's
    own remaining timeout (None = wait forever); with one, a short
    poll slice additionally bounded by the remaining deadline budget,
    so the caller's wait loop re-runs `checkpoint()` within ~poll_s of
    a cancel and never sleeps past the deadline."""
    tok = _TOKEN.get()
    if tok is None:
        return remaining_s
    s = poll_s
    if remaining_s is not None:
        s = min(s, remaining_s)
    rem = tok.remaining_s()
    if rem is not None:
        s = min(s, max(rem, 0.0))
    return max(s, 1e-3)


# ---------------------------------------------------------------------------
# Token registry: session.cancel(query_id) -> the token of a query
# running on another thread
# ---------------------------------------------------------------------------

_TOKENS: Dict[Tuple[str, int], CancelToken] = {}
_TOKENS_LOCK = threading.Lock()


def enter_query_scope(app_id: str, query_id: int, conf):
    """Open the lifecycle scope for a query execution: install a fresh
    token (deadline armed from queryDeadlineMs) unless an outer scope —
    the SQL service, or an enclosing execution — already installed one,
    and register it for session.cancel. Returns an opaque scope for
    `exit_query_scope`."""
    tok = _TOKEN.get()
    created = None
    if tok is None:
        ms = float(conf.get(DEADLINE_KEY))
        tok = CancelToken(deadline_ms=ms if ms > 0 else None)
        created = _TOKEN.set(tok)
    key = (app_id, int(query_id))
    with _TOKENS_LOCK:
        # a nested scope under the same key (collect() wraps
        # execute_batch with the same query_id) must not claim the
        # registration: the OUTER scope's exit owns the pop, so the
        # query stays cancellable through the whole outer scope (e.g.
        # the result's device->host transfer after execute_batch)
        inserted = key not in _TOKENS
        if inserted:
            _TOKENS[key] = tok
    return (key, created, inserted)


def exit_query_scope(scope) -> None:
    if scope is None:
        return
    key, created, inserted = scope
    if inserted:
        with _TOKENS_LOCK:
            _TOKENS.pop(key, None)
    if created is not None:
        _TOKEN.reset(created)


def cancel(app_id: str, query_id: int) -> bool:
    """Cancel the identified running query (the session.cancel seat).
    Returns False when no such execution is registered (already
    finished, or never started)."""
    with _TOKENS_LOCK:
        tok = _TOKENS.get((app_id, int(query_id)))
    if tok is None:
        return False
    tok.cancel()
    return True


def cancel_current() -> None:
    """Cancel the token installed in this context — the `cancel` chaos
    fault's effect (testing/faults.py): the next checkpoint raises."""
    tok = _TOKEN.get()
    if tok is not None:
        tok.cancel()
