"""Cross-query device resource arbiter + shared caches.

The `UnifiedMemoryManager.scala:49` analog for a process serving many
concurrent queries: ONE device (HBM) byte pool that every query leases
scan residency from, instead of each query consulting its own private
`spark_tpu.sql.memory.deviceBudget`. The pool is unified with the
device table cache (io/device_cache.py) the way the reference unifies
execution and storage memory: lease pressure first evicts cached
tables (storage), then denies the lease — and a denied lease routes
the query down the out-of-core spill/streaming paths it already has
(execution/external.py, streaming_agg partial spill), never a crash.
The PR-2 OOM ladder composes unchanged: its rung-2 overlay pins an
explicit 1-byte deviceBudget, which takes precedence over the arbiter
(a forced re-route must stay forced).

Also arbiter-owned, because they are process resources the way HBM is:

- the compiled-stage cache shared across every pooled session (stage
  keys are plan-describe + compile-relevant conf, bucket-aligned since
  PR 4, so cross-session hit rates are high — the Janino-cache seat);
- the plan-fingerprint result cache (`ResultCache`), promoting the
  per-session `_data_cache` dict behind `QueryExecution._apply_cache`
  to a size-bounded, thread-safe LRU (the CacheManager /
  InMemoryRelation seat).

Installation is process-level (`install_arbiter` / `get_arbiter`),
matching device_cache.CACHE: HBM is a process resource. The SQL
service installs one at startup from `spark_tpu.service.hbmBudget`;
without one, every legacy single-session code path is byte-identical.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from contextvars import ContextVar
from typing import Dict, Optional

DEVICE_BUDGET_KEY = "spark_tpu.sql.memory.deviceBudget"
HBM_BUDGET_KEY = "spark_tpu.service.hbmBudget"
RESULT_CACHE_BYTES_KEY = "spark_tpu.service.resultCacheBytes"
SESSION_HBM_SHARE_KEY = "spark_tpu.service.session.hbmShare"


class _Owner:
    """Identity of one query execution's leases (created per
    execute_batch / external collect via `enter_query`). `group` is
    the session identity (the app_id prefix of the executor's
    "app:qN" label) — the unit the per-session hbmShare quota
    aggregates leases over."""

    __slots__ = ("label", "group")

    def __init__(self, label: str = ""):
        self.label = label
        self.group = label.rsplit(":q", 1)[0] if ":q" in label else label


#: the owner of the query execution running in the current context;
#: set by the executor, read by the deep streaming/external gates
_OWNER: ContextVar[Optional[_Owner]] = ContextVar(
    "spark_tpu_arbiter_owner", default=None)


class DeviceResourceArbiter:
    """One shared HBM byte pool, leased per (query, scan).

    `try_acquire` is idempotent per (owner, key): the same scan is
    gate-checked from several sites along one execution (external
    collect, streaming splice, resident-preference), and they must all
    see one stable verdict. Denials are memoized per owner for the
    same reason — a lease freed mid-execution must not flip a query
    that already committed to the spill path back to resident.
    """

    def __init__(self, total_bytes: int, metrics=None,
                 result_cache_bytes: int = 0):
        self.total = int(total_bytes)
        self.metrics = metrics
        self._cv = threading.Condition()
        self._leases: Dict[_Owner, Dict[object, int]] = {}
        self._denied: Dict[_Owner, set] = {}
        #: device-cache keys each owner was admitted against as
        #: STORAGE: pinned in the cache so lease-pressure eviction
        #: can't reclaim bytes a running query still references
        self._pins: Dict[_Owner, set] = {}
        #: sessions-shared compiled-stage cache (the Janino-cache seat;
        #: pooled sessions all point their _stage_cache here).
        #: Deliberately unlocked (guarded-by waiver): dict get/set are
        #: GIL-atomic and keys are deterministic content hashes, so
        #: the worst concurrent-fill race is a duplicate compile whose
        #: last write wins with an equivalent value.
        self.stage_cache: Dict[str, object] = {}
        #: beside it, under the same keys and the same waiver: each
        #: stage's host record (ExecContext.host), written while the
        #: stage is traced and read after every dispatch
        self.stage_host: Dict[str, Dict[str, object]] = {}
        #: arbiter-owned plan-fingerprint result cache (pooled sessions
        #: all point their _data_cache here)
        self.result_cache = ResultCache(max_bytes=result_cache_bytes,
                                        metrics=metrics)

    # -- accounting ---------------------------------------------------------

    @property
    def leased_bytes(self) -> int:
        with self._cv:
            return self._leased_locked()

    def _leased_locked(self) -> int:
        return sum(sum(d.values()) for d in self._leases.values())

    def _storage_bytes(self) -> int:
        from ..io.device_cache import CACHE
        return CACHE.nbytes

    def headroom(self) -> int:
        with self._cv:
            return self.total - self._leased_locked() - self._storage_bytes()

    # -- leasing ------------------------------------------------------------

    def try_acquire(self, owner: Optional[_Owner], key, nbytes: int,
                    wait_ms: float = 0.0, share: float = 0.0) -> bool:
        """Lease `nbytes` of residency for (owner, key). Storage (the
        device table cache) is evicted LRU-first under pressure — the
        UnifiedMemoryManager storage-eviction move — then the request
        waits up to `wait_ms` for other queries to release, then is
        denied (the caller takes the out-of-core path).

        `share` (spark_tpu.service.session.hbmShare) caps ONE owner
        group's (= session's) total leases at share * pool: a lease
        that would push the session past its share is denied
        immediately (`session_quota_rejections`) — waiting could only
        succeed by the session releasing its own leases, which happens
        at query end, after this query already committed to a path.

        Lease waits are cancellable: with a lifecycle token installed
        the cv wait runs in deadline-capped slices and a
        cancelled/deadlined waiter raises the structured error out of
        the gate (the query is stopping — there is no path to route)."""
        from ..execution import lifecycle
        from ..io.device_cache import CACHE
        if owner is None:
            # no query scope (direct engine use with an arbiter
            # installed): grant against headroom without tracking —
            # there is no release point to hold a lease open for
            return nbytes <= self.headroom()
        deadline = time.monotonic() + wait_ms / 1e3
        group_cap = int(share * self.total) if share > 0 else 0
        with self._cv:
            held = self._leases.get(owner, {})
            if key in held:
                return True
            if key in self._denied.get(owner, ()):
                return False
            while True:
                if group_cap > 0:
                    group_leased = sum(
                        sum(d.values())
                        for o, d in self._leases.items()
                        if o.group == owner.group)
                    if group_leased + nbytes > group_cap:
                        self._denied.setdefault(owner, set()).add(key)
                        self._count("arbiter_lease_denied")
                        self._count("session_quota_rejections")
                        return False
                free = (self.total - self._leased_locked()
                        - self._storage_bytes())
                if nbytes <= free:
                    self._leases.setdefault(owner, {})[key] = int(nbytes)
                    self._count("arbiter_lease_granted")
                    self._gauges()
                    return True
                # queued eviction: shrink the storage pool before
                # denying execution memory
                freed = CACHE.evict_bytes(nbytes - free)
                if freed > 0:
                    self._count("arbiter_storage_evicted_bytes", freed)
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self._denied.setdefault(owner, set()).add(key)
                    self._count("arbiter_lease_denied")
                    return False
                self._cv.wait(lifecycle.wait_slice(remaining))
                lifecycle.checkpoint("lease_wait")

    def pin_storage(self, owner: Optional[_Owner], key) -> None:
        """Record that `owner` is executing against the CACHED copy of
        `key`: pin it so another query's lease pressure can't evict
        bytes this query still references (evicting them frees
        nothing — the live reference keeps the HBM held — while the
        accounting would credit them as free)."""
        from ..io.device_cache import CACHE
        if owner is None or key is None:
            return
        with self._cv:
            pins = self._pins.setdefault(owner, set())
            if key in pins:
                return
            if CACHE.pin(key):
                pins.add(key)

    def convert_lease_to_pin(self, owner: Optional[_Owner], key) -> None:
        """The owner's leased scan just landed in the device cache:
        its bytes now count as storage (headroom subtracts
        CACHE.nbytes), so keeping the lease would double-count — drop
        it and pin the cache entry for the rest of the execution."""
        from ..io.device_cache import CACHE
        if owner is None:
            return
        with self._cv:
            held = self._leases.get(owner)
            if not held or key not in held:
                return
            pins = self._pins.setdefault(owner, set())
            if key not in pins and not CACHE.pin(key):
                # the put was rejected (entry never landed in storage):
                # the batch is still live on device but NOT in
                # CACHE.nbytes, so the lease stays — dropping it would
                # credit phantom headroom
                return
            pins.add(key)
            del held[key]
            self._gauges()
            self._cv.notify_all()

    def release(self, owner: Optional[_Owner]) -> None:
        """Drop every lease, pin and denial memo the owner holds —
        called when its query execution ends or the OOM ladder
        re-plans."""
        from ..io.device_cache import CACHE
        if owner is None:
            return
        with self._cv:
            self._leases.pop(owner, None)
            self._denied.pop(owner, None)
            for key in self._pins.pop(owner, ()):
                CACHE.unpin(key)
            self._gauges()
            self._cv.notify_all()

    # -- observability ------------------------------------------------------

    def _count(self, name: str, n: int = 1) -> None:
        if self.metrics is not None:
            self.metrics.counter(name).inc(n)

    def _gauges(self) -> None:
        if self.metrics is not None:
            self.metrics.gauge("arbiter_leased_bytes").set(
                self._leased_locked())
            self.metrics.gauge("arbiter_total_bytes").set(self.total)

    def stats(self) -> Dict[str, int]:
        with self._cv:
            return {"total_bytes": self.total,
                    "leased_bytes": self._leased_locked(),
                    "owners": len(self._leases),
                    "headroom_bytes": (self.total - self._leased_locked()
                                       - self._storage_bytes())}


# ---------------------------------------------------------------------------
# Process-level installation (device_cache.CACHE discipline: HBM is a
# process resource)
# ---------------------------------------------------------------------------

_ARBITER: Optional[DeviceResourceArbiter] = None


def install_arbiter(arbiter: Optional[DeviceResourceArbiter]) -> None:
    global _ARBITER
    _ARBITER = arbiter


def get_arbiter() -> Optional[DeviceResourceArbiter]:
    return _ARBITER


# ---------------------------------------------------------------------------
# Query-scope plumbing (executor-facing)
# ---------------------------------------------------------------------------


#: token for a scope opened inside an enclosing scope: the outer owner
#: keeps the leases, so nested exit is a no-op. Without this, the
#: external-collect gate's exit would release the residency lease it
#: just granted BEFORE the resident execution it authorized runs —
#: and concurrent queries would each see full headroom.
_NESTED = ("nested-arbiter-scope",)


def enter_query(label: str = "") -> Optional[tuple]:
    """Open a lease scope for the query execution starting in this
    context. Returns an opaque token for `exit_query`, or None when no
    arbiter is installed (zero overhead on the legacy path). Re-entrant:
    a scope opened under an existing scope shares the outer owner, so
    leases live until the OUTERMOST exit (collect() opens that scope —
    residency granted at the external-collect gate must stay accounted
    while the resident execution runs)."""
    if _ARBITER is None:
        return None
    if _OWNER.get() is not None:
        return _NESTED
    owner = _Owner(label)
    return owner, _OWNER.set(owner)


def exit_query(token: Optional[tuple]) -> None:
    """Close a lease scope: release every lease it acquired (no-op for
    nested scopes — the outermost exit releases)."""
    if token is None or token is _NESTED:
        return
    owner, ctx_token = token
    _OWNER.reset(ctx_token)
    arb = _ARBITER
    if arb is not None:
        arb.release(owner)


def release_current() -> None:
    """Release the running query's leases without closing the scope —
    the OOM ladder calls this before a degraded re-plan so the retry's
    admit decisions start from a clean slate."""
    arb = _ARBITER
    owner = _OWNER.get()
    if arb is not None and owner is not None:
        arb.release(owner)


# ---------------------------------------------------------------------------
# Budget gates (the former per-query deviceBudget read sites call these)
# ---------------------------------------------------------------------------


def admit_scan_resident(conf, leaf, mesh) -> bool:
    """May this scan's working set stay device-resident? The ONE
    residency verdict consulted by every out-of-core gate (external
    collect, streaming partial spill, resident-preference). `mesh` is
    the one the scan would be laid over (`device_cache.scan_mesh`;
    None: one device), which its cache key names. Budget, pool and the
    cache's count are a chip's, so what is weighed and leased is what
    one chip must hold of the estimate (`device_cache.chip_share`):

    - explicit per-query deviceBudget (a test conf or the OOM ladder's
      rung-2 overlay) keeps legacy semantics: est <= budget, unknown
      est streams;
    - otherwise, with an arbiter installed, the query leases the
      estimated footprint from the shared pool (False = denied =
      spill/stream re-plan);
    - otherwise legacy: no budget configured = always resident.
    """
    from ..io.device_cache import (chip_share, estimated_scan_bytes,
                                   is_cached, scan_cache_key)
    budget = int(conf.get(DEVICE_BUDGET_KEY))
    arb = _ARBITER
    if budget > 0:
        est = chip_share(estimated_scan_bytes(leaf), mesh)
        return est is not None and est <= budget
    if arb is None:
        return True
    if is_cached(leaf, mesh):
        # already device-resident: its bytes count against the pool as
        # STORAGE (headroom subtracts CACHE.nbytes), so taking a lease
        # too would double-count — and evict the very table the query
        # is about to reuse. Pin it instead: lease pressure must not
        # evict bytes this execution still references.
        arb.pin_storage(_OWNER.get(), scan_cache_key(leaf, mesh))
        return True
    est = chip_share(estimated_scan_bytes(leaf), mesh)
    if est is None:
        return False  # unsizeable lease: stream it
    key = scan_cache_key(leaf, mesh) or ("scan", id(leaf))
    # per-session share quota: one session's leases are capped at
    # hbmShare * pool — over-share scans stream instead of pinning HBM
    share = float(conf.get(SESSION_HBM_SHARE_KEY))
    return arb.try_acquire(_OWNER.get(), key, est, share=share)


def note_scan_cached(key) -> None:
    """Hook from io/device_cache.load_scan: the scan keyed `key` just
    landed in the device cache. If the running query leased residency
    for it, convert the lease to a storage pin (no double-count)."""
    arb = _ARBITER
    if arb is not None:
        arb.convert_lease_to_pin(_OWNER.get(), key)


def out_of_core_active(conf) -> bool:
    """Whether ANY out-of-core budget discipline is in force — the
    cheap early gate executor._try_external_collect uses before doing
    plan-shape work."""
    return int(conf.get(DEVICE_BUDGET_KEY)) > 0 or _ARBITER is not None


# ---------------------------------------------------------------------------
# Plan-fingerprint result cache (the CacheManager seat, promoted from
# the per-session `_data_cache` dict)
# ---------------------------------------------------------------------------


class ResultCache:
    """Size-bounded, thread-safe LRU of materialized Arrow tables keyed
    by plan fingerprint. Drop-in for the former per-session dict (the
    subset of the mapping protocol `_apply_cache` and session cache
    bookkeeping use). `max_bytes=0` disables bounding."""

    def __init__(self, max_bytes: int = 0, metrics=None):
        self.max_bytes = int(max_bytes)
        self.metrics = metrics
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, tuple]" = OrderedDict()
        self._bytes = 0

    def get(self, fp, default=None):
        with self._lock:
            entry = self._entries.get(fp)
            if entry is None:
                return default
            self._entries.move_to_end(fp)
            return entry[0]

    def __contains__(self, fp) -> bool:
        with self._lock:
            return fp in self._entries

    def __setitem__(self, fp, table) -> None:
        nbytes = int(getattr(table, "nbytes", 0))
        with self._lock:
            old = self._entries.pop(fp, None)
            if old is not None:
                self._bytes -= old[1]
            if self.max_bytes > 0 and nbytes > self.max_bytes:
                self._count("result_cache_rejected")
                return  # larger than the whole bound: don't thrash
            self._entries[fp] = (table, nbytes)
            self._bytes += nbytes
            while self.max_bytes > 0 and self._bytes > self.max_bytes \
                    and len(self._entries) > 1:
                _, (_, evicted) = self._entries.popitem(last=False)
                self._bytes -= evicted
                self._count("result_cache_evictions")

    def pop(self, fp, default=None):
        with self._lock:
            entry = self._entries.pop(fp, None)
            if entry is None:
                return default
            self._bytes -= entry[1]
            return entry[0]

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def nbytes(self) -> int:
        with self._lock:
            return self._bytes

    def _count(self, name: str) -> None:
        if self.metrics is not None:
            self.metrics.counter(name).inc()
