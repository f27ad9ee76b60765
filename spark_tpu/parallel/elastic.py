"""Elastic mesh recovery: gang restart, graceful decommission, and
straggler chunk rebalancing.

PR 5 left mesh failure a one-way door: `is_mesh_failure` -> permanent
single-device fallback, throwing away 7/8 of a v5e-8 gang's throughput
for the rest of the query. PR 8 shipped the DETECTION half (per-shard
telemetry + StragglerMonitor); this module ships the MITIGATION half —
the `BlockManagerDecommissioner`/task-speculation seats (SURVEY §5,
§2.5) re-thought for gang SPMD, where there are no independent task
attempts to relaunch:

- **Gang restart** (`ElasticMeshState`): on a mesh/collective failure
  the executor no longer degrades straight to single-device — it
  re-executes the query still mesh-planned, up to
  `spark_tpu.execution.meshRestart.maxRestarts` times with the
  existing exponential-backoff RetryPolicy. The mesh streaming driver
  finds its own surviving checkpoint (execution/recovery.py) and
  resumes at the checkpointed chunk cursor ON THE MESH, so a
  kill-one-host mid-stream replays at most `checkpoint.everyChunks`
  chunks. Single-device fallback becomes the FINAL rung, not the
  first. The `mesh_restart` chaos seam fires at each restart boundary:
  a fault injected there fails that attempt (budget consumed) and the
  ladder falls through — ultimately to the single-device rung.
- **Graceful decommission** (`MeshDecommissionRequest` +
  `pending_decommission`): `spark_tpu.execution.decommission.shards`
  (or `session.decommission_shards([...])`) requests a drain; the mesh
  chunk driver honors it at the next chunk boundary — forces a
  checkpoint at the current cursor, fires the `decommission` seam, and
  raises the request. The executor excludes the draining shards'
  devices at SESSION level (`spark_tpu.sql.mesh.excludeDevices`, so
  the drain outlives this query), clears the request, and re-executes
  on the reduced gang, which resumes from the forced checkpoint — the
  `BlockManagerDecommissioner:39` analog.
- **Straggler rebalancing** (`RebalanceState` + `ElasticRebalancer`):
  a built-in `on_straggler` bus consumer closes the detect->act loop.
  When the StragglerMonitor flags a shard mid-stream, subsequent
  chunks re-assign live rows AWAY from the flagged shard (its share
  drops by `spark_tpu.sql.straggler.rebalance.maxSkew`, spread over
  the healthy shards) — the moral analog of speculation: the gang
  still steps together, but the slow device steps over fewer rows.
  Assignment is pure data movement inside the (slightly re-padded)
  chunk; per-shard SLOT capacity stays uniform so XLA re-specializes
  at most once per weight change. Results are identical for
  integer/decimal aggregates (partial aggregation is row-assignment
  independent); float aggregates may differ in the last ulp, exactly
  as any change of mesh size or chunk boundaries already does
  (summation order moves).

All three flow through `_record_fault` -> fault_summary -> event
log/history/`GET /queries/<id>/timeline` as the actions
`mesh_restart`, `decommission`, `shard_rebalance`; the registry counts
`mesh_restart_attempts` and `rebalance_rows`.
"""

from __future__ import annotations

import contextlib
import warnings
from contextvars import ContextVar
from typing import Dict, Optional, Sequence, Set, Tuple

from ..observability.listener import QueryListener

RESTART_ENABLED_KEY = "spark_tpu.execution.meshRestart.enabled"
RESTART_MAX_KEY = "spark_tpu.execution.meshRestart.maxRestarts"
DECOMMISSION_KEY = "spark_tpu.execution.decommission.shards"
EXCLUDE_KEY = "spark_tpu.sql.mesh.excludeDevices"
REBALANCE_ENABLED_KEY = "spark_tpu.sql.straggler.rebalance.enabled"
REBALANCE_MAX_SKEW_KEY = "spark_tpu.sql.straggler.rebalance.maxSkew"
REBALANCE_DECAY_KEY = "spark_tpu.sql.straggler.rebalance.decayChunks"
BACKOFF_KEY = "spark_tpu.execution.backoffMs"


def _parse_int_set(spec, warn: bool = True) -> Set[int]:
    """Comma-separated ints -> set. `warn=False` for per-chunk hot-path
    callers (pending_decommission): toggling process-global warning
    filters there would race the concurrent SQL service's threads, so
    those callers parse silently and one coherent warning fires per
    query instead (discard_stale_decommission)."""
    out: Set[int] = set()
    for part in str(spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        try:
            out.add(int(part))
        except ValueError:
            if warn:
                warnings.warn(f"ignoring non-integer entry {part!r} in "
                              f"shard/device list {spec!r}")
    return out


# ---------------------------------------------------------------------------
# Gang restart
# ---------------------------------------------------------------------------

class ElasticMeshState:
    """Per-query-execution gang-restart budget: N bounded restart
    attempts with exponential backoff (the existing RetryPolicy), each
    gated by the `mesh_restart` chaos seam. Created fresh by every
    `execute_batch`, so the budget is per execution like every other
    recovery budget."""

    def __init__(self, conf):
        from ..execution.failures import RetryPolicy
        self.enabled = bool(conf.get(RESTART_ENABLED_KEY))
        self.max_restarts = int(conf.get(RESTART_MAX_KEY))
        self.policy = RetryPolicy(self.max_restarts,
                                  float(conf.get(BACKOFF_KEY)))
        #: restart attempts that passed their seam (i.e. were applied)
        self.restarts = 0

    def try_restart(self, record) -> Optional[float]:
        """Consume restart attempts until one passes its chaos seam or
        the budget runs out. Returns the backoff slept (ms) for the
        attempt that will be applied, or None when the ladder must fall
        through to the single-device rung. A fault injected at the
        `mesh_restart` seam fails THAT attempt — recorded with
        ok=False, budget consumed — proving the ladder still lands on
        single-device fallback when restarts keep dying."""
        from ..testing import faults
        if not self.enabled:
            return None
        while True:
            slept = self.policy.attempt_retry()
            if slept is None:
                return None
            try:
                # chaos seam: the restart boundary (host-side, once per
                # attempt) — models the re-admitted host dying again
                faults.fire("mesh_restart")
            except Exception as e:  # noqa: BLE001 — attempt failed
                record("mesh_restart", e, attempt=self.policy.attempts,
                       ok=False)
                continue
            self.restarts += 1
            return slept


def healthy_device_count(conf) -> Optional[int]:
    """Devices currently visible and not decommissioned — the pool a
    gang restart may re-mesh over. None when the backend cannot even
    enumerate (the restart then keeps the configured size and lets the
    next attempt classify whatever happens)."""
    try:
        import jax
        from .mesh import excluded_device_ids
        excl = excluded_device_ids(conf)
        return len([d for d in jax.devices() if d.id not in excl])
    except Exception:  # noqa: BLE001 — probing must never raise
        return None


# ---------------------------------------------------------------------------
# Graceful decommission
# ---------------------------------------------------------------------------

class MeshDecommissionRequest(Exception):
    """Control-flow signal, not a failure: a drain request reached a
    chunk boundary of a running mesh stream. Carries the draining mesh
    positions and their device ids; the executor applies the exclusion
    at session level and re-executes on the reduced gang."""

    def __init__(self, shards: Sequence[int], device_ids: Sequence[int]):
        super().__init__(
            f"decommission requested for shard(s) {sorted(shards)} "
            f"(device ids {sorted(device_ids)})")
        self.shards = tuple(shards)
        self.device_ids = tuple(device_ids)


def pending_decommission(conf, mesh) -> Tuple[Tuple[int, ...],
                                              Tuple[int, ...]]:
    """The drain request's (mesh positions, device ids) valid for the
    CURRENT mesh — empty tuples when nothing is pending. Positions
    outside [0, n) are ignored (a request naming an already-drained
    position must not re-fire forever)."""
    spec = str(conf.get(DECOMMISSION_KEY) or "").strip()
    if not spec:
        return (), ()
    n = int(mesh.devices.size)
    # silent parse: this runs at every chunk boundary, and parse noise
    # is handled ONCE per query by discard_stale_decommission
    requested = _parse_int_set(spec, warn=False)
    positions = sorted(p for p in requested if 0 <= p < n)
    if not positions:
        return (), ()
    devs = list(mesh.devices.flat)
    ids = tuple(int(getattr(devs[p], "id", p)) for p in positions)
    return tuple(positions), ids


def discard_stale_decommission(session_conf, mesh) -> None:
    """Drop a drain request with NO position valid for the gang about
    to run (e.g. `decommission_shards([9])` on an 8-gang): left armed,
    the stale request would silently fire months later the first time
    a LARGER mesh makes the position valid. Called by the executor at
    mesh-query start; a partially-valid request is kept (its valid
    positions still drain)."""
    spec = str(session_conf.get(DECOMMISSION_KEY) or "").strip()
    if not spec:
        return
    n = int(mesh.devices.size)
    requested = _parse_int_set(spec, warn=False)  # re-warned below
    if not requested:
        # nothing parseable at all: the request could never fire, and
        # left armed it would re-warn at every chunk boundary forever
        warnings.warn(
            f"discarding unparseable decommission request {spec!r}")
        session_conf.set(DECOMMISSION_KEY, "")
    elif not any(0 <= p < n for p in requested):
        warnings.warn(
            f"discarding stale decommission request {spec!r}: no "
            f"requested position is valid for the {n}-shard gang")
        session_conf.set(DECOMMISSION_KEY, "")


def apply_decommission(session_conf, device_ids: Sequence[int]) -> None:
    """Persist a drain: merge the device ids into the SESSION-level
    exclusion set (the decommission outlives this query — get_mesh
    builds every later gang over the surviving pool), clear the
    one-shot request key, and follow mesh.size down to the surviving
    pool so PLANNING (join-strategy and exchange sizing divide by n)
    agrees with the gang that will actually run — for this query's
    re-execution and every later one."""
    merged = _parse_int_set(session_conf.get(EXCLUDE_KEY)) \
        | set(int(i) for i in device_ids)
    session_conf.set(EXCLUDE_KEY, ",".join(str(i) for i in sorted(merged)))
    session_conf.set(DECOMMISSION_KEY, "")
    try:
        import jax
        pool = len([d for d in jax.devices()
                    if int(getattr(d, "id", -1)) not in merged])
    except Exception:  # noqa: BLE001 — probing must never fail a drain
        return
    n = int(session_conf.get("spark_tpu.sql.mesh.size") or 0)
    if n > 1 and pool < n:
        session_conf.set("spark_tpu.sql.mesh.size", max(pool, 0))


def decommission_shards(session, shards: Sequence[int]) -> None:
    """The drain API: request a graceful decommission of the given mesh
    positions. A running mesh stream drains at its next chunk boundary
    (checkpoint forced, `decommission` recorded); otherwise the next
    mesh query applies it at its first boundary. MERGES with any
    still-pending request — back-to-back drains of different shards
    must not silently drop the earlier one."""
    pending = _parse_int_set(session.conf.get(DECOMMISSION_KEY))
    merged = pending | {int(s) for s in shards}
    session.conf.set(DECOMMISSION_KEY,
                     ",".join(str(s) for s in sorted(merged)))


# ---------------------------------------------------------------------------
# Straggler chunk rebalancing
# ---------------------------------------------------------------------------

#: the mesh chunk driver installs its live rebalance state here for the
#: duration of its chunk loop; the ElasticRebalancer bus listener
#: (on_straggler fires synchronously on the driver thread, inside the
#: telemetry flush) flags shards into it — the same context-threading
#: pattern as ShardStreamTelemetry, so driver signatures stay stable
_REBALANCE: ContextVar[Optional["RebalanceState"]] = \
    ContextVar("spark_tpu_rebalance", default=None)


def current_rebalance() -> Optional["RebalanceState"]:
    return _REBALANCE.get()


@contextlib.contextmanager
def use_rebalance(state: Optional["RebalanceState"]):
    token = _REBALANCE.set(state)
    try:
        yield state
    finally:
        _REBALANCE.reset(token)


class RebalanceState:
    """Per-stream chunk-row assignment weights over the mesh axis.

    Until a shard is flagged the state is inert and padding takes the
    zero-cost `pad_batch_to_multiple` path. After `flag(shard)`, each
    chunk's live rows are re-assigned: the flagged shard's share drops
    to (1 - maxSkew) x fair, the deficit spreads evenly over healthy
    shards. With `straggler.rebalance.decayChunks` > 0 the penalty is
    not a life sentence: each rebalanced chunk fades every flagged
    shard's penalty linearly by 1/decayChunks, so a recovered shard
    earns its fair share back over that many healthy chunks and the
    state goes inert again (shares return to uniform; 0 keeps the
    legacy stay-flagged-forever behavior). Per-shard slot capacity is
    uniform and sized from the FULL-penalty trajectory (not the
    decayed weights), so shapes stay stable across the whole decay
    and the jitted update step re-specializes at most once per flag. Partial aggregation does not depend on which
    shard folds which row — integer/decimal results are bit-exact;
    float sums can move in the last ulp (summation order), as with
    any mesh-size or chunk-boundary change."""

    def __init__(self, n: int, conf, recovery=None):
        self.n = int(n)
        self.enabled = bool(conf.get(REBALANCE_ENABLED_KEY))
        self.max_skew = float(conf.get(REBALANCE_MAX_SKEW_KEY))
        self.recovery = recovery  # RecoveryContext: record() + metrics
        self.decay_chunks = int(conf.get(REBALANCE_DECAY_KEY))
        self.slow: Set[int] = set()
        #: shard -> remaining penalty in (0, 1]; 1.0 at flag time,
        #: fading by 1/decayChunks per rebalanced chunk (tick())
        self.penalty: Dict[int, float] = {}
        self.moved_rows = 0

    @property
    def active(self) -> bool:
        return bool(self.slow)

    def flag(self, shard: int) -> None:
        """Mark one shard slow (idempotent). Called by the
        ElasticRebalancer when the StragglerMonitor posts
        on_straggler; records ONE `shard_rebalance` action per shard."""
        shard = int(shard)
        if not self.enabled or self.max_skew <= 0:
            return
        if shard in self.slow:
            self.penalty[shard] = 1.0  # re-flag mid-decay: full again
            return
        if not 0 <= shard < self.n:
            return
        if len(self.slow) >= self.n - 1:
            return  # at least one healthy shard must absorb the skew
        self.slow.add(shard)
        self.penalty[shard] = 1.0
        if self.recovery is not None:
            self.recovery.record("shard_rebalance", None, shard=shard,
                                 max_skew=self.max_skew)

    # -- assignment math ----------------------------------------------------

    def _weights(self, decayed: bool = True):
        """Per-shard assignment weights. `decayed=True` scales each
        flagged shard's skew by its remaining penalty (the live
        assignment); `decayed=False` is the full-penalty trajectory
        slot_capacity sizes shapes from, stable across a decay."""
        import numpy as np
        w = np.ones(self.n)
        z = len(self.slow)
        if z and z < self.n:
            deficit = 0.0
            for i in self.slow:
                p = self.penalty.get(i, 1.0) if decayed else 1.0
                w[i] = 1.0 - self.max_skew * p
                deficit += self.max_skew * p
            boost = deficit / (self.n - z)
            for i in range(self.n):
                if i not in self.slow:
                    w[i] = 1.0 + boost
        return w

    def targets(self, live: int):
        """Per-shard live-row assignment for one chunk (sums to
        `live` exactly — largest-remainder rounding)."""
        import numpy as np
        raw = live * self._weights() / self.n
        t = np.floor(raw).astype(np.int64)
        for i in np.argsort(-(raw - t), kind="stable")[:live - t.sum()]:
            t[i] += 1
        return t

    def slot_capacity(self, chunk_capacity: int) -> int:
        """Uniform per-shard slot count: covers the worst-case target
        of a fully-live chunk (+1 rounding margin), constant while the
        flag set is stable so shapes stay stable."""
        import numpy as np
        wmax = float(np.max(self._weights(decayed=False)))
        return int(-(-int(chunk_capacity) * wmax // self.n)) + 1

    def rebalance(self, batch, n: int):
        """Re-assign one chunk's live rows to shard segments by the
        current weights. Pays one host pull of the selection mask per
        chunk — only on the mitigation path (state active), where the
        straggler's stall already dwarfs it."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from ..columnar import Batch, Column
        mask = np.asarray(jax.device_get(batch.selection_mask()))
        live_idx = np.flatnonzero(mask)
        live = int(live_idx.size)
        t = self.targets(live)
        s_cap = self.slot_capacity(batch.capacity)
        take = np.zeros(s_cap * n, np.int64)
        sel = np.zeros(s_cap * n, bool)
        off = 0
        for i in range(n):
            k = int(t[i])
            seg = i * s_cap
            take[seg:seg + k] = live_idx[off:off + k]
            sel[seg:seg + k] = True
            off += k
        # accounting: rows shifted OFF the flagged shards vs the even
        # split (the `rebalance_rows` counter)
        fair = live // n
        moved = sum(max(0, fair - int(t[i])) for i in self.slow)
        self.moved_rows += moved
        if self.recovery is not None and self.recovery.metrics is not None \
                and moved:
            self.recovery.metrics.counter("rebalance_rows").inc(moved)
        self.tick()
        take_d = jnp.asarray(take)
        cols = {}
        for name, c in batch.columns.items():
            data = jnp.take(c.data, take_d, axis=0)
            validity = None if c.validity is None \
                else jnp.take(c.validity, take_d, axis=0)
            cols[name] = Column(data, c.dtype, validity, c.dictionary)
        return Batch(cols, jnp.asarray(sel))

    def tick(self) -> None:
        """One rebalanced chunk elapsed: fade every flagged shard's
        penalty by 1/decayChunks; a shard whose penalty reaches zero
        unflags — when the last one does, `active` goes False and
        padding returns to the zero-cost path (shares uniform
        again)."""
        if self.decay_chunks <= 0:
            return
        step = 1.0 / self.decay_chunks
        for shard in sorted(self.slow):
            p = self.penalty.get(shard, 1.0) - step
            if p > 1e-12:
                self.penalty[shard] = p
            else:
                self.slow.discard(shard)
                self.penalty.pop(shard, None)


def pad_chunk_for_shards(batch, n: int,
                         state: Optional[RebalanceState] = None):
    """The mesh chunk driver's padding step: the plain
    `pad_batch_to_multiple` until a straggler was flagged, the skewed
    re-assignment afterwards."""
    from .shuffle import pad_batch_to_multiple
    if state is None or not state.active:
        return pad_batch_to_multiple(batch, n)
    return state.rebalance(batch, n)


class ElasticRebalancer(QueryListener):
    """Built-in bus subscriber closing the straggler detect->act loop:
    on_straggler (posted synchronously by the StragglerMonitor from the
    telemetry flush, on the driver thread mid-stream) flags the shard
    into the stream's live RebalanceState, so the NEXT chunk's rows
    already skew away from it. Stateless — the per-stream state lives
    in the context var, scoped to exactly the executing stream."""

    _builtin = True

    def on_straggler(self, event) -> None:
        state = current_rebalance()
        if state is not None:
            state.flag(int(event.shard))
