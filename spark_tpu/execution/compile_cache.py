"""Persistent cross-process AOT compile cache for stage executables.

XLA compile time is the new Janino compile time (SURVEY §7): Spark's
`CodeGenerator` cache is process-local, and so was ours — the in-memory
`session._stage_cache` dies with its process, so every fresh process
(each bench round, each service restart, each preflight stage) re-paid
the full trace + lower + backend-compile cost for TPC-H/TPC-DS shapes
it had compiled hundreds of times before. This module is the
cross-process seat layered UNDER that cache:

- On an in-memory miss with `spark_tpu.sql.compileCache.enabled` on,
  `executor._compile_stage` compiles the stage through the AOT path
  (``jit(fn).lower(args).compile()``), serializes the executable
  (`jax.experimental.serialize_executable`) and writes it to
  `compileCache.dir` via the shared `state_store.fsync_replace`
  atomic-rename helper — a torn write can never shadow a good entry,
  and concurrent writers (two pooled sessions racing one key) are
  last-write-wins of equivalent bytes.
- On the next process's miss of the same key, the entry deserializes
  (`compile_cache_disk_hits`, a `deserialize` sub-span) instead of
  compiling: a warm serving process never jits a known shape twice.

**Keying.** Entries are named by a digest of the full stage key (plan
describe + compile-relevant conf via `conf_compile_suffix`, exactly the
in-memory key) PLUS an environment fingerprint (jax/jaxlib versions,
backend platform, device kind/count, mesh shape + device ids) PLUS the
call signature (input pytree structure *including aux data* + leaf
shape/dtype). The fingerprint makes a jaxlib upgrade or a drained gang
miss cleanly rather than load a stale executable. The signature guard
matters for correctness, not just shapes: `Column` pytree aux embeds
host DICTIONARIES, so an executable compiled over one dictionary-
encoded table must never serve a batch whose dictionaries differ —
`jax.jit` would retrace on the aux mismatch, and the load path
replicates exactly that discipline by requiring treedef equality
before dispatching a deserialized `Compiled`.

**Faults.** The `compile_cache_load` chaos seam fires inside the
guarded load of an existing entry: ANY failure there (corrupted /
truncated file, unpickle error, backend deserialize rejection, an
injected fault) logs a warning, counts `compile_cache_corrupt`, falls
back to a fresh compile and overwrites the bad entry — a damaged cache
can never fail a query.

**Bounds.** The directory is size-bounded (`compileCache.maxBytes`,
LRU by mtime — loads touch their entry); `manifest.jsonl` records
recently-seen stage keys for the warm-start replay
(`session.warmup()` / `SqlService.start()`), compacted in place.

**JAX's own cache** (`jax_compilation_cache_dir`, keyed on HLO +
compile options) is a separate thing placed once, at package import
(`spark_tpu/__init__.py`): a fingerprint or signature miss here that
re-lowers an unchanged HLO can still skip the backend compile there.

Concurrency: `CompileCache._lock` (registered `execution.compile_cache`
in the concurrency registry) serializes writes, eviction and manifest
maintenance within a process; cross-process safety is carried entirely
by the atomic renames + the tolerance of every read path to files
vanishing underneath it.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import threading
import time
import warnings
from typing import Dict, List, Optional, Tuple

ENABLED_KEY = "spark_tpu.sql.compileCache.enabled"
DIR_KEY = "spark_tpu.sql.compileCache.dir"
MAX_BYTES_KEY = "spark_tpu.sql.compileCache.maxBytes"
WARM_START_KEY = "spark_tpu.sql.compileCache.warmStart"

#: entry format version: bumped on any incompatible change to the
#: pickled entry dict, so an old-layout file reads as a clean miss
ENTRY_FORMAT = 1

#: manifest compaction: rewrite once the file passes the byte
#: threshold (an os.stat per append — never a full-file read on the
#: query path), keeping the newest _MANIFEST_MAX_LINES // 2 records
_MANIFEST_MAX_LINES = 4096
_MANIFEST_MAX_BYTES = 1 << 20


# ---------------------------------------------------------------------------
# Keying: environment fingerprint + call signature
# ---------------------------------------------------------------------------


def env_fingerprint(mesh=None) -> Dict:
    """What must match for a serialized executable to be loadable AND
    correct in this process: toolchain versions, backend, device kind
    and pool size — plus, for mesh stages, the exact gang shape and
    device ids (`shard_map` closes over the Mesh; a drained gang or a
    re-numbered pool must miss cleanly, the same reason
    `mesh.excludeDevices` rides conf_compile_suffix)."""
    import jax
    import jaxlib

    import spark_tpu

    devs = jax.devices()
    fp: Dict = {
        # the ENGINE version too: a spark_tpu upgrade whose kernel
        # semantics changed without touching describe() or any
        # compile-relevant conf must not serve a pre-upgrade
        # executable off a persistent volume — the same staleness
        # class as a jaxlib upgrade, one layer up
        "spark_tpu": getattr(spark_tpu, "__version__", "dev"),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "",
        "n_devices": len(devs),
    }
    if mesh is not None:
        fp["mesh_shape"] = tuple(int(x) for x in mesh.devices.shape)
        fp["mesh_devices"] = tuple(
            int(d.id) for d in mesh.devices.flat)
    return fp


def call_signature(args) -> Tuple:
    """(treedef, leaf avals) of the stage call: the treedef carries the
    pytree STRUCTURE + aux (column names, dtypes, dictionaries — the
    exact identity `jax.jit` retraces on), the aval tuple carries what
    treedefs do not (leaf shapes/dtypes, which a shape-specialized
    `Compiled` raises on). Both must match for dispatch."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, {}))
    avals = tuple(
        (tuple(getattr(leaf, "shape", ())),
         str(getattr(leaf, "dtype", type(leaf).__name__)))
        for leaf in leaves)
    return treedef, avals


def _sig_hash(sig: Tuple) -> str:
    """Content digest of a call signature for the entry filename.
    Pickle bytes of equal treedefs are content-deterministic across
    processes (proven by the cross-process test); a spurious mismatch
    would only cost a cache miss — the load path re-verifies equality
    before any dispatch."""
    treedef, avals = sig
    h = hashlib.sha256()
    try:
        h.update(pickle.dumps(treedef))
    except Exception:  # noqa: BLE001 — unpicklable aux: sig-less key
        h.update(repr(treedef).encode())
    h.update(repr(avals).encode())
    return h.hexdigest()


def _deserialize(entry: Dict):
    """Backend-load a validated entry's executable (the shared tail of
    the query-path load and the warm-start replay) onto the devices it
    was compiled for: its mesh's gang, or the default device for a
    single-device stage. `deserialize_and_load` would otherwise bind it
    to EVERY local device and dispatch fails with one argument list per
    device on any host that has more than one."""
    import jax
    from jax.experimental import serialize_executable as se

    mesh_ids = (entry.get("fingerprint") or {}).get("mesh_devices")
    if mesh_ids:
        by_id = {int(d.id): d for d in jax.devices()}
        devices = [by_id[i] for i in mesh_ids]
    else:
        devices = jax.devices()[:1]
    return se.deserialize_and_load(
        entry["payload"], entry["in_tree"], entry["out_tree"],
        execution_devices=devices)


def entry_hash(stage_key: str, fingerprint: Dict, sig: Tuple) -> str:
    h = hashlib.sha256()
    h.update(stage_key.encode())
    h.update(b"\x00")
    h.update(json.dumps(fingerprint, sort_keys=True,
                        default=str).encode())
    h.update(b"\x00")
    h.update(_sig_hash(sig).encode())
    return h.hexdigest()[:32]


# ---------------------------------------------------------------------------
# The signature-dispatching stage callable
# ---------------------------------------------------------------------------


class CachedStageFn:
    """Stage-cache value wrapping deserialized/AOT `Compiled` programs:
    dispatches to the Compiled whose signature matches the call, and
    falls back to a lazily-built `jax.jit` for any other signature
    (mirroring the plain-jit entry's retrace behavior — a Compiled is
    shape- and treedef-specialized, a jit is polymorphic).

    Instances live in the sessions-shared stage cache, so service
    threads race `add`/`_jit` — both are GIL-atomic stores whose worst
    case is a duplicate compile (waived in the concurrency registry,
    the `arbiter.stage_cache` precedent)."""

    def __init__(self, make_jit=None):
        #: thunk building the polymorphic jit fallback; warm-start
        #: installs entries builder-less and the executor binds one
        #: before first use (it owns the plan needed to build it)
        self._make_jit = make_jit
        self._jit = None
        #: [(treedef, avals, Compiled)] — tiny linear scan (a stage
        #: key almost always sees exactly one signature)
        self._compiled: List[Tuple] = []

    @property
    def has_builder(self) -> bool:
        return self._make_jit is not None

    def bind_builder(self, make_jit) -> None:
        """Attach the jit-fallback builder if none is bound yet. The
        thunk must close over the built stage callable (conf + plan),
        never the QueryExecution — wrappers outlive queries in the
        shared stage cache."""
        if self._make_jit is None:
            self._make_jit = make_jit

    def add(self, sig: Tuple, compiled) -> None:
        treedef, avals = sig
        if self.compiled_for_sig(sig) is None:
            self._compiled.append((treedef, avals, compiled))

    def compiled_for_sig(self, sig: Tuple):
        treedef, avals = sig
        for td, av, compiled in self._compiled:
            if av == avals and td == treedef:
                return compiled
        return None

    def compiled_for(self, args):
        return self.compiled_for_sig(call_signature(args))

    def _fallback(self):
        if self._jit is None:
            if self._make_jit is None:
                raise RuntimeError(
                    "CachedStageFn has no jit builder bound (warm-start "
                    "entry dispatched before the executor bound one)")
            self._jit = self._make_jit()
        return self._jit

    def __call__(self, *args):
        compiled = self.compiled_for(args)
        if compiled is not None:
            return compiled(*args)
        return self._fallback()(*args)

    def lower(self, *args):
        """AOT-lowering compatibility (xla_cost.analyze_jit consumes a
        `.lower`-bearing callable)."""
        return self._fallback().lower(*args)


# ---------------------------------------------------------------------------
# The on-disk cache
# ---------------------------------------------------------------------------


class CompileCache:
    """One cache directory: entry files `cc-<hash>.pkl`, a
    `manifest.jsonl` of recently-seen stage keys, LRU-by-mtime bounded
    at `max_bytes`."""

    def __init__(self, cache_dir: str, max_bytes: int):
        self.dir = cache_dir
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()

    # -- paths ---------------------------------------------------------------

    def _entry_path(self, ehash: str) -> str:
        return os.path.join(self.dir, f"cc-{ehash}.pkl")

    @property
    def _manifest_path(self) -> str:
        return os.path.join(self.dir, "manifest.jsonl")

    # -- load ----------------------------------------------------------------

    def load(self, stage_key: str, mesh, args, metrics=None):
        """Deserialize the entry for (stage key, env, call signature);
        None on miss. NEVER raises: a corrupt/truncated entry (or an
        injected `compile_cache_load` fault) warns, counts
        `compile_cache_corrupt`, deletes the bad file and reads as a
        miss — the caller's fresh compile then overwrites it."""
        from ..testing import faults

        fp = env_fingerprint(mesh)
        sig = call_signature(args)
        path = self._entry_path(entry_hash(stage_key, fp, sig))
        if not os.path.exists(path):
            if metrics is not None:
                metrics.counter("compile_cache_disk_misses").inc()
            return None
        t0 = time.perf_counter()
        try:
            # chaos seam: models the entry-load failure class (torn
            # write, truncated pickle, backend rejection) — fired
            # inside the guard so injected faults prove the fallback
            faults.fire("compile_cache_load")
            entry = self._read_entry(path, stage_key)
            treedef, avals = sig
            if (entry is None
                    or entry.get("fingerprint") != fp
                    or entry.get("avals") != avals
                    or entry["in_tree"] != treedef):
                # digest collision, stale layout, or another
                # environment/signature: clean miss
                if metrics is not None:
                    metrics.counter("compile_cache_disk_misses").inc()
                return None
            compiled = _deserialize(entry)
        except FileNotFoundError:
            # a concurrent process's LRU eviction won the race between
            # the exists() check and open(): a plain miss, NOT
            # corruption — routine eviction must not light up the
            # compile_cache_corrupt signal
            if metrics is not None:
                metrics.counter("compile_cache_disk_misses").inc()
            return None
        except Exception as e:  # noqa: BLE001 — never fail the query
            self._discard_corrupt(path, e, metrics,
                                  "recompiling and overwriting it")
            if metrics is not None:
                metrics.counter("compile_cache_disk_misses").inc()
            return None
        if metrics is not None:
            metrics.counter("compile_cache_disk_hits").inc()
            metrics.counter("compile_cache_deser_ms").inc(
                round((time.perf_counter() - t0) * 1e3, 3))
        return self._finish_load(path, stage_key, compiled)

    def _finish_load(self, path: str, stage_key: str, compiled):
        """LRU touch + manifest recency for a successful load."""
        # LRU recency: a loaded entry is fresh again
        with contextlib.suppress(OSError):
            os.utime(path)
        self._note_seen(stage_key, os.path.basename(path))
        return compiled

    def _read_entry(self, path: str, stage_key: str) -> Optional[Dict]:
        """Open + unpickle + format/stage-key validation — THE entry
        reader shared by the query-path load and the warm-start
        replay, so their validation can never drift. Returns None on
        a clean structural mismatch (stale layout, digest collision);
        raises on damage (caller routes to `_discard_corrupt`);
        FileNotFoundError propagates (concurrent eviction = skip)."""
        with open(path, "rb") as f:
            entry = pickle.load(f)
        if entry.get("format") != ENTRY_FORMAT \
                or entry.get("stage_key") != stage_key:
            return None
        return entry

    def _discard_corrupt(self, path: str, err, metrics,
                         followup: str) -> None:
        """ONE corrupt-entry policy for the query-path load AND the
        warm-start replay: warn, count `compile_cache_corrupt`, and
        DELETE the damaged file — so it is rewritten by the next
        fresh compile instead of re-warning on every consult (or
        every service restart) forever."""
        warnings.warn(
            f"compile cache entry {os.path.basename(path)} failed to "
            f"load ({type(err).__name__}: {err}); {followup}")
        if metrics is not None:
            metrics.counter("compile_cache_corrupt").inc()
        with contextlib.suppress(OSError):
            os.remove(path)

    # -- store ---------------------------------------------------------------

    def store(self, stage_key: str, mesh, args, compiled,
              metrics=None) -> bool:
        """Serialize + atomically publish one executable; False (with a
        warning) when the backend cannot serialize or the write fails —
        the query proceeds on the in-memory entry either way."""
        from jax.experimental import serialize_executable as se

        from .state_store import fsync_replace

        fp = env_fingerprint(mesh)
        sig = call_signature(args)
        treedef, avals = sig
        try:
            payload, in_tree, out_tree = se.serialize(compiled)
            blob = pickle.dumps({
                "format": ENTRY_FORMAT,
                "stage_key": stage_key,
                "fingerprint": fp,
                "avals": avals,
                "in_tree": in_tree,
                "out_tree": out_tree,
                "payload": payload,
                "ts": time.time(),
            })
        except Exception as e:  # noqa: BLE001 — backend w/o serialization
            warnings.warn(f"compile cache: executable not serializable "
                          f"({type(e).__name__}: {e}); entry skipped")
            return False
        path = self._entry_path(entry_hash(stage_key, fp, sig))
        try:
            with self._lock:
                os.makedirs(self.dir, exist_ok=True)
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "wb") as f:
                    f.write(blob)
                fsync_replace(tmp, path)
                self._manifest_append_locked(
                    stage_key, os.path.basename(path))
                self._evict_locked(keep=os.path.basename(path))
        except OSError as e:
            warnings.warn(f"compile cache write failed: {e}")
            return False
        if metrics is not None:
            metrics.counter("compile_cache_write_bytes").inc(len(blob))
        return True

    # -- bounds --------------------------------------------------------------

    def _entries_by_age(self) -> List[Tuple[float, int, str]]:
        """[(mtime, size, path)] oldest first over the cc-*.pkl
        entries. Files vanishing under a concurrent process's eviction
        are skipped."""
        out = []
        try:
            names = os.listdir(self.dir)
        except OSError:
            return out
        paths = [os.path.join(self.dir, n) for n in names
                 if n.startswith("cc-") and n.endswith(".pkl")]
        for path in paths:
            try:
                st = os.stat(path)
            except OSError:
                continue
            out.append((st.st_mtime, st.st_size, path))
        out.sort()
        return out

    def _evict_locked(self, keep: str = "") -> int:
        """LRU-by-mtime down to max_bytes; the just-written entry
        (`keep`) is never its own victim even when it alone exceeds
        the bound. Returns files removed."""
        if self.max_bytes <= 0:
            return 0
        entries = self._entries_by_age()
        total = sum(size for _, size, _ in entries)
        removed = 0
        for _, size, path in entries:
            if total <= self.max_bytes:
                break
            if os.path.basename(path) == keep:
                continue
            with contextlib.suppress(OSError):
                os.remove(path)
                removed += 1
                total -= size
        return removed

    def evict(self) -> int:
        with self._lock:
            return self._evict_locked()

    # -- manifest (warm-start replay) ----------------------------------------

    def _note_seen(self, stage_key: str, file_name: str) -> None:
        try:
            with self._lock:
                self._manifest_append_locked(stage_key, file_name)
        except OSError as e:
            warnings.warn(f"compile cache manifest append failed: {e}")

    def _manifest_append_locked(self, stage_key: str,
                                file_name: str) -> None:
        os.makedirs(self.dir, exist_ok=True)
        line = json.dumps({"file": file_name, "stage_key": stage_key,
                           "ts": round(time.time(), 3)})
        path = self._manifest_path
        with open(path, "a") as f:
            f.write(line + "\n")
        # bound the append-only log: rewrite keeping the newest record
        # per entry file (atomic swap — a concurrent reader sees the
        # old or the new manifest, never a torn one). The trigger is
        # an os.stat byte threshold — appends run on the query path
        # (every store and disk hit), so counting lines by reading
        # the whole file each time would tax exactly the hot path
        # the cache exists to speed up.
        try:
            if os.path.getsize(path) <= _MANIFEST_MAX_BYTES:
                return
        except OSError:
            return
        from .state_store import fsync_replace
        records = self._read_manifest()
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            # _read_manifest returns newest-first; the FILE must stay
            # chronological (oldest-first) — readers reverse it, so
            # writing newest-first here would invert every later read
            # and make compaction keep the stalest half
            for rec in reversed(records[:_MANIFEST_MAX_LINES // 2]):
                f.write(json.dumps(rec) + "\n")
        fsync_replace(tmp, path)

    def _read_manifest(self) -> List[Dict]:
        """Newest-first, unique per entry file; torn/garbage lines are
        skipped (the append is not atomic by design — losing the tail
        record costs a warm-start seed, never correctness)."""
        path = self._manifest_path
        try:
            with open(path) as f:
                lines = f.readlines()
        except OSError:
            return []
        seen = set()
        out = []
        for line in reversed(lines):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            name = rec.get("file")
            if not name or name in seen or "stage_key" not in rec:
                continue
            seen.add(name)
            out.append(rec)
        return out

    # -- warm start ----------------------------------------------------------

    def warm_start(self, stage_cache: Dict, metrics=None,
                   max_entries: int = 256) -> int:
        """Replay the manifest of recently-seen stage keys into an
        in-memory stage cache: deserialize each entry whose environment
        fingerprint matches this process and install a (builder-less)
        `CachedStageFn` under its stage key — a restarted serving
        process opens with a hot cache. Returns entries installed.
        Never raises; unloadable entries are skipped (corrupt ones
        counted, exactly like the query-path load)."""
        import jax

        base = env_fingerprint(None)
        device_ids = {int(d.id) for d in jax.devices()}
        installed = 0
        for rec in self._read_manifest():
            if installed >= max_entries:
                break
            skey = rec["stage_key"]
            existing = stage_cache.get(skey)
            if existing is not None \
                    and not isinstance(existing, CachedStageFn):
                continue  # a plain jit already serves this key
            path = os.path.join(self.dir, rec["file"])
            if not os.path.exists(path):
                continue
            try:
                entry = self._read_entry(path, skey)
                if entry is None:
                    continue
                if isinstance(existing, CachedStageFn) \
                        and existing.compiled_for_sig(
                            (entry["in_tree"], entry["avals"])) \
                        is not None:
                    continue  # this signature is already warm
                # compare BASE fields only; mesh entries additionally
                # require their gang's device ids to exist here
                efp = dict(entry.get("fingerprint") or {})
                efp.pop("mesh_shape", None)
                mesh_ids = efp.pop("mesh_devices", ())
                if efp != base:
                    continue  # other toolchain/backend: not ours
                if mesh_ids and not set(mesh_ids) <= device_ids:
                    continue  # gang over devices this process lacks
                compiled = _deserialize(entry)
            except FileNotFoundError:
                continue  # concurrent eviction: plain skip, not corrupt
            except Exception as e:  # noqa: BLE001 — skip, never raise
                self._discard_corrupt(
                    path, e, metrics,
                    "warm start skips it (the next fresh compile of "
                    "its stage rewrites the entry)")
                continue
            fn = stage_cache.get(skey)
            if not isinstance(fn, CachedStageFn):
                fn = CachedStageFn()
                stage_cache[skey] = fn
            fn.add((entry["in_tree"], entry["avals"]), compiled)
            installed += 1
            # LRU recency, exactly like the query-path load: a service
            # that only ever opens via warm start must not see its
            # hottest entries become the oldest-mtime eviction victims
            with contextlib.suppress(OSError):
                os.utime(path)
        if metrics is not None and installed:
            metrics.counter("compile_cache_warm_entries").inc(installed)
        return installed


# ---------------------------------------------------------------------------
# Conf-driven accessor + warm-start entry points
# ---------------------------------------------------------------------------

#: process-global instances per (abs dir, maxBytes). GIL-atomic dict
#: get/set (guarded-by waiver): a duplicate CompileCache for one dir is
#: equivalent — every write goes through atomic renames and every read
#: tolerates concurrent eviction, so two instances' locks merely guard
#: their own manifest/eviction bookkeeping.
_CACHES: Dict[Tuple[str, int], CompileCache] = {}


def get_cache(conf) -> Optional[CompileCache]:
    """The conf-selected CompileCache, or None when disabled (the
    default) or pointed at no directory."""
    if not bool(conf.get(ENABLED_KEY)):
        return None
    d = str(conf.get(DIR_KEY) or "").strip()
    if not d:
        return None
    # a relative dir resolves against the checkout, not the working
    # directory: every process of a deployment must find the same cache
    from .. import CHECKOUT
    key = (os.path.normpath(os.path.join(CHECKOUT, d)),
           int(conf.get(MAX_BYTES_KEY)))
    cc = _CACHES.get(key)
    if cc is None:
        cc = _CACHES[key] = CompileCache(*key)
    return cc


def warm_start(stage_cache: Dict, conf, metrics=None) -> int:
    """Module-level warm-start over the conf-selected cache (the
    `session.warmup()` / `SqlService.start()` entry point). 0 when the
    cache is disabled."""
    cc = get_cache(conf)
    if cc is None:
        return 0
    return cc.warm_start(stage_cache, metrics=metrics)
