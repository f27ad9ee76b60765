"""Persistent cross-process AOT compile cache (execution/compile_cache.py).

Covers the ISSUE-14 acceptance surface: cross-process reuse proven
with a real subprocess (disk-hit counter + byte parity vs the cold
run), environment-fingerprint invalidation (an altered version string
misses cleanly, never crashes), maxBytes LRU eviction, corrupt-entry
chaos parity through the `compile_cache_load` seam, concurrent pooled
writers racing one key under lockwatch, and the warm-start surfaces
(`session.warmup()` / `SqlService.start()`).
"""

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pandas as pd
import pytest

from spark_tpu.execution import compile_cache as CC
from spark_tpu.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _enable(session, base: str) -> str:
    cc_dir = os.path.join(base, "cc")
    session.conf.set(CC.ENABLED_KEY, True)
    session.conf.set(CC.DIR_KEY, cc_dir)
    # the fixture session's IN-MEMORY stage cache persists across
    # tests: clear it so this test's "cold" run actually consults
    # (and fills) its own fresh on-disk cache dir
    session._stage_cache.clear()
    return cc_dir


def _counter(session, name: str) -> float:
    return session.metrics.counter(name).value


def _query(session, domain: int = 64):
    from spark_tpu import functions as F
    from spark_tpu.functions import col
    return (session.range(1 << 12)
            .select(F.pmod(col("id"), domain).alias("k"))
            .group_by(col("k")).agg(F.sum(col("k")).alias("s"))
            .order_by(col("k")))


def _entry_files(cc_dir: str):
    if not os.path.isdir(cc_dir):
        return []
    return sorted(f for f in os.listdir(cc_dir)
                  if f.startswith("cc-") and f.endswith(".pkl"))


# ---------------------------------------------------------------------------
# units
# ---------------------------------------------------------------------------


def test_get_cache_disabled_by_default(session):
    from spark_tpu.config import Conf
    assert CC.get_cache(Conf()) is None
    c = Conf()
    c.set(CC.ENABLED_KEY, True)
    c.set(CC.DIR_KEY, "")
    assert CC.get_cache(c) is None  # no directory = no cache


def test_env_fingerprint_fields():
    fp = CC.env_fingerprint()
    for field in ("spark_tpu", "jax", "jaxlib", "backend",
                  "device_kind", "n_devices"):
        assert field in fp, fp
    assert "mesh_devices" not in fp

    class _Dev:
        def __init__(self, i):
            self.id = i

    class _Mesh:
        devices = np.array([_Dev(0), _Dev(1)])

    fpm = CC.env_fingerprint(_Mesh())
    assert fpm["mesh_shape"] == (2,) and fpm["mesh_devices"] == (0, 1)
    # a different gang over the same base environment keys differently
    assert CC.entry_hash("k", fp, ((), ())) \
        != CC.entry_hash("k", fpm, ((), ()))


def test_call_signature_distinguishes_dictionaries():
    """Column pytree aux embeds host dictionaries: two batches equal in
    shape but differing in dictionary CONTENT must sign differently —
    a deserialized Compiled whose out_tree carries trace-time
    dictionaries would silently decode wrong strings otherwise (the
    exact reason dispatch requires treedef equality, like jit)."""
    import pyarrow as pa

    from spark_tpu.columnar import Batch
    t1 = pa.table({"s": pa.array(["a", "b", "a"]).dictionary_encode()})
    t2 = pa.table({"s": pa.array(["a", "Z", "a"]).dictionary_encode()})
    b1, b2 = Batch.from_arrow(t1), Batch.from_arrow(t2)
    sig1, sig2 = CC.call_signature(([b1],)), CC.call_signature(([b2],))
    assert sig1[1] == sig2[1]          # same leaf shapes/dtypes
    assert sig1[0] != sig2[0]          # different treedef aux
    same = CC.call_signature(
        ([Batch.from_arrow(pa.table(
            {"s": pa.array(["a", "b", "a"]).dictionary_encode()}))],))
    assert same == sig1


@pytest.mark.parametrize("n_mesh", [0, 4])
def test_entry_loads_onto_the_devices_it_was_compiled_for(tmp_path,
                                                          n_mesh):
    """With 8 devices visible, a stored executable loads onto its own
    devices — one for a single-device stage, the gang for a mesh stage
    — and runs. Loaded onto every local device (the library's default)
    it failed at dispatch with "8 argument lists when local device
    count is 1"; a one-device host hides that."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    assert len(jax.devices()) == 8
    x = jnp.arange(8, dtype=jnp.int64)
    mesh = None
    if n_mesh:
        mesh = Mesh(np.array(jax.devices()[2:2 + n_mesh]), ("data",))
        x = jax.device_put(x, NamedSharding(mesh, PartitionSpec("data")))
    compiled = jax.jit(lambda a: a * 2 + 1).lower(x).compile()
    cc = CC.CompileCache(str(tmp_path), 0)
    assert cc.store("k", mesh, (x,), compiled)
    loaded = cc.load("k", mesh, (x,))
    assert loaded is not None
    out = loaded(x)
    assert np.array_equal(np.asarray(out), np.arange(8) * 2 + 1)
    want = {d.id for d in mesh.devices.flat} if n_mesh \
        else {jax.devices()[0].id}
    assert {d.id for d in out.sharding.device_set} == want


def test_jax_cache_is_placed_once_and_only_by_the_package(tmp_path):
    """The cache rule, in fresh interpreters (the directory is decided
    at package import). JAX_COMPILATION_CACHE_DIR set: JAX reads it and
    no code path of the engine — import, a session, the AOT cache, a
    query — changes `jax_compilation_cache_dir`. Unset: it is
    <checkout>/.jax_cache whatever the working directory."""
    probe = (
        "import json, os, jax\n"
        "seen = [jax.config.jax_compilation_cache_dir]\n"
        "import spark_tpu\n"
        "seen.append(jax.config.jax_compilation_cache_dir)\n"
        "from spark_tpu.execution import compile_cache as CC\n"
        "s = spark_tpu.SparkTpuSession.builder().get_or_create()\n"
        "s.range(64).count()\n"
        "s.conf.set(CC.ENABLED_KEY, True)\n"
        "s.conf.set(CC.DIR_KEY, 'rel-cc')\n"
        "cc = CC.get_cache(s.conf)  # resolves the dir, writes nothing\n"
        "seen.append(jax.config.jax_compilation_cache_dir)\n"
        "print(json.dumps({'seen': seen, 'aot': cc.dir,\n"
        "                  'checkout': spark_tpu.CHECKOUT}))\n")

    def run(cwd, **env_extra):
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.update(env_extra)
        proc = subprocess.run([sys.executable, "-c", probe], cwd=cwd,
                              env=env, capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    fixed = os.path.join(REPO, ".jax_cache")
    for cwd in (str(tmp_path), REPO):
        got = run(cwd)
        assert got["checkout"] == REPO
        assert got["seen"] == [None, fixed, fixed], got
        # the engine's own AOT cache resolves against the checkout too
        assert got["aot"] == os.path.join(REPO, "rel-cc"), got
    placed = str(tmp_path / "operator-cache")
    got = run(str(tmp_path), JAX_COMPILATION_CACHE_DIR=placed)
    assert got["seen"] == [placed, placed, placed], got
    # nothing in the package sets a directory except that one line
    hits = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "spark_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    if '"jax_compilation_cache_dir"' in fh.read():
                        hits.append(os.path.relpath(
                            os.path.join(root, f), REPO))
    assert hits == [os.path.join("spark_tpu", "__init__.py")], hits
    # tests themselves run with the persistent cache off
    import jax
    assert jax.config.jax_enable_compilation_cache is False


def test_cached_stage_fn_requires_builder_for_novel_sig():
    fn = CC.CachedStageFn()
    with pytest.raises(RuntimeError, match="no jit builder"):
        fn(np.zeros(4))
    fn.bind_builder(lambda: (lambda *a: "jitted"))
    assert fn(np.zeros(4)) == "jitted"


def test_manifest_compaction_keeps_newest_chronological(tmp_path,
                                                        monkeypatch):
    """Compaction must keep the NEWEST records and leave the file in
    chronological order: readers reverse the file, so a newest-first
    rewrite would invert every later read and make the next compaction
    keep the stalest half."""
    monkeypatch.setattr(CC, "_MANIFEST_MAX_LINES", 6)
    monkeypatch.setattr(CC, "_MANIFEST_MAX_BYTES", 200)
    cc = CC.CompileCache(str(tmp_path), 0)
    for i in range(10):
        cc._note_seen(f"key-{i}", f"cc-{i}.pkl")
    names = [r["file"] for r in cc._read_manifest()]
    assert names[0] == "cc-9.pkl", names          # newest first
    assert "cc-0.pkl" not in names                # oldest compacted away
    cc._note_seen("key-x", "cc-x.pkl")            # appends stay newest
    assert cc._read_manifest()[0]["file"] == "cc-x.pkl"


def test_concurrent_eviction_is_miss_not_corruption(tmp_path,
                                                    monkeypatch):
    """A file vanishing between the exists() check and open() (another
    process's LRU eviction) is a plain disk miss — it must not warn or
    light the compile_cache_corrupt signal."""
    import warnings as w

    from spark_tpu.observability import MetricsRegistry
    cc = CC.CompileCache(str(tmp_path / "e"), 0)
    m = MetricsRegistry()
    monkeypatch.setattr(CC.os.path, "exists", lambda p: True)
    with w.catch_warnings():
        w.simplefilter("error")
        out = cc.load("k", None, (np.zeros(2),), metrics=m)
    assert out is None
    assert m.counter("compile_cache_disk_misses").value == 1
    assert m.counter("compile_cache_corrupt").value == 0


def test_lru_eviction_unit(tmp_path):
    cc = CC.CompileCache(str(tmp_path), max_bytes=3000)
    for i, name in enumerate(["cc-old.pkl", "cc-mid.pkl", "cc-new.pkl"]):
        p = os.path.join(str(tmp_path), name)
        with open(p, "wb") as f:
            f.write(b"x" * 1500)
        os.utime(p, (time.time() - 100 + i, time.time() - 100 + i))
    removed = cc.evict()
    assert removed == 1
    assert _entry_files(str(tmp_path)) == ["cc-mid.pkl", "cc-new.pkl"]


# ---------------------------------------------------------------------------
# in-process disk round trip
# ---------------------------------------------------------------------------


def test_disk_roundtrip_in_process(session, tmp_path):
    cc_dir = _enable(session, str(tmp_path))
    h0 = _counter(session, "compile_cache_disk_hits")
    w0 = _counter(session, "compile_cache_write_bytes")
    cold = _query(session).to_pandas()
    assert _entry_files(cc_dir), "no entry written on the cold miss"
    assert _counter(session, "compile_cache_write_bytes") > w0
    assert os.path.exists(os.path.join(cc_dir, "manifest.jsonl"))
    # a fresh-process miss is modeled by clearing the in-memory cache
    session._stage_cache.clear()
    qe = _query(session)._qe()
    warm = qe.collect().to_pandas()
    assert _counter(session, "compile_cache_disk_hits") >= h0 + 1
    assert _counter(session, "compile_cache_deser_ms") > 0
    pd.testing.assert_frame_equal(cold, warm)
    # the deserialize sub-span rode under the compile phase
    names = [s.name for s in qe.spans.spans]
    assert "deserialize" in names and "compile" in names, names
    disk_attr = [s.attrs.get("disk_hit") for s in qe.spans.spans
                 if s.name == "compile"]
    assert True in disk_attr, qe.spans.spans


def test_fingerprint_invalidation(session, tmp_path, monkeypatch):
    """An altered toolchain version string (the jaxlib-upgrade model)
    must MISS cleanly — recompile, not crash, and never load the
    stale executable."""
    _enable(session, str(tmp_path))
    cold = _query(session, domain=32).to_pandas()
    real = CC.env_fingerprint
    monkeypatch.setattr(
        CC, "env_fingerprint",
        lambda mesh=None: dict(real(mesh), jax="9.9.9-test"))
    session._stage_cache.clear()
    h0 = _counter(session, "compile_cache_disk_hits")
    m0 = _counter(session, "compile_cache_disk_misses")
    warm = _query(session, domain=32).to_pandas()
    assert _counter(session, "compile_cache_disk_hits") == h0
    assert _counter(session, "compile_cache_disk_misses") >= m0 + 1
    pd.testing.assert_frame_equal(cold, warm)


def test_maxbytes_lru_eviction_integration(session, tmp_path):
    """maxBytes=1: each store immediately evicts every OTHER entry
    (the just-written one is never its own victim), so re-running the
    first query is a disk miss that re-stores it."""
    cc_dir = _enable(session, str(tmp_path))
    session.conf.set(CC.MAX_BYTES_KEY, 1)
    _query(session, domain=16).to_pandas()
    assert len(_entry_files(cc_dir)) == 1
    first = _entry_files(cc_dir)[0]
    _query(session, domain=48).to_pandas()  # different plan, new entry
    assert _entry_files(cc_dir) != [first]
    assert len(_entry_files(cc_dir)) == 1
    session._stage_cache.clear()
    m0 = _counter(session, "compile_cache_disk_misses")
    _query(session, domain=16).to_pandas()
    assert _counter(session, "compile_cache_disk_misses") >= m0 + 1


def test_mesh_stage_roundtrip(session, tmp_path):
    """shard_map-wrapped mesh executables serialize/deserialize too,
    and their entries carry the gang fingerprint (shape + device ids)
    so a re-numbered or drained pool misses instead of loading a
    program compiled over other devices."""
    import pickle
    cc_dir = _enable(session, str(tmp_path))
    session.conf.set("spark_tpu.sql.mesh.size", 8)
    cold = _query(session, domain=24).to_pandas()
    assert _entry_files(cc_dir)
    session._stage_cache.clear()
    h0 = _counter(session, "compile_cache_disk_hits")
    warm = _query(session, domain=24).to_pandas()
    assert _counter(session, "compile_cache_disk_hits") >= h0 + 1
    pd.testing.assert_frame_equal(cold, warm)
    entries = []
    for f in _entry_files(cc_dir):
        with open(os.path.join(cc_dir, f), "rb") as fh:
            entries.append(pickle.load(fh))
    mesh_fps = [e["fingerprint"] for e in entries
                if "mesh_devices" in e.get("fingerprint", {})]
    assert mesh_fps and mesh_fps[0]["mesh_shape"] == (8,), entries


# ---------------------------------------------------------------------------
# corruption: chaos seam + torn files
# ---------------------------------------------------------------------------


def test_corrupt_entry_falls_back_and_overwrites(session, tmp_path):
    cc_dir = _enable(session, str(tmp_path))
    cold = _query(session).to_pandas()
    entry = os.path.join(cc_dir, _entry_files(cc_dir)[0])
    good_size = os.path.getsize(entry)
    with open(entry, "wb") as f:
        f.write(b"torn-write-garbage")
    session._stage_cache.clear()
    c0 = _counter(session, "compile_cache_corrupt")
    with pytest.warns(UserWarning, match="failed to load"):
        warm = _query(session).to_pandas()
    pd.testing.assert_frame_equal(cold, warm)
    assert _counter(session, "compile_cache_corrupt") >= c0 + 1
    # the bad entry was overwritten by the fresh compile...
    assert os.path.getsize(entry) == good_size
    # ...and serves the next process-miss again
    session._stage_cache.clear()
    h0 = _counter(session, "compile_cache_disk_hits")
    _query(session).to_pandas()
    assert _counter(session, "compile_cache_disk_hits") >= h0 + 1


def test_compile_cache_load_fault_seam(session, tmp_path):
    """The registered chaos seam: an injected fault during entry load
    counts as corrupt, falls back to a fresh compile and NEVER fails
    the query (golden parity)."""
    cc_dir = _enable(session, str(tmp_path))
    cold = _query(session).to_pandas()
    assert _entry_files(cc_dir), "cold run stored nothing — vacuous"
    session._stage_cache.clear()
    c0 = _counter(session, "compile_cache_corrupt")
    with faults.inject(session.conf, "compile_cache_load:fatal:1") as fp:
        with pytest.warns(UserWarning, match="failed to load"):
            warm = _query(session).to_pandas()
    assert fp.fired_log, "compile_cache_load never fired — vacuous"
    assert _counter(session, "compile_cache_corrupt") >= c0 + 1
    pd.testing.assert_frame_equal(cold, warm)


def test_second_signature_fills_wrapper_from_disk(session, tmp_path):
    """One stage key, two call signatures (same plan over two tables
    whose dictionary CONTENT differs): the 'never jit a known shape
    twice' contract holds per SIGNATURE — a warm key meeting a novel
    signature consults the disk (and persists a fresh compile), and
    warm_start installs every signature onto one wrapper."""
    from spark_tpu import functions as F
    from spark_tpu.functions import col
    cc_dir = _enable(session, str(tmp_path))
    d1 = pd.DataFrame({"s": ["a", "b", "a", "c"], "v": [1, 2, 3, 4]})
    d2 = pd.DataFrame({"s": ["x", "y", "x", "z"], "v": [1, 2, 3, 4]})

    def q():
        return (session.table("cc_sig").group_by(col("s"))
                .agg(F.sum(col("v")).alias("t"))
                .order_by(col("s"))).to_pandas()

    session.register_table("cc_sig", d1)
    r1 = q()                              # sig S1: AOT + store
    session.register_table("cc_sig", d2)
    w0 = _counter(session, "compile_cache_write_bytes")
    q()                                   # warm KEY, novel sig S2:
    assert _counter(session, "compile_cache_write_bytes") > w0, \
        "second signature's compile was not persisted"
    assert len(_entry_files(cc_dir)) >= 2
    # a fresh process touching S2 first, then S1: the S1 executable
    # must come off DISK, not a jit fallback
    session._stage_cache.clear()
    q()                                   # S2 from disk
    session.register_table("cc_sig", d1)
    h0 = _counter(session, "compile_cache_disk_hits")
    w1 = _counter(session, "compile_cache_write_bytes")
    r3 = q()                              # warm key, S1 from disk
    assert _counter(session, "compile_cache_disk_hits") >= h0 + 1
    assert _counter(session, "compile_cache_write_bytes") == w1
    pd.testing.assert_frame_equal(r1, r3)
    # warm_start stacks both signatures onto ONE wrapper
    cc = CC.get_cache(session.conf)
    fresh = {}
    assert cc.warm_start(fresh) >= 2
    assert any(len(v._compiled) >= 2 for v in fresh.values()
               if isinstance(v, CC.CachedStageFn)), \
        "warm start installed only one signature per stage key"


def test_trace_time_chaos_rules_bypass_disk_cache(session, tmp_path):
    """`join_build`/`shuffle` seams fire at TRACE time, once per
    (re)compile. A disk hit deserializes with zero trace, so while a
    rule on those sites is armed the disk cache must be bypassed —
    otherwise the rule's hit silently never arrives and the chaos test
    goes vacuous (and a transient-retry eviction stops re-tracing)."""
    from spark_tpu import functions as F
    from spark_tpu.functions import col
    _enable(session, str(tmp_path))
    dim = session.create_dataframe(pd.DataFrame(
        {"k2": np.arange(8, dtype=np.int64),
         "w": np.arange(8, dtype=np.int64)}), "cc_dim")

    def q():
        return (session.range(64)
                .select(F.pmod(col("id"), 8).alias("k"))
                .join(dim, left_on=col("k"), right_on=col("k2"))
                .agg(F.sum(col("w")).alias("s"))).to_pandas()

    clean = q()  # stores the stage's executable on disk
    session._stage_cache.clear()
    session.conf.set("spark_tpu.execution.backoffMs", 1)
    import warnings as w
    with w.catch_warnings():
        w.simplefilter("ignore")  # the retry warning is the point
        with faults.inject(session.conf,
                           "join_build:unavailable:1") as fp:
            got = q()
    assert fp.fired_log, \
        "trace-time seam never fired — a disk hit swallowed the trace"
    pd.testing.assert_frame_equal(clean, got)


# ---------------------------------------------------------------------------
# cross-process reuse (the acceptance criterion)
# ---------------------------------------------------------------------------

_CHILD = r'''
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
from spark_tpu import SparkTpuSession
from spark_tpu import functions as F
from spark_tpu.functions import col

path, cc_dir = sys.argv[1], sys.argv[2]
spark = SparkTpuSession.builder().get_or_create()
spark.conf.set("spark_tpu.sql.compileCache.enabled", True)
spark.conf.set("spark_tpu.sql.compileCache.dir", cc_dir)
df = (spark.read_parquet(path, "t").filter(col("v") > 10)
      .group_by(col("k")).agg(F.sum(col("v")).alias("s"),
                              F.count().alias("c"))
      .order_by(col("k")))
out = df.to_pandas()
m = spark.metrics
print("CHILD " + json.dumps({
    "csv": out.to_csv(index=False),
    "disk_hits": int(m.counter("compile_cache_disk_hits").value),
    "disk_misses": int(m.counter("compile_cache_disk_misses").value),
}), flush=True)
'''


def test_cross_process_reuse(tmp_path):
    """Two REAL processes over one cache dir: the second must open
    warm (disk hits >= 1, zero disk misses = zero backend recompiles
    of cached shapes) with byte-identical results."""
    rs = np.random.RandomState(7)
    data = pd.DataFrame({
        "k": rs.randint(0, 32, 4096).astype(np.int64),
        "v": rs.randint(0, 1000, 4096).astype(np.int64)})
    src = str(tmp_path / "t.parquet")
    data.to_parquet(src)
    cc_dir = str(tmp_path / "cc")

    def run_child():
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD, src, cc_dir],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=REPO)
        for line in proc.stdout.splitlines():
            if line.startswith("CHILD "):
                return json.loads(line[len("CHILD "):])
        raise AssertionError(
            f"child rc={proc.returncode}: {proc.stderr[-800:]}")

    cold = run_child()
    assert cold["disk_hits"] == 0 and cold["disk_misses"] >= 1, cold
    warm = run_child()
    assert warm["disk_hits"] >= 1, warm
    assert warm["disk_misses"] == 0, \
        f"warm process recompiled a cached shape: {warm}"
    assert warm["csv"] == cold["csv"]  # byte parity vs the cold run


# ---------------------------------------------------------------------------
# warm start
# ---------------------------------------------------------------------------


def test_session_warmup(session, tmp_path):
    from spark_tpu.config import Conf
    from spark_tpu.session import SparkTpuSession
    cc_dir = _enable(session, str(tmp_path))
    cold = _query(session).to_pandas()
    assert _entry_files(cc_dir)
    conf = Conf()
    conf.set(CC.ENABLED_KEY, True)
    conf.set(CC.DIR_KEY, cc_dir)
    s2 = SparkTpuSession(conf, register_active=False)
    n = s2.warmup()
    assert n >= 1 and len(s2._stage_cache) >= 1
    assert s2.metrics.counter("compile_cache_warm_entries").value == n
    # the warmed entry serves as an in-memory hit: no compiles at all
    got = _query(s2).to_pandas()
    assert s2.metrics.counter("compile_cache_hits").value >= 1
    assert s2.metrics.counter("compile_cache_disk_misses").value == 0
    pd.testing.assert_frame_equal(cold, got)
    # disabled cache: warmup is a 0 no-op
    from spark_tpu.config import Conf as _C
    s3 = SparkTpuSession(_C(), register_active=False)
    assert s3.warmup() == 0


def test_service_warm_start(tmp_path):
    """SqlService.start() replays the manifest into the sessions-shared
    stage cache (compileCache.warmStart), so a restarted serving
    process answers its first query without compiling."""
    from spark_tpu.config import Conf
    from spark_tpu.service.arbiter import install_arbiter
    from spark_tpu.service.server import SqlService

    data = pd.DataFrame({"k": np.arange(64, dtype=np.int64) % 8,
                         "v": np.arange(64, dtype=np.int64)})
    sql = "SELECT k, SUM(v) AS s FROM t GROUP BY k ORDER BY k"
    cc_dir = str(tmp_path / "cc")
    conf = Conf()
    conf.set(CC.ENABLED_KEY, True)
    conf.set(CC.DIR_KEY, cc_dir)
    conf.set("spark_tpu.service.port", 0)

    def init(s):
        s.register_table("t", data)

    svc = SqlService(conf, init_session=init)
    try:
        rec, cold = svc.submit(sql)
        assert rec["status"] == "ok"
    finally:
        svc.stop()
        install_arbiter(None)
    assert _entry_files(cc_dir)

    svc2 = SqlService(conf, init_session=init).start()
    try:
        # warm start replays on a background thread AFTER the socket
        # binds (a full manifest must never delay /healthz): join it
        # before asserting
        assert svc2._warm_thread is not None
        svc2._warm_thread.join(timeout=120)
        assert len(svc2.arbiter.stage_cache) >= 1, \
            "warm start installed nothing"
        assert svc2.metrics.gauge("service_warm_stages").value >= 1
        rec2, warm = svc2.submit(sql)
        assert rec2["status"] == "ok"
        assert svc2.metrics.counter("compile_cache_hits").value >= 1
        assert svc2.metrics.counter(
            "compile_cache_disk_misses").value == 0
    finally:
        svc2.stop()
        install_arbiter(None)
    assert warm.to_pandas().equals(cold.to_pandas())


# ---------------------------------------------------------------------------
# concurrent writers (two pooled sessions racing one key) + lockwatch
# ---------------------------------------------------------------------------


def test_concurrent_writers_under_lockwatch(tmp_path):
    from spark_tpu.config import Conf
    from spark_tpu.service.arbiter import install_arbiter
    from spark_tpu.service.server import SqlService
    from spark_tpu.testing.lockwatch import LockWatch

    data = pd.DataFrame({"k": np.arange(256, dtype=np.int64) % 16,
                         "v": np.arange(256, dtype=np.int64)})
    sql = "SELECT k, SUM(v) AS s FROM t GROUP BY k ORDER BY k"
    conf = Conf()
    cc_dir = str(tmp_path / "cc")
    conf.set(CC.ENABLED_KEY, True)
    conf.set(CC.DIR_KEY, cc_dir)
    svc = SqlService(conf,
                     init_session=lambda s: s.register_table("t", data))
    watch = LockWatch()
    try:
        # warm the pool so both session entries exist to be watched
        for name in ("a", "b"):
            svc.pool.get_or_create(name)
        watch.install_service(svc)
        cc = CC.get_cache(conf)
        watch.watch_attr(cc, "_lock", "execution.compile_cache")
        results, errors = [], []

        def run(name):
            try:
                for _ in range(2):
                    results.append(svc.submit(sql, session=name)[1])
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        ts = [threading.Thread(target=run, args=(n,))
              for n in ("a", "b")]
        [t.start() for t in ts]
        [t.join(300) for t in ts]
        assert not any(t.is_alive() for t in ts), "query thread wedged"
        assert not errors, errors
        assert len(results) == 4
        base = results[0].to_pandas()
        for table in results[1:]:
            pd.testing.assert_frame_equal(base, table.to_pandas())
        watch.assert_order_consistent()
    finally:
        watch.uninstall()
        svc.stop()
        install_arbiter(None)
    # the racing writers published a loadable entry
    assert _entry_files(cc_dir)
    fresh = {}
    assert cc.warm_start(fresh) >= 1
