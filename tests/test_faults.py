"""Chaos suite: deterministic fault injection (spark_tpu/testing/faults.py)
against the executor's failure taxonomy and degradation ladder
(spark_tpu/execution/failures.py).

Every injected fault class — RESOURCE_EXHAUSTED, UNAVAILABLE, stage
timeout, mesh failure — must be recovered or cleanly degraded with
TPC-H Q1/Q3 result parity against the independent pandas goldens, and
the recovery path must be visible in the fault_summary metrics."""

import pytest

from spark_tpu import functions as F
from spark_tpu.functions import col
from spark_tpu.execution.failures import (FailureClass, RetryPolicy,
                                          StageCompileError, StageOOMError,
                                          StageTimeoutError, classify,
                                          is_mesh_failure)
from spark_tpu.testing import faults
from spark_tpu.testing.faults import FaultInjected, FaultPlan
from spark_tpu.tpch import golden as G
from spark_tpu.tpch import queries as Q
from spark_tpu.tpch.datagen import write_parquet

SF = 0.002
MESH_KEY = "spark_tpu.sql.mesh.size"
BACKOFF_KEY = "spark_tpu.execution.backoffMs"
RETRIES_KEY = "spark_tpu.execution.maxRetries"
TIMEOUT_KEY = "spark_tpu.execution.stageTimeoutMs"


@pytest.fixture(scope="session")
def tpch_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tpch_faults") / "sf_small")
    write_parquet(path, SF)
    return path


@pytest.fixture(scope="session")
def tpch_session(session, tpch_path):
    Q.register_tables(session, tpch_path)
    return session


@pytest.fixture(autouse=True)
def fast_backoff(tpch_session):
    """Millisecond backoffs + a disarmed plan around every test."""
    tpch_session.conf.set(BACKOFF_KEY, 1)
    faults.reset()
    yield
    faults.reset()


def _cold(session):
    """Drop compiled stages + device-resident tables so trace-time
    injection sites (shuffle/join_build/mesh) deterministically fire on
    a fresh compile, and scan_load actually ingests."""
    from spark_tpu.io.device_cache import CACHE
    session._stage_cache.clear()
    session._aqe_caps.clear()
    CACHE.clear()


def _run_query(session, qname):
    """Execute through a QueryExecution (so fault_summary is
    inspectable) and return (normalized pandas, qe)."""
    df = Q.QUERIES[qname](session)
    qe = df._qe()
    table = qe.collect()
    got = G.normalize_decimals(table.to_pandas()).reset_index(drop=True)
    return got, qe


def _check_golden(got, tpch_path, qname):
    G.compare(got, G.GOLDEN[qname](tpch_path))


# -- spec parsing / plan mechanics -------------------------------------------

def test_spec_parse_and_fire_once():
    with faults.scoped_site("s"):
        plan = FaultPlan("s:unavailable:2,s:fatal:3")
        plan.fire("s")  # hit 1: below nth
        with pytest.raises(FaultInjected, match="UNAVAILABLE"):
            plan.fire("s")  # hit 2
        with pytest.raises(FaultInjected, match="INTERNAL"):
            plan.fire("s")  # hit 3: second rule
        plan.fire("s")  # hit 4: both rules spent
        assert plan.fired_log == [("s", 2, "unavailable"),
                                  ("s", 3, "fatal")]
        assert plan.hits["s"] == 4


def test_spec_sites_independent():
    with faults.scoped_site("a"), faults.scoped_site("b"):
        plan = FaultPlan("a:deadline:1")
        plan.fire("b")  # other sites never interfere
        with pytest.raises(FaultInjected, match="DEADLINE_EXCEEDED"):
            plan.fire("a")


@pytest.mark.parametrize("bad", ["scan_load:resource_exhausted",
                                 "scan_load:nope:1",
                                 "scan_load:slow:0", "justasite"])
def test_spec_rejects_malformed(bad):
    with pytest.raises(ValueError):
        FaultPlan(bad)


def test_spec_rejects_unknown_site():
    """The PR-4 satellite bug: a typo'd site (`stage_rnu`) used to parse
    fine and then silently never fire — the chaos test tested nothing.
    Parse-time validation against the wired-seam registry makes the
    typo loud."""
    typo = "stage_rnu"  # f-strings below keep the deliberate typo
    # invisible to the fault-site lint pass (static literals only)
    with pytest.raises(ValueError, match="unknown fault site 'stage_rnu'"):
        FaultPlan(f"{typo}:fatal:1")
    # conf-driven arming goes through the same parser
    from spark_tpu.config import Conf
    conf = Conf()
    conf.set(faults.INJECT_KEY, f"shuffle:unavailable:1,{typo}:fatal:1")
    faults.reset()
    with pytest.raises(ValueError, match="unknown fault site"):
        faults.arm(conf)
    faults.reset()
    # scoped_site opens an ad-hoc seam for test-planted fire() points,
    # and closes it again: a leaked registration would re-open the
    # silent-no-fire hole for the rest of the process
    with faults.scoped_site("my_test_seam"):
        plan = FaultPlan("my_test_seam:fatal:1")
        with pytest.raises(FaultInjected, match="INTERNAL"):
            plan.fire("my_test_seam")
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan("my_test_seam:fatal:1")  # registration is gone


def test_inject_context_restores(tpch_session):
    conf = tpch_session.conf
    with faults.inject(conf, "scan_load:fatal:1") as plan:
        assert faults.active() is plan
        assert conf.get(faults.INJECT_KEY) == "scan_load:fatal:1"
    assert faults.active() is None
    assert conf.get(faults.INJECT_KEY) == ""


def test_classify_taxonomy():
    assert classify(RuntimeError("RESOURCE_EXHAUSTED: oom")) \
        is FailureClass.OOM
    assert classify(RuntimeError("UNAVAILABLE: conn")) \
        is FailureClass.TRANSIENT
    assert classify(RuntimeError("Connection reset by peer")) \
        is FailureClass.TRANSIENT
    assert classify(StageTimeoutError("slow")) is FailureClass.TIMEOUT
    assert classify(ValueError("bad plan")) is FailureClass.FATAL
    assert classify(MemoryError()) is FailureClass.OOM
    assert is_mesh_failure(RuntimeError("shard_map lowering failed"))
    assert not is_mesh_failure(RuntimeError("UNAVAILABLE: conn"))
    # the device compiler refusing a kernel also says RESOURCE_EXHAUSTED:
    # FATAL, while HBM running out at run time stays with the ladder
    assert classify(RuntimeError(_VMEM_REFUSAL)) is FailureClass.FATAL
    assert classify(RuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: unaligned "
        "slice")) is FailureClass.FATAL
    assert classify(RuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting "
        "to allocate 8.00G. That was not possible. There are 3.12G "
        "free.; (0x0x0_HBM0)")) is FailureClass.OOM
    assert classify(RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
        "of memory in memory space hbm. Used 20.50G of 15.48G hbm.")) \
        is FailureClass.OOM


#: what the v5e compiler said of the factored kernel before its blocks
#: were fitted to the VMEM limit (ISSUE 22, finding 1), word for word
_VMEM_REFUSAL = (
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
    "allocating on stack for %dense_groupby_factored.1 = "
    "f32[16,9,128,512]{3,2,1,0:T(8,128)S(1)} custom-call(...), "
    "custom_call_target=\"tpu_custom_call\", metadata={op_name="
    "\"jit(run)/shard_map/dense_groupby_factored/pallas_call\"}. Scoped "
    "allocation with size 24.75M and limit 16.00M exceeded scoped vmem "
    "limit by 8.75M.")


@pytest.mark.parametrize("mesh_size", [0, 4])
def test_compiler_refusal_is_fatal_and_names_stage(
        tpch_session, monkeypatch, mesh_size):
    """A kernel the device compiler refuses fails at once as a
    StageCompileError naming the stage and the kernel. Its text holds
    the OOM ladder's token and, under a mesh, the gang-restart
    ladder's: neither may act on it."""
    from spark_tpu.execution.executor import QueryExecution

    def refuse(self, root, mesh=None, args=None):
        self._last_stage_key = self._stage_key(root, mesh)
        raise RuntimeError(_VMEM_REFUSAL)

    monkeypatch.setattr(QueryExecution, "_compile_stage", refuse)
    _cold(tpch_session)
    old = tpch_session.conf.get(MESH_KEY)
    tpch_session.conf.set(MESH_KEY, mesh_size)
    try:
        qe = Q.QUERIES["q1"](tpch_session)._qe()
        with pytest.raises(StageCompileError) as err:
            qe.collect()
    finally:
        tpch_session.conf.set(MESH_KEY, old)
    text = str(err.value)
    assert qe._last_stage_key[:80] in text
    assert "dense_groupby_factored" in text and "scoped vmem" in text
    assert classify(err.value) is FailureClass.FATAL
    assert not qe.fault_summary, qe.fault_summary
    assert qe._oom_rung == 0


def test_retry_policy_backoff_exponential_jittered():
    slept = []
    p = RetryPolicy(3, 100.0, sleep=lambda s: slept.append(s * 1e3))
    d0, d1, d2 = (p.attempt_retry() for _ in range(3))
    assert p.attempt_retry() is None  # budget spent
    assert 50 <= d0 <= 100 and 100 <= d1 <= 200 and 200 <= d2 <= 400
    # approx, not ==: the sleep callback sees seconds (ms / 1e3) and
    # re-scales, which round-trips with an ULP of error for ~1 in 4
    # jitter draws — exact equality made this test flaky
    assert slept == [pytest.approx(d) for d in (d0, d1, d2)]
    assert p.total_sleep_ms == pytest.approx(d0 + d1 + d2)


# -- recovery with TPC-H golden parity per fault class -----------------------

#: (site rules, fault_summary action asserted)
_SCENARIOS = [
    ("stage_run:unavailable:1", "transient_retry"),
    ("scan_load:unavailable:1", "transient_retry"),
    ("stage_run:resource_exhausted:1", "oom_cache_evict"),
    ("stage_run:resource_exhausted:1,stage_run:resource_exhausted:2",
     "oom_spill_reroute"),
]


@pytest.mark.parametrize("qname", ["q1", "q3"])
@pytest.mark.parametrize("spec,action", _SCENARIOS)
def test_recovery_parity(tpch_session, tpch_path, qname, spec, action):
    _cold(tpch_session)
    with faults.inject(tpch_session.conf, spec) as plan:
        got, qe = _run_query(tpch_session, qname)
        assert plan.fired_log, "fault never fired — scenario is vacuous"
    assert qe.fault_summary.get(action, 0) >= 1, qe.fault_summary
    _check_golden(got, tpch_path, qname)


def test_join_build_fault_recovers_q3(tpch_session, tpch_path):
    _cold(tpch_session)
    with faults.inject(tpch_session.conf,
                       "join_build:unavailable:1") as plan:
        got, qe = _run_query(tpch_session, "q3")
        assert plan.fired_log, "join_build site never fired"
    assert qe.fault_summary.get("transient_retry", 0) >= 1
    _check_golden(got, tpch_path, "q3")


def test_stage_timeout_retry_parity(tpch_session, tpch_path):
    """An injected slow stage blows stageTimeoutMs once; the retry (the
    compiled entry is kept — only the flake was slow) succeeds."""
    conf = tpch_session.conf
    _run_query(tpch_session, "q1")  # warm compile: the deadline bounds
    conf.set(TIMEOUT_KEY, 2000)     # run+sync, not cold XLA compiles
    try:
        with faults.inject(conf, "stage_run:slow:1:4000") as plan:
            got, qe = _run_query(tpch_session, "q1")
            assert plan.fired_log == [("stage_run", 1, "slow")]
    finally:
        conf.set(TIMEOUT_KEY, 0)
    assert qe.fault_summary.get("stage_timeout", 0) >= 1, qe.fault_summary
    _check_golden(got, tpch_path, "q1")


@pytest.mark.parametrize("qname", ["q1", "q3"])
def test_mesh_failure_falls_back_single_device(tpch_session, tpch_path,
                                               qname):
    """A fault in the mesh/shard_map path re-plans single-device: the
    degraded run must still hit golden parity and flag mesh_fallback.
    Gang restart (the elastic rung that would now win first — see
    tests/test_elastic.py) is disabled to pin the fallback rung."""
    _cold(tpch_session)
    tpch_session.conf.set("spark_tpu.execution.meshRestart.enabled",
                          False)
    tpch_session.conf.set(MESH_KEY, 8)
    try:
        with faults.inject(tpch_session.conf, "mesh:fatal:1") as plan:
            got, qe = _run_query(tpch_session, qname)
            assert plan.fired_log == [("mesh", 1, "fatal")]
    finally:
        tpch_session.conf.set(MESH_KEY, 0)
    assert qe.fault_summary.get("mesh_fallback", 0) == 1, qe.fault_summary
    assert qe.last_metrics.get("mesh_fallback") == 1
    _check_golden(got, tpch_path, qname)


def test_mesh_misconfiguration_surfaces(tpch_session):
    """get_mesh's 'mesh.size=N but only M devices visible' diagnostic is
    a pre-dispatch setup error, not a collective failure: it must
    surface with its remediation hint, not silently degrade the run to
    single-device via the mesh fallback."""
    conf = tpch_session.conf
    conf.set(MESH_KEY, 64)  # more than the 8 virtual CPU devices
    try:
        with pytest.raises(RuntimeError, match="devices visible"):
            tpch_session.range(100).agg(
                F.sum(col("id")).alias("s")).collect()
    finally:
        conf.set(MESH_KEY, 0)


def test_mesh_fallback_disabled_surfaces(tpch_session):
    """With BOTH elastic rungs off (no restart, no degrade), a fatal
    mesh failure surfaces unchanged. meshFallback.enabled=false alone
    no longer disables gang restarts — each rung has its own conf."""
    _cold(tpch_session)
    conf = tpch_session.conf
    conf.set(MESH_KEY, 8)
    conf.set("spark_tpu.execution.meshRestart.enabled", False)
    conf.set("spark_tpu.execution.meshFallback.enabled", False)
    try:
        with faults.inject(conf, "mesh:fatal:1"):
            with pytest.raises(FaultInjected, match="INTERNAL"):
                _run_query(tpch_session, "q1")
    finally:
        conf.set(MESH_KEY, 0)
        conf.set("spark_tpu.execution.meshFallback.enabled", True)


def test_shuffle_fault_retries_under_mesh(tpch_session, tpch_path):
    """A trace-time fault inside the collective exchange retries with a
    fresh compile (the stage entry is dropped, so the site re-fires its
    next hit and passes)."""
    _cold(tpch_session)
    tpch_session.conf.set(MESH_KEY, 8)
    try:
        with faults.inject(tpch_session.conf,
                           "shuffle:unavailable:1") as plan:
            got, qe = _run_query(tpch_session, "q1")
            assert plan.fired_log, "no exchange lowered — vacuous"
    finally:
        tpch_session.conf.set(MESH_KEY, 0)
    assert qe.fault_summary.get("transient_retry", 0) >= 1
    _check_golden(got, tpch_path, "q1")


# -- budget exhaustion / ladder bottom ---------------------------------------

def test_transient_budget_exhausted_surfaces(tpch_session):
    conf = tpch_session.conf
    conf.set(RETRIES_KEY, 1)
    try:
        with faults.inject(conf, "stage_run:unavailable:1,"
                                 "stage_run:unavailable:2"):
            with pytest.raises(FaultInjected, match="UNAVAILABLE"):
                tpch_session.range(1000).agg(
                    F.sum(col("id")).alias("s")).collect()
    finally:
        conf.set(RETRIES_KEY, 3)


def test_oom_ladder_exhausted_diagnostic(tpch_session):
    """Three OOMs burn every rung; the terminal error names the stage
    and its capacity stats (issue acceptance: a diagnostic, not a bare
    XLA error)."""
    spec = ",".join(f"stage_run:resource_exhausted:{n}" for n in (1, 2, 3))
    with faults.inject(tpch_session.conf, spec):
        with pytest.raises(StageOOMError) as ei:
            tpch_session.range(1000).agg(
                F.sum(col("id")).alias("s")).collect()
    msg = str(ei.value)
    assert "degradation ladder" in msg
    assert "stage:" in msg and "capacity stats" in msg


def test_legacy_max_task_failures_still_honored(tpch_session):
    """spark_tpu.sql.execution.maxTaskFailures, when explicitly set,
    overrides the new maxRetries key (deprecated alias)."""
    conf = tpch_session.conf
    conf.set("spark_tpu.sql.execution.maxTaskFailures", 0)
    try:
        with faults.inject(conf, "stage_run:unavailable:1"):
            with pytest.raises(FaultInjected, match="UNAVAILABLE"):
                tpch_session.range(100).agg(
                    F.sum(col("id")).alias("s")).collect()
    finally:
        conf.unset("spark_tpu.sql.execution.maxTaskFailures")


# -- observability ------------------------------------------------------------

def test_fault_summary_reaches_history(tpch_session, tmp_path):
    from spark_tpu import history
    log_dir = str(tmp_path / "events")
    conf = tpch_session.conf
    conf.set("spark_tpu.sql.eventLog.dir", log_dir)
    try:
        with faults.inject(conf, "stage_run:unavailable:1,"
                                 "stage_run:resource_exhausted:2"):
            df = tpch_session.range(10000).group_by(
                (col("id") % 7).alias("k")).agg(
                F.sum(col("id")).alias("s"))
            out = df.to_pandas().sort_values("k").reset_index(drop=True)
    finally:
        conf.set("spark_tpu.sql.eventLog.dir", "")
    assert out["s"].sum() == sum(range(10000))
    events = history.read_event_log(log_dir)
    summary = history.fault_summary(events)
    assert len(summary) >= 1, events.columns
    row = summary.iloc[-1]
    assert row["transient_retry"] >= 1
    assert row["oom_cache_evict"] >= 1
    assert row["retry_backoff_ms"] > 0
    assert any(ev.get("action") == "transient_retry"
               for ev in row["events"])


def test_fault_free_run_logs_no_summary(tpch_session, tmp_path):
    from spark_tpu import history
    log_dir = str(tmp_path / "events_clean")
    conf = tpch_session.conf
    conf.set("spark_tpu.sql.eventLog.dir", log_dir)
    try:
        tpch_session.range(100).agg(F.sum(col("id")).alias("s")).collect()
    finally:
        conf.set("spark_tpu.sql.eventLog.dir", "")
    events = history.read_event_log(log_dir)
    assert len(events) >= 1
    assert history.fault_summary(events).empty