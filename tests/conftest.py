"""Test harness: force an 8-device virtual CPU mesh before jax loads.

The analog of the reference's `local-cluster[N,...]` multi-process test
mechanism (SURVEY.md section 4): sharding/collective code paths run on
8 virtual CPU devices so multi-chip logic is exercised in CI without TPU
hardware. Must run before any jax import.
"""

import os

# The env var is read when jax is first imported; a pytest plugin may
# have imported it already, so the config update below pins the backend
# to CPU either way.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()

# The whole suite runs under FULL plan-change validation: every
# effective optimizer-rule application in every test is invariant- and
# determinism-checked (analysis/plan_integrity.py), so a bad rewrite
# fails loudly at its source instead of as a wrong result downstream.
# Registry DEFAULT (read by config.py at import, which is why this is
# set before spark_tpu loads), not a conf override — the per-test
# _session_conf_guard snapshot/restore leaves it alone, and a test
# that explicitly sets planChangeValidation still wins.
os.environ.setdefault("SPARK_TPU_PLAN_VALIDATION", "full")

# Tests neither fill nor read JAX's persistent compilation cache (the
# CPU loader logs machine-feature mismatches when it reloads entries).
# The env var reaches the child processes tests start; the config
# update covers a jax that a plugin imported before this file.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from the tier-1 run "
        "(-m 'not slow')")


_FIXTURE_SESSIONS = []


@pytest.fixture(scope="session")
def session():
    from spark_tpu import SparkTpuSession
    s = SparkTpuSession.builder().get_or_create()
    _FIXTURE_SESSIONS.append(s)
    return s


@pytest.fixture(autouse=True)
def _session_conf_guard():
    """Snapshot and restore session conf overrides around EVERY test,
    so one test's mesh size / kernel mode / threshold mutation (or a
    failure before its own restore ran) can no longer cascade through
    the session-scoped fixture into 100+ downstream failures (round-5
    post-mortem). Guards BOTH the shared fixture session and whatever
    session is currently active — tests that spin up fresh sessions
    (e.g. warehouse round-trips) repoint SparkTpuSession._active, and
    guarding only _active would silently skip the one the tests use."""
    from spark_tpu.session import SparkTpuSession
    sessions = []
    if _FIXTURE_SESSIONS:
        sessions.append(_FIXTURE_SESSIONS[0])
    active = SparkTpuSession._active
    if active is not None and active not in sessions:
        sessions.append(active)
    snaps = [(s, dict(s.conf._settings)) for s in sessions]
    yield
    for s, snap in snaps:
        s.conf._settings.clear()
        s.conf._settings.update(snap)
