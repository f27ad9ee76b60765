"""Rehearsal of chip_smoke.py without the chip (on-chip-measurement
guide, section 2): the script itself must refuse the CPU, so its
phases are rehearsed here by calling their functions at a tiny size —
the served path on the CPU backend, the mesh path on four of the
virtual devices."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

SF = 0.002


def _run_script(cwd, *argv):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *argv],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("argv", [(), ("--chips", "4")])
def test_script_refuses_the_cpu(argv):
    """No accelerator: another exit code than 0, no result line, and
    no query run (no data directory appears)."""
    before = os.path.isdir(os.path.join(REPO, "data"))
    proc = _run_script(REPO, *argv)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout, proc.stdout
    assert "no TPU" in proc.stderr, proc.stderr[-500:]
    assert os.path.isdir(os.path.join(REPO, "data")) == before


def test_script_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_script(str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout, proc.stdout


@pytest.fixture(scope="module")
def tpch_path(tmp_path_factory):
    from unittest import mock
    root = str(tmp_path_factory.mktemp("chip_smoke"))
    with mock.patch.object(chip_smoke, "CHECKOUT", root):
        return chip_smoke.phase_data(SF, seed=42)


def test_serve_phase(tpch_path):
    svc = chip_smoke.start_service(tpch_path)
    try:
        chip_smoke.phase_serve(svc, tpch_path, chip_smoke.SERVED)
    finally:
        svc.stop()


def test_serve_phase_fails_when_a_warm_submission_compiles(tpch_path):
    """Q3 goes four times; the second may compile the stage whose
    filters compact, the third and fourth no stage: with a count of
    stage compiles that grows at every look (what a capacity re-seeded
    after the run used to amount to) the phase must fail, and as
    that."""
    import itertools
    from unittest import mock
    svc = chip_smoke.start_service(tpch_path)
    count = itertools.count()
    try:
        # one more compile at every look, as if each submission compiled
        with mock.patch.object(chip_smoke, "_stage_compiles",
                               lambda base: next(count)):
            with pytest.raises(AssertionError,
                               match="a warm submission compiled"):
                chip_smoke.phase_serve(svc, tpch_path, ("Q3",))
    finally:
        svc.stop()


def test_aggregate_phase_demands_the_kernel(session):
    """Closed form and auto == scatter hold on any backend; what only a
    TPU can pass is the last assertion, that the Pallas kernel is in
    the `auto` program. Off the chip it must fail, and as that."""
    # four chunks, so the rows stream as the full width does
    session.conf.set("spark_tpu.sql.execution.streamingChunkRows",
                     chip_smoke.AGG_GROUPS)
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.phase_aggregate(session, 4 * chip_smoke.AGG_GROUPS)


def test_mesh_phase_on_virtual_devices(session, tpch_path):
    # 12 k rows of lineitem: chunks of 4 Ki rows have the residency
    # verdict asked, and a chip's budget of 512 KiB (a shard of four
    # would hold 0.5 MB of Q1's estimate) makes Q1 stream over the
    # mesh in three chunks, as SF1's does on the chips under
    # chip_smoke.CELL_STREAM_CONF; with the engine's own budget both
    # scans are held over the four devices
    cell = dict(zip(chip_smoke.CELL_CONF, (1 << 12,)))
    small = dict(zip(chip_smoke.CELL_STREAM_CONF, (1 << 12, 1 << 19)))
    assert set(small) - set(cell) == {"spark_tpu.sql.io.deviceCacheBytes"}
    chip_smoke.phase_mesh(session, tpch_path, 4,
                          chip_smoke.MESH_QUERIES, stream_conf=small,
                          cell_conf=cell)
