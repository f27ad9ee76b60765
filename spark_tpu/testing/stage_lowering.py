"""The whole-stage program of a single-device query, lowered and not
run: what the tests hold a stage's text to (the same in every process,
no reading of the host's clock in it) and what
`tests/test_chip_compile.py` hands the TPU compiler for a described
chip. The stage callable is `QueryExecution._build_stage_fn`'s, the
one the executor jits."""

from __future__ import annotations

import jax


def lower_stage(qe, sharding=None):
    """`jax.stages.Lowered` of `qe`'s stage over its scans as the
    device-table cache loads them (each once, as `_run_planned` does).
    With `sharding` the arguments are described (`ShapeDtypeStruct`s
    on that sharding's device) and not passed, so the lowering can be
    compiled for a chip that is not attached."""
    from ..io.device_cache import load_scan
    from ..plan import physical as P
    root = qe.executed_plan
    scans = []
    qe._collect_scans(root, scans)
    loaded = {}
    for s in scans:
        if id(s) not in loaded:
            loaded[id(s)] = load_scan(s, qe._conf, None)[0] \
                if isinstance(s, P.ScanExec) else s.load()
    batches = [loaded[id(s)] for s in scans]
    if sharding is not None:
        batches = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), batches)
    return jax.jit(qe._build_stage_fn(root, None)).lower(batches)
