"""mesh: device time of the collective operations, per request, from
the device trace. The harness keeps an event's instruction name and
drops its opcode, and XLA:TPU names a collective either after its
opcode (`all-gather.10`, `all-reduce-start.3`) or after the JAX
primitive it was lowered from (`all_to_all.15`, `pmax.6`, `psum.2`):
an event counts when its name, `_` read as `-`, starts with one of
either kind (`tests/test_chip_compile.py` compiles the engine's
exchanges for a described four-chip mesh and holds every collective
instruction to this). Summed over `ops_s`, which holds leaf events only
(a collective that holds nested events counts once, as they do) and is
the mean over the chips. A trace with no collective, as a one-chip
cell's, reads nothing."""

#: XLA's collective opcodes, and the JAX primitives that lower to them
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather",
               "collective-permute", "reduce-scatter",
               "psum", "pmax", "pmin", "ppermute")


def is_collective(op_name: str) -> bool:
    return op_name.replace("_", "-").startswith(COLLECTIVES)


def read(run):
    t = run["trace"]
    if not t or not run["requests"]:
        return None
    secs = [s for name, s in t["ops_s"].items() if is_collective(name)]
    if not secs:
        return None
    return sum(secs) * 1e3 / len(run["requests"])
