"""kernels: device time of the sorts, per request, from the device
trace. A join's build side is sorted by its keys, a sort-kernel join
and a sort aggregate sort theirs, and `ORDER BY .. LIMIT` sorts the
answer: XLA:TPU names each such instruction after its opcode or after
the JAX primitive it came from (`sort.12`; `tests/test_chip_compile.py`
compiles Q3's stage for a described v5e and holds it to having
instructions of that name). An event counts when its name starts with
`sort`, as `exchange_ms` matches the collectives. Summed over `ops_s`,
which holds leaf events only (a sort that holds nested events counts
once, as they do). A trace with no such event reads nothing."""

PREFIX = "sort"


def is_sort(op_name: str) -> bool:
    return op_name.startswith(PREFIX)


def read(run):
    t = run["trace"]
    if not t or not run["requests"]:
        return None
    secs = [s for name, s in t["ops_s"].items() if is_sort(name)]
    if not secs:
        return None
    return sum(secs) * 1e3 / len(run["requests"])
