"""executor: growth of the counter `stage_dispatches` (one per
`dispatch` span, a capacity re-plan's attempts included) over the
window's requests. A whole number where every request dispatches the
same stages; a program without the counter reads nothing."""

COUNTER = "spark_tpu_stage_dispatches"


def read(run):
    if COUNTER not in run["counters_after"] or not run["requests"]:
        return None
    grown = run["counters_after"][COUNTER] \
        - run["counters_before"].get(COUNTER, 0.0)
    return grown / len(run["requests"])
