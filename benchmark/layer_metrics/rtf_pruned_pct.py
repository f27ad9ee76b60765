"""executor: the share of the rows the window's runtime join filters
tested that they pruned before the join: growth of the counter
`rtf_pruned` over growth of `rtf_tested` (the metrics sink's sums of
every query's `rtf_pruned_*` / `rtf_tested_*`, folded at the query's
end), in %. A seed fixes both counts. A window in which no filter
tested a row reads nothing."""

PRUNED = "spark_tpu_rtf_pruned"
TESTED = "spark_tpu_rtf_tested"


def read(run):
    before, after = run["counters_before"], run["counters_after"]
    if PRUNED not in after or TESTED not in after:
        return None
    tested = after[TESTED] - before.get(TESTED, 0.0)
    if tested <= 0:
        return None
    return 100.0 * (after[PRUNED] - before.get(PRUNED, 0.0)) / tested
