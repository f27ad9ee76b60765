"""executor: the share of the slots the window's runtime join filters
handed on that hold a live row: growth of the counters `rtf_tested`
less `rtf_pruned` (the rows the filters kept) over growth of
`rtf_slots` (the metrics sink's sum of every query's `rtf_slots_*`:
the capacity of the batch each filter put out, its probe's own where
it only narrows the selection, the learned capacity where it compacts
its survivors), in %. A seed fixes all three counts. A program without
the slots counter, or a window in which no filter ran, reads
nothing."""

PRUNED = "spark_tpu_rtf_pruned"
TESTED = "spark_tpu_rtf_tested"
SLOTS = "spark_tpu_rtf_slots"


def read(run):
    before, after = run["counters_before"], run["counters_after"]
    if any(name not in after for name in (PRUNED, TESTED, SLOTS)):
        return None
    slots = after[SLOTS] - before.get(SLOTS, 0.0)
    if slots <= 0:
        return None
    kept = (after[TESTED] - before.get(TESTED, 0.0)) \
        - (after[PRUNED] - before.get(PRUNED, 0.0))
    return 100.0 * kept / slots
