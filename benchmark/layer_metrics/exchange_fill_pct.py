"""mesh: the share of what the window's exchanges carried over the
chips' interconnect that was rows: growth of the counter
`exchange_bytes` (every hash or range exchange's live rows times its
row width) over growth of `exchange_slot_bytes` (what the same
exchanges' `all_to_all`s carried at that width, padding included:
n x n x the block of slots a source and destination pair), in %. A
broadcast (`all_gather`) is counted by neither. A program without the
slot counter, or a window with no exchange, reads nothing."""

ROWS = "spark_tpu_exchange_bytes"
SLOTS = "spark_tpu_exchange_slot_bytes"


def _grown(run, name):
    return run["counters_after"].get(name, 0.0) \
        - run["counters_before"].get(name, 0.0)


def read(run):
    slots = _grown(run, SLOTS)
    if slots <= 0:
        return None
    return 100.0 * _grown(run, ROWS) / slots
