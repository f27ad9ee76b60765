"""executor: per request, the `dispatch.sync` spans (the one host pull
of a stage's flags and counters: the wait for the device, found ready
up to one polling tick of `_sync_dispatched` late); the median over
requests."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "dispatch.sync")
