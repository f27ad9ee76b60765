"""executor: per request, the query's events: `end_event` (the end
event's build, the listener bus, the sinks, the status store and the
history store's copy) and `stage_event` (the same for a completed
dispatch attempt); the median over requests. Only a query that
something listens to opens them (a service always does; a bare session
with no sink configured does not), and a program without these spans
reads nothing."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "end_event", "stage_event")
