"""egress: per request, the `egress` span (`Batch.to_arrow`: the device
-to-host pull and the Arrow build, after the engine's end event); the
median over requests."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "egress")
